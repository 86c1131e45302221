"""The PROGRAM of a pack that carries the tick's step (PR 54, S2), beside
``tests/test_dispatch_uploads.py``: each layer's weight stands in ONE
``dot_general`` whose operand holds the pack's T rows and the step's B, the
program does what the pack's and the step's did in turn (chain and key), a
mixed tick makes ONE upload and ONE fetch, and an engine on a
``LatentRunner`` keeps the parent's three programs.  CPU, tiny sizes: what is
traced and what is counted, never a time."""
import hashlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import model_runner  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, new_pack  # noqa: E402
from deepspeed_tpu.inference.paged import init_paged_cache  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402

# widths no activation shares: d 48, f 80, 4 query / 2 kv heads of 12, vocab 96
ODD = dict(vocab_size=96, hidden_size=48, intermediate_size=80, num_layers=2, num_heads=4,
           num_kv_heads=2, max_seq_len=64, dtype=jnp.float32)
T, B, BS, PAGES, BLOCKS = 16, 4, 8, 8, 16


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _weight_dots(name, step):
    """[(weight's shape, the other operand's rows)] of every ``dot_general``
    of ``model_runner.<name>`` that takes a 2-D weight of the model."""
    cfg = TransformerConfig(**ODD)
    params = jax.eval_shape(lambda k: init_params(k, cfg, dtype=cfg.dtype), jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: init_paged_cache(
        cfg.num_layers, BLOCKS, BS, cfg.num_kv_heads, cfg.hd, dtype=cfg.dtype))
    weights = {a.shape[1:] for a in jax.tree_util.tree_leaves(params["layers"]) if a.ndim == 3}
    weights |= {a.shape for a in jax.tree_util.tree_leaves(
        {k: v for k, v in params.items() if k != "layers"}) if a.ndim == 2}
    S = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)
    args = [S((T,))] * 3 + [S((T // BS,)), S((B,))]
    if name == "prefill_packed_ctx":
        args += [S((B, PAGES)), S((B,))]
    rows = (S((B,)), S((B,)), S((B, PAGES)), S((B,), jnp.bool_)) if step else None
    fn = getattr(model_runner, name)
    jaxpr = jax.make_jaxpr(lambda p, kv, step, *a: fn(p, cfg, *a, kv, step=step))(
        params, kv, rows, *args)
    found = []
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if rhs in weights:
                found.append((rhs, int(np.prod(lhs[:-1]))))
    return found, cfg


@pytest.mark.parametrize("name", ["prefill_packed", "prefill_packed_ctx"])
def test_a_mixed_pack_holds_each_weight_in_one_dot_over_both_kinds_of_row(name):
    alone, cfg = _weight_dots(name, step=False)
    mixed, _ = _weight_dots(name, step=True)
    # q k v o up gate down a layer, and the head: as many as the pack alone
    assert len(alone) == len(mixed) == 7 * cfg.num_layers + 1
    assert [w for w, _ in alone] == [w for w, _ in mixed]
    assert {r for _, r in alone[:-1]} == {T} and {r for _, r in mixed[:-1]} == {T + B}
    # ONE head matmul scores the pack's last rows and the step's rows
    assert alone[-1] == ((cfg.hidden_size, cfg.vocab_size), B)
    assert mixed[-1] == ((cfg.hidden_size, cfg.vocab_size), 2 * B)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)


KW = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16, 32),
          prefill_chunk=16, telemetry=True)


def _decoding(params, cfg, temperature=0.0, **kw):
    """An engine with sequences 1 and 2 a few steps into their answers."""
    eng = InferenceEngineV2(params, cfg, seed=5, **{**KW, **kw})
    samp = SamplingParams(temperature=temperature)
    eng.put([1, 2], [[5, 6, 7, 8, 9], [11, 12, 13]], samp)
    for _ in range(3):
        eng.step(samp)
    return eng, samp


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("start", [0, 16], ids=["cold", "ctx"])
def test_the_mixed_program_does_what_the_pack_and_the_step_did_in_turn(tiny, start, temperature):
    """Two engines in the same state: one runs a pack that carries the step,
    the other the pack alone and then the step.  The same first token, the
    same step tokens, the same chain and the same key (two splits, the pack's
    first); under sampling too."""
    cfg, params = tiny
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 255, start + 9)]
    results = []
    for mixed in (True, False):
        eng, samp = _decoding(params, cfg, temperature)
        if start:  # an earlier chunk: the pack starts on two cached pages
            c = eng.mgr.admit(3, prompt)
            eng.mgr.ensure_capacity(c, 0)
            eng.prefill_entries([(c, 0, start)], samp)
        else:
            c = eng.mgr.admit(3, prompt)
            eng.mgr.ensure_capacity(c, 0)
        step = [eng.mgr.seqs[1], eng.mgr.seqs[2]]
        first = {}
        if mixed:
            toks = eng.pack_collect(eng.pack_dispatch(
                [(c, start, len(prompt))], samp, step=step), first)
            assert eng.stats["mixed_dispatches"] == 1
        else:
            eng.pack_collect(eng.pack_dispatch([(c, start, len(prompt))], samp), first)
            toks = eng.decode_collect(eng.decode_dispatch(step, samp))
            assert eng.stats["mixed_dispatches"] == 0
        results.append((first, toks, np.asarray(eng._chain).tolist(),
                        np.asarray(jax.random.key_data(eng._rng)).tolist()))
        eng.flush([1, 2, 3])
        assert not any(eng.close().values())
    assert results[0] == results[1]
    assert set(results[0][0]) == {3} and set(results[0][1]) == {1, 2}


def test_a_pack_with_no_live_row_splits_the_key_once(tiny):
    """The back-to-back order's pack on an engine whose packs carry steps: the
    same program with every slot dead leaves the key the pack alone leaves."""
    cfg, params = tiny
    keys = []
    for carries in (True, False):
        eng = InferenceEngineV2(params, cfg, seed=5, **KW)
        if not carries:  # today's program: built as an offloaded engine builds it
            eng = InferenceEngineV2(params, cfg, seed=5, offload_weights=True, **KW)
        assert eng.packs_carry_step is carries
        out = eng.put([1], [[5, 6, 7, 8, 9]], SamplingParams(temperature=0.8))
        keys.append((out, np.asarray(jax.random.key_data(eng._rng)).tolist()))
        eng.flush([1])
    assert keys[0] == keys[1]


def test_a_mixed_tick_makes_one_upload_and_one_fetch(tiny):
    cfg, params = tiny
    eng, samp = _decoding(params, cfg)
    c = eng.mgr.admit(3, list(range(20, 29)))
    eng.mgr.ensure_capacity(c, 0)
    step = [eng.mgr.seqs[1], eng.mgr.seqs[2]]
    handed = []
    upload = eng._upload
    eng._upload = lambda x, held=True: handed.append(x.shape) or upload(x, held)
    before = dict(eng.stats)
    done = eng.pack_dispatch([(c, 0, 9)], samp, split=True, ahead=True, step=step)
    # the pack's one buffer, the step's rows and tables inside it: no table
    # upload, no second array, where a pack and a step handed over two or three
    assert len(handed) == 1 and len(handed[0]) == 1
    delta = {k: eng.stats[k] - before[k] for k in (
        "dispatch_uploads", "table_uploads", "prefill_dispatches", "decode_ticks",
        "decode_emitted", "mixed_dispatches", "dispatched_ahead")}
    assert delta == dict(dispatch_uploads=1, table_uploads=0, prefill_dispatches=1,
                         decode_ticks=1, decode_emitted=2, mixed_dispatches=1,
                         dispatched_ahead=1)
    assert done.sampled is not None and done.tokens is None  # enqueued, not fetched
    assert [s.pending for s in step] == [1, 1] and c.pending == 1
    first = {}
    toks = eng.pack_collect(done, first)
    assert set(toks) == {1, 2} and set(first) == {3}
    spans = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    # ONE dispatch span, ONE collect, ONE booking for the program of both
    mine = [e for e in spans if e["ts"] >= [x for x in spans if x["name"] == "prefill_pack"][-1]["ts"]]
    names = [e["name"] for e in mine if e["name"] in (
        "prefill_pack", "decode_tick", "tick_collect", "engine.pack_emit", "engine.decode_emit")]
    assert names == ["prefill_pack", "tick_collect", "engine.pack_emit"]
    pack = mine[0]["args"]
    assert pack["step_rows"] == 2 and pack["ahead"] == 1 and pack["synced"] is False
    assert pack["ctx_tokens"] == sum(s.cur_len for s in step) - 2  # lengths when dispatched
    assert [e["args"]["what"] for e in mine if e["name"] == "tick_collect"] == ["prefill_pack"]
    eng.flush([1, 2, 3])
    assert not any(eng.close().values())


# -- a LatentRunner's engine keeps the parent's programs ----------------------
def _latent_engine():
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])
    return InferenceEngineV2(init_params(jax.random.PRNGKey(7), cfg), cfg, max_seqs=4,
                             num_blocks=64, block_size=8, prefill_buckets=(32,),
                             prefill_chunk=32, max_seq_len=256, telemetry=True)


def latent_program_hashes(eng):
    """sha256 of the jaxpr (addresses blanked) of the three programs a
    ``cfg.latent`` engine runs: the pack, the tick, the burst's tick."""
    slots, pages, bs = eng.mgr.max_seqs, eng.max_pages, eng.block_size
    triple = (0.0, 0, 1.0)
    pack, _ = new_pack(32, bs, slots, pages, True)
    i32 = lambda *shape: np.zeros(shape, np.int32)
    tables = np.full((slots, pages), -1, np.int32)
    args = {
        "_packed_prefill_ctx_jit": (eng.params, pack, eng.kv, eng._rng, eng._chain, triple),
        "_decode_jit": (eng.params, i32(4, slots), tables, eng.kv, eng._rng, eng._chain, triple),
        "_decode_burst_jit": (eng.params, i32(slots), i32(slots), tables, np.zeros(slots, bool),
                              eng.kv, eng._rng, i32(9, slots), i32(), i32(slots), i32(slots),
                              i32(slots), triple),
    }
    return {name: hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", str(
        getattr(eng, name).trace(*a).jaxpr)).encode()).hexdigest() for name, a in args.items()}


# as the parent of PR 54 (09c2032) traced them, character for character
PARENTS_LATENT_PROGRAMS = {
    "_packed_prefill_ctx_jit": "356aa17b20a7cb541e758210e96bb556e69e30fc112ff61f2a259ff6c74528fb",
    "_decode_jit": "e45a8c0735c21aad134475476f384d55fc3d9cd86000c05d2e50adbd79a33b4c",
    "_decode_burst_jit": "825e524e9b40a71dcea127850c0f1f80a032e4159ec478e23a1b704940c11c59",
}


def test_a_latent_engine_never_mixes_and_runs_the_parents_three_programs():
    eng = _latent_engine()
    assert eng.runner.packs_carry_step is False and eng.packs_carry_step is False
    assert latent_program_hashes(eng) == PARENTS_LATENT_PROGRAMS
    sched = eng.scheduler
    rng = np.random.default_rng(3)
    samp = SamplingParams(max_new_tokens=8)
    sched.submit(1, [int(t) for t in rng.integers(1, 250, 9)], samp)
    for n in range(40):
        if n == 3:  # a prompt of three chunks arrives while 1 decodes
            sched.submit(2, [int(t) for t in rng.integers(1, 250, 70)], samp)
        sched.tick()
    assert sched.idle and len(sched.result(1)) == len(sched.result(2)) == 8
    s = eng.stats
    assert s["mixed_dispatches"] == 0 and s["dispatched_ahead"] > 0 and s["ahead_drains"] == 0
    spans = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    assert all("step_rows" not in e["args"] for e in spans if e["name"] == "prefill_pack")
    # a pack and a step stay two programs, two uploads
    assert s["dispatch_uploads"] == s["decode_ticks"] + s["prefill_dispatches"] + s["table_uploads"]
    assert not any(eng.close().values())
