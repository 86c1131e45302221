"""The PROGRAM of a pack that carries the tick's step (PR 54, S2; PR 56 for a
``LatentRunner``), beside ``tests/test_dispatch_uploads.py``: each layer's
weight stands in ONE ``dot_general`` whose operand holds the pack's T rows and
the step's B, the program does what the pack's and the step's did in turn
(chain and key; a ``cfg.latent`` family's cache too), a mixed tick makes ONE
upload and ONE fetch, and the programs WITHOUT a step are the parent's.  CPU,
tiny sizes: what is traced and what is counted, never a time."""
import collections
import dataclasses
import functools
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

from deepspeed_tpu.inference import latent_runner, model_runner  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, new_pack  # noqa: E402
from deepspeed_tpu.inference.paged import init_paged_cache  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402
from deepspeed_tpu.moe.layer import held_row_tile, held_rows_a_pass  # noqa: E402

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


# -- a LatentRunner's pack carries the step too (PR 56) -----------------------
# one tiny configuration of each ``cfg.latent`` family (the benchmark's, at its
# rehearsal size): its kinds of layer, and the recurrence whose projections run
# inside its ``write`` (the seam hands it ``(w, h)``: those weights alone still
# stand in a dot a kind of row)
FAMILIES = {
    "indexed": ("dots3_note_l5_e32", None),      # full (selector) + sliding (rings) + experts
    "every": ("deepseek_v2_l5_e40", None),       # latent attention over every row + experts
    "single": ("nemotron3_super_l11_e128", "mamba"),  # mamba + gqa + latent-space experts
    "deltanet": ("qwen3_next_l8_e128", "gdn"),   # gdn + gated attention on pages + experts
    "windowed": ("laguna_xs2_l5", None),         # gated attention on pages beside rings + experts
    "eva": ("evabyte_l8", None),                 # EVA attention on a table that shrinks
    "parallel": ("falcon_h1_34b_l6", "mamba"),   # mamba AND gqa in every block, a dense SwiGLU
    "by_block": ("granite4_h_small_l10_e36", "mamba"),  # two norms, mamba OR gqa by block + experts
}


def _config_of(path, rehearse):
    m = harness.rehearsed(harness.load_json(path), rehearse)
    return harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])


def _latent_cfg(family):
    return _config_of(ROOT / f"benchmark/configs/{FAMILIES[family][0]}_serve_1chip.json", True)


# the families whose ENGINE mixes (``LatentRunner.packs_carry_step``: the two-norm
# blocks that keep no recurrence's state, or hold no routed layer: PR 59); the
# runner's entry carries a step for every family, and the tests below hold every
# family to it through ``every_family_carries``
CARRIED = {"windowed", "eva", "parallel"}


@pytest.fixture
def every_family_carries(monkeypatch):
    """Engines built meanwhile mix whatever their family: the mechanism is one
    algorithm, which families the engine is TOLD to mix is what the chip read."""
    init = latent_runner.LatentRunner.__init__

    def carrying(self, cfg):
        init(self, cfg)
        self.packs_carry_step = True

    monkeypatch.setattr(latent_runner.LatentRunner, "__init__", carrying)


@functools.lru_cache(maxsize=None)
def _latent_model(family):
    """A family's configuration and weights, made ONCE: every engine below reads
    them and none writes them (an engine donates its pool, never its weights)."""
    cfg = _latent_cfg(family)
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def _latent_engine(family, **kw):
    cfg, params = _latent_model(family)
    kw = {**dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(32,),
                 prefill_chunk=32, max_seq_len=256, telemetry=True), **kw}
    return InferenceEngineV2(params, cfg, **kw)


def _latent_dots(cfg, step):
    """{(primitive, the row operand's shape, the weight operand's shape): count}
    of ``latent_runner.prefill_pack`` at T tokens (and B slot rows)."""
    t, b = 32, 4
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: latent_runner.init_cache(cfg, 24, BS, b, t))
    S = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)
    args = [S((t,))] * 3 + [S((t // BS,)), S((b,)), S((b, PAGES))]
    rows = (S((b,)), S((b,)), S((b, PAGES)), S((b,), jnp.bool_)) if step else None
    jaxpr = jax.make_jaxpr(lambda p, c, st, *a: latent_runner.prefill_pack(
        p, cfg, *a, c, step=st))(params, cache, rows, *args)
    found = collections.Counter()
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "dot_general" or eqn.primitive.name.startswith("ragged_dot"):
            lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
            found[eqn.primitive.name, lhs, rhs] += 1
    return found, params


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_mixed_latent_pack_streams_each_weight_once(family):
    """Each dense weight stands in as many dots as in the pack alone, over T + B
    rows where it stood over T; each held-expert layer is ONE grouped product a
    matrix over the rows of BOTH kinds, laid out on the PACK's row tile; ONE head
    matmul scores the pack's last rows and the step's.  A recurrence's own
    projections alone run inside its ``write`` and stay a dot a kind of row."""
    t, b = 32, 4
    cfg = _latent_cfg(family)
    s, recurrence = cfg.latent, FAMILIES[family][1]
    alone, params = _latent_dots(cfg, step=False)
    mixed, _ = _latent_dots(cfg, step=True)
    by_rows = lambda found, rows: collections.Counter({
        rhs: n for (name, lhs, rhs), n in found.items()
        if name == "dot_general" and len(lhs) == 2 and lhs[0] == rows and len(rhs) == 2})
    head = (cfg.hidden_size, cfg.vocab_size)
    if cfg.tie_embeddings:  # the embedding's rows, contracted over their width
        head = head[::-1]
    assert by_rows(alone, b)[head] == 1 and by_rows(mixed, 2 * b)[head] == 1
    # (a block of two parallel mixers keeps its recurrence's weights beside its
    # attention's, a level down: ``layers["par"][l]["mamba"]``)
    own = {a.shape for path, a in jax.tree_util.tree_leaves_with_path(params["layers"])
           if recurrence and any(getattr(k, "key", None) == recurrence for k in path)}
    # (``by_block``'s tied head [128, 64] has the shape of its recurrence's ``w_out``)
    assert (head in own or head not in by_rows(mixed, b)) and not by_rows(alone, t + b)
    split = by_rows(mixed, t)  # what is still the pack's rows alone
    assert by_rows(mixed, t + b) + split == by_rows(alone, t) and by_rows(mixed, t + b)
    if recurrence is None:
        assert not split
    else:
        assert split and set(split) <= own
        assert all(by_rows(mixed, b)[w] >= n for w, n in split.items())
    grouped = lambda found: {(lhs[0], rhs): n for (name, lhs, rhs), n in found.items()
                             if name.startswith("ragged_dot")}
    if not s.expert_layers:  # (a dense feed-forward in every block)
        assert family in ("eva", "parallel")
        assert not grouped(alone) and not grouped(mixed)
        return
    tile = held_row_tile(t, s)
    rows, carrying = held_rows_a_pass(t, s), held_rows_a_pass(t + b, s, tile)
    assert carrying == rows + b * s.experts_per_tok
    assert {r for r, _ in grouped(alone)} == {rows} and {r for r, _ in grouped(mixed)} == {carrying}
    assert sorted(grouped(alone).values()) == sorted(grouped(mixed).values())
    assert sum(grouped(mixed).values()) % len(s.expert_layers or range(cfg.num_layers - s.first_dense)) == 0


def _latent_decoding(family, temperature):
    """An engine of the family with sequences 1 and 2 a few steps into their
    answers and sequence 3's first chunk (a whole window of an EVA model)
    written: ``(engine, sampling, sequence 3, its next chunk)``."""
    eng = _latent_engine(family, seed=5)
    samp = SamplingParams(temperature=temperature)
    rng = np.random.default_rng(2)
    draw = lambda n: [int(t) for t in rng.integers(1, eng.cfg.vocab_size, n)]
    eng.put([1, 2], [draw(5), draw(11)], samp)
    for _ in range(3):
        eng.step(samp)
    c = eng.mgr.admit(3, draw(41))
    eng.mgr.ensure_pages(c, 32)
    eng.prefill_entries([(c, 0, 32)], samp)
    eng.mgr.ensure_pages(c, 41)
    return eng, samp, c, (c, 32, 41)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_mixed_latent_program_leaves_what_the_pack_and_then_the_step_leave(
        family, temperature, every_family_carries):
    """Two engines of one family in the same state: one runs a continuation
    chunk that carries the step, the other the pack and then the step.  The
    same first token, step tokens, chain and key; the same CACHE (every state,
    ring, page and pick count); the experts' counts by their rule: the routed
    and held pairs add up, the mixed program counts on the pack's side alone and
    an expert both kinds of row touch ONCE."""
    results = []
    for mixed in (True, False):
        eng, samp, c, chunk = _latent_decoding(family, temperature)
        step = [eng.mgr.seqs[1], eng.mgr.seqs[2]]
        before = jax.tree_util.tree_map(np.asarray, eng.kv)
        first = {}
        if mixed:
            toks = eng.pack_collect(eng.pack_dispatch([chunk], samp, step=step), first)
        else:
            eng.pack_collect(eng.pack_dispatch([chunk], samp), first)
            toks = eng.decode_collect(eng.decode_dispatch(step, samp))
        assert eng.stats["mixed_dispatches"] == int(mixed)
        results.append((first, toks, np.asarray(eng._chain).tolist(),
                        np.asarray(jax.random.key_data(eng._rng)).tolist(),
                        before, jax.tree_util.tree_map(np.asarray, eng.kv)))
        eng.flush([1, 2, 3])
        assert not any(eng.close().values())
    (*got, t0, kv), (*want, _, ref) = results
    assert got == want
    assert set(got[0]) == {3} and set(got[1]) == {1, 2}
    for name in sorted(set(kv) - {"stats", "touched"}):
        for a, b in zip(jax.tree_util.tree_leaves(kv[name]), jax.tree_util.tree_leaves(ref[name])):
            np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                                       rtol=2e-5, atol=2e-6, err_msg=name)
    assert (kv["stats"][:, :2] == ref["stats"][:, :2]).all()
    if family == "parallel":  # what a slot keeps for EVERY layer: state, conv tail, K / V pages
        assert {"ssm", "conv", "k", "v"} <= set(kv), sorted(kv)
        assert all((a != b).any() for name in ("ssm", "conv", "k", "v") for a, b in zip(
            jax.tree_util.tree_leaves(kv[name]), jax.tree_util.tree_leaves(t0[name])))  # every layer's moved
    if "touched" in kv and kv["touched"].size:
        mine, theirs = kv["touched"] - t0["touched"], ref["touched"] - t0["touched"]
        assert not mine[:, 1].any() and theirs[:, 1].any()  # [layer, pack | tick, (experts, pairs)]
        assert (mine[:, 0, 1] == theirs[:, :, 1].sum(1)).all()
        assert (mine[:, 0, 0] >= theirs[:, :, 0].max(1)).all()
        assert (mine[:, 0, 0] <= theirs[:, :, 0].sum(1)).all()


def latent_program_hashes(eng):
    """sha256 of the jaxpr (addresses blanked) of the programs of a ``cfg.latent``
    engine that take NO step: the tick, the burst's tick, and the runner's pack
    entry called as ``benchmark/drivers/serve.py:_runner_logits`` calls it."""
    slots, pages, bs = eng.mgr.max_seqs, eng.max_pages, eng.block_size
    triple = (0.0, 0, 1.0)
    i32 = lambda *shape: np.zeros(shape, np.int32)
    tables = np.full((slots, pages), -1, np.int32)
    sha = lambda jaxpr: hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)).encode()).hexdigest()
    entry = lambda p, kv, *a: eng.runner.prefill_packed_ctx(p, eng.cfg, *a, kv)
    return {
        "_decode_jit": sha(eng._decode_jit.trace(
            eng.params, i32(4, slots), tables, eng.kv, eng._rng, eng._chain, triple).jaxpr),
        "_decode_burst_jit": sha(eng._decode_burst_jit.trace(
            eng.params, i32(slots), i32(slots), tables, np.zeros(slots, bool), eng.kv, eng._rng,
            i32(9, slots), i32(), i32(slots), i32(slots), i32(slots), triple).jaxpr),
        "prefill_packed_ctx": sha(jax.make_jaxpr(entry)(
            eng.params, eng.kv, i32(32), i32(32), i32(32), i32(32 // bs), i32(slots), tables,
            i32(slots))),
    }


# as the parent of PR 56 (4ffe58e) traced them, character for character (the first
# two of ``indexed`` are PR 54's pins of its own parent, 09c2032; ``parallel`` as the
# parent of PR 59, 0fd9cb0, traced them; ``by_block`` as the parent of PR 64, bce27ec).
# PR 64 changed how a pack READS a state wider than one 128-lane tile
# (``latent_runner._slot_states``): at this size every family's state is 8 or 16
# wide, so ``single``, ``deltanet``, ``parallel`` and ``by_block`` keep the gather and
# the ``prefill_packed_ctx`` hashes of PR 64's parent stand, as the one-tile states of
# the benchmark's cells 6, 7 and 13 keep theirs at the real size
# (``test_a_real_size_pack_is_the_parents_where_its_state_is_one_tile``)
PARENTS_LATENT_PROGRAMS = {
    "indexed": {
        "_decode_jit": "e45a8c0735c21aad134475476f384d55fc3d9cd86000c05d2e50adbd79a33b4c",
        "_decode_burst_jit": "825e524e9b40a71dcea127850c0f1f80a032e4159ec478e23a1b704940c11c59",
        "prefill_packed_ctx": "2def77d854a485c0757bc0effc6203465b5b8571748d1fbdba1350f49ac3c202",
    },
    "every": {
        "_decode_jit": "6d278d0a3637bb218c8c317563c8d11e9ea8025c7d674f6eebc4fef98bfd5969",
        "_decode_burst_jit": "0de0fc4d2b26d8abda78c67f9e96028ba5474f76f25a4407617ee0b1ed675c37",
        "prefill_packed_ctx": "faa48f242aac6746211bc4d7063683967b99e32aed74c1e440e0bf27d1cd2611",
    },
    "single": {
        "_decode_jit": "ab1f7ab1e80c07dbc384b33884125c31d78001d723d650e7baead39898657499",
        "_decode_burst_jit": "ba1048a37c73818a911152b86c6b18dba5e17ea07d0a131593718100d0019d87",
        "prefill_packed_ctx": "f42693bdfa6f489d6d8bac145161e7b3813b6356423db05a0b18b7e03aa0fcf2",
    },
    "deltanet": {
        "_decode_jit": "cc1b7d62612a51c43875051740f788baf09cf32c781559479e8c9d5e5d517cb5",
        "_decode_burst_jit": "064f122290516ac78e75ced34ccc0e8c8c1501f5a607bf3ede9120a09ebda833",
        "prefill_packed_ctx": "c1c395d3678d0f6990d7cd558942a392f61e5cf3e63cf697fec09537fba8cfb3",
    },
    "windowed": {
        "_decode_jit": "57cf619f6474a7091abc37dd4da39c487a71781c046b39865e85e9a021738339",
        "_decode_burst_jit": "4153302ac7662e0c8077868a9005211ef14c557c5a7091ceb6d0edd5f8b514ea",
        "prefill_packed_ctx": "b6d7330e059438b65942984437dc1004c5c28803e918e90acf798b251a5a23ef",
    },
    "eva": {
        "_decode_jit": "350fb52bbe81c2268657bce556349f8cb152b71d70d7599f13a6cc2b8c21d942",
        "_decode_burst_jit": "56fb9f00dc52c50efffa626d523db819a3e15d52f7cf362d7aa1a84968d53c75",
        "prefill_packed_ctx": "0d4c20605549827b67a2b93e2d36e98e70b89b64b1e8421d2c99ce52ae3f580b",
    },
    "parallel": {
        "_decode_jit": "85099938ecc1ea2c71cc7722ad75bde6d9b5ff3665d990319411558a12245917",
        "_decode_burst_jit": "4508e456dea3e4b9d02f4703ac4488fda3d7e772e162221cef365af938925c49",
        "prefill_packed_ctx": "01f7e54bd89b0f2da9fd59c786cabf4b4b95d1506910abef822686a10440c655",
    },
    "by_block": {
        "_decode_jit": "3f8f86b03615d3251d383a9188dca2d6cd29ffff11bb34698659ecaf65acae44",
        "_decode_burst_jit": "417d675b7824f52dcc2db988ca7b667a9376c0618d0ce52ce336d24edb863983",
        "prefill_packed_ctx": "5516172e6249b4012f0b4eb035e14a1e308d7104fbd71266799e84c329db17a7",
    },
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_latent_engines_programs_without_a_step_are_the_parents(family):
    eng = _latent_engine(family)
    assert eng.runner.packs_carry_step is eng.packs_carry_step is (family in CARRIED)
    assert eng.runner.packs_are_one_program is True
    assert latent_program_hashes(eng) == PARENTS_LATENT_PROGRAMS[family]
    assert not any(eng.close().values())


@pytest.mark.parametrize("family", ["indexed", "windowed", "parallel"])
def test_a_latent_engine_mixes_every_pack_beside_decoding_rows_where_its_family_carries(family):
    """The scheduler's run of PR 54's pin, on the contract of PR 56: a prompt of
    three chunks arrives while another request decodes.  Where the family
    carries, every pack beside a decoding row carries it (ONE program, ONE
    upload); where it does not, the parent's order: nothing mixes.  Nothing
    drains either way."""
    eng = _latent_engine(family)
    mixes = family in CARRIED
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
    assert s["mixed_dispatches"] == (3 if mixes else 0)
    assert s["dispatched_ahead"] > 0 and s["ahead_drains"] == 0
    spans = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    packs = [e["args"].get("step_rows") for e in spans if e["name"] == "prefill_pack"]
    assert packs == ([0, 1, 1, 1] if mixes else [None] * 4)
    # one upload a PROGRAM: a mixed tick's pack and step are one
    assert s["dispatch_uploads"] == s["decode_ticks"] + s["prefill_dispatches"] \
        - s["mixed_dispatches"] + s["table_uploads"]
    assert not any(eng.close().values())


# the sibling scopes a carried step's bodies take, by family (``la.carried_step``)
STEP_SCOPES = {
    "indexed": {"indexer_step", "topk_step", "sparse_attn_step", "window_attn_step"},
    "every": set(),  # (its tick's body is ``mla_decode``, opened on the chip alone)
    "single": {"gqa_attn_step", "ssm_step"},
    "deltanet": {"gated_attn_step", "gdn_conv_step", "gdn_step"},
    "windowed": {"full_attn_step", "window_attn_step"},
    "eva": {"eva_attend_step", "eva_summarise_step"},
    "parallel": {"gqa_attn_step", "ssm_step"},
    "by_block": {"gqa_attn_step", "ssm_step"},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_carried_steps_scopes_are_siblings_of_the_packs_never_children(family):
    """A trace reader divides a PACK's work by the time under ``full_attn``
    (``(^|/)full_attn(/|$)`` in ``jit_packed_ctx_impl``): the step's rows must not
    sit under that name.  In the mixed program every scope a tick's body opens
    ends in ``_step`` (or was a name of its own), none lies under a pack's scope
    and none holds one; the programs without a step hold no ``_step`` at all."""
    cfg = _latent_cfg(family)
    t, b = 32, 4
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: latent_runner.init_cache(cfg, 24, BS, b, t))
    S = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)
    args = [S((t,))] * 3 + [S((t // BS,)), S((b,)), S((b, PAGES))]
    rows = (S((b,)), S((b,)), S((b, PAGES)), S((b,), jnp.bool_))

    def stacks(step):
        jaxpr = jax.make_jaxpr(lambda p, c, st, *a: latent_runner.prefill_pack(
            p, cfg, *a, c, step=st))(params, cache, step, *args)
        # (a body under ``jax.vmap`` reads ``vmap(<scope>)``: a selector's tick)
        return {tuple(re.sub(r"^vmap\((.*)\)$", r"\1", c)
                      for c in str(e.source_info.name_stack).split("/"))
                for e in _equations(jaxpr.jaxpr)}

    own = {"gdn_step", "ssm_step", "mla_decode"}  # a tick's names that no pack's body has
    pack_names = {"full_attn", "window_attn", "gated_attn", "eva_attend", "gqa_attn",
                  "eva_summarise", "gdn_conv", "gdn_scan", "ssm_scan", "indexer", "topk",
                  "sparse_attn", "mla_prefill"}
    alone, mixed = stacks(None), stacks(rows)
    assert not any(c.endswith("_step") and c not in own for s in alone for c in s)
    found = {c for s in mixed for c in s if c.endswith("_step") or c in own}
    assert STEP_SCOPES[family] <= found, found
    for s in mixed:
        steps = [c for c in s if c in found]
        assert not (steps and pack_names & set(s)), s
    # and the pack's own scopes are all still there
    assert {c for s in alone for c in s} & pack_names == {c for s in mixed for c in s} & pack_names


# which ``cfg.latent`` serving configurations' ENGINES mix, as a table (PR 59): two-norm
# blocks (``hybrid``) that keep no recurrence's state OR hold no routed layer
PACKS_CARRY_STEP = {
    "laguna_xs2_l5": True,              # gated attention on pages and rings: no recurrence
    "evabyte_l8": True,                 # EVA attention: no recurrence (and no experts)
    "falcon_h1_34b_l6": True,           # a recurrence, and a dense SwiGLU in every block
    "qwen3_next_l8_e128": False,        # a recurrence AND routed experts (ROADMAP S2 (0))
    "nemotron3_super_l11_e128": False,  # single-mixer blocks, a recurrence AND routed experts
    "granite4_h_small_l10_e36": False,  # a mixer by block: nine recurrences AND routed experts
    "dots3_note_l5_e32": False,         # latent pages and rings: -3.3% on the chip (PR 56)
    "deepseek_v2_l5_e40": False,        # latent attention over every row: -0.2% (PR 56)
}


@pytest.fixture(scope="module")
def serving_configs():
    """{name: the model's configuration at its REAL size} of every serving
    configuration under ``benchmark/configs/`` that a ``LatentRunner`` serves."""
    suffix = "_serve_1chip.json"
    found = {path.name[:-len(suffix)]: _config_of(path, False)
             for path in sorted((ROOT / "benchmark/configs").glob("*" + suffix))}
    return {name: cfg for name, cfg in found.items() if getattr(cfg, "latent", None) is not None}


@pytest.mark.parametrize("name", sorted(PACKS_CARRY_STEP))
def test_which_latent_engines_mix_is_what_the_spec_says(serving_configs, name):
    """The rule reads ``LatentSpec``'s own fields and nothing else: no model's
    name, no knob.  The table names every latent serving configuration there is."""
    configs = serving_configs
    assert sorted(configs) == sorted(PACKS_CARRY_STEP)
    s = configs[name].latent
    said = bool(s.hybrid and (s.recurrence[1] is None or not s.expert_layers))
    assert latent_runner.LatentRunner(configs[name]).packs_carry_step is said is PACKS_CARRY_STEP[name]
    if name == "falcon_h1_34b_l6":  # what sets it apart from cell 7's family
        assert s.par and s.recurrence[0] == "mamba" and not s.expert_layers and not s.n_held
    if name == "granite4_h_small_l10_e36":  # two-norm blocks under the single-mixer kinds' names
        assert s.hybrid and s.two_norms and s.recurrence[0] == "mamba" and len(s.expert_layers) == 10
    if name == "qwen3_next_l8_e128":
        assert s.hybrid and s.recurrence[0] == "gdn" and s.expert_layers and s.n_held


# -- a pack fetches its own chunks' states (PR 64) ----------------------------
# ``_state_pack_seam.write`` read ``ssm[slot]`` by a gather, which XLA:TPU turns into
# a copy of EVERY slot's state where the state is wider than one 128-lane tile
# (``latent_runner._slot_states``).  The families that keep a recurrence's state, each
# at its rehearsal width (8 or 16: the gather stays) and 256 wide (a slice a chunk)
STATEFUL = sorted(f for f, (_, recurrence) in FAMILIES.items() if recurrence)


def _state_width(cfg, width):
    """``cfg`` with its recurrence's state ``width`` wide in its minor dimension."""
    s = cfg.latent
    kind, mixer = s.recurrence
    wide = dataclasses.replace(mixer, **{"state" if kind == "mamba" else "v_dim": width})
    return dataclasses.replace(cfg, latent=dataclasses.replace(s, **{kind: wide}))


def _parents_read(ssm, slot):
    return ssm[slot]  # the parent's expression (bce27ec, ``_state_pack_seam.write``)


def _state_gathers(cfg, slots=B, t=32, bs=BS, num_blocks=24, pages=PAGES):
    """(how many ``gather``s of the pack's jaxpr read an operand of a state's shape
    ``[slots, H, P, N]``, how many blocks keep such a state, the jaxpr)."""
    params = jax.eval_shape(lambda k: init_params(k, cfg, dtype=cfg.dtype), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: latent_runner.init_cache(cfg, num_blocks, bs, slots, t))
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, c, *a: latent_runner.prefill_pack(p, cfg, *a, c))(
        params, cache, S(t), S(t), S(t), S(t // bs), S(slots), S(slots, pages))
    state = cache["ssm"][0].shape
    assert state == (slots, *cfg.latent.recurrence[1].state_shape)
    found = sum(eqn.primitive.name == "gather" and eqn.invars[0].aval.shape == state
                for eqn in _equations(jaxpr.jaxpr))
    return found, len(cache["ssm"]), str(jaxpr)


@pytest.mark.parametrize("family", STATEFUL)
def test_a_pack_reads_no_state_wider_than_a_lane_tile_through_a_gather(family, monkeypatch):
    """The jaxpr's side of it: a state of one tile or less is read by the parent's
    gather, one a block, and the jaxpr IS the parent's expression's; a wider one by
    no gather at all, whatever the slot count."""
    cfg = _latent_cfg(family)
    assert cfg.latent.recurrence[1].state_shape[-1] <= 128
    found, blocks, mine = _state_gathers(cfg)
    assert found == blocks > 0
    for slots in (B, 6):
        assert _state_gathers(_state_width(cfg, 256), slots)[:2] == (0, blocks)
    assert _state_gathers(_state_width(cfg, 128))[:2] == (blocks, blocks)  # one tile: the gather
    monkeypatch.setattr(latent_runner, "_slot_states", _parents_read)
    assert _state_gathers(cfg)[2] == mine
    assert _state_gathers(_state_width(cfg, 256))[:2] == (blocks, blocks)


# the benchmark's cells that run this seam, by the minor dimension of the state they keep
# at the REAL size: 6, 7 and 13 keep one tile, 12 two
STATE_LANES = {"nemotron3_super_l11_e128": 128, "qwen3_next_l8_e128": 128,
               "granite4_h_small_l10_e36": 128, "falcon_h1_34b_l6": 256}


@pytest.mark.parametrize("name", sorted(STATE_LANES))
def test_a_real_size_pack_is_the_parents_where_its_state_is_one_tile(
        serving_configs, name, monkeypatch):
    """At the cell's own widths, slots and pack: the pack's jaxpr is the parent's
    expression's character for character where the state is one lane tile wide (so
    the chip's program is the parent's), and reads no state by a gather where it is
    wider.  The table names every serving configuration that keeps such a state."""
    assert sorted(STATE_LANES) == sorted(
        n for n, c in serving_configs.items() if c.latent.recurrence[1] is not None)
    cfg = serving_configs[name]
    e = harness.load_json(ROOT / f"benchmark/configs/{name}_serve_1chip.json")["engine"]
    assert cfg.latent.recurrence[1].state_shape[-1] == STATE_LANES[name]

    traced = lambda: _state_gathers(cfg, e["max_seqs"], e["prefill_chunk"], e["block_size"],
                                    e["num_blocks"], -(-e["max_seq_len"] // e["block_size"]))
    gathers, blocks, mine = traced()
    monkeypatch.setattr(latent_runner, "_slot_states", _parents_read)
    theirs, _, parents = traced()
    assert theirs == blocks
    if STATE_LANES[name] <= 128:
        assert mine == parents and gathers == blocks
    else:
        assert mine != parents and gathers == 0


def _pack_of_every_kind_of_chunk(cfg, slots, state_as=None):
    """A pack of five chunks over a cache of ``slots`` slots whose every state, tail
    and page holds noise, live sequences on ODD slots only (the drivers' replays):
    two chunks of ONE sequence (slot 1: the first loads the kept state, the second
    takes the first's inside the scan), a FRESH chunk (slot 3: zeroed after the
    read), a chunk loading the kept state of the LAST slot, a DEAD chunk."""
    assert slots % 2 == 0 and slots >= 6
    g, t = 5, 5 * BS
    rng = np.random.default_rng(11)
    params = init_params(jax.random.PRNGKey(7), cfg)
    cache = latent_runner.init_cache(cfg, slots * PAGES + 1, BS, slots, t)
    noise = lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    cache = {k: jax.tree_util.tree_map(noise, v) if k in ("ssm", "conv", "k", "v") else v
             for k, v in cache.items()}
    if state_as is not None:  # a control of the drivers: the state kept in another precision
        cache = {**cache, "ssm": tuple(a.astype(state_as) for a in cache["ssm"])}
    tables = np.arange(slots * PAGES, dtype=np.int32).reshape(slots, PAGES)
    # (slot, first position) a chunk; a dead chunk is segment 0
    chunks = [(1, 2 * BS), (1, 3 * BS), (3, 0), (slots - 1, BS), None]
    seg, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    pages, last = np.full(g, -1, np.int32), np.full(slots, -1, np.int32)
    for i, c in enumerate(chunks):
        if c is not None:
            slot, start = c
            seg[i * BS:(i + 1) * BS] = slot + 1
            pos[i * BS:(i + 1) * BS] = start + np.arange(BS)
            pages[i] = tables[slot, start // BS]
            last[slot] = (i + 1) * BS - 1
    tok = rng.integers(1, cfg.vocab_size, t).astype(np.int32)
    ends_in = sorted({c[0] for c in chunks if c})
    return params, cache, (tok, seg, pos, pages, last, tables), ends_in


# (family, the state's minor dimension, slots, the dtype the state is kept in)
READS = [(f, w, 8, None) for f in STATEFUL for w in (None, 256)] + [
    ("parallel", 256, 8, jnp.bfloat16),   # ``ssm_state_bf16``: the drivers re-cast the state
    ("by_block", 256, 8, jnp.bfloat16),
    ("parallel", 256, 6, None),           # a replay's cache: another slot count than the engine's
    ("by_block", 256, 6, None),
]


@pytest.mark.parametrize("family,width,slots,state_as", READS, ids=[
    f"{f}-{w or 'own'}-{n}slots-{jnp.dtype(d).name if d else 'f32'}" for f, w, n, d in READS])
def test_a_pack_reads_its_chunks_states_bit_for_bit_as_the_parents_gather_did(
        family, width, slots, state_as, monkeypatch):
    """Data movement only: logits, every block's state and tail come out BIT FOR BIT
    what the parent's ``ssm[slot]`` gives, in the dtype the cache holds, for a
    repeated slot, a fresh chunk, the last slot and a dead chunk; a slot no chunk of
    the pack ends in keeps every bit it had."""
    cfg = _latent_cfg(family)
    if width:
        cfg = _state_width(cfg, width)
    params, cache, args, ends_in = _pack_of_every_kind_of_chunk(cfg, slots, state_as)
    before = jax.tree_util.tree_map(np.asarray, cache)

    def run():
        logits, after = jax.jit(lambda p, c, *a: latent_runner.prefill_pack(p, cfg, *a, c))(
            params, cache, *args)
        return np.asarray(logits), jax.tree_util.tree_map(np.asarray, after)

    mine, kept = run()
    monkeypatch.setattr(latent_runner, "_slot_states", _parents_read)
    theirs, ref = run()
    bits = lambda a: np.ascontiguousarray(a).view(np.uint8)
    assert np.isfinite(mine[ends_in]).all() and (bits(mine) == bits(theirs)).all()
    assert len(kept["ssm"]) == len(kept["conv"]) > 0
    others = [n for n in range(slots) if n not in ends_in]
    for name in ("ssm", "conv"):
        for a, b, a0 in zip(kept[name], ref[name], before[name]):
            assert a.dtype == a0.dtype and a.shape == a0.shape  # the stored layout and dtype
            assert (bits(a) == bits(b)).all(), name
            assert (bits(a[others]) == bits(a0[others])).all(), name
            assert all((bits(a[n]) != bits(a0[n])).any() for n in ends_in), name
    for name in set(kept) - {"ssm", "conv"}:
        for a, b in zip(jax.tree_util.tree_leaves(kept[name]), jax.tree_util.tree_leaves(ref[name])):
            assert (bits(a) == bits(b)).all(), name
