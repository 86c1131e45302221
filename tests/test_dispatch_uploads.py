"""A serving dispatch hands the device ONE host array and no key (PR 40).

The key lives on the device (every program splits it inside and hands the
carried key back), a decode tick's rows and a pack's seven arrays (with the
step's rows behind them where the pack carries the tick's step, PR 54) are one
int32 buffer each, a dirty block table is a plain transfer, and
``stats["dispatch_uploads"]`` counts every host array a dispatch body hands
over.  CPU, tiny sizes: what is called, what is counted, which tokens."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import engine_v2  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import (  # noqa: E402
    InferenceEngineV2, new_pack, unpack_pack)
from deepspeed_tpu.inference.faults import FaultInjector, InjectedFault  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

KW = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16, 32),
          prefill_budget=32, prefill_chunk=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)


def _latent():
    """The benchmark's ``cfg.latent`` configuration at its rehearsal size."""
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def _prompts(vocab, lengths=(20, 29, 38)):
    rng = np.random.default_rng(11)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


def _serve(eng, prompts, samp, first_uid=0):
    """Chunked packs (cold and ctx), then decode ticks, through the scheduler."""
    sched = eng.scheduler
    uids = list(range(first_uid, first_uid + len(prompts)))
    for u, p in zip(uids, prompts):
        assert sched.try_submit(u, p, samp).accepted
    sched.run(wait_for=uids)
    return [sched.pop_result(u) for u in uids]


# -- (a) no key is split on the host ------------------------------------------
def test_no_dispatch_splits_a_key_on_the_host(tiny, monkeypatch):
    """With ``jax.random.split`` refusing a CONCRETE key (a tracer passes: the
    programs split while they are traced), packs, decode ticks and a
    ``step_n`` burst run; the engine's source holds no split outside them."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, max_new_tokens=6)
    eng = InferenceEngineV2(params, cfg, **KW)
    _serve(eng, _prompts(cfg.vocab_size), samp)  # warmed: every program traced
    real = jax.random.split

    def traced_only(key, *a, **kw):
        if not isinstance(key, jax.core.Tracer):
            raise AssertionError("jax.random.split on the host")
        return real(key, *a, **kw)

    monkeypatch.setattr(jax.random, "split", traced_only)
    with pytest.raises(AssertionError, match="on the host"):
        jax.random.split(jax.random.PRNGKey(0))  # the patch bites
    before = dict(eng.stats)
    out = _serve(eng, _prompts(cfg.vocab_size, (18, 33)), samp, first_uid=10)
    assert [len(o) for o in out] == [6, 6]
    eng.put([7], [[3, 4, 5, 6, 7]], samp)
    assert eng.step_n(4, samp)[7] >= 0
    for k in ("prefill_dispatches", "decode_ticks", "decode_bursts"):
        assert eng.stats[k] > before[k], k
    eng.flush([7])
    assert not any(eng.close().values())


def test_the_engine_source_splits_keys_only_inside_its_programs():
    import inspect

    src = inspect.getsource(engine_v2)
    assert "jax.random.split(self._rng)" not in src
    assert src.count("jax.random.split(") == 3  # decode_sample, sampled_pack, spec_impl
    assert "_commit_rep" not in src and "jnp.array(" not in src


# -- (b) the counter -----------------------------------------------------------
def test_dispatch_uploads_counts_one_a_tick_one_a_pack_one_a_dirty_table(tiny):
    cfg, params = tiny
    eng = InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=32,
                            block_size=16, prefill_buckets=(16,))
    s = eng.stats
    eng.put([1], [[5, 6, 7]], SamplingParams())
    assert (s["dispatch_uploads"], s["prefill_dispatches"], s["table_uploads"]) == (1, 1, 0)
    eng.step()  # the first tick brings the block table with it
    assert (s["dispatch_uploads"], s["decode_ticks"], s["table_uploads"]) == (3, 1, 1)
    for _ in range(10):  # positions 4..13 of a 16-token page: nothing grows
        eng.step()
    assert (s["dispatch_uploads"], s["decode_ticks"], s["table_uploads"]) == (13, 11, 1)
    for _ in range(4):  # position 16 opens a page: one dirty table
        eng.step()
    assert (s["dispatch_uploads"], s["decode_ticks"], s["table_uploads"]) == (18, 15, 2)
    eng.put([2], [[9] * 12], SamplingParams())  # a pack adds one
    assert (s["dispatch_uploads"], s["prefill_dispatches"]) == (19, 2)
    eng.flush([1, 2])
    assert not any(eng.close().values())


# -- (c) the pack's layout -----------------------------------------------------
@pytest.mark.parametrize("t_pad,bs,slots,pages,ctx,step", [
    (24, 8, 3, 5, True, False), (24, 8, 3, 5, False, False), (40, 4, 5, 7, True, False),
    (48, 8, 4, 9, True, False),   # serve_replicas 2: two chunks of 24
    (256, 32, 64, 128, True, False), (256, 32, 64, 128, False, False),
    # a pack that carries the tick's step (PR 54): the tables for a cold pack
    # too, and the step's [4, slots] rows last
    (24, 8, 3, 5, False, True), (256, 32, 64, 128, True, True),
    (256, 32, 64, 128, False, True)])
def test_a_pack_round_trips_through_its_one_buffer(t_pad, bs, slots, pages, ctx, step):
    """``new_pack``'s views tile the buffer without overlap, and the traced
    ``unpack_pack`` hands back what the host wrote, shapes and all."""
    buf, views = new_pack(t_pad, bs, slots, pages, ctx, step)
    names = ["tokens", "seg", "pos", "pack_pages", "last_idx"] + (
        ["ctx_tables"] if ctx or step else []) + (["ctx_lens"] if ctx else []) + (
        ["step_rows"] if step else [])
    shapes = [(t_pad,)] * 3 + [(t_pad // bs,), (slots,)] + (
        [(slots, pages)] if ctx or step else []) + ([(slots,)] if ctx else []) + (
        [(4, slots)] if step else [])
    assert [v.shape for v in views] == shapes and buf.dtype == np.int32
    assert sum(v.size for v in views) == buf.size
    assert all(np.shares_memory(v, buf) for v in views)
    empty = dict(zip(names, views))
    for name in names:  # nothing to run: no token, page, sampled row, context or live slot
        want = -1 if name in ("pack_pages", "last_idx", "ctx_tables") else 0
        assert (empty[name] == want).all(), name
    rng = np.random.default_rng(t_pad + pages)
    wrote = [rng.integers(-1, 1 << 20, v.shape).astype(np.int32) for v in views]
    for v, w in zip(views, wrote):
        v[...] = w
    got = jax.jit(lambda b: unpack_pack(b, bs, slots, pages, ctx, step))(buf)
    assert len(got) == len(wrote)
    for name, g, w in zip(names, got, wrote):
        assert g.shape == w.shape and (np.asarray(g) == w).all(), name
    with pytest.raises(ValueError, match="no pack of"):
        unpack_pack(buf[:-1], bs, slots, pages, ctx, step)
    if step:  # ... and a buffer of one form is no buffer of the other
        with pytest.raises(ValueError, match="no pack of"):
            unpack_pack(buf, bs, slots, pages, ctx, False)


# -- (d) greedy tokens are the parent's ---------------------------------------
PARENT_GREEDY = {
    "dense": [[223, 224, 18, 141, 141, 141, 141, 141, 116, 129],
              [223, 158, 94, 89, 223, 94, 94, 212, 218, 158],
              [56, 255, 94, 31, 191, 149, 26, 129, 125, 172]],
    "latent": [[104, 103, 124, 65, 152, 82, 356, 289, 486, 41],
               [297, 482, 123, 152, 72, 458, 500, 497, 374, 467],
               [91, 353, 190, 5, 139, 504, 297, 123, 77, 5]],
}


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_greedy_tokens_are_the_parents(kind, tiny):
    """Pinned from commit 009d596 (host split, seven uploads): the programs'
    arithmetic did not move, so greedy streams are bit-identical."""
    if kind == "dense":
        cfg, params = tiny
        eng = InferenceEngineV2(params, cfg, **KW)
    else:
        cfg, params = _latent()
        eng = InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=64, block_size=8,
                                prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    got = _serve(eng, _prompts(min(cfg.vocab_size, 250)), SamplingParams(max_new_tokens=10))
    assert got == PARENT_GREEDY[kind]
    # one upload a PROGRAM: a pack that carried a step (PR 54, the dense
    # runner; of the latent families the two-norm blocks', PR 56: this one's
    # engine keeps two programs) counts as a pack and as a tick and hands over
    # one buffer
    mixed = eng.stats["mixed_dispatches"]
    assert (mixed > 0) == (kind == "dense")
    assert eng.stats["dispatch_uploads"] == (
        eng.stats["decode_ticks"] + eng.stats["prefill_dispatches"] - mixed
        + eng.stats["table_uploads"])
    assert not any(eng.close().values())


# -- (e) sampling: the key stream ---------------------------------------------
def test_sampled_tokens_follow_the_seed_and_a_failed_dispatch_keeps_the_key(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, max_new_tokens=12)
    prompts = _prompts(cfg.vocab_size)
    runs = [_serve(InferenceEngineV2(params, cfg, seed=seed, **KW), prompts, samp)
            for seed in (3, 3, 4)]
    assert runs[0] == runs[1] and runs[0] != runs[2]
    greedy = _serve(InferenceEngineV2(params, cfg, seed=3, **KW), prompts,
                    SamplingParams(max_new_tokens=12))
    assert runs[0] != greedy  # the key is really drawn from
    # a dispatch that raises leaves the key where it was, alive
    inj = FaultInjector()
    eng = InferenceEngineV2(params, cfg, seed=3, faults=inj, **KW)
    eng.put([1, 2], prompts[:2], samp)
    key = eng._rng
    inj.arm("runner_exception", times=1)
    with pytest.raises(InjectedFault):
        eng.step(samp)
    assert eng._rng is key and not key.is_deleted()
    out = eng.step(samp)
    assert set(out) == {1, 2} and min(out.values()) >= 0
    # ... and a dispatch that ran did not consume the key it was handed
    assert eng._rng is not key and not key.is_deleted()
    assert (np.asarray(jax.random.key_data(eng._rng)) != np.asarray(jax.random.key_data(key))).any()


# -- (f) a serve mesh: the one buffer committed replicated --------------------
@pytest.mark.parametrize("mesh", ["replicas2", "tp2"])
def test_a_mesh_engine_takes_the_one_buffer_replicated(mesh, tiny, monkeypatch):
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    if mesh == "replicas2":
        grid = initialize_mesh(devices=jax.devices()[:2], batch=2, model=1)
        kw = dict(grid=grid, serve_replicas=2, **KW)
    else:
        grid = initialize_mesh(devices=jax.devices()[:2], model=2)
        kw = dict(grid=grid, **KW)
    prompts = _prompts(cfg.vocab_size)
    samp = SamplingParams(max_new_tokens=8)
    want = _serve(InferenceEngineV2(params, cfg, **KW), prompts, samp)
    eng = InferenceEngineV2(params, cfg, **kw)
    handed = []
    upload = eng._upload
    monkeypatch.setattr(eng, "_upload", lambda x, held=True: handed.append(
        (x.shape, upload(x, held))) or handed[-1][1])
    assert _serve(eng, prompts, samp) == want
    assert eng._rng.committed and eng._rng.sharding.is_fully_replicated
    shapes = {shape for shape, _ in handed}
    assert (4, 4) in shapes  # a tick's rows (PR 43: + the chain flags)
    assert any(len(shape) == 1 for shape in shapes)  # a pack's flat buffer
    for _, dev in handed:
        assert isinstance(dev, jax.Array) and dev.committed
        assert dev.sharding.is_fully_replicated and len(dev.sharding.device_set) == 2
    assert eng.stats["dispatch_uploads"] == len(handed) == (
        eng.stats["decode_ticks"] + eng.stats["prefill_dispatches"] + eng.stats["table_uploads"])
    for name in ("_decode_jit", "_packed_prefill_jit", "_packed_prefill_ctx_jit"):
        assert getattr(eng, name)._cache_size() <= 1, name  # no second program for the key
    assert not any(eng.close().values())
