"""A model with layers of several kinds (``TransformerConfig.latent``: latent
attention with a key selector, window layers with their own latent attention,
a held share of sigmoid-routed experts) through ``InferenceEngineV2`` and its
scheduler, against the benchmark's plain reference, at the rehearsal size of
the benchmark's configuration of it: float32, CPU, seeded weights."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import CausalLM  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

CONFIG = "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"
TOPK, WINDOW = 16, 9  # the rehearsal's: both DROP keys well inside 48 positions


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    assert (m["index_topk"], m["sliding_window_size"]) == (TOPK, WINDOW)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    params = init_params(jax.random.PRNGKey(7), cfg)
    ref = jax.jit(lambda p, t: arch.logits(p, t, m))
    return m, arch, cfg, params, ref


@pytest.fixture(scope="module")
def uncached(model):
    """The uncached forward's logits as ONE program a shape (op by op it costs
    six times as much here, and the cases below differ in shape alone)."""
    cfg = model[2]
    return jax.jit(lambda p, t: CausalLM(cfg).apply(p, t)[0])


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (32,))
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("max_seq_len", 256)
    return InferenceEngineV2(params, cfg, **kw)


def _agrees(ref, params, prompt, out):
    """The engine's greedy tokens are the reference's, position by position."""
    full = np.asarray([prompt + out], np.int32)
    lg = np.asarray(ref(params, full))[0][len(prompt) - 1: len(prompt) + len(out) - 1]
    short = lg.max(-1) - lg[np.arange(len(out)), out]
    return float(short.max())


def test_chunked_prefill_and_decode_match_the_reference(model):
    """Contexts longer than twice the window and the selector's top-k, chunk
    edges (32) crossing both, prompts of unequal length sharing packs (the
    tail of one and the head of the next), then decode; nothing is left."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    sched = eng.scheduler
    rng = np.random.default_rng(0)
    prompts = {1: 75, 2: 41, 3: 100, 4: 9}
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist() for u, n in prompts.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, SamplingParams(temperature=0.0, max_new_tokens=12)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        out = sched.pop_result(u)
        assert len(out) == 12 and _agrees(ref, params, p, out) <= 1e-4, u
    assert eng.stats["prefill_dispatches"] < sum(-(-len(p) // 32) for p in prompts.values())
    eng.refresh_routing_stats()  # the selectors count what they take on the device
    stats = dict(eng.stats)
    assert 0 < stats["index_keys_selected"] < stats["index_keys_scored"]
    # ... and a sound selector takes min(t + 1, top-k) keys at every position
    # the engine ran (the last token of an answer is never fed back)
    due = sum(min(t + 1, TOPK) for p in prompts.values() for t in range(len(p) + 11))
    assert stats["index_keys_selected"] == due * cfg.latent.count("full")
    assert stats["window_rows_discarded"] > 0
    audit = eng.close()
    assert audit == {"blocks_in_use": 0, "cached_blocks": 0, "window_rows": 0}
    # the routers' device-side counts were read at close()
    assert eng.stats["expert_pairs_routed"] > eng.stats["expert_pairs_held"] > 0
    share = eng.stats["expert_pairs_held"] / eng.stats["expert_pairs_routed"]
    assert 0.1 < share < 0.45  # 4 of 16 experts held: about a quarter


def test_a_preempted_sequence_is_resumed_with_the_same_tokens(model):
    """A pool too small for every request at once: preemption by recompute
    rebuilds pages AND rings from position 0."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=3, num_blocks=24)
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    prompts = {u: rng.integers(0, cfg.vocab_size, 40 + 9 * u).tolist() for u in range(1, 5)}
    for u, p in prompts.items():
        sched.submit(u, p, SamplingParams(temperature=0.0, max_new_tokens=30))
    res = sched.run()
    assert sched.stats["finished"] == 4 and sched.stats["preemptions"] >= 1
    for u, p in prompts.items():
        assert _agrees(ref, params, p, list(res[u])) <= 1e-4, u
    assert eng.close()["window_rows"] == 0


@pytest.mark.parametrize("n", [TOPK - 1, TOPK, TOPK + 1, WINDOW - 1, WINDOW, WINDOW + 1,
                               2 * TOPK + 3])
def test_selector_and_window_edges(model, uncached, n):
    """Next-token logits after a prompt of ``n`` tokens: the last query sits
    at position n - 1, just under, at and just over the selector's top-k and
    the window (positions 512 / 513 / 514 at the published window of 513)."""
    m, arch, cfg, params, ref = model
    prompt = np.random.default_rng(n).integers(0, cfg.vocab_size, (2, n)).astype(np.int32)
    got = np.asarray(uncached(params, prompt))
    want = np.asarray(ref(params, prompt))
    assert np.abs(got - want).max() <= 1e-4
    eng = _engine(cfg, params, prefill_buckets=(64,), prefill_chunk=64)
    first = eng.put([1], [prompt[0].tolist()], SamplingParams(temperature=0.0))[1]
    assert first == int(want[0, -1].argmax())
    eng.close()


def test_param_count_is_the_held_parameters(model):
    m, arch, cfg, params, ref = model
    assert cfg.param_count == sum(x.size for x in jax.tree_util.tree_leaves(params))
    published = arch.transformer_config(harness.rehearsed(harness.load_json(ROOT / CONFIG), False))
    assert 4.08e9 < published.param_count < 4.10e9  # the issue's table: 4087 M held
    with pytest.raises(NotImplementedError, match="flops_per_token"):
        CausalLM(cfg).flops_per_token(128)


def _mesh_grid():
    from deepspeed_tpu.parallel.topology import initialize_mesh

    return initialize_mesh(model=2)


@pytest.mark.parametrize("option,kw", [
    ("enable_speculation", {"enable_speculation": True}),
    ("quantize_weights", {"quantize_weights": "int8"}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("offload_weights", {"offload_weights": True}),
    ("grid", {"grid": "mesh"}),
    ("grid", {"serve_replicas": 2}),
    ("grid", {"seq_shards": 2}),
])
def test_what_is_not_served_is_refused_by_name(model, option, kw):
    m, arch, cfg, params, ref = model
    if kw.get("grid") == "mesh":
        kw = {"grid": _mesh_grid()}
    with pytest.raises(NotImplementedError, match=option):
        _engine(cfg, params, **kw)


def test_the_uncached_forward_refuses_what_it_does_not_do(model):
    m, arch, cfg, params, ref = model
    from deepspeed_tpu.inference import model_runner
    from deepspeed_tpu.models.transformer import forward

    tokens = jnp.zeros((1, 8), jnp.int32)
    for kw in ({"segment_ids": tokens}, {"cache_index": 0, "cache": ()},
               {"layer_keep": jnp.ones(5)}):
        with pytest.raises(NotImplementedError, match="forward"):
            forward(params, tokens, cfg, **kw)
    with pytest.raises(NotImplementedError, match="enable_speculation"):
        model_runner.verify_packed_ctx(params, cfg, *[None] * 8)


def test_the_index_scores_kernel_serves_the_same_tokens(model):
    """The engine with the Pallas index-scores kernel (interpret mode: the
    gate takes pages of 8) emits what the reference does."""
    from deepspeed_tpu.ops.pallas import index_scores as ik
    from deepspeed_tpu.ops.pallas import record_dispatch

    m, arch, cfg, params, ref = model
    with ik.interpreted(), record_dispatch() as log:
        eng = _engine(cfg, params)
        sched = eng.scheduler
        prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 70).tolist()
        assert sched.try_submit(1, prompt, SamplingParams(temperature=0.0,
                                                          max_new_tokens=6)).accepted
        sched.run(wait_for=[1])
        out = sched.pop_result(1)
    assert any(d["kernel"] == "index_scores" and d["ran"] for d in log)
    assert _agrees(ref, params, prompt, out) <= 1e-4
    eng.close()


@pytest.mark.parametrize("dense_max", [40, 1 << 30])
def test_the_selected_attn_kernel_serves_the_same_tokens(model, monkeypatch, dense_max):
    """The engine with the Pallas selected-attention kernel (interpret mode)
    emits what the reference does, with every group walked and with the
    groups past position 40 gathered; the groups it counts are the
    positions' arithmetic: a group a page of each pack entry, a decode row
    none, dense where the last position is under ``DENSE_KEYS_MAX``."""
    from deepspeed_tpu.ops import latent_attention as la
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.ops.pallas import selected_attention as sk

    m, arch, cfg, params, ref = model
    monkeypatch.setattr(la, "DENSE_KEYS_MAX", dense_max)
    with sk.interpreted(), record_dispatch() as log:
        eng = _engine(cfg, params, telemetry=True)
        sched = eng.scheduler
        prompt = np.random.default_rng(6).integers(0, cfg.vocab_size, 70).tolist()
        assert sched.try_submit(1, prompt, SamplingParams(temperature=0.0,
                                                          max_new_tokens=6)).accepted
        sched.run(wait_for=[1])
        out = sched.pop_result(1)
    took = [d for d in log if d["kernel"] == "selected_attn"]
    assert took and all(d["ran"] and d["shape"][0] == 8 for d in took)  # packs only: c = 8
    assert _agrees(ref, params, prompt, out) <= 1e-4
    # chunks of 32: entries [0, 32) [32, 64) [64, 70), a group a page of 8
    ends = [min(p + 8, b) for a, b in ((0, 32), (32, 64), (64, 70)) for p in range(a, b, 8)]
    full = cfg.latent.count("full")
    assert eng.stats["selected_groups"] == len(ends) * full == 9 * full
    assert eng.stats["selected_groups_dense"] == sum(e <= dense_max for e in ends) * full
    packs = [ev["args"] for ev in eng.telemetry.recorder.chrome_events()
             if ev.get("ph") == "X" and ev["name"] == "prefill_pack"]
    assert [a["selected_groups_dense_pct"] for a in packs] == (
        [100.0, 25.0, 0.0] if dense_max == 40 else [100.0] * 3)
    eng.close()


def test_the_selected_keys_count_carries_past_32_bits():
    """A window's selections pass 2^31: the device keeps two words a layer."""
    from deepspeed_tpu.inference import latent_runner as lr

    picks = jnp.zeros((2, 2), jnp.int32)
    step = jnp.asarray([(1 << 30) - 7, 4_194_304], jnp.int32)
    for _ in range(5):
        picks = lr._tally(picks, step)
    assert lr.picks_total(picks) == 5 * ((1 << 30) - 7) + 5 * 4_194_304 > 1 << 32
