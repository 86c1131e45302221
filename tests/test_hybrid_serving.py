"""The two families whose slots keep a recurrence's state beside K / V pages,
through ``InferenceEngineV2`` and its scheduler, against the benchmark's plain
reference, at the rehearsal size of the benchmark's configuration of each
(float32, CPU, seeded weights): single-mixer blocks (``models/latent.py:SINGLE``:
Mamba-2 state beside paged GQA, a held share of latent-space relu^2 experts)
and two-norm blocks (``HYBRID``: a Gated DeltaNet matrix state beside gated GQA
with partial rotary, a held share of softmax-routed experts in every block)."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

# configuration, its family, its recurrence's kind and how many blocks run it
CONFIGS = {
    "nemotron_h": ("benchmark/configs/nemotron3_super_l11_e128_serve_1chip.json",
                   "single", "mamba", 5),
    "qwen3_next": ("benchmark/configs/qwen3_next_l8_e128_serve_1chip.json",
                   "hybrid", "gdn", 6),
}
PAGE, CHUNK = 8, 32  # the engine's page (= the scan's chunk) and pack here
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    path, family, rec, n_rec = CONFIGS[request.param]
    m = harness.rehearsed(harness.load_json(ROOT / path), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert getattr(s, family) and s.stateful and s.single != s.hybrid
    assert s.recurrence[0] == rec and s.count(rec) == n_rec
    params = init_params(jax.random.PRNGKey(7), cfg)
    ref = jax.jit(lambda p, t: arch.logits(p, t, m))
    return m, arch, cfg, params, ref


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", PAGE)
    kw.setdefault("prefill_buckets", (CHUNK,))
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("max_seq_len", 256)
    return InferenceEngineV2(params, cfg, **kw)


def _short(ref, params, prompt, out):
    """How far under the reference's best logit the engine's greedy tokens
    score, at worst: LOGITS decide, not the tokens' identity."""
    full = np.asarray([prompt + out], np.int32)
    lg = np.asarray(ref(params, full))[0][len(prompt) - 1: len(prompt) + len(out) - 1]
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def test_chunked_prefill_and_decode_match_the_reference(model):
    """Prompts of 3, 2, 4 and 1 chunks whose edges fall inside pages' scans,
    sharing packs (the tail of one and the head of the next, each scanned from
    its own state), then decode ticks of unequal ages; nothing is left."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    sched = eng.scheduler
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist()
               for u, n in {1: 75, 2: 41, 3: 100, 4: 9}.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, GREEDY(12)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        out = sched.pop_result(u)
        assert len(out) == 12 and _short(ref, params, p, out) <= 1e-4, u
    assert eng.stats["prefill_dispatches"] < sum(-(-len(p) // CHUNK) for p in prompts.values())
    # the host's count of chunks is the positions' arithmetic: every chunk of a
    # prompt is ceil(tokens / page) pages, in each of the recurrence's blocks
    chunks = sum(-(-min(CHUNK, len(p) - a) // PAGE)
                 for p in prompts.values() for a in range(0, len(p), CHUNK))
    assert eng.stats["ssm_chunks_scanned"] == cfg.latent.count(cfg.latent.recurrence[0]) * chunks
    assert eng.stats["ssm_states_reset"] == 4 and eng.stats["ssm_states_recomputed"] == 0
    audit = eng.close()
    assert audit == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}
    # the routers' device-side counts were read at close()
    assert eng.stats["expert_pairs_routed"] > eng.stats["expert_pairs_held"] > 0
    share = eng.stats["expert_pairs_held"] / eng.stats["expert_pairs_routed"]
    assert 0.1 < share < 0.45  # 4 of 16 experts held: about a quarter
    assert 0 < eng.stats["experts_touched_decode"] < eng.stats["experts_touched"] \
        <= eng.stats["expert_pairs_held"]


def test_a_slots_next_owner_starts_from_zero(model):
    """One slot, two requests in turn: the second finds the first's state in
    the slot and must not read it."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=1)
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    for u, n in ((1, 50), (2, 23)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(u, p, GREEDY(6))
        out = list(sched.run()[u])
        assert _short(ref, params, p, out) <= 1e-4, u
    assert eng.stats["ssm_states_reset"] == 2
    assert eng.close()["ssm_states"] == 0


def test_a_preempted_sequence_is_resumed_by_recomputation(model):
    """A pool too small for every request at once: the preempted sequence's
    state is left behind and the resume scans it again from position 0."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=3, num_blocks=24)
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    prompts = {u: rng.integers(0, cfg.vocab_size, 40 + 9 * u).tolist() for u in range(1, 5)}
    for u, p in prompts.items():
        sched.submit(u, p, GREEDY(30))
    res = sched.run()
    assert sched.stats["finished"] == 4 and sched.stats["preemptions"] >= 1
    for u, p in prompts.items():
        assert _short(ref, params, p, list(res[u])) <= 1e-4, u
    assert eng.stats["ssm_states_recomputed"] == sched.stats["preemptions"]
    assert eng.stats["ssm_states_reset"] == 4 + sched.stats["preemptions"]
    assert eng.close()["ssm_states"] == 0


def test_the_tick_leaves_idle_slots_state_bit_identical(model):
    """Two live slots of four: a tick rewrites theirs and hands the other two
    slots' state and convolution tail back bit for bit."""
    from deepspeed_tpu.inference import latent_runner

    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(5)
    cache = latent_runner.init_cache(cfg, 16, PAGE, 4, CHUNK)
    (rec, mixer), (att, _) = cfg.latent.recurrence, cfg.latent.attention
    assert len(cache["ssm"]) == cfg.latent.count(rec) and len(cache["k"]) == cfg.latent.count(att)
    assert cache["ssm"][0].shape == (4, *mixer.state_shape)
    assert cache["ssm"][0].dtype == jax.numpy.float32
    noise = lambda a: jax.numpy.asarray(rng.standard_normal(a.shape), a.dtype)
    cache = {**cache, "ssm": tuple(map(noise, cache["ssm"])),
             "conv": tuple(map(noise, cache["conv"]))}
    tables = np.arange(16, dtype=np.int32).reshape(4, 4)
    active = np.array([True, False, True, False])
    _, new = jax.jit(lambda *a: latent_runner.decode_step(params, cfg, *a))(
        np.array([3, 0, 9, 0], np.int32), np.array([5, 0, 11, 0], np.int32), tables,
        active, cache)
    for key in ("ssm", "conv"):
        for old, got in zip(cache[key], new[key]):
            old, got = np.asarray(old), np.asarray(got)
            assert np.array_equal(old[~active], got[~active])
            assert not np.array_equal(old[active], got[active])


@pytest.mark.parametrize("says,kw", [
    ("enable_speculation.*state-space state cannot be rolled back", dict(enable_speculation=True)),
    ("quantize_weights.*no quantized form", dict(quantize_weights="int8")),
    ("enable_prefix_caching.*state snapshot", dict(enable_prefix_caching=True)),
    ("offload_weights", dict(offload_weights=True)),
    ("replica / seq-shard serve mesh", dict(serve_replicas=2)),
    ("replica / seq-shard serve mesh", dict(seq_shards=2)),
])
def test_what_would_serve_it_wrongly_is_refused_by_name(model, says, kw):
    m, arch, cfg, params, ref = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)
