"""What only a model of gated GQA of TWO kinds has (``models/latent.py:HYBRID``
with ``wattn`` beside ``gattn``: full layers on K / V pages, window layers on a
K / V ring a slot, their own head counts and rotary tables, YaRN on the full
layers', a gate a head, a leading dense layer, sigmoid routing over experts that
are ALL held), at the rehearsal size of the benchmark's configuration of it
(float32, CPU, seeded weights): the runner's two bodies and the engine's
scheduler against the reference's LOGITS, the window's edge key by key, the
YaRN table by hand, the per-kind shapes, the share tied to the model, the
refusals, and cell 7's spec as it was."""
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import latent_runner  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import latent as lm  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402
from deepspeed_tpu.ops import gated_attention as ga  # noqa: E402

CONFIG = "benchmark/configs/laguna_xs2_l5_serve_1chip.json"
PAGE, CHUNK = 8, 32  # the engine's page and pack here; the window is 12: a ring of 48 rows
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.hybrid and s.stateful and s.ringed and not s.single
    assert s.layer_kinds == ("gattn", "wattn", "wattn", "wattn", "gattn")
    assert s.first_dense == 1 and s.expert_layers == (1, 2, 3, 4) and s.n_held == s.n_routed
    assert latent_runner.ring_rows(cfg, PAGE, CHUNK) == 48
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


def test_the_logits_of_the_runners_bodies_match_the_reference_past_the_rings_wrap(model):
    """Prefill in chunks, then decode, straight through ``latent_runner``'s two
    bodies on pages that are not contiguous, 93 + 20 tokens through a ring of 48
    rows (it wraps twice): the LOGITS at every chunk's last position and of every
    decode step against the reference's full forward."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(3)
    n, steps, slots, slot = 93, 20, 3, 2
    seq = rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
    want = np.asarray(ref(params, seq[None]))[0]
    pages = -(-(n + steps) // PAGE)
    table = np.full((slots, pages), -1, np.int32)
    table[slot] = np.arange(pages)[::-1] + 3
    cache = latent_runner.init_cache(cfg, pages + 4, PAGE, slots, CHUNK)
    assert cache["ssm"] == () and len(cache["k"]) == 2 and len(cache["wk"]) == 3
    assert cache["wk"][0].shape == (slots * 48 // PAGE, PAGE, 2, 16)
    pack = jax.jit(lambda *a: latent_runner.prefill_pack(params, cfg, *a))
    for start in range(0, n, CHUNK):
        end = min(start + CHUNK, n)
        tok, seg, pos = (np.zeros(CHUNK, np.int32) for _ in range(3))
        tok[:end - start], seg[:end - start] = seq[start:end], slot + 1
        pos[:end - start] = np.arange(start, end)
        pp = np.full(CHUNK // PAGE, -1, np.int32)
        used = -(-(end - start) // PAGE)
        pp[:used] = table[slot, start // PAGE: start // PAGE + used]
        last = np.full(slots, -1, np.int32)
        last[slot] = end - start - 1
        lg, cache = pack(tok, seg, pos, pp, last, table, cache)
        assert np.abs(np.asarray(lg)[slot] - want[end - 1]).max() <= 1e-4, start
    dec = jax.jit(lambda *a: latent_runner.decode_step(params, cfg, *a))
    active = np.arange(slots) == slot
    for j in range(steps):
        t1, lens = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        t1[slot], lens[slot] = seq[n + j], n + j
        lg, cache = dec(t1, lens, table, active, cache)
        assert np.abs(np.asarray(lg)[slot] - want[n + j]).max() <= 1e-4, j
    # the KEPT ring: row p % 48 of the slot's ring holds position p's key and value
    _, seen = jax.jit(lambda p, t: arch.probe(p, t, m))(params, seq[None])
    rings = [r for r in seen if "ring_k" in r]
    at = np.arange(n + steps - m["sliding_window"], n + steps)
    for layer, r in enumerate(rings):
        for mine, theirs in ((cache["wk"][layer], r["ring_k"]), (cache["wv"][layer], r["ring_v"])):
            kept = np.asarray(mine).reshape(slots, 48, 2, 16)[slot][at % 48]
            assert np.abs(kept - np.asarray(theirs)[0, at]).max() <= 1e-5, layer


def test_chunked_prefill_shared_packs_and_unequal_ages_match_the_reference(model):
    """Prompts of 3, 2, 4 and 1 chunks sharing packs (the tail of one and the
    head of the next), then decode ticks of unequal ages, through the engine and
    its scheduler; the host's counts are the positions' arithmetic; nothing is left."""
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
    w = m["sliding_window"]
    ends = [len(p) + 11 for p in prompts.values()]  # positions 0 .. end - 1 were queries
    causal = sum(e * (e + 1) // 2 for e in ends)
    windowed = sum(sum(min(p + 1, w) for p in range(e)) for e in ends)
    assert eng.stats["full_keys_attended"] == 2 * causal
    assert eng.stats["window_keys_attended"] == 3 * windowed
    assert eng.stats["causal_keys"] == 3 * causal
    assert eng.stats["window_rows_discarded"] == 3 * sum(max(e - w, 0) for e in ends)
    audit = eng.close()
    assert audit == {"blocks_in_use": 0, "cached_blocks": 0, "window_rows": 0}
    # the routers' device-side counts were read at close(): every expert is held
    assert eng.stats["expert_pairs_routed"] == eng.stats["expert_pairs_held"] > 0
    assert 0 < eng.stats["experts_touched_decode"] < eng.stats["experts_touched"]
    # ... and the rows its expert layers laid out follow from the programs' shapes alone:
    # ``t k + g x tile`` a layer: a tick of 4 slots, and a pack of CHUNK tokens with the 4
    # slot rows it carries behind them (on the PACK's row tile), live or not
    from deepspeed_tpu.moe.layer import held_row_tile, held_rows_a_pass

    s = cfg.latent
    a_pack, a_tick = held_rows_a_pass(CHUNK, s), held_rows_a_pass(4, s)
    carrying = held_rows_a_pass(CHUNK + 4, s, held_row_tile(CHUNK, s))
    assert a_tick == 4 * s.experts_per_tok + s.n_held * 16 < a_pack
    assert carrying == a_pack + 4 * s.experts_per_tok and eng.stats["mixed_dispatches"] > 0
    assert eng.stats["expert_rows_laid_out"] == len(s.expert_layers) * (
        eng.stats["prefill_dispatches"] * carrying
        + (eng.stats["decode_ticks"] - eng.stats["mixed_dispatches"]) * a_tick)
    assert eng.stats["expert_rows_laid_out"] > eng.stats["expert_pairs_held"]


def test_a_slots_next_owner_overwrites_the_ring_from_zero(model):
    """One slot, two requests in turn: the second finds the first's rows in the
    ring (a longer sequence's, wrapped) and must not take one for a key."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=1)
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    for u, n in ((1, 90), (2, 23)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(u, p, GREEDY(6))
        out = list(sched.run()[u])
        assert _short(ref, params, p, out) <= 1e-4, u
    assert eng.close()["window_rows"] == 0


def test_a_preempted_sequence_is_resumed_from_position_zero(model):
    """A pool too small for every request at once: the preempted sequence's
    ring is left as it is and the resume overwrites it from position 0."""
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
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0, "window_rows": 0}


@pytest.mark.parametrize("pos", [0, 5, 11, 12, 13, 40, 47, 48, 49, 100])
def test_the_windows_edge_key_by_key(pos):
    """A query at position ``pos`` of a window of 12 sees exactly the keys
    ``max(0, pos - 11) .. pos``, out of a ring whose every row holds the latest
    position of its residue (and, past ``pos``, a stale one): each key's value is
    its own position, so the output under uniform scores is their mean."""
    window, page, rc, slots, slot = 12, 8, 6, 3, 1
    ring_len = rc * page
    holds = np.full((slots, ring_len), -1.0, np.float32)
    for p in range(pos + 30):  # the ring as a sequence 30 positions OLDER would leave it ...
        holds[slot, p % ring_len] = p
    for p in range(pos + 1):   # ... overwritten from 0 by the slot's next owner up to ``pos``
        holds[slot, p % ring_len] = p
    v_ring = jnp.asarray(np.broadcast_to(holds.reshape(slots * rc, page, 1, 1),
                                         (slots * rc, page, 1, 4)).copy())
    k_ring = jnp.zeros_like(v_ring)  # uniform scores
    q = jnp.ones((1, 1, 2, 4), jnp.float32)
    seen: list = []
    o = ga.ring_attention(q, jnp.array([[pos]]), k_ring, v_ring, jnp.array([slot]),
                          jnp.array([pos // page]), rc, window, seen)
    keys = np.arange(max(0, pos - window + 1), pos + 1)
    assert np.allclose(np.asarray(o)[0, 0], keys.mean(), atol=1e-5)
    assert int(seen[0]["window_seen"][0]) == len(keys) == min(pos + 1, window)
    assert int(seen[0]["window_oldest"][0]) == keys[0]


def test_the_yarn_table_by_hand():
    """Laguna-XS.2's full layers: rotary dim 64, theta 5e5, factor 64 over 4096
    positions, beta 64 / 1.  ``c(t) = 64 ln(4096 / (2 pi t)) / (2 ln 5e5)``: c(64) =
    5.66 -> lo 5, c(1) = 15.80 -> hi 16; dim 3 is untouched, dim 10 is (10 - 5) / 11
    of the way to a 64th, dim 20 is divided by 64."""
    y = lm.Yarn(factor=64.0, original_max=4096, beta_fast=64.0, beta_slow=1.0,
                attention_factor=1.4158883083359672)
    assert y.attention_factor == pytest.approx(0.1 * np.log(64.0) + 1.0, abs=1e-12)
    ramp = lm.yarn_ramp(64, 5e5, y)
    assert ramp.shape == (32,) and ramp[3] == 0.0 and ramp[20] == 1.0
    assert ramp[5] == 0.0 and ramp[16] == 1.0 and ramp[10] == pytest.approx(5 / 11)
    # the table through the program's rotation: x = (1, 0) pairs at position 1
    x = jnp.zeros((1, 1, 64)).at[..., :32].set(1.0)
    plain = np.asarray(lm._rope(x, jnp.array([1]), 5e5))[0, 0]
    scaled = np.asarray(lm._rope(x, jnp.array([1]), 5e5, y))[0, 0]
    inv = 5e5 ** (-np.arange(32) / 32.0)
    want = {3: inv[3], 10: inv[10] / 64 * 5 / 11 + inv[10] * 6 / 11, 20: inv[20] / 64}
    for i, w in want.items():
        assert plain[i] == pytest.approx(np.cos(inv[i]), abs=1e-6)
        assert scaled[i] == pytest.approx(y.attention_factor * np.cos(w), abs=1e-6), i
        assert scaled[32 + i] == pytest.approx(y.attention_factor * np.sin(w), abs=1e-6), i


def test_each_kind_has_its_own_heads_gate_and_rotary(model):
    """A 4-head full layer and a 6-head window layer in one model (48 and 64 at
    the published size): other ``W_q`` shapes, a gate one value a HEAD, rotary on
    half the head with YaRN against the whole head without."""
    m, arch, cfg, params, ref = model
    s, d = cfg.latent, cfg.hidden_size
    full, win = params["layers"]["gattn"][0], params["layers"]["wattn"][0]
    assert (s.gattn.num_heads, s.wattn.num_heads) == (4, 6)
    assert full["wq"].shape == (d, 4 * 16) and win["wq"].shape == (d, 6 * 16)
    assert full["w_g"].shape == (d, 4) and win["w_g"].shape == (d, 6)
    assert full["wk"].shape == win["wk"].shape == (d, 2 * 16)
    assert full["wo"].shape == (4 * 16, d) and win["wo"].shape == (6 * 16, d)
    assert (s.gattn.rope_dim, s.wattn.rope_dim) == (8, 16)
    assert s.gattn.rope_scaling is not None and s.wattn.rope_scaling is None
    assert (s.gattn.window, s.wattn.window) == (0, m["sliding_window"])
    assert set(params["layers"]) == {"attn_norm", "mlp_norm", "gdn", "gattn", "wattn", "moe",
                                     "mlp"}
    assert len(params["layers"]["mlp"]) == 1 and len(params["layers"]["moe"]) == 4
    assert "w_sg" not in params["layers"]["moe"][0] and "bias" in params["layers"]["moe"][0]
    published = harness.load_json(ROOT / CONFIG)
    spec = arch.transformer_config(published).latent
    assert (spec.gattn.num_heads, spec.wattn.num_heads, spec.gattn.rope_dim) == (48, 64, 64)
    assert lm.param_count(arch.transformer_config(published)) == pytest.approx(3869.9e6, rel=1e-3)


@pytest.mark.parametrize("hq,hkv", [(6, 2), (12, 2), (10, 2), (8, 2)])
def test_a_group_that_is_no_power_of_two_is_attended_a_power_of_two_at_a_time(hq, hkv):
    """48 query heads on 8 K / V heads are groups of 6 = 4 + 2: each part is a
    call of its own over the same keys, and the parts' heads land where the one
    call's would."""
    rng = np.random.default_rng(hq)
    q = jnp.asarray(rng.standard_normal((5, hq, 4)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((5, hkv, 4)), jnp.float32)
    calls = []

    def attend(q):
        calls.append(q.shape[1] // hkv)
        g = q.shape[1] // hkv
        return q * jnp.repeat(k, g, axis=1)  # any map that pairs a head with its K / V head

    got = latent_runner._by_whole_groups(attend, q, hkv)
    assert np.allclose(got, q * jnp.repeat(k, hq // hkv, axis=1))
    g = hq // hkv
    assert sum(calls) == g and all(c & (c - 1) == 0 for c in calls)
    assert len(calls) == bin(g).count("1")


def test_all_experts_held_is_the_sum_of_four_shares_is_the_reference_layer(model):
    """The share tied to the model: one expert layer with ALL its routed
    experts through ``moe_block_held`` equals the four 2-expert members' partial
    sums, the ungated shared expert counted once, equals the REFERENCE layer."""
    from deepspeed_tpu.moe.layer import moe_block_held

    m, arch, cfg, params, ref = model
    total = m["deployment"]["num_experts_total"]
    assert total == m["num_experts"] == 8
    lw = params["layers"]["moe"][0]
    x = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.hidden_size))
    want = arch.uncut_expert_layer(lw, x[None], m)[0]
    whole, (stats, picks, _) = moe_block_held(lw, x, cfg.latent)
    assert int(stats[0]) == int(stats[1]) == 40 * m["num_experts_per_tok"]
    assert float(jnp.abs(whole - want).max()) <= 1e-5
    shared = (jax.nn.silu(x @ lw["s_gate"]) * (x @ lw["s_up"])) @ lw["s_down"]
    got, pairs, held = jnp.zeros_like(x), 0, total // 4
    for off in range(0, total, held):
        mine = dict(lw, **{k: lw[k][off:off + held] for k in ("w_gate", "w_up", "w_down")})
        y, (st, _, _) = moe_block_held(mine, x, replace(cfg.latent, n_held=held, held_offset=off))
        got += y - shared
        pairs += int(st[1])
    assert pairs == 40 * m["num_experts_per_tok"]  # every pick fell on exactly one member
    assert float(jnp.abs(got + shared - want).max()) <= 1e-5
    # the routed weights carry the scaling factor: without it the layer reads otherwise
    plain, _ = moe_block_held(lw, x, replace(cfg.latent, routed_scale=1.0))
    assert float(jnp.abs(plain - want).max()) > 1e-2


@pytest.mark.parametrize("says,kw", [
    ("enable_speculation.*rolled back out of a ring", dict(enable_speculation=True)),
    ("quantize_weights.*no quantized form", dict(quantize_weights="int8")),
    ("enable_prefix_caching.*window's ring", dict(enable_prefix_caching=True)),
    ("offload_weights", dict(offload_weights=True)),
    ("replica / seq-shard serve mesh", dict(serve_replicas=2)),
    ("replica / seq-shard serve mesh", dict(seq_shards=2)),
])
def test_what_would_serve_it_wrongly_is_refused_by_name(model, says, kw):
    m, arch, cfg, params, ref = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)


def test_a_ring_built_for_a_smaller_pack_is_refused(model):
    m, arch, cfg, params, ref = model
    cache = latent_runner.init_cache(cfg, 8, PAGE, 2, CHUNK // 2)
    z = lambda n: np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="needs rings of 48 rows"):
        latent_runner.prefill_pack(params, cfg, z(CHUNK), z(CHUNK) + 1, z(CHUNK),
                                   z(CHUNK // PAGE), z(2), np.zeros((2, 8), np.int32), cache)


# cell 7's configuration builds the LatentSpec it built before this model came:
# every field of its two mixers and of its expert layer, and the new fields at
# the values that leave its programs as they were
QWEN3_NEXT = {
    "layer_kinds": ("gdn", "gdn", "gdn", "gattn") * 2, "first_dense": 0, "n_routed": 512,
    "n_held": 128, "held_offset": 0, "experts_per_tok": 10, "moe_width": 512, "n_shared": 1,
    "shared_width": 512, "routed_scale": 1.0, "routing": "softmax", "shared_gate": True,
    "unit_offset": True, "expert_form": "swiglu", "moe_latent": 0, "wattn": None,
    "gdn": lm.Gdn(num_k_heads=16, k_dim=128, num_v_heads=32, v_dim=128, conv=4, chunk=64),
    "gattn": lm.GatedGqa(num_heads=16, num_kv_heads=2, head_dim=256, rope_dim=64,
                         rope_theta=1e7, window=0, gate="channel", rope_scaling=None),
}


@pytest.mark.parametrize("field", sorted(QWEN3_NEXT))
def test_cell_7s_configuration_builds_the_spec_it_built_before(field):
    m = harness.load_json(ROOT / "benchmark/configs/qwen3_next_l8_e128_serve_1chip.json")
    spec = harness.module("models", m["model_type"]).transformer_config(m).latent
    assert getattr(spec, field) == QWEN3_NEXT[field]
    assert spec.hybrid and spec.stateful and not spec.ringed
    assert spec.attention == ("gattn", spec.gattn) and spec.recurrence == ("gdn", spec.gdn)
    assert latent_runner._attn_scope(spec, "gattn") == "gated_attn"
