"""What only a model of latent attention over EVERY cached row has
(``models/latent.py``: the ``every`` kind, latent pages and nothing beside them,
YaRN on the rope key, no head gate, group-limited softmax routing over a held
share of experts), at the rehearsal size of the benchmark's configuration of it
(float32, CPU, seeded weights): the engine's scheduler against the reference's
LOGITS, the prefix cache over latent pages (a hit, an evicted prefix, a
preemption, a slot's second owner), the host's counts, the share tied to the
model, and the refusals that stay."""
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
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

CONFIG = "benchmark/configs/deepseek_v2_l5_e40_serve_1chip.json"
PAGE, CHUNK = 8, 32  # the engine's page and pack here
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.layer_kinds == ("every",) * 5 and not (s.ringed or s.stateful or s.indexed)
    assert s.first_dense == 1 and s.routing == "group_limited" and (s.n_group, s.topk_group) == (8, 3)
    assert (s.n_routed, s.n_held, s.held_offset) == (32, 8, 0)  # groups 0 and 1 of 8
    assert not s.every.gate and not s.rescale_lora
    params = init_params(jax.random.PRNGKey(11), cfg)
    assert "w_g" not in params["layers"]["every"][0] and "bias" not in params["layers"]["moe"][0]
    ref = jax.jit(lambda p, t: arch.logits(p, t, m))
    return m, arch, cfg, params, ref


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", PAGE)
    kw.setdefault("prefill_buckets", (CHUNK,))
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("enable_prefix_caching", True)
    return InferenceEngineV2(params, cfg, **kw)


def _short(ref, params, prompt, out):
    """How far under the reference's best logit the engine's greedy tokens
    score, at worst: LOGITS decide, not the tokens' identity."""
    full = np.asarray([prompt + out], np.int32)
    lg = np.asarray(ref(params, full))[0][len(prompt) - 1: len(prompt) + len(out) - 1]
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def test_chunked_prefill_shared_packs_and_unequal_ages_match_the_reference(model):
    """Prompts of 3, 2, 4 and 1 chunks sharing packs, then decode ticks of unequal
    ages, through the engine and its scheduler; the host's counts are the
    positions' arithmetic; nothing is left."""
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
    ends = [len(p) + 11 for p in prompts.values()]  # positions 0 .. end - 1 were queries
    causal = sum(e * (e + 1) // 2 for e in ends)
    decode = sum(sum(range(len(p) + 1, len(p) + 12)) for p in prompts.values())
    assert eng.stats["mla_keys_attended"] == 5 * causal
    assert eng.stats["mla_keys_attended_decode"] == 5 * decode
    audit = eng.close()
    assert audit == {"blocks_in_use": 0, "cached_blocks": audit["cached_blocks"], "window_rows": 0}
    # the routers' device-side counts were read at close(): a share of the picks fell here
    assert 0 < eng.stats["expert_pairs_held"] < eng.stats["expert_pairs_routed"]
    assert eng.stats["expert_pairs_routed"] == 4 * 6 * sum(ends)
    assert 0 < eng.stats["experts_touched_decode"] < eng.stats["experts_touched"]


def test_a_prefix_hit_serves_the_logits_of_a_cold_request(model):
    """One document asked twice: the second request's whole blocks are HITS (its
    pack starts at a position > 0 on pages the first wrote), and both answers are
    the reference's over the whole prompt; the same second request served cold,
    by an engine without the cache, gives the same tokens."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(4)
    doc = rng.integers(0, cfg.vocab_size, 83).tolist()
    asks = [doc + rng.integers(0, cfg.vocab_size, n).tolist() for n in (9, 14)]
    eng = _engine(cfg, params)
    sched = eng.scheduler
    outs = []
    for u, p in enumerate(asks, 1):
        before = eng.mgr.cached_prompt_tokens
        sched.submit(u, p, GREEDY(8))
        outs.append(list(sched.run()[u]))
        assert _short(ref, params, p, outs[-1]) <= 1e-4, u
        assert eng.mgr.cached_prompt_tokens - before == (0 if u == 1 else 80)  # 10 whole pages
    # the second request's prefill computed 17 tokens, not 97
    assert eng.stats["prefill_tokens_dispatched"] == len(asks[0]) + len(asks[1]) - 80
    # only WHOLE blocks are shared and a hit's own rows go to fresh pages: no page is ever
    # cloned (the engine's copy-on-write program knows K / V pairs only: ROADMAP M12)
    assert eng.mgr.cow_copies == 0
    assert eng.close()["blocks_in_use"] == 0
    cold = _engine(cfg, params, enable_prefix_caching=False)
    cold.scheduler.submit(1, asks[1], GREEDY(8))
    assert list(cold.scheduler.run()[1]) == outs[1]
    assert cold.close() == {"blocks_in_use": 0, "cached_blocks": 0, "window_rows": 0}


def test_an_evicted_prefix_is_recomputed(model):
    """A pool that the next document pushes the first one's blocks out of: the
    first document asked again finds no hit, is computed again, and reads the
    same as the reference."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, cfg.vocab_size, 120).tolist() for _ in range(3)]
    eng = _engine(cfg, params, num_blocks=40, max_seqs=2)  # 320 rows: two documents, not three
    sched = eng.scheduler
    for u, p in enumerate(docs + [docs[0] + [7, 8, 9]], 1):
        before = eng.mgr.cached_prompt_tokens
        sched.submit(u, p, GREEDY(6))
        assert _short(ref, params, p, list(sched.run()[u])) <= 1e-4, u
        assert eng.mgr.cached_prompt_tokens == before  # nothing is shared, and document 1 is gone
    assert eng.close()["blocks_in_use"] == 0


def test_a_preempted_sequence_is_resumed_and_a_slots_second_owner_reads_its_own(model):
    """A pool too small for every request at once: the preempted sequences are
    resumed (on a hit of their own published pages or from position 0) with the
    reference's tokens; then ONE slot serves two requests in turn, the second
    shorter than the first."""
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
    assert eng.close()["blocks_in_use"] == 0
    one = _engine(cfg, params, max_seqs=1)
    for u, n in ((1, 90), (2, 23)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        one.scheduler.submit(u, p, GREEDY(6))
        assert _short(ref, params, p, list(one.scheduler.run()[u])) <= 1e-4, u
    assert one.close()["blocks_in_use"] == 0


def test_a_pack_that_walks_its_pages_through_the_kernel_serves_the_same_tokens(model):
    """Where the Pallas kernels take the shape (here in interpret mode), an
    ``every`` layer's pack attends in place in the form its runs' lengths
    choose: a run of 3 pages or more (the rehearsal's crossing: 22 queries)
    DECOMPRESSED through ``latent_prefill``, a shorter one (a prompt's last 2
    pages) ABSORBED through ``selected_attn``, the causal positions its mask;
    a tick's rows walk theirs through ``latent_decode``: the same tokens as
    the reference's, behind a prefix hit too (a dead page of the pack between
    two prompts comes back zeros)."""
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.ops.pallas import selected_attention as sk

    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(8)
    doc = rng.integers(0, cfg.vocab_size, 70).tolist()
    asks = {1: doc + [3, 4, 5], 2: rng.integers(0, cfg.vocab_size, 21).tolist(), 3: doc + [9] * 12}
    with sk.interpreted(), record_dispatch() as log:
        eng = _engine(cfg, params)
        sched = eng.scheduler
        for u in (1, 2):
            assert sched.try_submit(u, asks[u], GREEDY(6)).accepted
        sched.run(wait_for=[1, 2])
        assert sched.try_submit(3, asks[3], GREEDY(6)).accepted  # 64 of its 82 tokens are a hit
        sched.run(wait_for=[3])
        outs = {u: sched.pop_result(u) for u in asks}
        assert eng.mgr.cached_prompt_tokens == 64
    took = [d for d in log if d["kernel"] == "selected_attn"]
    assert took and all(d["ran"] and d["shape"][0] == PAGE for d in took)  # packs only: c = 8
    long = [d for d in log if d["kernel"] == "latent_prefill"]  # ... beside the long runs' kernel
    assert long and all(d["ran"] and d["shape"][0] == CHUNK for d in long)
    ticks = [d for d in log if d["kernel"] == "latent_decode"]  # ... and the ticks' own kernel
    assert ticks and all(d["ran"] and d["shape"] == (4, 128, 64, PAGE) for d in ticks)
    for u, p in asks.items():
        assert _short(ref, params, p, outs[u]) <= 1e-4, u
    # both forms ran: 73 = 32 + 32 + 9 tokens end on a run of 2 pages, which walks
    assert 0 < eng.stats["mla_keys_decompressed"] < (
        eng.stats["mla_keys_attended"] - eng.stats["mla_keys_attended_decode"])
    assert eng.close()["blocks_in_use"] == 0


def test_the_four_shares_add_up_to_the_reference_layer(model):
    """The share test of the guide: the four members of the deployment (two of
    the eight routing groups each) route over all 32 experts and compute their
    own; their partial sums, the shared experts counted once, are the UNCUT
    reference layer; the held share alone is the reference's partial sum."""
    from deepspeed_tpu.moe.layer import moe_block_held

    m, arch, cfg, params, ref = model
    s, d = cfg.latent, cfg.hidden_size
    total, held = s.n_routed, s.n_held
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    lw = dict(params["layers"]["moe"][0])
    wide = lambda k, *shape: jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
    lw.update(w_gate=wide(ks[0], total, d, s.moe_width), w_up=wide(ks[1], total, d, s.moe_width),
              w_down=wide(ks[2], total, s.moe_width, d))
    x = jax.random.normal(ks[3], (40, d))
    # (each call ONE program, traced anew: op by op the layer's scans compile again a call)
    uncut = lambda: jax.jit(lambda w, x: arch.uncut_expert_layer(w, x, m))(lw, x[None])[0]
    held_layer = lambda spec: jax.jit(lambda w, x: moe_block_held(w, x, spec))
    want = uncut()
    shared = (jax.nn.silu(x @ lw["s_gate"]) * (x @ lw["s_up"])) @ lw["s_down"]
    assert lw["s_gate"].shape == (d, 2 * s.moe_width)  # n_shared_experts 2: one SwiGLU twice as wide
    got, pairs = jnp.zeros_like(x), 0
    for off in range(0, total, held):
        mine = dict(lw, **{k: lw[k][off:off + held] for k in ("w_gate", "w_up", "w_down")})
        y, (st, picks, _) = held_layer(replace(s, held_offset=off))(mine, x)
        got += y - shared
        pairs += int(st[1])
        # picks never leave the kept groups: at most 3 of the 8 groups of 4 experts
        assert all(len({e // 4 for e in row}) <= 3 for row in np.asarray(picks).tolist())
    assert pairs == 40 * s.experts_per_tok  # every pick fell on exactly one member
    assert float(jnp.abs(got + shared - want).max()) <= 1e-5
    # this member's partial sum is the reference's for the share the file states
    mine = dict(lw, **{k: lw[k][:held] for k in ("w_gate", "w_up", "w_down")})
    here, (st, _, _) = held_layer(s)(mine, x)
    part = jax.jit(lambda w, x: arch._experts(w, x, m, None, None))(mine, x[None])[0]
    assert float(jnp.abs(here - part).max()) <= 1e-5 and 0 < int(st[1]) < 40 * s.experts_per_tok
    # the weights are the scores x 16, not renormalised: either departure reads otherwise
    for name in ("routing_renormalised", "routing_not_scaled"):
        with arch.departure(name):
            other = uncut()
        assert float(jnp.abs(other - want).max()) > 1e-2, name


@pytest.mark.parametrize("says,kw", [
    ("enable_speculation.*rolled back", dict(enable_speculation=True)),
    ("quantize_weights.*no quantized form", dict(quantize_weights="int8")),
    ("offload_weights", dict(offload_weights=True)),
    ("replica / seq-shard serve mesh", dict(serve_replicas=2)),
    ("replica / seq-shard serve mesh", dict(seq_shards=2)),
])
def test_what_would_serve_it_wrongly_is_still_refused_by_name(model, says, kw):
    m, arch, cfg, params, ref = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)


@pytest.mark.parametrize("config,says", [
    ("dots3_note_l5_e32_serve_1chip", "enable_prefix_caching.*window's ring"),      # ringed, indexed
    ("laguna_xs2_l5_serve_1chip", "enable_prefix_caching.*window's ring"),          # ringed, stateful
    ("qwen3_next_l8_e128_serve_1chip", "enable_prefix_caching.*state snapshot"),    # stateful
    ("nemotron3_super_l11_e128_serve_1chip", "enable_prefix_caching.*state snapshot"),
])
def test_a_ringed_an_indexed_and_a_stateful_model_still_refuse_the_prefix_cache(config, says):
    m = harness.rehearsed(harness.load_json(ROOT / f"benchmark/configs/{config}.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.ringed or s.stateful or s.indexed
    with pytest.raises(NotImplementedError, match=says):
        InferenceEngineV2(None, cfg, enable_prefix_caching=True)


def test_an_indexed_model_without_rings_would_refuse_too():
    """The third reason, alone: index keys beside the latent pages."""
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(m)
    only_full = replace(cfg.latent, layer_kinds=("full",) * cfg.num_layers)
    assert only_full.indexed and not only_full.ringed and not only_full.stateful
    with pytest.raises(NotImplementedError, match="enable_prefix_caching"):
        InferenceEngineV2(None, replace(cfg, latent=only_full), enable_prefix_caching=True)


def test_param_count_is_the_held_parameters(model):
    from deepspeed_tpu.models.latent import param_count

    m, arch, cfg, params, ref = model
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert param_count(cfg) == n
    # ... and at the published widths the file's arithmetic: 5164 M
    real = harness.load_json(ROOT / CONFIG)
    big = harness.module("models", real["model_type"]).transformer_config(real)
    assert round(param_count(big) / 1e6) == 5164
    assert latent_runner._lanes(big.latent.every.row) == 640
