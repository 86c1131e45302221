"""A model of two-norm blocks whose mixer is chosen BY BLOCK (``models/latent.py``:
``LatentSpec.two_norms``; a Mamba-2 recurrence in nine blocks of ten, position-free
GQA at the configuration's own softmax scale in the tenth, a held share of
softmax-routed SwiGLU experts and a shared one behind every mixer, one constant on
both residual branches, a tied head), through ``InferenceEngineV2`` and its
scheduler, against the benchmark's plain reference at the rehearsal size of the
benchmark's configuration (float32, CPU, seeded weights).  Limits of the 1e-4
class: both sides are float32 on the same weights and the same expert picks,
logits of std ~0.5, and what differs is the order of float32 sums (the chunked scan
against the one-token recurrence, pages against a dense mask, grouped products
against a loop over experts): rounding of ~1e-6, three orders under a paging,
hand-over, routing or constant's fault."""
import dataclasses
import sys
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
from deepspeed_tpu.models.transformer import forward, init_params  # noqa: E402

CONFIG = "benchmark/configs/granite4_h_small_l10_e36_serve_1chip.json"
PAGE, CHUNK = 8, 32  # the engine's page (= the scan's chunk) and pack here
TOL = 1e-4
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)
CONSTANTS = ["embedding_multiplier", "attention_multiplier", "residual_multiplier",
             "logits_scaling"]
EMPTY = {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    # two norms a block, said ONCE: the kinds are the single-mixer family's names
    assert s.two_norms and s.hybrid and s.stateful and not s.single and not s.par
    assert set(s.layer_kinds) == set(lm.PAR_MIXERS) and s.layer_kinds[5] == "gqa"
    # what a slot keeps follows the KINDS PRESENT: nine states, one layer of pages
    assert s.recurrence == ("mamba", s.mamba) and s.attention == ("gqa", s.gqa)
    assert (s.count("mamba"), s.count("gqa"), len(s.expert_layers)) == (9, 1, 10)
    assert s.mamba.n_groups == 1 and s.gqa.rope_theta == 0 and s.gqa.scale == 0.4
    assert (s.n_routed, s.n_held, s.held_offset, s.routing) == (8, 4, 0, "softmax")
    params = init_params(jax.random.PRNGKey(7), cfg)
    # a TIED head: ONE array in the tree, the embedding's held rows
    assert "lm_head" not in params and params["embed"]["embedding"].shape == (128, 64)
    assert lm.param_count(cfg) == sum(a.size for a in jax.tree_util.tree_leaves(params))
    return m, arch, cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", PAGE)
    kw.setdefault("prefill_buckets", (CHUNK,))
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("max_seq_len", 256)
    return InferenceEngineV2(params, cfg, **kw)


@pytest.fixture(scope="module")
def ref(model):
    m, arch, cfg, params = model
    return jax.jit(lambda p, t: arch.logits(p, t, m))


def _short(ref, params, prompt, out):
    """How far under the reference's best logit the engine's greedy tokens
    score, at worst: LOGITS decide, not the tokens' identity."""
    full = np.asarray([prompt + out], np.int32)
    lg = np.asarray(ref(params, full))[0][len(prompt) - 1: len(prompt) + len(out) - 1]
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def _through_the_runner(cfg, params, prompt, steps, state_as=None):
    """One request in slot 1 of 3 on pages interleaved with nothing else's: its
    prompt in packs of ``CHUNK`` whose last is ragged, then ``steps`` decode
    ticks fed the reference-free argmax.  Returns (logit rows [1 + steps,
    vocab], the tokens fed, the cache, what each Mamba block's recurrence
    consumed, each block's expert picks [tokens, k])."""
    n_pages = -(-(len(prompt) + steps) // PAGE)
    table = np.full((3, 16), -1, np.int32)
    table[1, :n_pages] = 2 + 2 * np.arange(n_pages)
    cache = latent_runner.init_cache(cfg, 2 * n_pages + 4, PAGE, 3, CHUNK)
    if state_as is not None:
        cache = {**cache, "ssm": tuple(a.astype(state_as) for a in cache["ssm"])}
    rows, fed, seen_all = [], [], []

    def pack(tok, seg, pos, pages, last, cache):
        seen: list = []
        lg, cache = latent_runner.prefill_pack(params, cfg, tok, seg, pos, pages, last,
                                               jnp.asarray(table), cache, probe=seen)
        return lg, cache, seen

    def step(tok, lens, active, cache):
        seen: list = []
        lg, cache = latent_runner.decode_step(params, cfg, tok, lens, jnp.asarray(table), active,
                                              cache, probe=seen)
        return lg, cache, seen

    pack, step = jax.jit(pack), jax.jit(step)
    for start in range(0, len(prompt), CHUNK):
        end = min(start + CHUNK, len(prompt))
        n = end - start
        tok, seg, pos = (np.zeros(CHUNK, np.int32) for _ in range(3))
        tok[:n], seg[:n], pos[:n] = prompt[start:end], 2, np.arange(start, end)
        pages = np.full(CHUNK // PAGE, -1, np.int32)
        pages[:-(-n // PAGE)] = table[1, start // PAGE: start // PAGE - (-n // PAGE)]
        last = np.full(3, -1, np.int32)
        last[1] = n - 1
        lg, cache, seen = pack(tok, seg, pos, pages, last, cache)
        seen_all.append([{k: np.asarray(v)[:n] for k, v in p.items()} for p in seen])
    rows.append(np.asarray(lg[1]))
    for j in range(steps):
        fed.append(int(rows[-1].argmax()))
        tok, lens = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tok[1], lens[1] = fed[-1], len(prompt) + j
        lg, cache, seen = step(tok, lens, np.array([False, True, False]), cache)
        seen_all.append([{k: np.asarray(v)[1:2] for k, v in p.items()} for p in seen])
        rows.append(np.asarray(lg[1]))
    joined = [{k: np.concatenate([d[b][k] for d in seen_all]) for k in seen_all[0][b]}
              for b in range(len(seen_all[0]))]
    consumed = [p for p in joined if "ssm_x" in p]
    picks = [p["experts_picked"] for p in joined if "experts_picked" in p]
    return np.stack(rows), fed, cache, consumed, picks


def _probe(arch, m):
    """The reference's probe as ONE program (op by op, each block's scans compile
    again at every call): ``(params, tokens, forced picks or None) -> (logits, seen)``."""
    return jax.jit(lambda p, t, forced: arch.probe(p, t, m, forced=forced))


def _on_the_programs_picks(arch, params, m, prompt, fed, picks):
    """The reference's rows [1 + len(fed), vocab] on the experts the program picked."""
    tokens = np.asarray([prompt + fed], np.int32)
    forced = [jnp.asarray(p[None]) for p in picks]
    lg, _ = _probe(arch, m)(params, tokens, forced)
    return np.asarray(lg)[0][len(prompt) - 1:]


def test_chunked_prefill_and_decode_match_the_reference(model, ref):
    """Prompts of 3, 2, 4 and 1 chunks of unequal length sharing packs (the tail
    of one and the head of the next, each scanned from its own state, the one
    attention block's K / V pages filling beside the nine states), then decode
    ticks of unequal ages; nothing is left, of either kind of cache."""
    m, arch, cfg, params = model
    eng = _engine(cfg, params)
    assert not eng.runner.packs_carry_step  # a recurrence AND routed experts: two programs
    sched = eng.scheduler
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist()
               for u, n in {1: 75, 2: 41, 3: 100, 4: 9}.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, GREEDY(12)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        out = sched.pop_result(u)
        assert len(out) == 12 and _short(ref, params, p, out) <= TOL, u
    assert eng.stats["prefill_dispatches"] < sum(-(-len(p) // CHUNK) for p in prompts.values())
    chunks = sum(-(-min(CHUNK, len(p) - a) // PAGE)
                 for p in prompts.values() for a in range(0, len(p), CHUNK))
    assert eng.stats["ssm_chunks_scanned"] == 9 * chunks  # the Mamba blocks alone
    assert eng.stats["ssm_states_reset"] == 4 and eng.stats["ssm_states_recomputed"] == 0
    eng.refresh_routing_stats()
    assert 0 < eng.stats["expert_pairs_held"] < eng.stats["expert_pairs_routed"]
    assert eng.close() == EMPTY


def test_the_runners_logits_match_the_references_full_forward(model):
    """The logits themselves, float32, after a ragged last chunk and at every
    decode step through pages and state, against ONE reference forward on the
    program's own expert picks; the picks lie on the reference's router's top."""
    m, arch, cfg, params = model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 75).tolist()
    rows, fed, _, consumed, picks = _through_the_runner(cfg, params, prompt, 10)
    assert len(consumed) == 9 and len(picks) == 10 and picks[0].shape == (85, 3)
    want = _on_the_programs_picks(arch, params, m, prompt, fed, picks)
    assert want.std() > 0.1 and np.abs(rows - want).max() <= TOL
    _, seen = _probe(arch, m)(params, np.asarray([prompt + fed], np.int32), None)
    for ex, r in zip(picks, seen):
        theirs = np.take_along_axis(np.asarray(r["router_biased"])[0], ex, axis=1)
        assert (np.asarray(r["router_cutoff"])[0][:, None] - theirs).max() <= TOL


@pytest.mark.parametrize("key", CONSTANTS)
def test_each_constant_decides_a_logit(model, key):
    """No constant is silently 1 (or ``head_dim ** -0.5``): moved from its value
    in the PROGRAM's configuration, the runner's logits move off the reference's
    (which keeps the configuration's own), and a reference moved alike follows."""
    m, arch, cfg, params = model
    moved = dict(m, **{key: m[key] * 1.5})
    cfg2 = arch.transformer_config(moved, max_seq_len=m["engine"]["max_seq_len"])
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 37).tolist()
    rows, fed, _, _, picks = _through_the_runner(cfg2, params, prompt, 3)
    sound = _on_the_programs_picks(arch, params, m, prompt, fed, picks)
    follows = _on_the_programs_picks(arch, params, moved, prompt, fed, picks)
    assert np.abs(rows - sound).max() > 100 * TOL, "the constant moved nothing"
    assert np.abs(rows - follows).max() <= TOL


@pytest.mark.parametrize("name", ["softmax_scale_rsqrt", "residual_multiplier_one"])
def test_each_departure_of_the_reference_decides_a_logit(model, name):
    m, arch, cfg, params = model
    ids = np.random.default_rng(11).integers(0, m["vocab_size"], (1, 60)).astype(np.int32)
    logits = lambda: np.asarray(jax.jit(lambda p, t: arch.logits(p, t, m))(params, ids))[0]  # traced anew a call
    want = logits()
    with arch.departure(name):
        got = logits()
    assert np.abs(got - want).max() > 2e-3
    assert np.array_equal(logits(), want)  # gone with its context


def test_the_reference_in_blocks_is_the_reference(model):
    """The forward a block and a column block of the head at a time (what the chip
    has room for), on forced picks, gives the whole forward's rows and scores."""
    m, arch, cfg, params = model
    ids = np.random.default_rng(5).integers(0, m["vocab_size"], (1, 40)).astype(np.int32)
    whole, seen = _probe(arch, m)(params, ids, None)
    picks = [np.asarray(jax.lax.top_k(r["router_biased"], m["num_experts_per_tok"])[1]) for r in seen]
    rows = [0, 17, 39]
    got, scores = arch.logits_in_blocks(params, ids, m, rows, picks, cols=50)
    assert got.shape == (3, m["vocab_size"]) and np.abs(got - np.asarray(whole)[0][rows]).max() <= 1e-5
    assert len(scores) == 10 and scores[0]["router_biased"].shape == (40, 8)
    assert np.abs(scores[3]["router_cutoff"] - np.asarray(seen[3]["router_cutoff"])[0]).max() <= 1e-5
    unforced, _ = arch.logits_in_blocks(params, ids, m, rows)
    assert np.abs(unforced - got).max() <= 1e-5


def test_the_shares_add_up_to_the_uncut_layer(model):
    """The two members' routed parts plus what every member computes alike (the
    mixer, the shared expert) counted ONCE give the uncut reference's block, for
    the reference handed each share and for the program's expert layer alike; a
    pick on an absent expert keeps its place in the softmax and adds nothing; the
    vocabulary's slice is the uncut logits' columns."""
    from deepspeed_tpu.moe.layer import moe_block_held

    m, arch, cfg, params = model
    whole = dict(m, num_local_experts=8)  # every one of the 8 routed experts held
    cfg_w = arch.transformer_config(whole, max_seq_len=64)
    uncut = init_params(jax.random.PRNGKey(9), cfg_w)
    shares = [{"experts": (0, 4)}, {"experts": (4, 4)}]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    for l in (0, 5):  # a Mamba block and the attention block
        kind = arch._kinds(whole)[l]
        run = lambda p, mm: jax.jit(lambda p: arch.block(x, *arch._block_weights(p, mm, l), mm, kind))(p)
        want = run(uncut, whole)
        parts = [run(*arch.cut_to_share(uncut, whole, s)) for s in shares]
        alike = run(*arch.cut_to_share(uncut, whole, {"experts": (0, 0)}))  # mixer + shared expert
        assert np.abs(want - alike).max() > 100 * TOL and np.abs(parts[0] - parts[1]).max() > 100 * TOL
        assert np.abs(sum(parts) - alike - want).max() <= TOL
        # the program's expert layer as each member runs it, on the block's normed input
        n1, n2, mw, fw = arch._block_weights(uncut, whole, l)
        h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
        held_layer = lambda spec: jax.jit(lambda w, h: moe_block_held(w, h, spec))
        full, _ = held_layer(cfg_w.latent)(fw, h)
        held = []
        for s in shares:
            p_s, m_s = arch.cut_to_share(uncut, whole, s)
            spec = arch.transformer_config(m_s, max_seq_len=64).latent
            assert (spec.n_routed, spec.n_held, spec.held_offset) == (8, 4, s["experts"][0])
            y, (stats, picked, _) = held_layer(spec)(p_s["layers"]["moe"][l], h)
            assert 0 < int(stats[1]) < int(stats[0]) == 24 * 3  # some picks fall on the other member
            routed, shared = jax.jit(lambda w, h: arch.ffn_parts(w, h, m_s))(p_s["layers"]["moe"][l], h[None])
            assert np.abs(y - (routed + shared)[0]).max() <= TOL
            held.append(y - shared[0])
        assert np.abs(sum(held) + shared[0] - full).max() <= TOL
    # a member's whole forward: the reference handed the share is the program on the share's tree
    ids = rng.integers(0, 64, (1, 20)).astype(np.int32)
    share = {"experts": (4, 4), "vocab_rows": (0, 64)}
    p_s, m_s = arch.cut_to_share(uncut, whole, share)
    cfg_s = arch.transformer_config(m_s, max_seq_len=64)
    assert cfg_s.vocab_size == 64 and p_s["embed"]["embedding"].shape == (64, 64)
    logits = lambda **kw: np.asarray(jax.jit(lambda p, t: arch.logits(p, t, whole, **kw))(uncut, ids))
    got = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg_s)[0])(p_s, ids))
    assert np.abs(got - logits(share=share)).max() <= TOL
    # ... and a slice of the vocabulary alone is the uncut model's columns
    rows = logits(share={"vocab_rows": (0, 64)})
    assert np.abs(rows - logits()[..., :64]).max() <= TOL


def test_the_cache_holds_nine_states_and_one_layer_of_pages(model):
    m, arch, cfg, params = model
    cache = latent_runner.init_cache(cfg, 16, PAGE, 3, CHUNK)
    mb, g = cfg.latent.mamba, cfg.latent.gqa
    assert len(cache["ssm"]) == len(cache["conv"]) == 9 and len(cache["k"]) == len(cache["v"]) == 1
    assert cache["ssm"][0].shape == (3, *mb.state_shape) and cache["ssm"][0].dtype == jnp.float32
    assert cache["conv"][0].shape == (3, mb.conv - 1, mb.conv_width)
    assert cache["k"][0].shape[0] == 16 and cache["k"][0].shape[-1] == g.head_dim
    assert cache["stats"].shape[0] == cache["touched"].shape[0] == 10  # an expert layer a block


def test_a_slots_next_owner_starts_from_zero(model, ref):
    """One slot, two requests in turn: the second finds the first's states in
    the slot and the first's rows in re-used pages, and must read neither."""
    m, arch, cfg, params = model
    eng = _engine(cfg, params, max_seqs=1, num_blocks=12)
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    for u, n in ((1, 50), (2, 23)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(u, p, GREEDY(6))
        out = list(sched.run()[u])
        assert _short(ref, params, p, out) <= TOL, u
    assert eng.stats["ssm_states_reset"] == 2
    assert eng.close() == EMPTY


def test_a_preempted_sequence_is_resumed_by_recomputation(model, ref):
    """A pool too small for every request at once: the preempted sequence's pages
    (the attention block's) are freed and its nine states are left behind, and the
    resume recomputes both from position 0."""
    m, arch, cfg, params = model
    eng = _engine(cfg, params, max_seqs=3, num_blocks=24)
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    prompts = {u: rng.integers(0, cfg.vocab_size, 40 + 9 * u).tolist() for u in range(1, 5)}
    for u, p in prompts.items():
        sched.submit(u, p, GREEDY(30))
    res = sched.run()
    assert sched.stats["finished"] == 4 and sched.stats["preemptions"] >= 1
    for u, p in prompts.items():
        assert _short(ref, params, p, list(res[u])) <= TOL, u
    assert eng.stats["ssm_states_recomputed"] == sched.stats["preemptions"]
    assert eng.stats["ssm_states_reset"] == 4 + sched.stats["preemptions"]
    assert eng.close() == EMPTY


def test_the_gauges_say_what_both_kinds_of_cache_hold(model):
    """``state_bytes_live`` and ``kv_page_bytes_in_use`` follow the live sequences
    wherever a slot keeps both kinds of cache: nine blocks' states, ONE block's pages."""
    m, arch, cfg, params = model
    eng = _engine(cfg, params)
    assert set(latent_runner.CACHE_GAUGES) <= set(eng.runner.counters)
    sched = eng.scheduler
    rng = np.random.default_rng(4)
    for u, n in ((1, 20), (2, 9)):
        sched.submit(u, rng.integers(0, cfg.vocab_size, n).tolist(), GREEDY(3))
    sched.tick()  # both prompts' one pack
    mb, g = cfg.latent.mamba, cfg.latent.gqa
    slot = 9 * (int(np.prod(mb.state_shape)) * 4 + (mb.conv - 1) * mb.conv_width * 4)
    page = 1 * 2 * PAGE * g.num_kv_heads * g.head_dim * 4
    assert eng.stats["state_bytes_live"] == 2 * slot
    assert eng.stats["kv_page_bytes_in_use"] == (-(-20 // PAGE) + -(-9 // PAGE)) * page
    sched.run()
    assert eng.close()["ssm_states"] == 0


@pytest.mark.parametrize("program,bodies", [
    ("jit_packed_ctx_impl", ("ssm_scan", "ssm_conv", "gqa_attn", "router", "expert_layout",
                             "expert_matmul", "shared_expert", "lm_head")),
    ("jit_decode_impl", ("ssm_step", "ssm_conv", "gqa_attn", "router", "expert_layout",
                         "expert_matmul", "shared_expert", "lm_head"))])
def test_both_programs_name_the_blocks_bodies_and_the_head(model, program, bodies):
    """The compiled pack and step carry the scopes the benchmark's readers look
    for: the accepted ``ssm_*`` / ``gqa_attn`` / ``expert_*`` entries and this
    cell's own shares read THIS family's bodies."""
    from deepspeed_tpu import telemetry

    m, arch, cfg, params = model
    eng = _engine(cfg, params)
    eng.scheduler.submit(1, list(range(1, 21)), GREEDY(3))
    eng.scheduler.run()
    paths = set(telemetry.program_scopes()[program].values())
    eng.close()
    for body in bodies:
        assert any(f"/{body}/" in path + "/" for path in paths), body


@pytest.mark.parametrize("state_as,held", [(None, True), ("bfloat16", False)])
def test_the_kept_state_is_the_one_token_recurrences(model, state_as, held):
    """The state each Mamba block KEEPS for the slot after chunks, a ragged chunk
    and decode steps, against the reference's float32 one-token recurrence on what
    the block's own recurrence consumed (ONE group: every head reads the same B
    and C); a state kept in bfloat16 is told apart."""
    m, arch, cfg, params = model
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 77).tolist()
    _, _, cache, consumed, _ = _through_the_runner(
        cfg, params, prompt, 9, None if state_as is None else jnp.dtype(state_as))
    assert len(consumed) == len(cache["ssm"]) == 9 and consumed[0]["ssm_b"].shape[1] == 1
    worst = 0.0
    for kept, c, w in zip(cache["ssm"], consumed, params["layers"]["mamba"]):
        _, again = arch.recurrence(*(c[k][None] for k in ("ssm_x", "ssm_b", "ssm_c", "ssm_dt")),
                                   -jnp.exp(w["a_log"]))
        mine, again = np.asarray(kept[1].astype(jnp.float32)), np.asarray(again[0])
        assert c["ssm_x"].shape[0] == 77 + 9 and np.linalg.norm(again) > 0
        worst = max(worst, float(np.linalg.norm(mine - again) / np.linalg.norm(again)))
    assert (worst <= TOL) == held, worst
    if not held:
        assert worst > 10 * TOL


@pytest.mark.parametrize("says,kw", [
    ("enable_prefix_caching.*state snapshot", dict(enable_prefix_caching=True)),
    ("enable_speculation.*state-space state cannot be rolled back", dict(enable_speculation=True)),
])
def test_what_would_serve_it_wrongly_is_refused_by_name(model, says, kw):
    m, arch, cfg, params = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)


def test_the_toy_preset_serves_through_the_engine_and_the_uncached_forward():
    """``get_preset("tiny_block_mixers")`` (3 Mamba blocks : 1 attention block, 4 of 8
    experts held, a tied head): the engine's greedy tokens score at the best logit
    of ``CausalLM``'s uncached forward on the same weights."""
    from deepspeed_tpu.models.presets import get_preset

    cfg = get_preset("tiny_block_mixers", max_seq_len=256, dtype="float32")
    s = cfg.latent
    assert s.two_norms and (s.count("mamba"), s.count("gqa")) == (3, 1) and cfg.tie_embeddings
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert "lm_head" not in params
    uncached = jax.jit(lambda p, t: forward(p, t, cfg)[0])
    eng = _engine(cfg, params)
    sched = eng.scheduler
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist() for u, n in {1: 75, 2: 41, 3: 9}.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, GREEDY(6)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        out = sched.pop_result(u)
        lg = np.asarray(uncached(params, np.asarray([p + out], np.int32)))[0]
        lg = lg[len(p) - 1: len(p) + len(out) - 1]
        assert lg.std() > 0.1 and (lg.max(-1) - lg[np.arange(len(out)), out]).max() <= TOL, u
    assert len(eng.kv["ssm"]) == 3 and len(eng.kv["k"]) == 1
    assert eng.close() == EMPTY


def test_the_kinds_names_alone_are_the_single_mixer_familys(model):
    """``mamba`` / ``gqa`` without ``two_norms`` are blocks of ONE norm (no experts
    behind a mixer): the spec says which ONCE, and the families read that."""
    m, arch, cfg, params = model
    one_norm = dataclasses.replace(cfg.latent, two_norms=False)
    assert one_norm.single and not one_norm.hybrid
    assert one_norm.recurrence[0] == "mamba" and one_norm.attention[0] == "gqa"
    mixed = dataclasses.replace(cfg.latent, layer_kinds=("mamba", "gattn"))
    assert mixed.hybrid and not mixed.single  # (a family of its own flag would need a fourth)


@pytest.mark.parametrize("preset,config", [
    ("tiny_parallel_mixers", None),
    (None, "benchmark/configs/nemotron3_super_l11_e128_serve_1chip.json")])
def test_the_new_constants_at_their_defaults_leave_the_other_families_programs_alone(preset, config):
    """``residual_multiplier`` 1.0 and ``Gqa.scale`` None are what the state-space
    cells' toys carry, a branch at 1.0 is the plain sum (no multiply, no convert),
    and the softmax scale spelled out as ``head_dim ** -0.5`` gives the SAME logits
    bit for bit through the runner: the seams pass the kernels' own default on."""
    from deepspeed_tpu.models.presets import get_preset

    if preset:
        cfg = get_preset(preset, max_seq_len=128, dtype="float32")
    else:
        m = harness.rehearsed(harness.load_json(ROOT / config), True)
        cfg = harness.module("models", m["model_type"]).transformer_config(m, max_seq_len=128)
    s = cfg.latent
    assert s.residual_multiplier == 1.0 and s.gqa.scale is None and not s.two_norms
    x, y = jnp.ones((4, 8), jnp.bfloat16), jnp.full((4, 8), 0.3, jnp.bfloat16)
    ops = [e.primitive.name for e in jax.make_jaxpr(lambda a, b: lm.residual(a, b, 1.0))(x, y).eqns]
    assert ops == ["add"]
    params = init_params(jax.random.PRNGKey(1), cfg)
    spelled = dataclasses.replace(cfg, latent=dataclasses.replace(
        s, gqa=dataclasses.replace(s.gqa, scale=float(s.gqa.head_dim) ** -0.5)))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 21).tolist()
    rows = [_through_the_runner(c, params, prompt, 3)[0] for c in (cfg, spelled)]
    assert np.array_equal(*rows) and rows[0].std() > 0
