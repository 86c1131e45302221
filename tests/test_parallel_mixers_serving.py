"""A model whose EVERY block holds two parallel mixers on one normed input
(``models/latent.py``: ``par``; a Mamba-2 recurrence's state AND K / V pages for
every layer of a sequence, constant multipliers on every projection), through
``InferenceEngineV2`` and its scheduler, against the benchmark's plain
reference at the rehearsal size of the benchmark's configuration (float32, CPU,
seeded weights).  Limits of the 1e-4 class: both sides are float32 on the same
weights, logits of std ~1, and what differs is the order of float32 sums (the
chunked scan against the one-token recurrence, pages against a dense mask):
rounding of ~1e-6, three orders under a paging, hand-over or multiplier fault."""
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import latent_runner  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

CONFIG = "benchmark/configs/falcon_h1_34b_l6_serve_1chip.json"
PAGE, CHUNK = 8, 32  # the engine's page (= the scan's chunk) and pack here
TOL = 1e-4
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)
# every constant multiplier, by the configuration key that holds it (and the place in a list)
MULTIPLIERS = [("embedding_multiplier", None), ("lm_head_multiplier", None),
               ("key_multiplier", None), ("attention_in_multiplier", None),
               ("attention_out_multiplier", None), ("ssm_in_multiplier", None),
               ("ssm_out_multiplier", None), ("ssm_multipliers", 0), ("ssm_multipliers", 1),
               ("ssm_multipliers", 2), ("ssm_multipliers", 3), ("ssm_multipliers", 4),
               ("mlp_multipliers", 0), ("mlp_multipliers", 1)]


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.hybrid and s.stateful and s.par and not s.single
    # BOTH kinds of cache for EVERY layer: a block counts as one of each mixer
    assert s.recurrence[0] == "mamba" and s.attention[0] == "gqa"
    assert s.count("mamba") == s.count("gqa") == s.count("par") == cfg.num_layers
    assert s.gqa.num_heads // s.gqa.num_kv_heads == 5  # a group that is no power of two
    assert s.mamba.state > s.mamba.head_dim and s.mamba.n_groups == 2
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


def _through_the_runner(cfg, params, prompt, steps, state_as=None):
    """One request in slot 1 of 3 on pages interleaved with nothing else's: its
    prompt in packs of ``CHUNK`` whose last is ragged, then ``steps`` decode
    ticks fed the reference-free argmax.  Returns (logit rows [1 + steps,
    vocab], the tokens fed, the cache, what each block's recurrence consumed)."""
    import jax.numpy as jnp

    n_pages = -(-(len(prompt) + steps) // PAGE)
    table = np.full((3, 16), -1, np.int32)
    table[1, :n_pages] = 2 + 2 * np.arange(n_pages)
    cache = latent_runner.init_cache(cfg, 2 * n_pages + 4, PAGE, 3, CHUNK)
    if state_as is not None:
        cache = {**cache, "ssm": tuple(a.astype(state_as) for a in cache["ssm"])}
    rows, fed, consumed = [], [], []

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
        consumed.append([{k: np.asarray(v)[:n] for k, v in p.items()} for p in seen if "ssm_x" in p])
    rows.append(np.asarray(lg[1]))
    for j in range(steps):
        fed.append(int(rows[-1].argmax()))
        tok, lens = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tok[1], lens[1] = fed[-1], len(prompt) + j
        lg, cache, seen = step(tok, lens, np.array([False, True, False]), cache)
        consumed.append([{k: np.asarray(v)[1:2] for k, v in p.items()} for p in seen if "ssm_x" in p])
        rows.append(np.asarray(lg[1]))
    joined = [{k: np.concatenate([d[b][k] for d in consumed]) for k in consumed[0][b]}
              for b in range(cfg.num_layers)]
    return np.stack(rows), fed, cache, joined


def test_chunked_prefill_and_decode_match_the_reference(model):
    """(i) Prompts of 3, 2, 4 and 1 chunks of unequal length sharing packs (the
    tail of one and the head of the next, each scanned from its own state, each
    block's K / V pages filling beside it), then decode ticks of unequal ages
    through pages and state; nothing is left, of either kind of cache."""
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
        assert len(out) == 12 and _short(ref, params, p, out) <= TOL, u
    assert eng.stats["prefill_dispatches"] < sum(-(-len(p) // CHUNK) for p in prompts.values())
    chunks = sum(-(-min(CHUNK, len(p) - a) // PAGE)
                 for p in prompts.values() for a in range(0, len(p), CHUNK))
    assert eng.stats["ssm_chunks_scanned"] == cfg.num_layers * chunks
    assert eng.stats["ssm_states_reset"] == 4 and eng.stats["ssm_states_recomputed"] == 0
    audit = eng.close()
    assert audit == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}


def test_the_runners_logits_match_the_references_full_forward(model):
    """(i) The logits themselves, float32, after a ragged last chunk and at
    every decode step through pages and state, against ONE reference forward."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 75).tolist()
    rows, fed, _, _ = _through_the_runner(cfg, params, prompt, 10)
    full = np.asarray(ref(params, np.asarray([prompt + fed], np.int32)))[0]
    want = full[len(prompt) - 1: len(prompt) + 10]
    assert want.std() > 0.1 and np.abs(rows - want).max() <= TOL


def _moved(m, key, at, factor=1.5):
    """The configuration with ONE multiplier moved from its value."""
    out = dict(m)
    if at is None:
        out[key] = m[key] * factor
    else:
        out[key] = [v * factor if i == at else v for i, v in enumerate(m[key])]
    return out


@pytest.mark.parametrize("key,at", MULTIPLIERS, ids=lambda v: str(v))
def test_every_multiplier_moves_the_logits(model, key, at):
    """(ii) No multiplier is silently 1: moved from its value in the PROGRAM's
    configuration, the runner's logits move off the reference's (which keeps the
    configuration's own), and a reference moved alike follows them."""
    m, arch, cfg, params, ref = model
    moved = _moved(m, key, at)
    cfg2 = arch.transformer_config(moved, max_seq_len=m["engine"]["max_seq_len"])
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 37).tolist()
    rows, fed, _, _ = _through_the_runner(cfg2, params, prompt, 3)
    tokens = np.asarray([prompt + fed], np.int32)
    sound = np.asarray(ref(params, tokens))[0][len(prompt) - 1: len(prompt) + 3]
    follows = np.asarray(jax.jit(lambda p, t: arch.logits(p, t, moved))(params, tokens))[0]
    follows = follows[len(prompt) - 1: len(prompt) + 3]
    assert np.abs(rows - sound).max() > 100 * TOL, "the multiplier moved nothing"
    assert np.abs(rows - follows).max() <= TOL


@pytest.mark.parametrize("side,dropped", [("ssm_out_multiplier", "mamba"),
                                          ("attention_out_multiplier", "gqa")])
def test_the_blocks_output_is_the_sum_of_the_two_sides(model, side, dropped):
    """(iii) With one side's output multiplier 0 the program gives what the
    reference gives with THAT side left out on the same weights; each side
    alone is off the whole."""
    m, arch, cfg, params, ref = model
    one_sided = dict(m, **{side: 0.0})
    cfg2 = arch.transformer_config(one_sided, max_seq_len=m["engine"]["max_seq_len"])
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, 50).tolist()
    rows, fed, _, _ = _through_the_runner(cfg2, params, prompt, 4)
    tokens = np.asarray([prompt + fed], np.int32)
    cut = slice(len(prompt) - 1, len(prompt) + 4)
    logits = lambda mm: np.asarray(jax.jit(lambda p, t: arch.logits(p, t, mm))(params, tokens))[0][cut]
    if dropped == "gqa":  # the reference's own departure: the attention side never computed
        with arch.departure("attention_dropped"):
            want = logits(m)  # (traced inside the departure)
    else:
        want = logits(one_sided)
    whole = np.asarray(ref(params, tokens))[0][cut]
    assert np.abs(rows - want).max() <= TOL
    assert np.abs(rows - whole).max() > 100 * TOL


def test_a_slots_next_owner_starts_from_zero(model):
    """(iv) One slot, two requests in turn: the second finds the first's state
    in the slot and the first's rows in re-used pages, and must read neither."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=1, num_blocks=12)
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    for u, n in ((1, 50), (2, 23)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(u, p, GREEDY(6))
        out = list(sched.run()[u])
        assert _short(ref, params, p, out) <= TOL, u
    assert eng.stats["ssm_states_reset"] == 2
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}


def test_a_preempted_sequence_is_resumed_by_recomputation(model):
    """(iv) A pool too small for every request at once: BOTH happen for the
    preempted sequence, its pages are freed and its state is left behind, and
    the resume recomputes both from position 0."""
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
        assert _short(ref, params, p, list(res[u])) <= TOL, u
    assert eng.stats["ssm_states_recomputed"] == sched.stats["preemptions"]
    assert eng.stats["ssm_states_reset"] == 4 + sched.stats["preemptions"]
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}


def test_the_gauges_say_what_both_kinds_of_cache_hold(model):
    """(iv) ``state_bytes_live`` and ``kv_page_bytes_in_use`` follow the live
    sequences: a slot's state of every block, a page of every block."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    sched = eng.scheduler
    rng = np.random.default_rng(4)
    for u, n in ((1, 20), (2, 9)):
        sched.submit(u, rng.integers(0, cfg.vocab_size, n).tolist(), GREEDY(3))
    sched.tick()  # both prompts' one pack
    mb, g, L = cfg.latent.mamba, cfg.latent.gqa, cfg.num_layers
    slot = L * (int(np.prod(mb.state_shape)) * 4 + (mb.conv - 1) * mb.conv_width * 4)
    page = L * 2 * PAGE * g.num_kv_heads * g.head_dim * 4
    assert eng.stats["state_bytes_live"] == 2 * slot
    assert eng.stats["kv_page_bytes_in_use"] == (-(-20 // PAGE) + -(-9 // PAGE)) * page
    sched.run()
    assert eng.close()["ssm_states"] == 0


@pytest.mark.parametrize("program,bodies", [
    ("jit_packed_ctx_impl", ("ssm_scan", "ssm_conv", "gqa_attn", "lm_head")),
    ("jit_decode_impl", ("ssm_step", "ssm_conv", "gqa_attn", "lm_head"))])
def test_both_programs_name_the_mixers_bodies_and_the_head(model, program, bodies):
    """The compiled pack and step carry the scopes the benchmark's readers look
    for: a traced run's ``ssm_*`` / ``gqa_attn`` entries read THIS family's bodies."""
    from deepspeed_tpu import telemetry

    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    eng.scheduler.submit(1, list(range(1, 21)), GREEDY(3))
    eng.scheduler.run()
    paths = set(telemetry.program_scopes()[program].values())
    eng.close()
    for body in bodies:
        assert any(f"/{body}/" in path + "/" for path in paths), body


@pytest.mark.parametrize("state_as,held", [(None, True), ("bfloat16", False)])
def test_the_kept_state_is_the_one_token_recurrences(model, state_as, held):
    """(v) The state each block KEEPS for the slot after chunks, a ragged
    chunk and decode steps, against the reference's float32 one-token recurrence
    on what the block's own recurrence consumed (state 16 > head 8, 2 groups:
    the small stand-in of 256 > 128, 2); a state kept in bfloat16 is told apart."""
    import jax.numpy as jnp

    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 77).tolist()
    _, _, cache, consumed = _through_the_runner(
        cfg, params, prompt, 9, None if state_as is None else jnp.dtype(state_as))
    assert len(consumed) == len(cache["ssm"]) == cfg.num_layers
    worst = 0.0
    for l, (kept, c) in enumerate(zip(cache["ssm"], consumed)):
        a = -jnp.exp(params["layers"]["par"][l]["mamba"]["a_log"])
        _, again = arch.recurrence(*(c[k][None] for k in ("ssm_x", "ssm_b", "ssm_c", "ssm_dt")), a)
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
    """(vi) Prefix caching stays refused for a recurrent state, with a message."""
    m, arch, cfg, params, ref = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)


def test_a_model_is_of_parallel_blocks_throughout_or_not_at_all(model):
    m, arch, cfg, params, ref = model
    mixed = dataclasses.replace(cfg.latent, layer_kinds=("par", "gattn"))
    with pytest.raises(ValueError, match="two parallel mixers"):
        init_params(jax.random.PRNGKey(0), cfg.replace(latent=mixed))


def test_the_tiny_preset_serves_what_its_forward_computes():
    """``get_preset("tiny_parallel_mixers")`` (the CPU tests' stand-in, no file of the
    benchmark behind it): the engine's greedy tokens through pages and state score
    within the limit of the uncached forward's best logit."""
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny_parallel_mixers")
    assert cfg.latent.par and cfg.latent.count("mamba") == cfg.latent.count("gqa") == 2
    params = init_params(jax.random.PRNGKey(3), cfg)
    forward = jax.jit(lambda p, t: CausalLM(cfg).apply(p, t)[0])
    eng = _engine(cfg, params, max_seq_len=128)
    sched = eng.scheduler
    rng = np.random.default_rng(6)
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist() for u, n in {1: 45, 2: 17}.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, GREEDY(8)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        assert _short(forward, params, p, sched.pop_result(u)) <= TOL, u
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}


def test_a_chunk_beside_decoding_rows_is_one_program_and_leaves_what_two_left(monkeypatch):
    """PR 59: this family's ENGINE mixes (a recurrence, but no routed layer).  A
    prompt of two chunks arrives while two requests decode: the tick that holds
    its first chunk makes ONE upload for ONE program (the pack, the step's two
    rows inside it) and that program is fetched ONCE, where an engine told not to
    mix makes a pack and a step; after it the two gauges read what the two
    programs left, and the requests end on the same tokens."""
    from deepspeed_tpu.models import get_preset

    cfg = get_preset("tiny_parallel_mixers")
    params = init_params(jax.random.PRNGKey(3), cfg)
    init = latent_runner.LatentRunner.__init__

    def told_not_to(self, cfg):
        init(self, cfg)
        self.packs_carry_step = False

    def run(mixes):
        with monkeypatch.context() as mp:
            if not mixes:
                mp.setattr(latent_runner.LatentRunner, "__init__", told_not_to)
            eng = _engine(cfg, params, max_seq_len=128, telemetry=True)
        assert eng.runner.packs_carry_step is eng.packs_carry_step is mixes
        sched = eng.scheduler
        rng = np.random.default_rng(6)
        draw = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(1, draw(9), GREEDY(12))
        sched.submit(2, draw(5), GREEDY(12))
        for _ in range(3):
            sched.tick()
        sched.submit(3, draw(41), GREEDY(4))  # chunks of 32 + 9, beside rows 1 and 2
        ticks = []
        for _ in range(2):  # the first chunk's tick; the second's, which fetches the first's program
            before, seen = dict(eng.stats), len(eng.telemetry.recorder.chrome_events())
            sched.tick()
            spans = [e for e in eng.telemetry.recorder.chrome_events()[seen:] if e.get("ph") == "X"]
            ticks.append(dict(
                dispatched=sorted((e["name"], e["args"].get("step_rows")) for e in spans
                                  if e["name"] in ("prefill_pack", "decode_tick")),
                fetched=[e["args"]["what"] for e in spans if e["name"] == "tick_collect"],
                delta={k: eng.stats[k] - before[k] for k in (
                    "dispatch_uploads", "prefill_dispatches", "decode_ticks", "decode_emitted",
                    "mixed_dispatches")},
                gauges=(eng.stats["state_bytes_live"], eng.stats["kv_page_bytes_in_use"])))
        kv = eng.kv
        slot = sum(a[0].nbytes for name in ("ssm", "conv") for a in kv[name])
        page = sum(a[0].nbytes for name in ("k", "v") for a in kv[name])
        sched.run()
        out = {u: sched.pop_result(u) for u in (1, 2, 3)}
        assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0, "ssm_states": 0}
        return ticks, out, slot, page

    (first, second), out, slot, page = run(True)
    assert first["dispatched"] == [("prefill_pack", 2)] and len(first["fetched"]) == 1
    assert first["delta"] == dict(dispatch_uploads=1, prefill_dispatches=1, decode_ticks=1,
                                  decode_emitted=2, mixed_dispatches=1)
    assert second["fetched"] == ["prefill_pack"]  # ONE fetch brings the pack's and the step's tokens
    (first2, second2), out2, _, _ = run(False)
    assert first2["dispatched"] == [("decode_tick", None), ("prefill_pack", None)]
    assert first2["delta"]["mixed_dispatches"] == 0 and first2["delta"]["dispatch_uploads"] >= 2
    for k in ("prefill_dispatches", "decode_ticks", "decode_emitted"):
        assert first2["delta"][k] == first["delta"][k]
    # three slots' states; 32 prompt rows' pages beside the pages of the two decoding rows
    for mixed, two in ((first, first2), (second, second2)):
        assert mixed["gauges"] == two["gauges"]
    assert first["gauges"][0] == second["gauges"][0] == 3 * slot
    assert first["gauges"][1] == (32 // PAGE + -(-(9 + 4) // PAGE) + -(-(5 + 4) // PAGE)) * page
    assert second["gauges"][1] == (-(-41 // PAGE) + -(-(9 + 5) // PAGE) + -(-(5 + 5) // PAGE)) * page
    assert out == out2 and [len(out[u]) for u in (1, 2, 3)] == [12, 12, 4]
