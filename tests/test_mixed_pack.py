"""A pack that carries the tick's decode step (PR 54, S2): where the scheduler
plans prompt chunks AND decoding rows one ahead on an engine whose packs take
the step's rows (``packs_carry_step``: the dense runner and, PR 56, a
``LatentRunner`` of every family; no mesh, no offload), ONE program runs both.
CPU, a tiny dense model and one tiny model of each ``cfg.latent`` family: the
tokens are those of the back-to-back order (a pack, then a step), the counts
say which dispatches were mixed; never a time."""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import scheduler as S
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, unpack_pack
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params

KW = dict(max_seqs=4, num_blocks=64, block_size=8, seed=3, telemetry=True,
          prefill_buckets=(16, 32), prefill_chunk=16)


@pytest.fixture(scope="module")
def model():
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)


def _tokens(n, seed, vocab=255):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


class _Watch:
    """What the engine's two pack programs were handed and what its pack
    dispatches returned: (program, live step rows) a call, the handles."""

    def __init__(self, eng):
        self.calls, self.handles, self.dead_in_step = [], [], 0
        slots, pages, bs = eng.mgr.max_seqs, eng.max_pages, eng.block_size
        for name, ctx in (("_packed_prefill_jit", False), ("_packed_prefill_ctx_jit", True)):
            real = getattr(eng, name)

            def spy(*args, real=real, name=name, ctx=ctx):
                rows = unpack_pack(np.asarray(args[1]), bs, slots, pages, ctx, True)[-1]
                self.calls.append((name, int(rows[2].sum())))
                return real(*args)

            setattr(eng, name, spy)
        dispatch, collect = eng.pack_dispatch, eng.pack_collect

        def dispatched(*a, **kw):
            self.handles.append(dispatch(*a, **kw))
            return self.handles[-1]

        def collected(done, out, dead=()):
            self.dead_in_step += len({s.uid for s in done.step} & set(dead))
            return collect(done, out, dead=dead)

        eng.pack_dispatch, eng.pack_collect = dispatched, collected

    def mixed(self, name):
        return [n for prog, n in self.calls if prog == name and n]


def _serve(model, schedule, back_to_back=False, **kw):
    """``schedule``: {call: [(uid, prompt, sampling)]}, submitted before that
    call of ``tick()``; ticks until idle.  ({uid: tokens}, engine, watch)."""
    cfg, params = model
    eng = InferenceEngineV2(params, cfg, **{**KW, **kw})
    watch = _Watch(eng)
    sched = eng.scheduler
    if back_to_back:
        sched._back_to_back = lambda: "test"
    n, uids = 0, []
    while not sched.idle or n <= max(schedule):
        for uid, prompt, samp in schedule.get(n, ()):
            sched.submit(uid, prompt, samp)
            uids.append(uid)
        sched.tick()
        n += 1
        assert len(sched._inflight) <= 1 and n < 500
    return {u: sched.result(u) for u in uids}, eng, watch


def _closed(eng, cached=False):
    audit = eng.close()
    assert audit["blocks_in_use"] == 0 and (cached or not any(audit.values())), audit


LONG = SamplingParams(max_new_tokens=14)


def _scene(name, model):
    """(schedule, engine options) of one case: request 1 decodes while the
    others' prompts arrive."""
    a = (1, _tokens(6, 1), LONG)
    if name == "cold":  # one chunk from position 0: the flash pack carries the step
        return {0: [a], 3: [(2, _tokens(10, 2), LONG)]}, {}
    if name == "ctx":  # chunks 2 and 3 attend cached pages: the context pack does
        return {0: [a], 3: [(2, _tokens(40, 2), LONG)]}, {}
    if name == "prefix_hit":  # the second prompt's first chunk starts on 3 cached pages
        shared = _tokens(24, 5)
        return {0: [(3, shared + [9, 8], SamplingParams(max_new_tokens=2))],
                6: [a], 9: [(2, shared + _tokens(5, 6), LONG)]}, dict(enable_prefix_caching=True)
    if name == "completes":  # a prompt of exactly one chunk: sampled in the tick that steps 1
        return {0: [a], 3: [(2, _tokens(16, 2), LONG)]}, {}
    if name == "dead_row":  # 1 stops while a step enqueued behind a chunk carries it
        clean, eng, _ = _serve(model, {0: [a]})
        _closed(eng)
        stop = SamplingParams(max_new_tokens=14, stop_token=clean[1][5])
        return {0: [(1, a[1], stop)], 2: [(2, _tokens(64, 2), LONG)]}, {}
    if name == "pool":  # growth finds the pool short: the plan drains and preempts
        return ({0: [(u + 1, _tokens(n, u), SamplingParams(max_new_tokens=24))
                     for u, n in enumerate((14, 15, 13))]},
                dict(num_blocks=11, kv_watermark=0.0))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["cold", "ctx", "prefix_hit", "completes", "dead_row", "pool"])
def test_greedy_tokens_of_mixed_dispatches_are_the_back_to_back_orders(model, name):
    schedule, kw = _scene(name, model)
    got, eng, watch = _serve(model, schedule, **kw)
    want, ref, ref_watch = _serve(model, schedule, back_to_back=True, **kw)
    assert got == want and all(got.values())
    s, sched = eng.stats, eng.scheduler
    # the same engine, the same two pack programs: the back-to-back order's
    # packs carry no live row and its steps are programs of their own
    assert eng.packs_carry_step and ref.packs_carry_step
    assert ref.stats["mixed_dispatches"] == 0 and not any(n for _, n in ref_watch.calls)
    assert s["mixed_dispatches"] == len([n for _, n in watch.calls if n]) > 0
    # a mixed dispatch is a pack AND a tick: every token still counts once
    assert s["decode_emitted"] == sum(
        len(h.step) for h in watch.handles) + _steps_alone(eng)
    for h in watch.handles:  # no sequence in a pack's entries and among its step's rows
        assert not {id(e[0]) for e in h.rows} & {id(q) for q in h.step}
    spans = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    packs = [e for e in spans if e["name"] == "prefill_pack"]
    assert [e["args"]["step_rows"] for e in packs] == [n for _, n in watch.calls]
    assert sum(1 for e in spans if e["name"] == "decode_tick") == s["decode_ticks"] - s["mixed_dispatches"]
    if name == "cold":
        assert watch.mixed("_packed_prefill_jit") and not watch.mixed("_packed_prefill_ctx_jit")
    if name == "ctx":
        assert len(watch.mixed("_packed_prefill_ctx_jit")) == 2
    if name == "prefix_hit":
        assert eng.mgr.cached_prompt_tokens == 24 == ref.mgr.cached_prompt_tokens
        assert watch.mixed("_packed_prefill_ctx_jit") and not watch.mixed("_packed_prefill_jit")
    if name == "completes":
        assert any(h.finishing and h.step for h in watch.handles)
    if name == "dead_row":
        assert s["ahead_rows_dropped"] == 1 == watch.dead_in_step
        assert sched.requests[1].state == S.FINISHED and len(got[1]) == 5
    if name == "pool":
        assert sched.drains.get("pool", 0) >= 1 and sched.stats["preemptions"] >= 1
        assert sched.stats["preemptions"] == ref.scheduler.stats["preemptions"]
    else:
        assert s["ahead_drains"] == 0
    _closed(eng, cached=name == "prefix_hit")
    _closed(ref, cached=name == "prefix_hit")


def _steps_alone(eng):
    spans = [e for e in eng.telemetry.recorder.chrome_events()
             if e.get("ph") == "X" and e["name"] == "decode_tick"]
    return sum(e["args"]["batch"] for e in spans)


def test_sampled_tokens_and_the_key_follow_the_two_programs_order(model):
    """The key's order (ISSUE 54, point 4): a mixed program splits the key for
    its pack and then for its step, as the two programs did in turn, so a run
    under sampling draws what the back-to-back order draws, call by call, and
    leaves the same key behind; a pack with no live row splits it once."""
    samp = SamplingParams(temperature=0.8, max_new_tokens=9)
    schedule = {0: [(u + 1, _tokens(n, u), samp) for u, n in enumerate((5, 40, 17))]}
    got, eng, watch = _serve(model, schedule)
    want, ref, _ = _serve(model, schedule, back_to_back=True)
    assert eng.stats["mixed_dispatches"] >= 2 and ref.stats["mixed_dispatches"] == 0
    assert got == want and all(len(t) == 9 for t in got.values())
    key = lambda e: np.asarray(jax.random.key_data(e._rng))
    assert (key(eng) == key(ref)).all()
    greedy, other, _ = _serve(model, {0: [(u, p, SamplingParams(max_new_tokens=9))
                                          for u, p, _ in schedule[0]]})
    assert greedy != got  # the key is really drawn from
    # programs run: the reference's count is the mixed run's plus one a mixed dispatch
    programs = lambda e: e.stats["decode_ticks"] + e.stats["prefill_dispatches"] - e.stats["mixed_dispatches"]
    assert programs(ref) == programs(eng) + eng.stats["mixed_dispatches"]
    for e in (eng, ref, other):
        _closed(e)


def test_a_step_rides_only_where_the_pack_programs_take_it(model):
    """A pack handed a step on an engine whose packs carry none, or a sequence
    both in the pack and in its step, is refused before anything is built."""
    cfg, params = model
    eng = InferenceEngineV2(params, cfg, **KW)
    eng.put([1, 2], [_tokens(6, 1), _tokens(7, 2)], SamplingParams())
    a, b = eng.mgr.seqs[1], eng.mgr.seqs[2]
    c = eng.mgr.admit(3, _tokens(9, 3))
    eng.mgr.ensure_capacity(c, 0)
    with pytest.raises(ValueError, match="no sequence twice"):
        eng.pack_dispatch([(c, 0, 9)], SamplingParams(), step=[a, c])
    eng.packs_carry_step = False
    with pytest.raises(ValueError, match="packs_carry_step"):
        eng.pack_dispatch([(c, 0, 9)], SamplingParams(), step=[a, b])
    eng.packs_carry_step = True
    done = eng.pack_dispatch([(c, 0, 9)], SamplingParams(), step=[a, b])
    first = {}
    toks = eng.pack_collect(done, first)
    assert set(first) == {3} and set(toks) == {1, 2}
    assert a.tokens[-1] == toks[1] and c.tokens[-1] == first[3] and a.pending == 0
    assert eng.stats["mixed_dispatches"] == 1 == eng.stats["decode_ticks"]
    eng.flush([1, 2, 3])
    _closed(eng)


# -- a LatentRunner of each family (PR 56) ------------------------------------
sys.path.insert(0, str(Path(__file__).resolve().parent))
from test_mixed_program import FAMILIES, _latent_cfg, every_family_carries  # noqa: E402,F401

LATENT_KW = dict(prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)


@functools.lru_cache(maxsize=None)
def _latent(family):
    cfg = _latent_cfg(family)
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def _both_orders(family, schedule, **kw):
    """``schedule`` served one ahead and in the back-to-back order: the same
    tokens; ((tokens, engine, watch), the reference's)."""
    model = _latent(family)
    got = _serve(model, schedule, **{**LATENT_KW, **kw})
    want = _serve(model, schedule, back_to_back=True, **{**LATENT_KW, **kw})
    assert got[0] == want[0] and all(got[0].values())
    assert got[1].packs_carry_step and want[1].packs_carry_step
    assert want[1].stats["mixed_dispatches"] == 0 and not any(n for _, n in want[2].calls)
    return got, want


@pytest.mark.parametrize("scene", ["chunks", "dead_row"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_latent_engines_mixed_dispatches_give_the_back_to_back_orders_tokens(
        family, scene, every_family_carries):
    """``chunks``: while request 1 decodes, a prompt of three chunks arrives (a
    cold chunk, a continuation chunk, and a last one that completes it: the
    prompt joins the NEXT step) and then a prompt of one chunk; every pack
    beside a decoding row carries it.  ``dead_row``: request 1 stops while a
    step that a chunk carries holds its row."""
    vocab = _latent(family)[0].vocab_size
    a = (1, _tokens(6, 1, vocab), LONG)
    if scene == "chunks":
        schedule = {0: [a], 3: [(2, _tokens(70, 2, vocab), LONG)], 5: [(3, _tokens(20, 3, vocab), LONG)]}
    else:
        clean, eng, _ = _serve(_latent(family), {0: [a]}, **LATENT_KW)
        _closed(eng)
        # (the first of its tokens that did not come before: the stop must not fall earlier)
        at = next(i for i in range(4, 14) if clean[1][i] not in clean[1][:i])
        stop = SamplingParams(max_new_tokens=14, stop_token=clean[1][at])
        schedule = {0: [(1, a[1], stop)], at - 3: [(2, _tokens(120, 2, vocab), LONG)]}
    (got, eng, watch), (_, ref, _) = _both_orders(family, schedule)
    s = eng.stats
    assert s["mixed_dispatches"] == len(watch.mixed("_packed_prefill_ctx_jit")) > 0
    assert not watch.mixed("_packed_prefill_jit")  # a latent engine has ONE pack program
    assert s["decode_emitted"] == sum(len(h.step) for h in watch.handles) + _steps_alone(eng)
    for h in watch.handles:
        assert not {id(e[0]) for e in h.rows} & {id(q) for q in h.step}
    spans = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    packs = [e for e in spans if e["name"] == "prefill_pack"]
    assert [e["args"]["step_rows"] for e in packs] == [n for _, n in watch.calls]
    assert s["ahead_drains"] == 0
    if scene == "chunks":
        # every pack of requests 2 and 3 stood beside request 1's row, and carried it
        assert all(n for _, n in watch.calls[1:])
        assert any(h.finishing and h.step for h in watch.handles)
        assert any(e[1] > 0 for h in watch.handles if h.step for e in h.rows)  # a continuation chunk
    else:
        assert s["ahead_rows_dropped"] == 1 == watch.dead_in_step
        assert eng.scheduler.requests[1].state == S.FINISHED and len(got[1]) < 14
    _closed(eng)
    _closed(ref)


def test_a_step_row_that_fills_its_window_inside_a_mixed_tick_gives_its_pages_back():
    """EVA attention, window 32, pages of 8: request 1's decode crosses position
    31 while request 2's chunks are packed, so the tick that writes the window's
    last position is a MIXED one: the window's 4 exact pages go back to the pool
    at that dispatch, the slot's table is rewritten, and the tokens are the
    back-to-back order's."""
    family = "eva"
    vocab = _latent(family)[0].vocab_size
    schedule = {0: [(1, _tokens(26, 1, vocab), LONG)],
                2: [(2, _tokens(120, 2, vocab), SamplingParams(max_new_tokens=4))]}
    closes = []

    def served(back_to_back):
        cfg, params = _latent(family)
        eng = InferenceEngineV2(params, cfg, **{**KW, **LATENT_KW})
        sched = eng.scheduler
        if back_to_back:
            sched._back_to_back = lambda: "test"
        dispatch, close = eng.pack_dispatch, eng.mgr.close_window
        riding = []

        def dispatched(entries, *a, step=(), **kw):
            riding[:] = [s.uid for s in step]
            try:
                done = dispatch(entries, *a, step=step, **kw)
            finally:
                riding.clear()
            for seq in step:  # the table the NEXT program is handed is the shrunk one
                row = eng._tables_np[seq.slot]
                assert row[row >= 0].tolist() == list(seq.blocks)
            return done

        def closing(seq, n):
            before = len(seq.blocks)
            got = close(seq, n)
            if not back_to_back:
                closes.append((seq.uid, n, seq.uid in riding, got))
            assert before - len(seq.blocks) == got
            return got

        eng.pack_dispatch, eng.mgr.close_window = dispatched, closing
        n = 0
        while not sched.idle or n <= 2:
            for uid, prompt, samp in schedule.get(n, ()):
                sched.submit(uid, prompt, samp)
            sched.tick()
            n += 1
            assert n < 300
        return {u: sched.result(u) for u in (1, 2)}, eng

    got, eng = served(False)
    want, ref = served(True)
    assert got == want and len(got[1]) == 14
    # request 1 (26 + 14 tokens) closed its first window as a STEP row of a mixed tick
    assert (1, 32, True, 4) in closes
    assert any(uid == 2 and not rode for uid, _, rode, _ in closes)  # and request 2's as chunks
    assert eng.stats["eva_windows_closed"] == len(closes) == ref.stats["eva_windows_closed"]
    assert eng.stats["eva_pages_returned"] == 4 * len(closes) == ref.stats["eva_pages_returned"]
    assert eng.stats["mixed_dispatches"] > 0 == ref.stats["mixed_dispatches"]
    _closed(eng)
    _closed(ref)


@pytest.mark.parametrize("family", ["single", "deltanet"])
def test_a_recurrences_state_left_by_a_preemption_is_recomputed_under_mixed_ticks(
        family, every_family_carries):
    """A pool too small for three answers: growth drains the plan and preempts;
    the state the victim's slot held is left behind (``_discarded``) and its
    resume scans from position 0, in packs that carry the others' steps."""
    vocab = _latent(family)[0].vocab_size
    schedule = {0: [(u + 1, _tokens(n, u, vocab), SamplingParams(max_new_tokens=24))
                    for u, n in enumerate((14, 15, 13))]}
    (got, eng, watch), (_, ref, _) = _both_orders(
        family, schedule, num_blocks=11, kv_watermark=0.0)
    assert all(len(t) == 24 for t in got.values())
    assert eng.scheduler.stats["preemptions"] == ref.scheduler.stats["preemptions"] >= 1
    assert eng.scheduler.drains.get("pool", 0) >= 1
    assert eng.stats["ssm_states_recomputed"] == ref.stats["ssm_states_recomputed"] >= 1
    assert eng.stats["ssm_states_reset"] == ref.stats["ssm_states_reset"] >= 4
    assert eng.stats["mixed_dispatches"] > 0 and eng.runner._discarded == 0
    _closed(eng)
    _closed(ref)
