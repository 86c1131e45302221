"""What only a model of EVA attention has (``models/latent.py:HYBRID`` with
``eva``: plain multi-head attention over the exact keys of the query's own
window and one learned summary per chunk of every window before it, on K / V
pages that are GIVEN BACK while their sequence lives), at the rehearsal size of
the benchmark's configuration of it (float32, CPU, seeded weights; window 32,
chunk 4, page 8: a window is 4 exact pages + its summary page, and 4 of the 5 go
back when it closes): the runner's two bodies and the engine's scheduler against
the reference's LOGITS across window closes, the table's arithmetic, the pool's
free count, the order of page reuse one ahead, preemption, refusals."""
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
from deepspeed_tpu.inference.ragged import (SequenceDescriptor, StateManager,  # noqa: E402
                                            WindowCompaction)
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import latent as lm  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

CONFIG = "benchmark/configs/evabyte_l8_serve_1chip.json"
PAGE, CHUNK, WINDOW, SUMMARY = 8, 32, 32, 4  # the engine's page and pack here; the model's window and chunk
GREEDY = lambda n: SamplingParams(temperature=0.0, max_new_tokens=n)
COMPACT = WindowCompaction(WINDOW, SUMMARY)


@pytest.fixture(scope="module")
def model():
    m = harness.rehearsed(harness.load_json(ROOT / CONFIG), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    s = cfg.latent
    assert s.hybrid and s.stateful and not s.ringed and not s.single
    assert s.layer_kinds == ("eva", "eva") and s.first_dense == 2 and s.expert_layers == ()
    assert (s.eva.window, s.eva.chunk, s.pred_heads, s.unit_offset) == (WINDOW, SUMMARY, 2, True)
    params = init_params(jax.random.PRNGKey(7), cfg)
    assert params["lm_head"]["kernel"].shape == (64, 2 * m["vocab_size"])
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


def _through_the_bodies(cfg, params, seq, n, chunks, slots=3, slot=2, spare=3):
    """``seq``'s first ``n`` positions in packs cut at ``chunks`` (ends), the rest
    a tick at a time, through ``latent_runner``'s two bodies on a table a block
    manager with the compaction keeps; yields (position whose next-token logits
    these are, logits, the manager, the sequence, the cache)."""
    mgr = StateManager(40, PAGE, slots)
    mgr.compaction = COMPACT
    mgr.allocators[0].allocate(spare)  # so that the table's pages do not start at 0
    s = SequenceDescriptor(uid=1, slot=slot)
    table = np.full((slots, 32), -1, np.int32)

    def tabled():
        table[slot] = -1
        table[slot, :len(s.blocks)] = s.blocks

    cache = latent_runner.init_cache(cfg, 40, PAGE, slots, CHUNK)
    pack = jax.jit(lambda *a: latent_runner.prefill_pack(params, cfg, *a))
    start = 0
    for end in chunks:
        mgr.ensure_pages(s, end)
        tabled()
        tok, seg, pos = (np.zeros(CHUNK, np.int32) for _ in range(3))
        tok[:end - start], seg[:end - start] = seq[start:end], slot + 1
        pos[:end - start] = np.arange(start, end)
        pp = np.full(CHUNK // PAGE, -1, np.int32)
        used, col = -(-(end - start) // PAGE), COMPACT.column(start, PAGE)
        pp[:used] = s.blocks[col: col + used]
        last = np.full(slots, -1, np.int32)
        last[slot] = end - start - 1
        lg, cache = pack(tok, seg, pos, pp, last, table, cache)
        if end % WINDOW == 0:
            mgr.close_window(s, end)
        yield end - 1, np.asarray(lg)[slot], mgr, s, cache
        start = end
    dec = jax.jit(lambda *a: latent_runner.decode_step(params, cfg, *a))
    active = np.arange(slots) == slot
    for p in range(n, len(seq)):
        mgr.ensure_pages(s, p + 1)
        tabled()
        t1, lens = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        t1[slot], lens[slot] = seq[p], p
        lg, cache = dec(t1, lens, table, active, cache)
        if (p + 1) % WINDOW == 0:
            mgr.close_window(s, p + 1)
        yield p, np.asarray(lg)[slot], mgr, s, cache


def test_the_bodies_logits_match_the_reference_across_two_window_closes(model):
    """Prefill in chunks (a pack closes window 0), then decode (ticks close
    windows 1 and 2): the LOGITS at every chunk's last position and of every
    step against the reference's full forward at 1e-4; after every dispatch the
    table holds what ``n`` positions keep and the pool misses exactly those pages."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(3)
    n, steps = 50, 55
    seq = rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
    want = np.asarray(ref(params, seq[None]))[0]
    seen = 0
    for p, lg, mgr, s, cache in _through_the_bodies(cfg, params, seq, n, (32, 50)):
        assert np.abs(lg - want[p]).max() <= 1e-4, p
        written = p + 1
        held = written // WINDOW if written % WINDOW == 0 else COMPACT.pages_for(written, PAGE)
        assert len(s.blocks) == held, p
        assert mgr.allocator.free_blocks == 40 - 3 - held, p
        seen += 1
    assert seen == 2 + steps and len(s.blocks) == COMPACT.pages_for(n + steps, PAGE)
    assert len(cache["k"]) == 2 and cache["k"][0].shape == (40, PAGE, 4, 16)


def test_the_kept_summaries_are_the_references_and_rotary_follows_the_position(model):
    """Past the first close a position and its row are different numbers: the
    summaries the pages keep are the reference's ``k~, v~`` (pooled from keys
    rotated by POSITION), row ``p // 32 * 8 + p % 32`` of the table holds
    position ``p``'s exact key, and a reference whose rotary is fed the row
    reads other logits."""
    m, arch, cfg, params, ref = model
    rng = np.random.default_rng(4)
    n, steps = 70, 9
    seq = rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
    *_, (p, lg, mgr, s, cache) = _through_the_bodies(cfg, params, seq, n, (32, 64, 70))
    _, seen = jax.jit(lambda p, t: arch.probe(p, t, m))(params, seq[None])
    chunks = (n + steps) // SUMMARY
    for layer, r in enumerate(seen):
        for mine, theirs in ((cache["k"][layer], r["eva_k"]), (cache["v"][layer], r["eva_v"])):
            kept = np.asarray(mine)[np.asarray(s.blocks[:3])].reshape(-1, 4, 16)[:chunks]
            assert np.abs(kept - np.asarray(theirs)[0, :chunks]).max() <= 1e-5, layer
    # position 77 (window 2, the 14th of its window): its exact row, by the table
    written = n + steps
    assert COMPACT.rows_live(written) == 2 * 8 + 15 == arch.rows(written, m)
    page = s.blocks[COMPACT.column(77, PAGE)]
    with jax.default_matmul_precision("highest"):
        h = lm.rms_centred(params["embed"]["embedding"][seq[77:78]].astype(np.float32),
                           params["layers"]["attn_norm"]["scale"][0], cfg.norm_eps)
        _, k77, _ = lm.eva_inputs(params["layers"]["eva"][0], h, np.asarray([77]), cfg.latent.eva)
    assert np.abs(np.asarray(cache["k"][0])[page, 77 % PAGE] - np.asarray(k77)[0]).max() <= 1e-5
    want = np.asarray(ref(params, seq[None]))[0]
    with arch.departure("row_for_position"):
        other = np.asarray(jax.jit(lambda p, t: arch.logits(p, t, m))(params, seq[None]))[0]
    assert np.abs(other[:32] - want[:32]).max() <= 1e-5   # row = position inside window 0
    assert np.abs(other[40:] - want[40:]).max() > 1e-2


def test_a_pack_is_the_ticks_one_by_one(model):
    """The same 40 positions as two packs and as one pack + 8 ticks: the same
    logits after position 39 and the same rows kept."""
    m, arch, cfg, params, ref = model
    seq = np.random.default_rng(5).integers(0, cfg.vocab_size, 41).astype(np.int32)
    *_, (pa, la, _, sa, ca) = _through_the_bodies(cfg, params, seq[:40], 40, (32, 40))
    *_, (pb, lb, _, sb, cb) = _through_the_bodies(cfg, params, seq[:40], 32, (32,))
    assert pa == pb == 39 and np.abs(la - lb).max() <= 1e-5
    assert sa.blocks == sb.blocks
    for a, b in zip(ca["k"] + ca["v"], cb["k"] + cb["v"]):
        rows = np.asarray(sa.blocks)
        assert np.abs(np.asarray(a)[rows] - np.asarray(b)[rows])[:, :1].max() <= 1e-5


def test_chunked_prefill_shared_packs_and_unequal_ages_match_the_reference(model):
    """Prompts of 3, 2, 5 and 1 chunks sharing packs, chunks cut at the windows'
    edges, then decode ticks of unequal ages, through the engine and its
    scheduler one ahead; the host's counts are the positions' arithmetic;
    nothing is left."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    sched = eng.scheduler
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, cfg.vocab_size, n).tolist()
               for u, n in {1: 75, 2: 41, 3: 150, 4: 9}.items()}
    for u, p in prompts.items():
        assert sched.try_submit(u, p, GREEDY(40)).accepted
    sched.run(wait_for=list(prompts))
    for u, p in prompts.items():
        out = sched.pop_result(u)
        assert len(out) == 40 and _short(ref, params, p, out) <= 1e-4, u
    written = [len(p) + 39 for p in prompts.values()]
    closed = sum(n // WINDOW for n in written)
    assert eng.stats["eva_windows_closed"] == closed
    assert eng.stats["eva_pages_returned"] == closed * (WINDOW // PAGE)
    # the ticks' queries, at positions len(p) .. len(p) + 38: a summary row per chunk of
    # the windows before, the exact rows of their own window up to themselves
    at = [q for p in prompts.values() for q in range(len(p), len(p) + 39)]
    assert eng.stats["eva_summary_rows_read"] == sum(q // WINDOW * 8 for q in at)
    assert eng.stats["eva_exact_rows_read"] == sum(q % WINDOW + 1 for q in at)
    assert eng.stats["dispatched_ahead"] > 0
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0}


def test_no_chunk_crosses_a_windows_edge(model):
    """The scheduler cuts a prompt's chunks at every 32nd position whatever the
    budget left it, and the engine refuses a chunk that crosses one."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, telemetry=True)  # (a request's trace keeps its chunks)
    sched = eng.scheduler
    rng = np.random.default_rng(6)
    for u, n in ((1, 24), (2, 100)):  # the second starts its chunks at a budget of 8
        sched.submit(u, rng.integers(0, cfg.vocab_size, n).tolist(), GREEDY(3))
    sched.run()
    for u in (1, 2):
        start = 0
        for _, _, n in sched.requests[u].trace.chunks:
            assert start // WINDOW == (start + n - 1) // WINDOW, (u, start, n)
            start += n
    seq = eng.mgr.admit(9, list(range(40)))
    eng.mgr.ensure_pages(seq, 40)
    with pytest.raises(ValueError, match="crosses a window's edge"):
        eng.prefill_entries([(seq, 24, 40)], GREEDY(1))
    eng.mgr.release(9)
    eng.close()


def test_a_slots_second_owner_finds_the_first_ones_pages_gone(model):
    """One slot, two requests in turn: the second's table starts empty, on pages
    the first gave back (some while it lived), and reads none of its rows."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=1, num_blocks=16)
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    for u, n in ((1, 90), (2, 45)):
        p = rng.integers(0, cfg.vocab_size, n).tolist()
        sched.submit(u, p, GREEDY(6))
        out = list(sched.run()[u])
        assert _short(ref, params, p, out) <= 1e-4, u
        assert eng.mgr.allocator.free_blocks == 16
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0}


def test_a_preempted_sequence_is_resumed_from_position_zero(model):
    """A pool too small for every request at once (a window in the filling holds
    5 pages, four of them at once do not fit beside the summaries): a chunk or a
    row that finds the pool dry preempts the youngest, whose table is dropped,
    and the resume recomputes from the tokens."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=4, num_blocks=18)
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    prompts = {u: rng.integers(0, cfg.vocab_size, 50 + 21 * u).tolist() for u in range(1, 5)}
    for u, p in prompts.items():
        sched.submit(u, p, GREEDY(45))
    res = sched.run()
    assert sched.stats["finished"] == 4 and sched.stats["preemptions"] >= 1
    for u, p in prompts.items():
        assert _short(ref, params, p, list(res[u])) <= 1e-4, u
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0}


def test_a_returned_page_is_handed_out_only_after_the_execution_that_last_read_it(model):
    """One ahead: every program's call is numbered; a page a close gives back was
    last read by the execution that wrote the window's last position (the call
    before the free), and whoever holds it next first shows it to a LATER call."""
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params, max_seqs=3, num_blocks=22)
    calls, freed = [], []   # (number, {slot: pages its table showed}); (number, slot, pages)

    def numbered(name, tables_of):
        jitted = getattr(eng, name)

        def call(*args):
            calls.append({slot: set(row[row >= 0].tolist())
                          for slot, row in enumerate(tables_of(args))})
            return jitted(*args)

        setattr(eng, name, call)

    from deepspeed_tpu.inference.engine_v2 import unpack_pack

    # (a slot that is not live in the call keeps a row nobody reads: shown as empty)
    numbered("_decode_jit", lambda a: np.where(
        np.asarray(a[1])[2][:, None] != 0, np.asarray(a[2]), -1))
    numbered("_packed_prefill_ctx_jit", lambda a: np.asarray(
        unpack_pack(np.asarray(a[1]), PAGE, 3, eng.max_pages, True, eng.packs_carry_step)[5]))
    close = eng.mgr.close_window

    def closing(seq, n):
        before = list(seq.blocks)
        got = close(seq, n)
        freed.append((len(calls) - 1, seq.slot, set(before) - set(seq.blocks)))
        return got

    eng.mgr.close_window = closing
    sched = eng.scheduler
    rng = np.random.default_rng(8)
    for u, n in ((1, 60), (2, 31), (3, 90), (4, 70), (5, 20)):
        sched.submit(u, rng.integers(0, cfg.vocab_size, n).tolist(), GREEDY(40))
    sched.run()
    assert eng.stats["dispatched_ahead"] > 0 and len(freed) >= 8
    reused = 0
    for at, slot, pages in freed:
        assert len(pages) == WINDOW // PAGE
        assert pages <= calls[at][slot]                     # the closing call read them
        for shown in calls[at + 1:]:  # whoever is handed them next (its own next window, too) shows them LATER
            reused += any(pages & rows for other, rows in shown.items() if other != slot)
        for page in pages:  # and while it held a page, back to when it was handed it, nobody else had
            for held in reversed(calls[:at + 1]):
                if page not in held[slot]:
                    break
                assert all(page not in rows for other, rows in held.items() if other != slot)
    for shown in calls:  # (no page is in two tables of one call)
        assert sum(map(len, shown.values())) == len(set().union(*shown.values()))
    assert reused > 0  # the pool of 22 pages is small enough that a page comes round
    assert eng.close() == {"blocks_in_use": 0, "cached_blocks": 0}


@pytest.mark.parametrize("n,pages,rows", [
    (0, 0, 0), (1, 2, 1), (8, 2, 8), (9, 3, 9), (32, 5, 8), (33, 3, 9), (63, 6, 39),
    (64, 6, 16), (65, 4, 17), (100, 5, 28), (2048, 68, 512)])
def test_the_tables_arithmetic(n, pages, rows):
    """Pages while position n - 1 is the newest written (its window open: the
    closed windows' pages, the open window's summary page, its exact pages) and
    rows attended next (a window that has just filled counts as closed)."""
    assert COMPACT.pages_for(n, PAGE) == pages
    assert COMPACT.rows_live(n) == rows == int(COMPACT.rows_live(np.asarray([n]))[0])
    by_hand = sum(1 for p in range(n) if p // WINDOW == n // WINDOW) \
        + sum(1 for p in range(0, n // WINDOW * WINDOW, SUMMARY))
    assert rows == by_hand
    if n:
        w = (n - 1) // WINDOW
        assert COMPACT.column(n - 1, PAGE) == w + 1 + (n - 1) % WINDOW // PAGE == pages - 1


def test_close_window_gives_back_the_exact_pages_and_keeps_what_was_reserved_past_them():
    mgr = StateManager(32, PAGE, 2)
    mgr.compaction = COMPACT
    seq = mgr.admit(1, list(range(70)))
    mgr.ensure_capacity(seq, 0)                       # what 70 positions keep: 2 + 1 + 1
    assert len(seq.blocks) == COMPACT.pages_for(70, PAGE) == 4
    mgr.ensure_pages(seq, 32)
    assert len(seq.blocks) == 5 and mgr.blocks_needed(seq, 0) == 0
    first, spare = seq.blocks[0], None
    mgr.ensure_pages(seq, 20)                          # never shrinks
    assert len(seq.blocks) == 5
    seq.blocks.extend(mgr.allocator.allocate(2))       # reserved past the window's own
    spare = seq.blocks[5:]
    assert mgr.close_window(seq, 32) == 4
    assert seq.blocks == [first] + spare and mgr.allocator.free_blocks == 32 - 3
    with pytest.raises(ValueError, match="no window closes"):
        mgr.close_window(seq, 40)
    mgr.release(1)
    assert mgr.allocator.free_blocks == 32
    plain = StateManager(32, PAGE, 2)
    assert plain.pages_for(70) == 9 and plain.compaction is None
    with pytest.raises(ValueError, match="no window closes"):
        plain.close_window(SequenceDescriptor(uid=1, slot=0), 32)


@pytest.mark.parametrize("says,kw", [
    ("enable_speculation.*chunk's summary", dict(enable_speculation=True)),
    ("quantize_weights.*no quantized form", dict(quantize_weights="int8")),
    ("enable_prefix_caching.*summary pages could be shared a window at a time",
     dict(enable_prefix_caching=True)),
    ("offload_weights", dict(offload_weights=True)),
    ("replica / seq-shard serve mesh", dict(serve_replicas=2)),
])
def test_what_would_serve_it_wrongly_is_refused_by_mechanism(model, says, kw):
    m, arch, cfg, params, ref = model
    with pytest.raises(NotImplementedError, match=says):
        _engine(cfg, params, **kw)


def test_a_burst_a_verify_pass_and_a_backward_are_refused_by_mechanism(model):
    m, arch, cfg, params, ref = model
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="compacted by the host between two ticks"):
        eng._decode_burst([], GREEDY(1), 2)
    with pytest.raises(NotImplementedError, match="chunk's summary"):
        eng.runner.verify_packed_ctx()
    eng.close()
    from deepspeed_tpu.models import CausalLM

    ids = np.zeros((1, 9), np.int32)
    with pytest.raises(NotImplementedError, match="chunk summaries of EVA attention"):
        jax.grad(lambda p: CausalLM(cfg).loss_fn(p, {"input_ids": ids}))(params)
    with pytest.raises(NotImplementedError, match="kinds that train"):
        CausalLM(cfg).flops_per_token(64)


def test_a_block_size_that_is_not_a_closed_windows_rows_is_refused(model):
    m, arch, cfg, params, ref = model
    with pytest.raises(ValueError, match="ONE page only at a block size"):
        latent_runner.init_cache(cfg, 8, 16, 2, 32)


def test_the_heads_are_laid_out_as_many_a_pool_as_the_packed_kernels_gate_takes(model, monkeypatch):
    """At the published widths 32 heads x 512 queries are past the packed-ctx
    kernel's VMEM estimate and 8 a pool are taken; the split computes what one
    pool computes."""
    from deepspeed_tpu.ops.pallas import ctx_attention as ck

    m, arch, cfg, params, ref = model
    big = lm.Eva(num_heads=32, head_dim=128, rope_theta=1e5, window=2048, chunk=16)
    assert latent_runner.eva_heads_a_pool(big, 128, 512, np.dtype("bfloat16")) == 8
    assert latent_runner.eva_heads_a_pool(big, 128, 256, np.dtype("bfloat16")) == 16
    assert latent_runner.eva_heads_a_pool(cfg.latent.eva, PAGE, CHUNK, np.float32) == 4
    seq = np.random.default_rng(11).integers(0, cfg.vocab_size, 45).astype(np.int32)
    *_, (_, whole, _, _, one) = _through_the_bodies(cfg, params, seq, 40, (32, 40))
    monkeypatch.setattr(ck, "fits_vmem", lambda t, hq, *rest: hq <= 1)  # the rule halves the heads twice
    assert latent_runner.eva_heads_a_pool(cfg.latent.eva, PAGE, CHUNK, np.float32) == 1
    *_, (_, split, _, _, four) = _through_the_bodies(cfg, params, seq, 40, (32, 40))
    assert len(four["k"]) == 4 * len(one["k"]) == 8 and four["k"][0].shape[2] == 1
    assert np.abs(whole - split).max() <= 1e-5


def test_the_head_holds_every_prediction_heads_columns_and_reads_the_first(model):
    m, arch, cfg, params, ref = model
    ids = np.random.default_rng(12).integers(0, cfg.vocab_size, (1, 12)).astype(np.int32)
    want = np.asarray(ref(params, ids))
    other = {**params, "lm_head": {"kernel": params["lm_head"]["kernel"].at[:, m["vocab_size"]:].set(7.0)}}
    assert want.shape[-1] == m["vocab_size"] == cfg.vocab_size
    assert np.abs(np.asarray(ref(other, ids)) - want).max() == 0.0
    from deepspeed_tpu.models import CausalLM

    assert np.abs(np.asarray(CausalLM(cfg).apply(other, ids)[0]) - want).max() <= 1e-4
    assert cfg.param_count == sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
