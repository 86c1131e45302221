"""The Pallas kernel that attends a pack's RUNS in the decompressed form
(``ops/pallas/latent_prefill.py``, interpret mode), the rule that finds the runs
(``latent_attention.pack_runs`` / ``run_groups``), the seam that sends each group
through one of the two forms (``latent_runner._attend_every``) and the host's
count of what the rule sent, against the absorbed XLA body
(``latent_attention.dense_attention_pack``) at the rehearsal's widths, float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import latent_runner as lr
from deepspeed_tpu.models import latent as lm
from deepspeed_tpu.models.latent import LatentAttn
from deepspeed_tpu.ops import latent_attention as la
from deepspeed_tpu.ops.pallas import latent_prefill as lp
from deepspeed_tpu.ops.pallas import record_dispatch

H, R, NOPE, ROPE, V, BS, NB, P, G = 4, 64, 16, 8, 16, 8, 128, 20, 16
LANES = 128
A = LatentAttn(H, 32, R, NOPE, ROPE, V, 1e4, gate=False)
V2 = LatentAttn(128, 1536, 512, 128, 64, 128, 1e4, gate=False)  # DeepSeek-V2's widths
T = G * BS


@pytest.fixture
def interpreted():
    with lp.interpreted():
        yield


def _pack(runs, seed=0, ragged=0):
    """A pack of G pages of queries holding ``runs`` [(first page, pages, first
    position, slot)], the other pages dead; the last ``ragged`` rows of each
    run are padding (position 0).  The sequences' pages are scattered over a
    pool that holds NaN wherever no sequence of the pack has a page (one
    sequence more than the runs name has a table of such pages)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (T, H, NOPE + ROPE))
    w_uk = jax.random.normal(ks[1], (R, H * NOPE)) / 8
    w_uv = jax.random.normal(ks[2], (R, H * V)) / 8
    slots = 2 + max(s for *_, s in runs)
    tables = np.asarray(jax.random.permutation(ks[3], NB)[: slots * P]).reshape(slots, P)
    pages = np.full((NB, BS, LANES), np.nan, np.float32)
    rows = np.asarray(jax.random.normal(ks[4], (NB, BS, R + ROPE)))
    slot, live = np.zeros(G, np.int32), np.zeros(G, bool)
    q_pos, real = np.zeros((G, BS), np.int32), np.zeros((G, BS), bool)
    for g0, n, p0, s in runs:
        slot[g0:g0 + n], live[g0:g0 + n] = s, True
        at = p0 + np.arange(n * BS)
        ok = np.arange(n * BS) < n * BS - ragged
        q_pos[g0:g0 + n] = np.where(ok, at, 0).reshape(n, BS)
        real[g0:g0 + n] = ok.reshape(n, BS)
        pages[tables[s]] = np.pad(rows[tables[s]], ((0, 0), (0, 0), (0, LANES - R - ROPE)))
    return dict(q=q, w_uk=w_uk, w_uv=w_uv, pages=jnp.asarray(pages), tables=jnp.asarray(tables),
                slot=jnp.asarray(slot), live=jnp.asarray(live), q_pos=jnp.asarray(q_pos),
                real=real)


def _absorbed(k):
    """The XLA body's values for every group of the pack: [T, H, V]."""
    q_abs = lm.absorbed_queries(k["w_uk"], k["q"], k["q"][..., NOPE:], A)
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, LANES - R - ROPE))).reshape(G, BS, H, LANES)
    pages = jnp.nan_to_num(k["pages"])  # the body gathers whole blocks of a table
    o = la.dense_attention_pack(q_abs, pages, k["tables"][k["slot"]], k["live"], k["q_pos"], A)
    return lm.latent_values(k["w_uv"], o.reshape(T, H, R), A)


def _kernel(k, runs):
    q = jnp.pad(k["q"], ((0, 0), (0, 0), (0, LANES - R - ROPE))).transpose(1, 0, 2)
    w = jnp.concatenate([k["w_uk"].reshape(R, H, NOPE), k["w_uv"].reshape(R, H, V)], -1)
    spec = jnp.asarray([r[:3] for r in runs], jnp.int32)
    tables = k["tables"][jnp.asarray([r[3] if r[1] else -1 for r in runs])]  # no run: the NaN pages
    return lp.latent_prefill(q, w.transpose(1, 0, 2), k["pages"], tables, spec, R,
                             A.scale).transpose(1, 0, 2)


def _same(got, want, real):
    rows = real.reshape(-1)
    assert rows.any()
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows], rtol=2e-5, atol=2e-5)


CASES = {
    "one page": [(3, 1, 0, 0)],
    "two pages": [(0, 2, 0, 0)],
    "sixteen pages": [(0, 16, 0, 0)],
    "behind a prefix hit": [(2, 5, 24, 0)],               # positions 24.. on pages it did not write
    "two sequences": [(0, 6, 16, 1), (6, 10, 40, 0)],
    "a dead page between": [(1, 3, 8, 0), (5, 4, 0, 1), (0, 0, 0, 0)],
    "one sequence twice": [(0, 3, 0, 0), (8, 3, 64, 0)],  # two runs: its pages do not follow
}


# whole tiles alone; tiles of four pages visited a page at a time where a run starts or ends
# inside one; one tile for the pack, a quarter at a time
@pytest.mark.parametrize("tq,tail,kp,hb", [(16, 16, 2, 2), (32, 8, 3, 4), (128, 32, 20, 1)])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_absorbed_bodys_attention(interpreted, monkeypatch, case, tq, tail, kp,
                                                    hb):
    monkeypatch.setattr(lp, "TQ", tq)
    monkeypatch.setattr(lp, "TAIL", tail)
    monkeypatch.setattr(lp, "KP", kp)
    monkeypatch.setattr(lp, "HB", hb)
    runs = CASES[case]
    k = _pack([r for r in runs if r[1]])
    _same(_kernel(k, runs), _absorbed(k), k["real"])


@pytest.mark.parametrize("ragged", [1, 5, BS - 1])
def test_a_context_that_ends_inside_a_page(interpreted, monkeypatch, ragged):
    """The last page's padding takes positions past the context's end inside
    the kernel (and 0 in the pack): the real rows see none of it."""
    monkeypatch.setattr(lp, "TQ", 64)
    monkeypatch.setattr(lp, "TAIL", 16)
    runs = [(4, 6, 32, 0)]
    k = _pack(runs, seed=ragged, ragged=ragged)
    _same(_kernel(k, runs), _absorbed(k), k["real"])


def test_a_run_of_no_pages_is_skipped_and_reads_no_page(interpreted):
    """Its table points at pages that hold NaN; the live run's rows are what
    they are without it, and the rows of no run are never written."""
    k = _pack([(2, 4, 16, 0)])
    got = _kernel(k, [(0, 0, 0, 0), (2, 4, 16, 0), (9, 0, 40, 0)])
    _same(got, _absorbed(k), k["real"])
    assert np.isfinite(np.asarray(got)[k["real"].reshape(-1)]).all()


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def test_the_crossing_is_the_widths_own():
    """``ceil(r (nope + v) / ((2 r + rope) - (nope + rope + v)))``: 171 queries
    at DeepSeek-V2's widths, two pages of 128; 22 at the rehearsal's, three
    pages of 8; a kind whose absorbed form is never dearer has none."""
    assert la.crossing(V2) == 171 and la.run_groups(V2, 128) == 2
    assert la.crossing(A) == -(-64 * 32 // (136 - 40)) == 22 and la.run_groups(A, BS) == 3
    assert la.run_groups(LatentAttn(4, 32, 16, 64, 8, 64, 1e4), 8) > 1 << 20


def test_the_runs_are_consecutive_pages_of_one_sequence():
    slot = jnp.asarray([0, 0, 0, 1, 1, 0, 0, 2, 2, 2, 2, 0])
    live = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 1, 0], bool)
    first = jnp.asarray([16, 24, 32, 0, 8, 40, 56, 8, 16, 24, 32, 0])
    long, runs, run_slot = la.pack_runs(slot, live, first, 8, 2)
    # 0-2 | 3-4 | 5 (a gap behind 2, another sequence between) | 6 (its page does
    # not follow 5's) | 7-8 | 9 dead | 10 alone
    assert long.tolist() == [1, 1, 1, 1, 1, 0, 0, 1, 1, 0, 0, 0]
    assert runs.tolist() == [[0, 3, 16], [3, 2, 0], [7, 2, 8], [0, 0, 0], [0, 0, 0], [0, 0, 0]]
    assert run_slot.tolist()[:3] == [0, 1, 2]
    long, runs, _ = la.pack_runs(slot, live, first, 8, 3)
    assert long.tolist() == [1, 1, 1] + [0] * 9 and runs.tolist() == [[0, 3, 16]] + [[0, 0, 0]] * 3


def test_supports_declines_what_mosaic_would_not_take(interpreted, monkeypatch):
    monkeypatch.setattr(lp, "TQ", 32)
    monkeypatch.setattr(lp, "TAIL", 16)
    monkeypatch.setattr(lp, "HB", 4)
    assert lp.supports(T, H, LANES, R, NOPE, V, BS)       # interpreted: any widths
    assert not lp.supports(40, H, LANES, R, NOPE, V, BS)  # ... but whole query tiles
    assert not lp.supports(T, 6, LANES, R, NOPE, V, BS)   # ... and whole head blocks


def test_supports_on_the_chip_wants_whole_lane_tiles():
    assert not lp.interpret()
    assert lp.supports(2048, 128, 640, 512, 128, 128, 128)      # DeepSeek-V2's
    assert not lp.supports(2048, 128, 640, 512, 128, 128, 64)   # pages of 64 keys
    assert not lp.supports(2048, 128, 576, 512, 128, 128, 128)  # rows not padded to lanes
    assert not lp.supports(2048, 128, 640, 512, 64, 128, 128)   # a head's key half a tile
    assert not lp.supports(T, H, LANES, R, NOPE, V, BS)         # the rehearsal's widths


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------
def _attend(k):
    return lr._attend_every(A, k["q"], k["w_uk"], k["w_uv"], k["pages"], k["tables"], k["slot"],
                            k["live"], k["q_pos"])


MIXED = [(0, 5, 24, 0), (5, 1, 16, 1), (6, 2, 0, 2), (9, 4, 8, 3), (14, 2, 96, 1)]


def test_a_pack_that_mixes_the_forms_gives_the_xla_bodys_rows_for_both(interpreted):
    """Runs of 5 and 4 pages attend decompressed, groups of 1, 2 and 2 pages
    (under ``run_groups`` = 3) walk absorbed, one page is dead: every real row
    is the XLA body's, the dead page's rows are zeros."""
    k = _pack(MIXED, seed=3, ragged=3)
    with record_dispatch() as rec:
        got = jax.jit(lambda: _attend(k))()
    assert {"latent_prefill", "selected_attn"} <= {d["kernel"] for d in rec if d["ran"]}
    _same(got, _absorbed(k), k["real"])
    assert not np.asarray(got).reshape(G, BS, H, V)[8].any()


@pytest.mark.parametrize("runs", [[(0, 16, 32, 0)], [(3, 2, 0, 1), (8, 1, 40, 0)]],
                         ids=["all decompressed", "all walked"])
def test_a_pack_of_one_form_skips_the_other(interpreted, runs):
    k = _pack(runs, seed=5)
    _same(_attend(k), _absorbed(k), k["real"])


def test_off_the_chip_every_group_takes_the_xla_body():
    k = _pack(MIXED, seed=3)
    with record_dispatch() as rec:
        got = _attend(k)
    assert rec and not [d for d in rec if d["ran"]]
    _same(got, _absorbed(k), k["real"])


def test_the_host_counts_what_the_programs_rule_sent():
    """``mla_keys_decompressed`` (``LatentRunner._every_dispatched``, from the
    pack's entries) is the causal keys of exactly the groups ``pack_runs`` marks
    in the pack those entries lay out, all layers."""
    from types import SimpleNamespace

    class Counter:
        n = 0

        def inc(self, by=1):
            self.n += by

    spec = SimpleNamespace(every=A, count=lambda kind: 5 if kind == "every" else 0, stateful=False)
    runner = lr.LatentRunner(SimpleNamespace(latent=spec))
    runner._block = BS
    # (slot, start, end): 5 pages less 3 rows, 1 page, 2 pages, 4 pages, 2 pages
    work = [(s, p0, p0 + n * BS - (3 if n == 5 else 0)) for _, n, p0, s in MIXED]
    counters = {k: Counter() for k in lr.MLA_COUNTERS}
    args = runner.dispatched(counters, work, pack=True)
    k = _pack(MIXED, ragged=0)
    long, _, _ = la.pack_runs(k["slot"], k["live"], k["q_pos"][:, 0], BS, la.run_groups(A, BS))
    keys = lambda lo, hi: (hi * (hi + 1) - lo * (lo + 1)) // 2
    sent = sum(keys(lo, hi) for (g0, *_), (_, lo, hi) in zip(MIXED, work) if long[g0])
    assert sent == keys(24, 61) + keys(8, 40)
    assert counters["mla_keys_decompressed"].n == 5 * sent
    assert counters["mla_keys_attended"].n == 5 * sum(keys(lo, hi) for _, lo, hi in work)
    assert args["mla_keys_decompressed_pct"] == pytest.approx(
        100.0 * counters["mla_keys_decompressed"].n / counters["mla_keys_attended"].n)
    # a tick's rows are single queries: never decompressed, and no such argument
    ticks = runner.dispatched(counters, [(0, 70, 71), (1, 9, 10)])
    assert "mla_keys_decompressed_pct" not in ticks
    assert counters["mla_keys_decompressed"].n == 5 * sent
