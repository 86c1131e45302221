"""The traffic generators: a fixed multiset per cell, order from the seed."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness  # noqa: E402
from benchmark.distributions import quantiles  # noqa: E402

TRAFFIC = sorted(p.stem for p in (harness.HERE / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def plan(name, seed, rehearse=False, seconds=45.0):
    t = harness.rehearsed(harness.traffic_of(name), rehearse)
    return harness.module("generators", t["kind"]).build(
        t, seed=seed, seconds=seconds, vocab=32000)


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_multiset_for_any_two_seeds(name):
    a, b, c = plan(name, 1), plan(name, 7), plan(name, BIG_SEED)
    assert a.multiset() == b.multiset() == c.multiset()


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_inputs(name):
    a, b = plan(name, BIG_SEED), plan(name, BIG_SEED)
    if hasattr(a, "initial"):
        ia, ib = a.initial(), b.initial()
        assert [(t, r.prompt, r.max_new) for t, r in ia] == \
               [(t, r.prompt, r.max_new) for t, r in ib]
    else:
        import numpy as np

        xa, xb = next(a.batches(2)), next(b.batches(2))
        assert np.array_equal(xa["input_ids"], xb["input_ids"])


def _order(p):
    """The plan's lengths in the order it will use them."""
    if hasattr(p, "sessions"):
        return [len(u) for s in p.sessions for u in s["users"]]
    return list(p.lengths)


def _every_round_fixed(name):
    t = harness.traffic_of(name)
    return "fixed_rounds" in t and t["fixed_rounds"] * t["strata"] == t["pool"]


@pytest.mark.parametrize("name", TRAFFIC)
def test_other_seed_other_order(name):
    pa, pb = plan(name, 1), plan(name, 2)
    if not hasattr(pa, "initial"):
        # training traffic, told by what its generator's plan offers and never by
        # a file's name: one fixed shape, no lengths to order; the seed draws the ids
        import numpy as np

        xa, xb = next(pa.batches(2)), next(pb.batches(2))
        assert xa["input_ids"].shape == xb["input_ids"].shape
        assert not np.array_equal(xa["input_ids"], xb["input_ids"])
        return
    a, b = _order(pa), _order(pb)
    assert sorted(a) == sorted(b)
    if _every_round_fixed(name):  # the lengths are the cell's; the seed draws the content
        assert a == b
        assert [r.prompt for _, r in pa.initial()] != [r.prompt for _, r in pb.initial()]
    else:
        assert a != b


@pytest.mark.parametrize("name,tasks", [("longctx_qa_closed", 64), ("longdocs_closed", 64)])
def test_the_tasks_a_faster_program_would_reach_are_the_same_for_every_seed(name, tasks):
    """Cells 7 and 5 (PR 41): the first ``tasks`` tasks, lengths AND their order,
    hold for three seeds; a window reaches ~45 of cell 7's and ~43 of cell 5's."""
    plans = [plan(name, seed) for seed in (1, 7, BIG_SEED)]
    t = harness.traffic_of(name)
    assert t["fixed_rounds"] * t["strata"] >= tasks
    heads = [(p.lengths[:tasks], getattr(p, "answers", [])[:tasks]) for p in plans]
    assert heads[0] == heads[1] == heads[2]
    assert sorted(heads[0][0]) != heads[0][0]  # dealt, not ascending
    if tasks < t["pool"]:  # the rounds after them are the seed's
        assert plans[0].lengths != plans[1].lengths


def test_chat_rate_is_a_whole_number_of_session_starts_a_stratum():
    t = harness.traffic_of("chat_sessions")
    per = t["session_starts_per_s"] * t["stratum_s"]
    assert abs(per - round(per)) < 1e-3 and round(per) >= 1
    assert (t["ramp_s"] / t["stratum_s"]).is_integer()
    assert {"knee_session_starts_per_s", "rate_note", "swept_on"} <= set(t)
    assert t["session_starts_per_s"] <= 0.8 * t["knee_session_starts_per_s"] + 1e-3


def test_chat_sessions_schedule_is_stratified():
    t = harness.traffic_of("chat_sessions")
    per = round(t["session_starts_per_s"] * t["stratum_s"])
    by_seed = []
    for seed in (3, 4):
        p = plan("chat_sessions", seed)
        starts = sorted(s["start"] for s in p.sessions)
        assert starts[0] >= -t["ramp_s"] and len(starts) % per == 0
        # every stratum holds the same number of session starts ...
        for k in range(len(starts) // per):
            lo = -t["ramp_s"] + k * t["stratum_s"]
            block = starts[k * per:(k + 1) * per]
            assert lo <= block[0] and block[-1] < lo + t["stratum_s"]
        # ... and, turn by turn, the same multiset of lengths, whatever the seed
        by_seed.append([[sorted(s["answers"][turn] for s in p.sessions[k * per:(k + 1) * per])
                         for turn in range(p.turns)] for k in range(len(starts) // per)])
    assert by_seed[0] == by_seed[1]
    assert all(stratum[0] == stratum[-1] for stratum in by_seed[0])


def test_chat_turn_prompt_is_session_so_far():
    p = plan("chat_sessions", 5)
    (t0, first), = [x for x in p.initial() if x[1].session == 0]
    assert first.prompt[:len(p.system)] == p.system and first.turn == 0
    answer = [11, 12, 13]
    (due, nxt), = p.on_finish(first, 2.0, answer)
    assert nxt.prompt[:len(first.prompt) + 3] == first.prompt + answer
    assert nxt.turn == 1 and due == 2.0 + p.sessions[0]["thinks"][0]
    last = nxt
    for _ in range(p.turns - 2):
        (_, last), = p.on_finish(last, 3.0, answer)
    assert p.on_finish(last, 4.0, answer) == []


def test_docs_prompts_fit_the_window():
    t = harness.traffic_of("docs_closed")
    p = plan("docs_closed", 9)
    assert max(p.lengths) + t["output_tokens"] <= 4096 and min(p.lengths) >= 1024
    first = p.initial()
    assert len(first) == t["clients"] and all(due == -t["ramp_s"] for due, _ in first)
    (due, nxt), = p.on_finish(first[0][1], 1.5, [1] * 32)
    assert due == 1.5 and nxt.session == first[0][1].session and nxt.turn == 1


@pytest.mark.parametrize("spec,n,expect", [
    ({"dist": "const", "value": 3}, 4, [3.0] * 4),
    # median of a lognormal is its 50% quantile; clipping holds at both ends
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 50, "max": 200,
      "integer": True}, 3, [50, 100, 200]),
])
def test_quantiles_by_hand(spec, n, expect):
    assert quantiles(spec, n) == expect


def test_gamma_quantiles_sum_to_the_schedule():
    xs = quantiles({"dist": "gamma", "mean": 0.75, "cv": 1.5}, 12)
    assert abs(sum(xs) - 12 * 0.75) < 1e-9 and xs == sorted(xs) and xs[0] > 0
    # burstier than Poisson: the coefficient of variation is well above 1
    import statistics

    assert statistics.pstdev(xs) / statistics.mean(xs) > 1.1
