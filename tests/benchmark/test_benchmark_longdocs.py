"""What the long-document cell adds to the harness: lengths dealt in rounds
(``generators/closed_loop_strata``) and the planted faults its driver's
comparison has to refuse (``drivers/serve_latent.CONTROLS``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.distributions import quantiles, rng_for  # noqa: E402
from benchmark.generators import closed_loop_strata  # noqa: E402

CELL = "dots3_note_longdocs_closed"


def _plan(seed):
    t = harness.traffic_of("longdocs_closed")
    return t, closed_loop_strata.build(t, seed=seed, seconds=45.0, vocab=19008)


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 12345])
def test_every_round_holds_one_document_of_each_stratum_in_bit_reversed_turns(seed):
    t, p = _plan(seed)
    pool, strata = t["pool"], t["strata"]
    q = quantiles(t["prompt_tokens"], pool)
    assert sorted(p.lengths) == q
    per = pool // strata
    stratum = {}
    for i, v in enumerate(q):  # equal lengths (clipped ends) may sit in two strata
        stratum.setdefault(v, set()).add(i // per)
    turns = [0, 4, 2, 6, 1, 5, 3, 7]
    for r in range(per):
        for j, v in enumerate(p.lengths[r * strata:(r + 1) * strata]):
            assert turns[j] in stratum[v], (r, j, v)
    # the callers start with one round: short and long alternate
    first = [req.prompt for _, req in p.initial()]
    assert [len(x) for x in first] == p.lengths[:strata]
    assert max(map(max, first)) < 19008


def test_the_seed_chooses_only_which_document_of_a_stratum_joins_which_round():
    t = harness.traffic_of("longdocs_closed")
    q = quantiles(t["prompt_tokens"], t["pool"])
    a = closed_loop_strata.dealt(q, 8, rng_for(3, 1))
    b = closed_loop_strata.dealt(q, 8, rng_for(4, 1))
    assert a != b and sorted(a) == sorted(b)
    for s in range(8):  # position j of every round is the same stratum in both
        assert sorted(a[s::8]) == sorted(b[s::8])


def test_the_rounds_a_window_reaches_are_the_same_for_every_seed():
    t, p = _plan(3)
    fixed, strata = t["fixed_rounds"], t["strata"]
    per = t["pool"] // strata
    assert fixed * strata == t["pool"] == 64  # every round (PR 41): a window reaches 41-43
    other = _plan(2**31 + 9)[1]
    assert p.lengths == other.lengths  # the seed draws the token ids, not the lengths
    # a fixed round holds every rank of a stratum once, so the rounds agree:
    q = quantiles(t["prompt_tokens"], t["pool"])
    for r in range(fixed):
        round_ = p.lengths[r * strata:(r + 1) * strata]
        ranks = sorted(q.index(v) % per for v in round_)
        assert ranks == list(range(per)), (r, ranks)
    sums = [sum(p.lengths[r * strata:(r + 1) * strata]) for r in range(fixed)]
    assert max(sums) < 1.05 * min(sums)


@pytest.mark.parametrize("n,strata", [(64, 6), (60, 8), (8, 16)])
def test_strata_must_be_a_power_of_two_that_divides_the_pool(n, strata):
    with pytest.raises(ValueError, match="strata"):
        closed_loop_strata.dealt(list(range(n)), strata, rng_for(1, 1))


def test_no_more_fixed_rounds_than_rounds():
    with pytest.raises(ValueError, match="fixed rounds"):
        closed_loop_strata.dealt(list(range(16)), 4, rng_for(1, 1), fixed_rounds=5)


def test_one_stratum_is_the_free_permutation():
    vals = list(range(16))
    out = closed_loop_strata.dealt(vals, 1, rng_for(5, 1))
    assert sorted(out) == vals and out != vals


def test_planted_faults_come_out_not_correct_at_the_rehearsal_size():
    """The controls that recompile the replay, through the comparison that
    decides ``correct`` (the float8 weights have nothing to round in float32
    and the float8 index keys are inside the limits at toy widths: those two
    are read on the chip)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "11",
         "--rehearse", "--seconds", "0.5",
         "--set", 'control=["index_rope_shift", "topk_minus_one"]'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    sound = [l for l in lines if l.startswith("correct: request")]
    assert len(sound) == 3 and all(l.endswith("-> True") for l in sound)
    planted = [l for l in lines if l.startswith("control ")]
    assert len(planted) == 2 and all(l.endswith("-> False") for l in planted)
    assert " 0 rows with another count" in planted[0]        # the shifted keys: by score
    assert " 0 rows with another count" not in planted[1]    # one key too few: by count
    assert "max|d| 0.0000 (tol 0.5), mean|d| 0.00000" in planted[1]
    assert ", 0 picks the reference did not make" in planted[1]
    assert any(l.startswith("controls: all of") for l in lines)
    assert json.loads(lines[-1])["failed"] == 0


def test_an_unknown_control_is_refused_by_name():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--rehearse",
         "--seconds", "0.5", "--set", 'control="int4_weights"'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and "unknown control 'int4_weights'" in out.stderr + out.stdout
