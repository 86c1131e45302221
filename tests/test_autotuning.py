"""Autotuner tests: deterministic search order, roofline pruning,
successive-halving promotion, trial teardown hygiene, and the e2e smokes
(`autotune_model` winner round-trip, `autotune_serving` under a trial cap).

The search-engine tests run on a STUBBED trial runner (no jax work), so
the promotion/determinism/skip logic is cheap to pin exactly; the real
engines appear only in the teardown and e2e smokes."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.autotuning import (
    Autotuner,
    RooflineConstants,
    SearchSpace,
    Knob,
    autotune_model,
    leaderboard,
    serving_space,
    training_space,
    write_leaderboard,
)
from deepspeed_tpu.autotuning import roofline
from deepspeed_tpu.autotuning.space import candidate_key


# ---------------------------------------------------------------------------
# space enumeration
# ---------------------------------------------------------------------------
def test_space_grid_deterministic_and_canonical():
    sp = serving_space(
        tp=(1, 2), serve_replicas=(1,), quant=(None, "int8"),
        prefill_chunk=(None,), kv_watermark=(0.0625,),
        spec=(False, True), spec_max_draft=(2, 4),
        quant_comm=("none", "int8"), comm_tiles=(1, 4),
    )
    a = sp.candidates()
    b = sp.candidates()
    assert a == b  # deterministic enumeration
    assert len(a) < sp.raw_size  # canonicalization deduplicated no-ops
    for c in a:
        if not c["spec"]:
            assert c["spec_max_draft"] == 0
        if c["tp"] == 1:
            assert c["quant_comm"] == "none" and c["comm_tiles"] == 1
        if c["quant_comm"] == "none":
            assert c["comm_tiles"] == 1
    # every canonical candidate is unique
    keys = [candidate_key(c) for c in a]
    assert len(keys) == len(set(keys))


def test_training_space_canonicalizes_zeropp_below_stage3():
    sp = training_space(micro_batches=(1,), remat_policies=("none",),
                        zero_stages=(1, 3), zero_quant=(False, True))
    cands = sp.candidates()
    assert all(not c["zero_quant"] for c in cands if c["zero_stage"] < 3)
    assert any(c["zero_quant"] for c in cands if c["zero_stage"] == 3)


# ---------------------------------------------------------------------------
# roofline: feasibility + cost ordering
# ---------------------------------------------------------------------------
def test_serving_feasibility_mirrors_engine_gates():
    from deepspeed_tpu.models import get_preset

    cfg = get_preset("tiny")  # 4 heads
    base = {"max_seqs": 4, "num_blocks": 64, "block_size": 8,
            "enable_prefix_caching": False}
    ok, _ = roofline.serving_feasible(
        {"tp": 1, "serve_replicas": 1}, cfg, base, 8)
    assert ok
    # head divisibility
    ok, why = roofline.serving_feasible(
        {"tp": 3, "serve_replicas": 1}, cfg, base, 8)
    assert not ok and "num_heads" in why
    # device budget
    ok, why = roofline.serving_feasible(
        {"tp": 4, "serve_replicas": 2}, cfg, base, 4)
    assert not ok and "devices" in why
    # replica-affine serving: caching / chunked prefill / speculation are
    # feasible at serve_replicas > 1 now (the engine gate is retired), so
    # the R>1 region of the grid must survive the static prune
    for knob in ({"spec": True}, {"prefill_chunk": 32},
                 {"prefix_caching": True}):
        ok, why = roofline.serving_feasible(
            {"tp": 1, "serve_replicas": 2, **knob}, cfg, base, 8)
        assert ok, why
    # replica divisibility of the pool
    ok, why = roofline.serving_feasible(
        {"tp": 1, "serve_replicas": 2}, cfg,
        {**base, "max_seqs": 3}, 8)
    assert not ok and "divide" in why
    # memory: a pool larger than HBM is pruned before any compile
    tiny_hbm = RooflineConstants(hbm_bytes=1e4)
    ok, why = roofline.serving_feasible(
        {"tp": 1, "serve_replicas": 1}, cfg, base, 8, tiny_hbm)
    assert not ok and why.startswith("memory")


def test_serve_cost_model_orders_formats():
    from deepspeed_tpu.models import get_preset

    cfg = get_preset("tiny")
    base = {"max_seqs": 8}
    cost = lambda c: roofline.predict_serve_cost(c, cfg, base)
    # narrower weights stream fewer HBM bytes -> cheaper per token
    assert cost({"quant": "int8"}) < cost({"quant": None})
    assert cost({"quant": "fp6"}) < cost({"quant": "int8"})
    # speculation amortizes the weight stream over more emitted tokens
    assert cost({"quant": None, "spec": True, "spec_max_draft": 4}) \
        < cost({"quant": None})
    # quantized TP transport beats exact psum at the same tp
    assert cost({"tp": 2, "quant_comm": "int8"}) \
        < cost({"tp": 2, "quant_comm": "none"})


def test_serve_cost_model_charges_ctx_attention_kv_traffic():
    from deepspeed_tpu.models import get_preset

    cfg = get_preset("tiny")
    big = {"max_seqs": 8, "num_blocks": 256, "block_size": 16}
    small = {"max_seqs": 8, "num_blocks": 32, "block_size": 16}
    costb = lambda c: roofline.predict_serve_cost(c, cfg, big)
    costs = lambda c: roofline.predict_serve_cost(c, cfg, small)
    # chunked prefill streams cached context pages through the packed-ctx
    # attention on top of the decode read — not free anymore
    assert costb({"prefill_chunk": 32}) > costb({})
    # spec verify re-reads the context KV, so its amortization margin
    # narrows as the pool (live context) grows...
    spec = {"spec": True, "spec_max_draft": 4}
    assert costb(spec) / costb({}) > costs(spec) / costs({})
    # ...but the per-token amortization still wins at these pool sizes
    assert costb(spec) < costb({})


def test_train_cost_model_prefers_bigger_micro_and_charges_remat():
    from deepspeed_tpu.models import get_preset

    cfg = get_preset("tiny")
    cost = lambda c: roofline.predict_train_cost(c, cfg, 64)
    assert cost({"micro_batch": 8, "remat": "none", "zero_stage": 1}) \
        < cost({"micro_batch": 1, "remat": "none", "zero_stage": 1})
    assert cost({"micro_batch": 4, "remat": "none", "zero_stage": 1}) \
        < cost({"micro_batch": 4, "remat": "full", "zero_stage": 1})
    # ZeRO++ int8 collectives shrink the stage-3 wire term
    assert cost({"micro_batch": 4, "remat": "none", "zero_stage": 3,
                 "zero_quant": True, "mesh": {"fsdp": 8}}) \
        < cost({"micro_batch": 4, "remat": "none", "zero_stage": 3,
                "zero_quant": False, "mesh": {"fsdp": 8}})


# ---------------------------------------------------------------------------
# the search engine, on a stubbed runner
# ---------------------------------------------------------------------------
def _line_space(n=8):
    return SearchSpace(knobs=[Knob("x", tuple(range(n)))])


def test_seeded_search_is_deterministic():
    def make_runner(seed):
        rng = np.random.default_rng(seed)
        noise = {x: rng.normal(0, 5) for x in range(8)}

        def runner(c, budget):
            return 50.0 + c["x"] + noise[c["x"]], {"b": budget}
        return runner

    def run(seed):
        t = Autotuner(_line_space(), make_runner(seed),
                      cost_model=lambda c: 1.0 / (1 + c["x"]),
                      rungs=(0.5, 1.0), top_k=4, seed=seed)
        w, trials = t.search()
        order = [(tr.index, tuple(tr.run_order)) for tr in trials
                 if tr.run_order]
        return candidate_key(w.candidate), order

    w0a, o0a = run(0)
    w0b, o0b = run(0)
    assert w0a == w0b and o0a == o0b  # same seed: same winner, same order
    # a different seed feeds different measurement noise through the same
    # deterministic machinery (winner may or may not move; the run is valid)
    w1, o1 = run(1)
    assert [i for i, _ in o1] == [i for i, _ in o0a]  # seeding order is static


def test_infeasible_and_oom_candidates_skipped_without_abort():
    calls = []

    def runner(c, budget):
        calls.append(c["x"])
        if c["x"] == 2:
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")
        if c["x"] == 5:
            raise RuntimeError("engine constructor refused")
        return float(c["x"]), {}

    t = Autotuner(
        _line_space(), runner,
        feasibility=lambda c: (False, "pruned:structural: odd")
        if c["x"] in (1, 3) else (True, "ok"),
        rungs=(1.0,), top_k=8,
    )
    w, trials = t.search()
    by_x = {tr.candidate["x"]: tr for tr in trials}
    assert by_x[1].verdict.startswith("pruned") and not by_x[1].run_order
    assert by_x[2].verdict.startswith("error:MemoryError")
    assert by_x[5].verdict.startswith("error:RuntimeError")
    assert w.candidate["x"] == 7  # best surviving measured candidate
    assert 1 not in calls and 3 not in calls  # pruned never launched
    # the board still records every candidate
    board = leaderboard(trials)
    assert board["candidates"] == 8 and board["pruned"] == 2


def test_successive_halving_promotion_on_stub():
    launches = []

    def runner(c, budget):
        launches.append((c["x"], budget))
        return float(c["x"]), {}

    inc = {"x": 0}
    t = Autotuner(_line_space(), runner, rungs=(0.25, 0.5, 1.0), eta=2,
                  top_k=4, incumbent=inc)
    w, trials = t.search()
    # rung 0: top_k=4 by grid order (flat predicted cost) + the incumbent
    r0 = [x for x, b in launches if b == 0.25]
    assert r0 == [0, 1, 2, 3]  # incumbent x=0 already in the cohort
    # rung 1: ceil(4/2)=2 best scores promoted + incumbent carried FIRST
    # (budget cuts the cohort tail, so the incumbent can never be cut)
    r1 = [x for x, b in launches if b == 0.5]
    assert r1 == [0, 3, 2]
    # rung 2: ceil(3/2)=2 best + incumbent
    r2 = [x for x, b in launches if b == 1.0]
    assert r2 == [0, 3, 2]
    assert w.candidate["x"] == 3 and w.rung == 2
    # the incumbent reached the final rung, so the winner's measured score
    # can never fall below the hand-tuned config's measured score
    inc_trial = next(tr for tr in trials if tr.candidate == inc)
    assert inc_trial.rung == 2 and w.score >= inc_trial.score


def test_incumbent_survives_tight_trial_budget():
    """The worse-than-hand-tuned guard must hold under max_trials: the
    incumbent is prepended to the cohort, so the budget cuts the ranked
    tail, never the incumbent."""
    launches = []

    def runner(c, budget):
        launches.append(c["x"])
        return float(c["x"]), {}

    inc = {"x": 0}
    # cost model ranks x=7 best, pushing the incumbent out of top_k=3;
    # max_trials=3 can only afford three launches
    t = Autotuner(_line_space(), runner,
                  cost_model=lambda c: 1.0 / (1 + c["x"]),
                  rungs=(1.0,), top_k=3, max_trials=3, incumbent=inc)
    w, trials = t.search()
    assert launches[0] == 0  # the incumbent launched first
    inc_trial = next(tr for tr in trials if tr.candidate == inc)
    assert inc_trial.measured
    assert w.score >= inc_trial.score


def test_higher_rung_error_keeps_lower_rung_measurement():
    calls = {}

    def runner(c, budget):
        calls[c["x"]] = calls.get(c["x"], 0) + 1
        if c["x"] == 3 and budget == 1.0:
            raise MemoryError("transient OOM at the full-budget rung")
        return float(c["x"]) * budget, {}

    t = Autotuner(_line_space(4), runner, rungs=(0.5, 1.0), top_k=4, eta=2)
    w, trials = t.search()
    t3 = next(tr for tr in trials if tr.candidate["x"] == 3)
    # the rung-0 measurement survives the rung-1 failure
    assert t3.measured and t3.score == 1.5 and t3.rung == 0
    assert t3.verdict == "ok"
    assert any(k.startswith("error_at_rung_") for k in t3.metrics)
    # the winner comes from the candidates that FINISHED the final rung
    assert w.candidate["x"] == 2 and w.rung == 1


def test_latency_metric_is_lower_is_better():
    # runner returns a latency-style score: candidate x has latency 10-x
    t = Autotuner(_line_space(4), lambda c, b: (10.0 - c["x"], {}),
                  metric="latency", rungs=(0.5, 1.0), top_k=4, eta=2)
    w, _ = t.search()
    assert w.candidate["x"] == 3  # lowest latency wins under 'latency'
    t2 = Autotuner(_line_space(4), lambda c, b: (10.0 - c["x"], {}),
                   metric="throughput", rungs=(1.0,), top_k=4)
    w2, _ = t2.search()
    assert w2.candidate["x"] == 0  # same scores, opposite direction


def test_max_trials_caps_launches():
    n = [0]

    def runner(c, budget):
        n[0] += 1
        return float(c["x"]), {}

    t = Autotuner(_line_space(), runner, rungs=(0.5, 1.0), top_k=8,
                  max_trials=5)
    w, trials = t.search()
    assert n[0] == 5
    assert w is not None
    unran = [tr for tr in trials if tr.verdict == "not_run"]
    assert unran  # the cap left candidates unmeasured, all recorded


def test_leaderboard_json_roundtrip(tmp_path):
    t = Autotuner(_line_space(4), lambda c, b: (float(c["x"]), {"m": 1}),
                  rungs=(1.0,), top_k=2)
    _, trials = t.search()
    path = tmp_path / "board.json"
    write_leaderboard(str(path), trials, meta={"mode": "test"})
    board = json.loads(path.read_text())
    assert board["meta"]["mode"] == "test"
    assert len(board["trials"]) == 4
    for row in board["trials"]:
        assert set(row) >= {"candidate", "predicted_cost", "verdict",
                            "score", "metrics", "rung"}
    # measured rows sort first, best score on top
    assert board["trials"][0]["score"] == 1.0


# ---------------------------------------------------------------------------
# serve-trial teardown hygiene (real engines)
# ---------------------------------------------------------------------------
def _tiny_serving():
    from deepspeed_tpu.models import get_preset
    from deepspeed_tpu.models.transformer import init_params

    cfg = get_preset("tiny", max_seq_len=256, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def test_engine_close_releases_blocks_and_namespaces():
    from deepspeed_tpu.inference.engine_v2 import build_serve_engine
    from deepspeed_tpu.telemetry import Telemetry

    cfg, params = _tiny_serving()
    sec = dict(max_seqs=2, num_blocks=16, block_size=8,
               prefill_buckets=[16, 32], enable_prefix_caching=True)
    tel = Telemetry(True)
    e1 = build_serve_engine(params, cfg, sec, telemetry=tel)
    e1.put([1], [[5, 6, 7]])
    e1.step()
    from deepspeed_tpu.inference.sampling import SamplingParams

    sched = e1.scheduler
    # left live on purpose: close must drain it to a terminal state
    sched.submit(2, [9, 8, 7, 6], SamplingParams(max_new_tokens=4))
    audit = e1.close()
    assert audit["blocks_in_use"] == 0
    assert sched.requests[2].state == "cancelled"
    assert e1.close() == audit  # idempotent
    # a second engine on the SAME telemetry reclaims the namespaces with
    # fresh counters instead of marching to serve2/sched2
    e2 = build_serve_engine(params, cfg, sec, telemetry=tel)
    assert (e2._ns, e2._sched_ns, e2._comm_ns) == ("serve", "sched", "comm")
    assert e2.stats["decode_ticks"] == 0
    e2.close()


def test_serve_trial_runner_back_to_back_clean(tmp_path):
    """Two full trials through the harness: the refcount audit between
    trials is the harness's own teardown gate (a leak raises)."""
    from deepspeed_tpu.autotuning import ServeTrialRunner, ServeWorkload

    cfg, params = _tiny_serving()
    base = dict(max_seqs=2, num_blocks=32, block_size=8, max_seq_len=128,
                prefill_buckets=[16, 32, 64], prefill_budget=64)
    wl = ServeWorkload(n_req=3, sys_len=16, sfx_len=8, max_new=4)
    runner = ServeTrialRunner(params, cfg, wl, base=base)
    s1, m1 = runner({"quant": None, "prefix_caching": True,
                     "prefill_chunk": 16, "kv_watermark": 0.0625,
                     "spec": False}, 1.0)
    s2, m2 = runner({"quant": "int8", "prefix_caching": False,
                     "kv_watermark": 0.25, "spec": True,
                     "spec_max_draft": 2}, 1.0)
    assert s1 > 0 and s2 > 0 and runner.trials_run == 2
    assert m1["finished"] == 3
    assert "ttft_ms" in m1["latency_percentiles"]
    # half-budget rung serves fewer requests of the same shape
    s3, m3 = runner({"quant": None, "prefix_caching": True,
                     "prefill_chunk": 16, "kv_watermark": 0.0625,
                     "spec": False}, 0.5)
    assert m3["requests"] == 2


# ---------------------------------------------------------------------------
# e2e smokes
# ---------------------------------------------------------------------------
def test_autotune_model_smoke_winner_roundtrips_config():
    """CPU-smoke end-to-end training search: the winner dict must be a
    valid engine config (parse_config round-trip; tuner provenance rides
    the accepted-and-stripped 'autotuning' passthrough key)."""
    from deepspeed_tpu.config.config import parse_config

    best, trials = autotune_model(
        "tiny", seq_len=32,
        base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
        micro_batches=(1, 2), remat_policies=("none",), zero_stages=(1,),
        mesh_candidates=({},), steps=1, top_k=2,
    )
    assert best is not None
    meta = best["autotuning"]
    assert meta["winner"]["micro_batch"] in (1, 2)
    measured = [t for t in trials if t.measured]
    assert meta["tokens_per_sec"] == max(t.score for t in measured)
    cfg = parse_config(best, dp_world_size=1)  # strips the passthrough key
    assert cfg.train_micro_batch_size_per_gpu == meta["winner"]["micro_batch"]
    assert cfg.zero_optimization.stage == meta["winner"]["zero_stage"]


_SEARCH_BASE = dict(max_seqs=4, num_blocks=64, block_size=8, max_seq_len=256,
                    prefill_buckets=[16, 32, 64, 128], prefill_budget=128)


def _search_space(serve_replicas):
    return serving_space(
        tp=(1,), serve_replicas=serve_replicas, quant=(None, "int8"),
        prefill_chunk=(None, 32), kv_watermark=(0.0625, 0.25),
        spec=(False, True), spec_max_draft=(4,), quant_comm=("none",),
        comm_tiles=(1,),
    )


def test_autotune_serving_bounded_search_measures_replicated_caching(tmp_path):
    """``autotune_serving`` on the stub-sized workload under a trial cap:
    at most ``max_trials`` + the incumbent are measured, the static model
    still prunes (``serve_replicas=3`` cannot split ``max_seqs=4``), every
    candidate has a leaderboard row, and the ``serve_replicas > 1`` x prefix
    caching region is among the measured.  Scores are CPU tokens/s: none is
    compared with another here."""
    from deepspeed_tpu.autotuning import ServeWorkload, autotune_serving

    cfg, params = _tiny_serving()
    space = _search_space(serve_replicas=(1, 2, 3))
    incumbent = space.canonicalize(dict(
        tp=1, serve_replicas=1, quant=None, prefix_caching=True,
        prefill_chunk=32, kv_watermark=0.0625, spec=False, spec_max_draft=4,
        quant_comm="none", comm_tiles=1))
    winner, trials, tuner = autotune_serving(
        params, cfg, workload=ServeWorkload(n_req=5, sys_len=48, sfx_len=16,
                                            max_new=6),
        base=_SEARCH_BASE, space=space, incumbent=incumbent, seed=0,
        top_k=6, rungs=(1.0,), max_trials=6,
    )
    assert winner is not None and winner.measured
    assert tuner.pruned_fraction > 0
    out = str(tmp_path / "board.json")
    board = write_leaderboard(out, trials)
    assert board == json.loads(open(out).read())
    assert 0 < board["measured"] <= 7  # max_trials=6 + the incumbent
    assert board["candidates"] == len(board["trials"]) \
        == len(space.candidates())
    for row in board["trials"]:
        assert set(row) >= {"candidate", "predicted_cost", "verdict", "score"}
    assert any(row["score"] is not None
               and int(row["candidate"].get("serve_replicas", 1)) > 1
               and row["candidate"].get("prefix_caching")
               for row in board["trials"])
    assert any(candidate_key(t.candidate) == candidate_key(incumbent)
               and t.measured for t in trials)


# slow: 42 s: builds and serves a real engine a candidate over two rungs; the bounded search beside it is in the lane
@pytest.mark.slow
def test_full_serving_search_with_halving():
    """A larger (slow-lane) search exercising two rungs + promotion on
    real engines end to end."""
    from deepspeed_tpu.autotuning import ServeWorkload, autotune_serving

    cfg, params = _tiny_serving()
    wl = ServeWorkload(n_req=6, sys_len=48, sfx_len=16, max_new=6)
    sp = _search_space(serve_replicas=(1, 2))
    winner, trials, tuner = autotune_serving(
        params, cfg, workload=wl, base=_SEARCH_BASE, space=sp,
        rungs=(0.5, 1.0), top_k=4, eta=2, seed=0,
    )
    assert winner is not None and winner.rung == 1
    # the serve_replicas x caching/spec region is feasible now (replica-
    # affine serving un-gated it), so the static prune no longer halves
    # this grid; the R>1 candidates must instead SURVIVE feasibility
    assert any(int(t.candidate.get("serve_replicas", 1)) > 1
               and t.verdict == "ok" for t in trials)
    # promoted trials were measured at both rungs
    assert any(len(t.run_order) == 2 for t in trials)
