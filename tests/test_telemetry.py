"""Unified telemetry: registry quantiles, disabled-path no-ops, request
lifecycle traces (TTFT/TBT/queue wait incl. preemption), Chrome trace-event
schema + per-track ordering, stats-compat read-through views vs registry
counters on a randomized serve run, telemetry-disabled twin equality, the
train-engine span/snapshot wiring, monitor-writer coverage (CSV append
semantics, Comet throttling, wandb step-grouped logging), the timer
``reset``/``last`` regression, and the tier-1 marker-hygiene audit."""
import json
import re
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params
from deepspeed_tpu.telemetry import (
    Histogram,
    MetricsRegistry,
    StatsView,
    Telemetry,
    format_percentile_table,
    percentile_summary,
)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def tiny():
    # fp32 so greedy twin runs cannot diverge on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def _serve_once(cfg, params, telemetry):
    """Overloaded randomized serve run (pool pressure -> preemption) with
    speculation + prefix caching live, deterministic across calls."""
    eng = InferenceEngineV2(
        params, cfg, max_seqs=3, num_blocks=8, block_size=8,
        prefill_buckets=(16, 32), enable_prefix_caching=True,
        enable_speculation=True, spec_max_draft=4, telemetry=telemetry,
    )
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    # random base + repeated tail so the prompt-lookup drafter fires
    prompts = {
        u: [int(t) for t in rng.integers(1, 255, 10)] + [7, 8] * 2
        for u in range(1, 5)
    }
    samp = SamplingParams(temperature=0.0, max_new_tokens=24)
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    res = sched.run()
    assert all(len(res[u]) == 24 for u in prompts)
    eng.mgr.allocator.audit()
    return eng, sched, res


@pytest.fixture(scope="module")
def serve_pair(tiny):
    """The same workload twice: telemetry on (inspected) and off (twin)."""
    cfg, params = tiny
    on = _serve_once(cfg, params, telemetry=True)
    off = _serve_once(cfg, params, telemetry=False)
    return on, off


# ---------------------------------------------------------------------------
# registry: counters, histograms, quantiles, disabled path, stats views
# ---------------------------------------------------------------------------
def test_counter_thread_safe_and_snapshot():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("x/hits")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(5000)])
               for _ in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value == 20000
    assert reg.counter("x/hits") is c  # get-or-create returns the same object
    assert ("x/hits", 20000.0, 7) in reg.snapshot(step=7)


def test_histogram_exact_quantiles_small_count():
    h = Histogram("h", exact_limit=4096)
    vals = list(range(1, 101))  # 1..100
    np.random.default_rng(0).shuffle(vals)
    for v in vals:
        h.observe(v)
    assert h.exact
    # nearest-rank: p50 of 1..100 = 50, p90 = 90, p99 = 99, p100 = max
    assert h.percentile(50) == 50
    assert h.percentile(90) == 90
    assert h.percentile(99) == 99
    assert h.percentile(100) == 100
    assert h.min == 1 and h.max == 100 and h.count == 100
    assert h.mean == pytest.approx(50.5)


def test_histogram_bucketed_quantiles_bounded_error():
    """Past exact_limit the raw samples drop and quantiles come from the
    log-spaced buckets: relative error is bounded by sqrt(growth)."""
    h = Histogram("h", exact_limit=16, growth=2 ** 0.25)
    rng = np.random.default_rng(0)
    vals = np.exp(rng.normal(3.0, 1.0, 2000))  # lognormal, decades of spread
    for v in vals:
        h.observe(v)
    assert not h.exact
    bound = (2 ** 0.25) ** 0.5 + 0.02
    for q in (50, 90, 99):
        est, true = h.percentile(q), float(np.percentile(vals, q))
        assert 1 / bound <= est / true <= bound, (q, est, true)
    # min/max clamp the tails exactly
    assert h.percentile(0) >= h.min and h.percentile(100) <= h.max


def test_disabled_registry_is_noop_but_counters_count():
    reg = MetricsRegistry(enabled=False, jsonl_path="/nonexistent/dir/x.jsonl")
    h = reg.histogram("a")
    g = reg.gauge("b")
    assert h is reg.histogram("zzz")  # shared null singleton
    h.observe(1.0)
    g.set(5)
    assert h.count == 0 and h.percentile(99) == 0.0 and g.value == 0.0
    reg.event("boom", x=1)  # no sink touched (the path is unwritable)
    assert reg.snapshot() == []
    # counters are the stats contract: they count regardless
    c = reg.counter("serve/ticks")
    c.inc(3)
    assert c.value == 3

    tel = Telemetry(None)
    assert not tel.enabled
    span = tel.recorder.start("x", track="t")
    assert span.end() is span and len(tel.recorder) == 0
    tr = tel.request_trace(1)
    tr.submitted(); tr.admitted(); tr.tokens(1); tr.finished()
    assert tel.h_ttft.count == 0
    assert tel.chrome_trace()["traceEvents"] == []


def test_histogram_reset_and_window():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("w")
    for v in (1.0, 10.0, 100.0):
        h.observe(v)
    c = reg.counter("kept")
    c.inc(5)
    reg.reset_histograms()
    assert h.count == 0 and h.percentile(99) == 0.0 and h.min == 0.0
    assert c.value == 5  # counters are baselined by differencing, not reset
    h.observe(7.0)  # still functional after reset
    assert h.count == 1 and h.percentile(50) == 7.0

    tel = Telemetry(True)
    tel.h_ttft.observe(3.0)
    tel.reset_window()
    assert tel.h_ttft.count == 0
    Telemetry(None).reset_window()  # disabled path: no-op, no error


def test_claim_prefix_second_engine_does_not_alias(tiny):
    """Two engines sharing one Telemetry must keep independent stats —
    the second claimant gets the serve2/sched2 namespaces."""
    tel = Telemetry(True)
    assert tel.claim_prefix("x") == "x"
    assert tel.claim_prefix("x") == "x2"
    assert tel.claim_prefix("x") == "x3"

    cfg, params = tiny
    kw = dict(max_seqs=2, num_blocks=8, block_size=8, prefill_buckets=(16, 32))
    e1 = InferenceEngineV2(params, cfg, telemetry=tel, **kw)
    e2 = InferenceEngineV2(params, cfg, telemetry=tel, **kw)
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    e1.scheduler.submit(1, list(range(1, 13)), samp)
    e1.scheduler.run()
    assert e1.stats["decode_ticks"] > 0
    assert e2.stats["decode_ticks"] == 0  # no aliasing through the registry
    assert dict(e2.scheduler.stats)["submitted"] == 0
    assert tel.registry.get("serve2/decode_ticks").value == 0
    assert e1.telemetry is e2.telemetry  # still one shared trace timeline
    # request-latency histograms are namespaced too, not just counters
    assert tel.registry.get("serve/ttft_ms").count == 1
    assert tel.registry.get("serve2/ttft_ms").count == 0
    e2.scheduler.submit(2, list(range(1, 13)), samp)
    e2.scheduler.run()
    assert tel.registry.get("serve2/ttft_ms").count == 1
    assert tel.registry.get("serve/ttft_ms").count == 1  # unchanged


def test_chunked_prefill_spans_defer_and_resolve_tick_tight(tiny):
    """An intermediate prefill chunk completes no prompt, so nothing is
    fetched host-side: its span closes at dispatch.  It is exported with
    ``"synced": false`` and its dispatch-side duration, and observes nothing
    into the pack histogram: the recorder invents no device time (a pack's
    device time is the profiler trace's)."""
    cfg, params = tiny
    eng = InferenceEngineV2(
        params, cfg, max_seqs=2, num_blocks=16, block_size=8,
        prefill_buckets=(8, 16, 32), prefill_chunk=8, telemetry=True,
    )
    sched = eng.scheduler
    sched.submit(1, list(range(1, 21)), SamplingParams(
        temperature=0.0, max_new_tokens=4))
    sched.run()
    assert eng.stats["prefill_dispatches"] >= 2  # 20 tokens / 8-chunk
    evs = eng.telemetry.chrome_trace()["traceEvents"]
    packs = [e for e in evs if e["ph"] == "X" and e["name"] == "prefill_pack"]
    assert len(packs) == eng.stats["prefill_dispatches"]
    unsynced = [e for e in packs if e["args"].get("synced") is False]
    synced = [e for e in packs if "synced" not in e["args"]]
    # one ahead (PR 43): NO pack is fetched inside its dispatch span; the
    # chunk that finished the prompt is waited for and fetched by ONE
    # ``tick_collect`` span of its own (in today's order, ``_back_to_back``,
    # that chunk's span held its fetch and was the one synced pack)
    assert len(synced) == 0 and len(unsynced) == len(packs)
    collects = [e for e in evs if e["ph"] == "X" and e["name"] == "tick_collect"
                and e["args"]["what"] == "prefill_pack"]
    assert len(collects) == 1 and collects[0]["ts"] >= packs[-1]["ts"]
    for e in unsynced:
        # dispatch-side duration: the span ended when the dispatch returned
        assert e["dur"] == pytest.approx(e["args"]["dispatch_ms"] * 1e3, abs=1.0)
    # the histogram holds synced packs only
    h = eng.telemetry.registry.get("serve/prefill_pack_ms")
    assert h.count == len(synced)
    # no invented device events, no extra track
    assert not any(e["ph"] == "X" and "window" in e["name"] for e in evs)
    assert not any(e["ph"] == "M" and e["args"]["name"].endswith("-device")
                   for e in evs)
    # every pack names the requests in it
    assert all(e["args"]["uids"] == [1] for e in packs)


def test_prefill_pack_span_carries_ctx_pages(tiny):
    """``ctx_pages`` on a ``prefill_pack`` span is the live context pages the
    pack's segments bring: ceil(start / block_size) summed over its entries,
    so a trace can divide the ctx kernel's time by the work it walked."""
    cfg, params = tiny
    bs = 8
    eng = InferenceEngineV2(
        params, cfg, max_seqs=4, num_blocks=32, block_size=bs,
        prefill_buckets=(16, 32), prefill_chunk=16, prefill_budget=32,
        telemetry=True,
    )
    seen = []
    run = eng.pack_dispatch  # (the body under ``_run_packed_prefill`` too)

    def spy(entries, sampling, **kw):
        seen.append(sum(-(-start // bs) for _, start, _ in entries))
        return run(entries, sampling, **kw)

    eng.pack_dispatch = spy
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=2)
    sched.submit(1, list(range(1, 41)), samp)    # 40 tokens: chunks at 0, 16, 32
    sched.submit(2, list(range(50, 71)), samp)   # 21 tokens: chunks at 0, 16
    sched.run()
    evs = eng.telemetry.chrome_trace()["traceEvents"]
    packs = [e for e in evs if e["ph"] == "X" and e["name"] == "prefill_pack"]
    assert len(packs) == len(seen) >= 3
    assert [e["args"]["ctx_pages"] for e in packs] == seen
    assert max(seen) >= 4 and seen[0] == 0  # cold pack first, ctx packs after
    eng.close()


def test_stats_view_mapping_semantics():
    reg = MetricsRegistry(enabled=True)
    c = {k: reg.counter(f"p/{k}") for k in ("a", "b")}
    view = StatsView(c)
    c["a"].inc(2)
    assert view["a"] == 2 and view["b"] == 0
    assert dict(view) == {"a": 2, "b": 0}
    assert list(view) == ["a", "b"] and len(view) == 2
    view["b"] += 5  # legacy external write path
    assert c["b"].value == 5
    with pytest.raises(TypeError):
        del view["a"]


# ---------------------------------------------------------------------------
# request trace lifecycle (fake clock): submit -> preempt -> finish
# ---------------------------------------------------------------------------
def test_request_trace_lifecycle(tmp_path):
    clk = _Clock()
    tel = Telemetry(True, jsonl_path=str(tmp_path / "events.jsonl"), clock=clk)
    tr = tel.request_trace(42)
    clk.t = 1.0
    tr.submitted(prompt_tokens=10)
    clk.t = 1.5
    tr.admitted()
    tr.prefill_chunk(1.5, 2.0, 8)
    clk.t = 2.5
    tr.tokens(1)  # first token
    clk.t = 3.0
    tr.preempted()
    clk.t = 3.5
    tr.admitted()  # re-admission: no second queue-wait observation
    clk.t = 4.0
    tr.tokens(2)  # spec tick: 2 tokens share the 1.5 s gap
    tr.add_spec(4, 2)
    clk.t = 5.0
    tr.finished()

    assert tr.queue_wait_ms == pytest.approx(500.0)
    assert tr.ttft_ms == pytest.approx(1500.0)
    assert tr.e2e_ms == pytest.approx(4000.0)
    assert tr.preemptions == 1 and tr.readmits == 1
    assert tr.tokens_emitted == 3 and tr.accept_rate == 0.5
    assert tr.tbt_gaps_ms == pytest.approx([750.0, 750.0])
    # histograms observed at the moment each quantity became known
    assert tel.h_queue_wait.count == 1
    assert tel.h_queue_wait.percentile(50) == pytest.approx(500.0)
    assert tel.h_ttft.count == 1
    assert tel.h_ttft.percentile(50) == pytest.approx(1500.0)
    assert tel.h_tbt.count == 2
    assert tel.h_e2e.percentile(50) == pytest.approx(4000.0)
    assert tel.h_accept.percentile(50) == pytest.approx(0.5)
    assert tel.finished_traces == [tr]
    # the finish wrote a structured JSONL event
    tel.close()
    lines = [json.loads(line) for line in
             (tmp_path / "events.jsonl").read_text().splitlines()]
    ev = next(rec for rec in lines if rec["event"] == "request_finished")
    assert ev["uid"] == 42 and ev["preemptions"] == 1
    assert ev["accept_rate"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Chrome trace export: schema validity + strict per-track ordering
# ---------------------------------------------------------------------------
def test_chrome_trace_schema_and_ordering():
    tel = Telemetry(True)
    rec = tel.recorder
    for i in range(3):
        rec.start("tick", track="serve", i=i).end()
    # a span that ends with a sync object is exported unsynced
    x = jnp.zeros((4,))
    rec.start("train_batch", track="train").end(sync_obj=x)
    rec.start("train_batch", track="train").end(sync_obj=x)
    tr = tel.request_trace(3)
    tr.submitted(prompt_tokens=4)
    tr.admitted()
    tr.tokens(1)
    tr.tokens(1)
    tr.finished()

    out = tel.chrome_trace()
    json.loads(json.dumps(out))  # round-trips as plain JSON
    evs = out["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs, "no complete events exported"
    for e in xs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # strictly increasing ts per (pid, tid)
    by_track = {}
    for e in xs:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for key, ts in by_track.items():
        assert all(b > a for a, b in zip(ts, ts[1:])), key
    # the unsynced train spans carry the flag and no invented device time
    trains = [e for e in xs if e["name"] == "train_batch"]
    assert len(trains) == 2
    assert all(e["args"]["synced"] is False for e in trains)
    assert all("device_window_avg_ms" not in e["args"] for e in xs)
    assert all("synced" not in e["args"] for e in xs if e["name"] == "tick")
    assert not any("window" in e["name"] for e in xs)
    track_names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"serve", "train"} <= track_names
    assert not any(t.endswith("-device") for t in track_names)
    # every span event carries its id; flush() let the sync objects go
    assert len({e["args"]["span_id"] for e in xs if e["pid"] == 0}) == 5
    assert not rec._pending
    assert any(e["pid"] == 1 and e["name"] == "queued" for e in xs)


# ---------------------------------------------------------------------------
# serve integration: compat views, traces under preemption, disabled twin
# ---------------------------------------------------------------------------
def test_stats_views_stay_equal_to_registry_counters(serve_pair):
    (eng, sched, _), _ = serve_pair
    reg = eng.telemetry.registry
    assert sched.telemetry is eng.telemetry  # one registry per pair
    for k, v in eng.stats.items():
        assert reg.get(f"serve/{k}").value == v, k
    for k, v in sched.stats.items():
        assert reg.get(f"sched/{k}").value == v, k
    # and the monitor-facing snapshot carries the same values
    snap = dict((label, val) for label, val, _ in reg.snapshot(step=1))
    assert snap["serve/decode_ticks"] == eng.stats["decode_ticks"]
    assert snap["sched/finished"] == sched.stats["finished"]
    assert eng.stats["spec_drafted"] > 0  # speculation was actually live


def test_request_traces_under_preemption(serve_pair):
    (eng, sched, _), _ = serve_pair
    tel = eng.telemetry
    assert sched.stats["preemptions"] >= 1  # pool pressure was real
    traces = tel.finished_traces
    assert len(traces) == 4
    assert sum(t.preemptions for t in traces) == sched.stats["preemptions"]
    assert tel.h_ttft.count == 4 and tel.h_queue_wait.count == 4
    assert tel.h_tbt.count > 0 and tel.h_e2e.count == 4
    for t in traces:
        assert t.tokens_emitted >= 24  # stop-trimmed tails may add a few
        assert t.e2e_ms >= t.ttft_ms >= t.queue_wait_ms >= 0
    for h in (tel.h_ttft, tel.h_tbt, tel.h_queue_wait, tel.h_e2e):
        assert h.percentile(50) <= h.percentile(90) <= h.percentile(99)
    # per-request accept rate folded across preemption incarnations
    drafted = sum(t.drafted for t in traces)
    accepted = sum(t.accepted for t in traces)
    assert drafted == eng.stats["spec_drafted"]
    assert accepted == eng.stats["spec_accepted"]
    # tick spans recorded + percentile table renders
    assert len(tel.recorder) > 0
    table = format_percentile_table(percentile_summary(
        tel.registry, ("serve/ttft_ms", "serve/tbt_ms", "serve/queue_wait_ms")))
    assert "ttft_ms" in table and "p99" in table
    # request tracks appear in the chrome export
    evs = tel.chrome_trace()["traceEvents"]
    assert any(e["ph"] == "X" and e["pid"] == 1 and e["name"] == "preempted"
               for e in evs)


def test_telemetry_disabled_twin_has_identical_stats(serve_pair):
    (eng_on, sched_on, res_on), (eng_off, sched_off, res_off) = serve_pair
    assert res_on == res_off  # observation does not change behavior
    assert dict(eng_on.stats) == dict(eng_off.stats)
    assert dict(sched_on.stats) == dict(sched_off.stats)
    # and the disabled engine recorded nothing
    assert len(eng_off.telemetry.recorder) == 0
    assert eng_off.telemetry.finished_traces == []
    assert eng_off.telemetry.registry.snapshot() == []


# ---------------------------------------------------------------------------
# train engine wiring: spans, deferred flush, registry -> monitor fan-out
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("async_metrics", [True, False])
def test_train_engine_telemetry_spans_and_monitor_fanout(async_metrics):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import CausalLM

    cfg = get_preset("tiny", max_seq_len=32)
    engine, _, _, _ = ds.initialize(
        model=CausalLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 0},
            "bf16": {"enabled": True},
            "steps_per_print": 2,
            "telemetry": {"enabled": True},
            "train_data": {"async_metrics": async_metrics},
        },
    )
    captured = []
    engine.monitor = types.SimpleNamespace(
        enabled=True, write_events=captured.extend
    )
    engine.telemetry.registry.counter("user/events").inc(3)
    rng = np.random.default_rng(0)
    # global batch = micro(1) x dp(8 virtual devices)
    dp = engine.config.dp_world_size
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (dp, 33), dtype=np.int64)}
    for _ in range(4):
        engine.train_batch(batch)
    engine.get_last_loss()
    assert len(engine.telemetry.recorder) == 4  # one span per step
    steps = [e for e in engine.telemetry.recorder.chrome_events()
             if e["ph"] == "X"]
    assert [e["name"] for e in steps] == ["train_batch"] * 4
    assert [e["args"]["step"] for e in steps] == [1, 2, 3, 4]
    # one path in both metric modes: the span adds no host read to the step,
    # closes at dispatch and is exported unsynced; no step latency is invented
    assert all(e["args"]["synced"] is False for e in steps)
    assert engine.telemetry.registry.get("train/step_ms") is None
    assert engine.telemetry.recorder._pending == []  # the flush let the losses go
    labels = {label for label, _, _ in captured}
    assert "Train/Samples/train_loss" in labels  # legacy rows intact
    assert "user/events" in labels  # registry snapshot rode along


# ---------------------------------------------------------------------------
# histogram merge laws: the fleet-observability wire primitive
# ---------------------------------------------------------------------------
def test_histogram_merge_matches_pooled_ground_truth():
    """Sharding a sample stream across N histograms and merging the states
    must reproduce the single pooled histogram bucket-for-bucket, and the
    merged quantiles stay within the documented sqrt(growth) bound of the
    true (raw-sample) percentiles — merging adds no error of its own."""
    rng = np.random.default_rng(7)
    vals = np.exp(rng.normal(3.0, 1.0, 3000))  # decades of spread
    growth = 2 ** 0.25
    shards = [Histogram(f"s{i}", exact_limit=16, growth=growth)
              for i in range(4)]
    pooled = Histogram("pooled", exact_limit=16, growth=growth)
    for i, v in enumerate(vals):
        shards[i % 4].observe(float(v))
        pooled.observe(float(v))
    merged = Histogram.from_state(shards[0].state_dict())
    for s in shards[1:]:
        merged.merge(s.state_dict())
    assert merged.count == pooled.count == len(vals)
    assert merged._counts == pooled._counts  # bucket-wise identical
    assert merged.min == pooled.min and merged.max == pooled.max
    assert merged.sum == pytest.approx(pooled.sum)
    bound = growth ** 0.5 + 0.02
    for q in (50, 90, 99):
        est, true = merged.percentile(q), float(np.percentile(vals, q))
        assert 1 / bound <= est / true <= bound, (q, est, true)
        assert merged.percentile(q) == pooled.percentile(q)


def test_histogram_merge_commutative_and_associative():
    rng = np.random.default_rng(3)
    shards = []
    for i in range(3):
        h = Histogram(f"s{i}", exact_limit=8)
        for v in rng.uniform(0.5, 500.0, 40):
            h.observe(float(v))
        shards.append(h)
    a, b, c = (s.state_dict() for s in shards)

    def fold(*states):
        m = Histogram.from_state(states[0])
        for st in states[1:]:
            m.merge(st)
        return m

    abc = fold(a, b, c)
    cba = fold(c, b, a)
    ab_c = fold(fold(a, b).state_dict(), c)
    a_bc = fold(a, fold(b, c).state_dict())
    for other in (cba, ab_c, a_bc):
        assert other._counts == abc._counts
        assert other.count == abc.count
        assert other.sum == pytest.approx(abc.sum)
        assert other.min == abc.min and other.max == abc.max
        for q in (50, 90, 99):
            assert other.percentile(q) == abc.percentile(q)


def test_histogram_merge_exact_until_cap_then_degrades():
    a = Histogram("a", exact_limit=10)
    b = Histogram("b", exact_limit=10)
    for v in (1.0, 2.0, 3.0):
        a.observe(v)
    for v in (4.0, 5.0):
        b.observe(v)
    m = Histogram.from_state(a.state_dict()).merge(b)
    assert m.exact and m.count == 5
    # exact+exact under the cap: quantiles == pooled nearest-rank, exactly
    assert m.percentile(50) == 3.0 and m.percentile(100) == 5.0
    # an empty merge is a no-op and cannot degrade exactness
    m.merge(Histogram("empty", exact_limit=10))
    assert m.exact and m.count == 5
    # pushing past the cap drops the raw samples; totals are preserved
    c = Histogram("c", exact_limit=10)
    for v in range(1, 9):
        c.observe(float(v))
    m.merge(c)
    assert not m.exact and m.count == 13
    assert m.min == 1.0 and m.max == 8.0
    # degradation is one-way: an exact shard cannot resurrect samples
    d = Histogram("d", exact_limit=10)
    d.observe(2.5)
    m.merge(d)
    assert not m.exact and m.count == 14


def test_histogram_merge_mismatched_geometry_raises():
    base = Histogram("base")
    base.observe(1.0)
    for bad in (Histogram("g", growth=1.5), Histogram("lo", lo=1e-2),
                Histogram("hi", hi=1e9)):  # hi changes the bucket COUNT
        bad.observe(2.0)
        with pytest.raises(ValueError):
            Histogram.from_state(base.state_dict()).merge(bad.state_dict())
    # the failed merge left the receiver untouched
    m = Histogram.from_state(base.state_dict())
    with pytest.raises(ValueError):
        m.merge(Histogram("g2", growth=1.5).state_dict())
    assert m.count == 1 and m.percentile(50) == 1.0


def test_histogram_state_dict_json_round_trip():
    h = Histogram("h", exact_limit=4)
    for v in (1.0, 10.0, 100.0, 1000.0, 10000.0):  # degraded (over cap)
        h.observe(v)
    state = json.loads(json.dumps(h.state_dict()))  # wire-safe
    back = Histogram.from_state(state)
    assert back._counts == h._counts and back.count == h.count
    assert back.min == h.min and back.max == h.max
    assert not back.exact
    back.merge(h.state_dict())  # geometry survived the round trip
    assert back.count == 2 * h.count


# ---------------------------------------------------------------------------
# chrome-trace pid namespaces: multi-engine exports must not alias
# ---------------------------------------------------------------------------
def _finish_req(tel, uid, ns):
    tr = tel.request_trace(uid, ns=ns)
    tr.submitted(prompt_tokens=2)
    tr.admitted()
    tr.tokens(1)
    tr.finished()


def test_chrome_trace_request_namespaces_get_distinct_pids():
    """Regression: two engines sharing one Telemetry used to export BOTH
    request tracks on pid 1 (uid collisions aliased the timelines).  Now
    ``serve`` keeps pid 1 (byte-compat single-process layout) and every
    other namespace gets its own odd pid plus a process_name row."""
    tel = Telemetry(True)
    for uid, ns in ((1, "serve"), (2, "serve2"), (3, "serve3")):
        _finish_req(tel, uid, ns)
    evs = tel.chrome_trace()["traceEvents"]
    req_pids = {e["pid"] for e in evs if e["ph"] == "X" and e["pid"] >= 1}
    assert req_pids == {1, 3, 5}
    names = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names[3] == "requests:serve2" and names[5] == "requests:serve3"
    # same uid in two namespaces: distinct (pid, tid) rows, no aliasing
    tel2 = Telemetry(True)
    _finish_req(tel2, 42, "serve")
    _finish_req(tel2, 42, "serve2")
    rows = {(e["pid"], e["tid"]) for e in tel2.chrome_trace()["traceEvents"]
            if e["ph"] == "X" and e["pid"] >= 1}
    assert len(rows) == 2


def test_drain_chrome_events_namespace_pids_stable_across_drains():
    tel = Telemetry(True)
    _finish_req(tel, 1, "serve2")
    first = tel.drain_chrome_events()
    pids1 = {e["pid"] for e in first if e["ph"] == "X" and e["pid"] >= 1}
    assert pids1 == {3}  # first non-serve namespace
    _finish_req(tel, 2, "serve2")
    _finish_req(tel, 3, "serve3")
    second = tel.drain_chrome_events()
    by_ns = {}
    for e in second:
        if e["ph"] == "X" and e["pid"] >= 1:
            by_ns.setdefault(e["pid"], 0)
    # serve2 kept pid 3 across drains; serve3 got the next odd pid
    assert set(by_ns) == {3, 5}
    # a drain is incremental: uid 1's lifecycle (tid = uid) from the first
    # batch is not re-exported
    assert not any(e["tid"] == 1 for e in second if e["ph"] == "X")


# ---------------------------------------------------------------------------
# heartbeat clock-offset estimation (fake timestamps)
# ---------------------------------------------------------------------------
def test_heartbeat_note_clock_offset_midpoint_and_min_rtt():
    from deepspeed_tpu.serving.transport import HeartbeatMonitor

    clk = _Clock()
    mon = HeartbeatMonitor(clock=clk)
    mon.watch(0, stream=None)
    assert mon.clock_offset(0) is None  # nothing folded yet
    # remote clock runs 100 s ahead; symmetric 2 s RTT -> exact midpoint
    mon.note_clock(0, t_send=10.0, t_recv=12.0, remote_ts=111.0)
    off, err = mon.clock_offset(0)
    assert off == pytest.approx(100.0) and err == pytest.approx(1.0)
    # a WORSE (higher-RTT) sample must not replace the estimate
    mon.note_clock(0, t_send=20.0, t_recv=30.0, remote_ts=128.0)
    off, err = mon.clock_offset(0)
    assert off == pytest.approx(100.0) and err == pytest.approx(1.0)
    # a tighter RTT wins and shrinks the error bound to RTT/2
    mon.note_clock(0, t_send=40.0, t_recv=40.5, remote_ts=140.35)
    off, err = mon.clock_offset(0)
    assert off == pytest.approx(100.1) and err == pytest.approx(0.25)
    # unknown endpoint: fold is a no-op, query returns None
    mon.note_clock(9, t_send=0.0, t_recv=1.0, remote_ts=5.0)
    assert mon.clock_offset(9) is None


# ---------------------------------------------------------------------------
# satellites: timer reset, monitor writers, marker hygiene
# ---------------------------------------------------------------------------
def test_timer_reset_clears_last():
    from deepspeed_tpu.utils.timer import _Timer

    t = _Timer("t")
    assert t.last() == 0.0  # defined before any stop
    t.start()
    t.stop()
    assert t.last() > 0.0
    t.reset()
    assert t.last() == 0.0  # regression: reset used to leave _last stale
    assert t.elapsed(reset=False) == 0.0


def test_csv_monitor_appends_and_groups_by_label(tmp_path):
    from deepspeed_tpu.monitor.monitor import CsvMonitor

    cfg = types.SimpleNamespace(enabled=True, output_path=str(tmp_path),
                                job_name="job")
    mon = CsvMonitor(cfg)
    mon.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1),
                      ("Train/loss", 0.5, 2)])
    mon.write_events([("Train/loss", 0.25, 3)])  # second flush appends
    loss = (tmp_path / "job" / "Train_loss.csv").read_text().splitlines()
    assert loss[0] == "step,Train/loss"  # header written once
    assert loss[1:] == ["1,1.0", "2,0.5", "3,0.25"]
    lr = (tmp_path / "job" / "Train_lr.csv").read_text().splitlines()
    assert lr == ["step,Train/lr", "1,0.1"]


def test_comet_monitor_throttles_by_samples_log_interval(monkeypatch):
    logged = []

    class _Exp:
        def log_metric(self, label, value, step=None):
            logged.append((label, value, step))

        def set_name(self, name):
            self.name = name

    stub = types.ModuleType("comet_ml")
    stub.start = lambda **kw: _Exp()
    monkeypatch.setitem(sys.modules, "comet_ml", stub)
    from deepspeed_tpu.monitor.monitor import CometMonitor

    cfg = types.SimpleNamespace(enabled=True, samples_log_interval=3)
    mon = CometMonitor(cfg)
    assert mon.enabled and mon.experiment is not None
    mon.write_events([("loss", float(s), s) for s in range(1, 10)])
    assert [step for _, _, step in logged] == [3, 6, 9]


def test_wandb_monitor_groups_events_by_step(monkeypatch):
    calls = []
    stub = types.ModuleType("wandb")
    stub.init = lambda **kw: None
    stub.log = lambda row, step=None: calls.append((step, dict(row)))
    monkeypatch.setitem(sys.modules, "wandb", stub)
    from deepspeed_tpu.monitor.monitor import WandbMonitor

    cfg = types.SimpleNamespace(enabled=True, project=None, group=None,
                                team=None)
    mon = WandbMonitor(cfg)
    assert mon.enabled
    mon.write_events([
        ("loss", 1.0, 1), ("lr", 0.1, 1), ("scale", 2.0, 1),
        ("loss", 0.5, 2), ("lr", 0.1, 2),
    ])
    # one wandb.log per STEP with all of that step's labels, not one per event
    assert calls == [
        (1, {"loss": 1.0, "lr": 0.1, "scale": 2.0}),
        (2, {"loss": 0.5, "lr": 0.1}),
    ]


# ---------------------------------------------------------------------------
# the span tree: ids, parents, self time, the profiler-side mirror
# ---------------------------------------------------------------------------
def _span_events(tel):
    return [e for e in tel.recorder.chrome_events() if e["ph"] == "X"]


def _tree(events):
    by_id = {e["args"]["span_id"]: e for e in events}

    def ancestors(e):
        p = e["args"].get("parent_id")
        while p is not None:
            yield by_id[p]
            p = by_id[p]["args"].get("parent_id")

    return by_id, ancestors


@pytest.fixture(scope="module")
def plain_serve(tiny):
    """A plain (no speculation) chunked-prefill run with telemetry on."""
    cfg, params = tiny
    eng = InferenceEngineV2(
        params, cfg, max_seqs=3, num_blocks=32, block_size=8,
        prefill_buckets=(16, 32), prefill_chunk=16, telemetry=True,
    )
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)
    for u in (1, 2):
        sched.submit(u, list(range(u, u + 40)), samp)
    sched.run()
    return eng, sched


def test_every_decode_tick_hangs_under_sched_decode_under_sched_tick(plain_serve):
    eng, _ = plain_serve
    events = _span_events(eng.telemetry)
    by_id, ancestors = _tree(events)
    assert len(by_id) == len(events)  # ids are unique
    ticks = [e for e in events if e["name"] == "decode_tick"]
    # a step that a pack carried (PR 54: the chunks of request 2 while request
    # 1 decodes) is that pack's span, under sched.prefill, with its rows on it
    carried = [e for e in events if e["name"] == "prefill_pack" and e["args"]["step_rows"]]
    assert len(carried) == eng.stats["mixed_dispatches"] > 0
    assert len(ticks) == eng.stats["decode_ticks"] - len(carried) > 0
    assert sum(e["args"]["batch"] for e in ticks) + sum(
        e["args"]["step_rows"] for e in carried) == eng.stats["decode_emitted"]
    for e in ticks:
        names = [a["name"] for a in ancestors(e)]
        assert names == ["sched.decode", "sched.tick"], names
        assert e["args"]["ctx_tokens"] >= e["args"]["batch"] >= 1
    for e in carried:
        assert e["args"]["ctx_tokens"] >= e["args"]["step_rows"]
    for name, parent in (("engine.decode_build", "sched.decode"),
                         ("engine.decode_emit", "sched.decode"),
                         ("engine.pack_build", "sched.prefill"),
                         ("prefill_pack", "sched.prefill"),
                         ("engine.pack_emit", "sched.prefill"),
                         ("sched.expire", "sched.tick"),
                         ("sched.admit", "sched.tick")):
        found = [e for e in events if e["name"] == name]
        assert found, name
        assert all(next(ancestors(e))["name"] == parent for e in found), name
    roots = [e for e in events if "parent_id" not in e["args"]]
    assert {e["name"] for e in roots} == {"sched.tick"}
    assert [e["args"]["tick"] for e in roots] == list(range(1, len(roots) + 1))


def test_children_lie_inside_parents_and_self_time_is_not_negative(plain_serve):
    eng, _ = plain_serve
    events = _span_events(eng.telemetry)
    by_id, _ = _tree(events)
    covered = {}
    for e in events:
        p = e["args"].get("parent_id")
        if p is None:
            continue
        parent = by_id[p]
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3  # us
        covered[p] = covered.get(p, 0.0) + e["dur"]
    for e in events:
        if e["name"] == "sched.tick":
            assert e["dur"] - covered.get(e["args"]["span_id"], 0.0) >= -1e-3
    # one ahead (PR 43): a decode phase is EITHER the enqueue of the next
    # step (build -> dispatch) or the collect of the step before it (the wait
    # and fetch -> emit), each in order; a tick holds one of each, the
    # enqueue first (in today's order one phase held build -> dispatch -> emit)
    halves = []
    for d in (e for e in events if e["name"] == "sched.decode"):
        kids = sorted((e for e in events
                       if e["args"].get("parent_id") == d["args"]["span_id"]),
                      key=lambda e: e["ts"])
        if kids:
            names = [k["name"] for k in kids]
            assert names in (["engine.decode_build", "decode_tick"],
                             ["tick_collect", "engine.decode_emit"])
            assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3
                       for a, b in zip(kids, kids[1:]))
            halves.append((d["args"]["parent_id"], names[0]))
    # (a step that a pack carried is collected in the pack's phase: PR 54)
    assert sum(n == "tick_collect" for _, n in halves) == (
        eng.stats["decode_ticks"] - eng.stats["mixed_dispatches"])
    by_tick = {}
    for tick, first in halves:
        by_tick.setdefault(tick, []).append(first)
    assert ["engine.decode_build", "tick_collect"] in by_tick.values()
    assert all(v in (["engine.decode_build"], ["tick_collect"],
                     ["engine.decode_build", "tick_collect"],
                     ["engine.decode_build", "engine.decode_build", "tick_collect"])
               for v in by_tick.values())


def test_request_traces_name_the_tick_of_every_chunk_and_token(plain_serve):
    eng, sched = plain_serve
    ticks = {e["args"]["tick"]: e for e in _span_events(eng.telemetry)
             if e["name"] == "sched.tick"}
    for tr in eng.telemetry.finished_traces:
        assert all(len(c) == 3 for c in tr.chunks)  # the shape readers rely on
        assert len(tr.chunk_ticks) == len(tr.chunks) >= 2  # 40 tokens / 16
        assert len(tr.emission_ticks) == len(tr.emissions) == 6
        assert tr.emission_ticks == sorted(tr.emission_ticks)
        for (t, _), tick in zip(tr.emissions, tr.emission_ticks):
            span = ticks[tick]  # the emission fell inside the tick it names
            assert span["ts"] <= t * 1e6 <= span["ts"] + span["dur"]
        chrome = [e for e in tr.chrome_events() if e["name"] == "prefill_chunk"]
        assert [e["args"]["tick"] for e in chrome] == tr.chunk_ticks


class _FakeAnnotation:
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, self.kw))


def test_span_mirrors_into_the_profiler_only_with_the_knob_on():
    off = Telemetry(enabled=True)
    assert off._annotate is None
    with off.span("x", track="t", n=1) as sp:
        assert sp._ann is None
    on = Telemetry(enabled=True, jax_profiler=True)
    assert on._annotate is jax.profiler.TraceAnnotation
    on._annotate, _FakeAnnotation.log = _FakeAnnotation, []
    with on.span("outer", track="t", tick=3, uids=[1, 2], ctx=True) as outer:
        with on.span("inner", track="t") as inner:
            pass
    log = _FakeAnnotation.log
    assert [(a, n) for a, n, _ in log] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]
    # the mirror carries the recorder's id and the scalar args, no lists
    assert log[0][2] == {"span_id": outer.id, "tick": 3, "ctx": True}
    assert log[1][2] == {"span_id": inner.id}
    assert inner.parent == outer.id and outer.parent is None
    # an explicit end() inside the block closes the mirror there, once
    _FakeAnnotation.log = []
    with on.span("pack") as sp:
        sp.end(sync_obj=jnp.zeros(()))
        assert [a for a, _, _ in _FakeAnnotation.log] == ["enter", "exit"]
    assert len(_FakeAnnotation.log) == 2 and sp.closed and not sp.synced
    # disabled telemetry never touches the profiler
    assert Telemetry(enabled=False, jax_profiler=True)._annotate is None


def test_disabled_telemetry_hands_out_the_null_span():
    from deepspeed_tpu.telemetry import NULL_SPAN

    tel = Telemetry(enabled=False)
    assert tel.span("x", track="t", hist=None, n=1) is NULL_SPAN
    with tel.span("x") as sp:
        sp.dispatched()
        assert sp.end(sync_obj=object()) is NULL_SPAN
    assert sp.duration_ms is None and len(tel.recorder) == 0
    assert not hasattr(tel, "step_annotation")  # one way to annotate, not two


def _marked_span(marks, unsynced=False):
    """One span on a hand clock: opened at 1.0, a mark every 0.5 s after,
    ended 0.5 s after the last; its Chrome event."""
    clock = _Clock()
    tel = Telemetry(enabled=True, clock=clock)
    clock.t = 1.0
    sp = tel.span("decode_tick", track="serve")
    for name in marks:
        clock.t += 0.5
        sp.dispatched() if name == "dispatch" else sp.mark(name)
    clock.t += 0.5
    sp.end(sync_obj=object()) if unsynced else sp.end()
    return sp, tel.recorder.chrome_events()[-1]


def test_marks_export_in_order_and_inside_the_duration():
    sp, ev = _marked_span(["upload", "dispatch", "fetched"])
    args = ev["args"]
    assert [k for k in args if k.endswith("_ms")] == [
        "upload_ms", "dispatch_ms", "fetched_ms"]
    assert (args["upload_ms"], args["dispatch_ms"], args["fetched_ms"]) == (
        500.0, 1000.0, 1500.0)
    assert 0 <= args["upload_ms"] <= args["dispatch_ms"] <= ev["dur"] * 1e-3 == 2000.0
    # a mark on a span that has ended reads no clock and keeps nothing
    assert sp.mark("late") is None and "late" not in sp.marks


@pytest.mark.parametrize("marks", [["dispatch"], ["upload", "dispatch"]],
                         ids=["plain", "after_an_upload_mark"])
def test_dispatched_is_the_dispatch_mark_and_dispatch_ms_is_what_it_was(marks):
    sp, ev = _marked_span(marks)
    at = 1.0 + 0.5 * len(marks)
    assert sp.t_dispatch == sp.marks["dispatch"] == at
    assert ev["args"]["dispatch_ms"] == round((at - 1.0) * 1e3, 3)
    sp2, ev2 = _marked_span(marks[:-1])  # never dispatched: end() stands in
    assert "dispatch" not in (sp2.marks or {})
    assert ev2["args"]["dispatch_ms"] == ev2["dur"] * 1e-3
    # the first call wins, as it always did
    clock = _Clock()
    tel = Telemetry(enabled=True, clock=clock)
    with tel.span("x") as sp3:
        clock.t = 2.0
        sp3.dispatched()
        clock.t = 3.0
        sp3.dispatched()
    assert sp3.t_dispatch == sp3.marks["dispatch"] == 2.0


def test_an_unsynced_span_keeps_its_marks():
    sp, ev = _marked_span(["upload", "dispatch"], unsynced=True)
    assert ev["args"]["synced"] is False and not sp.synced
    assert (ev["args"]["upload_ms"], ev["args"]["dispatch_ms"]) == (500.0, 1000.0)
    assert ev["dur"] == 1000.0 * 1e3  # the dispatch-side duration


def test_null_span_mark_is_a_no_op_and_disabled_telemetry_allocates_nothing():
    from deepspeed_tpu.telemetry import NULL_SPAN

    tel = Telemetry(enabled=False)
    with tel.span("decode_tick", track="serve", batch=3) as sp:
        assert sp is NULL_SPAN
        assert sp.mark("upload") is None
        sp.dispatched()
    assert not hasattr(NULL_SPAN, "marks") and NULL_SPAN.__slots__ == ()
    assert len(tel.recorder) == 0 and tel.recorder.chrome_events() == []
    # ... and a span that is never marked carries no dict of its own
    on = Telemetry(enabled=True)
    with on.span("sched.tick") as plain:
        pass
    assert plain.marks is None


def _phase_engine(kind):
    if kind == "dense":
        cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    else:
        from pathlib import Path

        root = Path(__file__).resolve().parents[1]
        sys.path.insert(0, str(root))
        from benchmark import harness

        m = harness.rehearsed(harness.load_json(
            root / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
        cfg = harness.module("models", m["model_type"]).transformer_config(
            m, max_seq_len=m["engine"]["max_seq_len"])
        params = init_params(jax.random.PRNGKey(7), cfg)
        assert cfg.latent is not None
    return InferenceEngineV2(
        params, cfg, max_seqs=4, num_blocks=64, block_size=8, max_seq_len=128,
        prefill_buckets=(32,), prefill_chunk=32, telemetry=True), cfg


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_dispatch_spans_carry_their_phase_marks(kind):
    """Every dispatch span of a short scheduler run reads open -> ``upload_ms``
    (arguments handed over) -> ``dispatch_ms`` (the jitted call returned) ->
    end (the fetch), every build span ``rows_ms`` before the rng's programs:
    chunked packs closed unsynced among them."""
    eng, cfg = _phase_engine(kind)
    sched = eng.scheduler
    rng = np.random.default_rng(3)
    for uid, n in ((1, 70), (2, 9), (3, 41)):
        sched.submit(uid, rng.integers(1, cfg.vocab_size, n).tolist(),
                     SamplingParams(temperature=0.0, max_new_tokens=5))
    sched.run()
    evs = [e for e in eng.telemetry.recorder.chrome_events() if e["ph"] == "X"]
    eng.close()
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["decode_tick"]) >= 4 and len(by_name["prefill_pack"]) >= 3
    # one ahead (PR 43): every dispatch span covers ITS program's build ->
    # upload -> dispatch and ends there, unsynced; the wait and the fetch are
    # ``tick_collect``'s, one for every program that sampled something
    for name in ("decode_tick", "prefill_pack"):
        assert all(e["args"].get("synced") is False for e in by_name[name])
        assert all(e["args"]["ahead"] in (0, 1) for e in by_name[name])
    assert sum(e["args"]["what"] == "decode_tick" for e in by_name["tick_collect"]) \
        == len(by_name["decode_tick"])
    assert 1 <= sum(e["args"]["what"] == "prefill_pack"
                    for e in by_name["tick_collect"]) <= 3  # prompts may share a pack
    for name in ("decode_tick", "prefill_pack"):
        for e in by_name[name]:
            a = e["args"]
            assert 0 <= a["upload_ms"] <= a["dispatch_ms"] <= e["dur"] * 1e-3 + 1e-3, (name, a)
    for name in ("engine.decode_build", "engine.pack_build"):
        assert len(by_name[name]) == len(by_name[
            "decode_tick" if "decode" in name else "prefill_pack"])
        for e in by_name[name]:
            assert 0 <= e["args"]["rows_ms"] <= e["dur"] * 1e-3 + 1e-3, (name, e["args"])
    # a boundary between layers stays a span: the marks added none
    assert set(by_name) <= {
        "sched.tick", "sched.expire", "sched.admit", "sched.prefill", "sched.decode",
        "engine.pack_build", "prefill_pack", "engine.pack_emit",
        "engine.decode_build", "decode_tick", "engine.decode_emit", "tick_collect"}


def test_spec_and_burst_bodies_export_the_names_of_a_tick(serve_pair, tiny):
    """The two dispatch bodies no benchmark cell runs have a tick's shape and
    its marks; a burst commits its buffers inside its build span, so its
    ``rows`` mark stands before them and ``upload`` at the span's opening."""
    (eng, _, _), _ = serve_pair
    evs = [e for e in eng.telemetry.recorder.chrome_events() if e["ph"] == "X"]
    spec = [e for e in evs if e["name"] == "spec_tick"]
    assert spec
    for e in spec:
        a = e["args"]
        assert 0 <= a["upload_ms"] <= a["dispatch_ms"] <= e["dur"] * 1e-3 + 1e-3
    cfg, params = tiny
    burst = InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=32,
                              block_size=16, telemetry=True)
    burst.put([1, 2], [[3, 4, 5, 6, 7], [9, 8, 7]], SamplingParams(temperature=0.0))
    burst.step_n(4, SamplingParams(temperature=0.0))
    evs = {e["name"]: e for e in burst.telemetry.recorder.chrome_events()
           if e["ph"] == "X"}
    a = evs["decode_burst"]["args"]
    assert 0 <= a["upload_ms"] <= a["dispatch_ms"] <= evs["decode_burst"]["dur"] * 1e-3 + 1e-3
    build = evs["engine.decode_build"]
    assert 0 <= build["args"]["rows_ms"] <= build["dur"] * 1e-3 + 1e-3


def test_detached_and_out_of_order_spans_do_not_bend_the_tree():
    tel = Telemetry(enabled=True)
    rec = tel.recorder
    with tel.span("tick") as tick:
        shed = rec.start("shed_mode", detached=True)  # outlives the tick
        with tel.span("phase") as phase:
            pass
    assert shed.parent == tick.id and phase.parent == tick.id
    with tel.span("tick2") as tick2:
        pass
    assert tick2.parent is None  # the open detached span is nobody's parent
    shed.end()
    a = rec.start("a")
    b = rec.start("b")
    a.end()  # out of order: b was still open under a
    assert rec.start("c").parent is None
    b.end()
    b.end()  # a second end() records nothing
    assert [s.name for s in rec._spans].count("b") == 1
    # two threads keep two stacks
    seen = {}

    def other():
        with tel.span("other") as sp:
            seen["parent"] = sp.parent

    with tel.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["parent"] is None


def test_a_full_ring_says_how_many_spans_it_let_go():
    tel = Telemetry(enabled=True, max_spans=4)
    for i in range(4):
        with tel.span("s", i=i):
            pass
    events = [e for e in tel.recorder.chrome_events() if e["ph"] == "X"]
    assert len(events) == 4 and all("spans_dropped" not in e["args"] for e in events)
    for i in range(4, 7):
        with tel.span("s", i=i):
            pass
    events = [e for e in tel.recorder.chrome_events() if e["ph"] == "X"]
    assert [e["args"]["i"] for e in events] == [3, 4, 5, 6]
    # the oldest span kept carries the count; the export's metadata agrees
    assert events[0]["args"]["spans_dropped"] == 3 == tel.recorder.dropped
    assert all("spans_dropped" not in e["args"] for e in events[1:])
    assert tel.chrome_trace()["metadata"]["spans_dropped"] == 3


# ---------------------------------------------------------------------------
# program_scopes / collective_bytes_per_step: lazy, from the compiled text
# ---------------------------------------------------------------------------
def test_program_scopes_names_optimizer_and_loss_and_computes_nothing_until_asked():
    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models import CausalLM
    from jax import monitoring

    cfg = get_preset("tiny", max_seq_len=32)
    engine, _, _, _ = ds.initialize(
        model=CausalLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
            "bf16": {"enabled": True},
            "steps_per_print": 1000,
        },
    )
    assert not engine.telemetry.enabled  # tracking does not wait for telemetry
    dp = engine.config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (dp, 33), dtype=np.int64)}
    engine.train_batch(batch)
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if "backend_compile" in event or "jaxpr_to_mlir" in event else None)
    for _ in range(2):
        engine.train_batch(batch)
    jax.block_until_ready(engine.state.params)
    step = engine._step_program
    assert len(step.signatures) == 1  # one compiled signature, shapes only
    assert not any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(step.signatures))
    assert compiles == []  # nothing lowered or compiled by tracking itself
    scopes = telemetry.program_scopes()["jit_train_step"]
    assert compiles == []  # asking hits the caches of the step that ran
    names = set(scopes.values())
    assert any("/optimizer/" in n for n in names)
    assert any("jvp(loss)" in n for n in names)
    assert any("transpose(jvp(loss))" in n for n in names)
    assert any(n.endswith("/attn/dot_general") and "transpose(" in n for n in names)
    assert any("zero/gather" in n for n in names)
    # every mapped instruction is named as the trace names it (base.number)
    assert all(re.match(r"^[\w\-]+(\.\d+)*$", k) for k in scopes)
    # the ZeRO-3 step gathers parameters and scatters gradients over 8 devices
    moved = telemetry.collective_bytes_per_step()["jit_train_step"]
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(engine.state.params))
    assert moved >= 2 * n_params  # at least one bf16 gather of every parameter


def test_collective_bytes_counts_loops_by_trip_count_and_async_pairs_once():
    from deepspeed_tpu.telemetry.programs import collective_bytes

    text = """HloModule jit_step, is_scheduled=true

%body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %x = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %ag = f32[32,4]{1,0:T(8,128)} all-gather(%x), dimensions={0}
  %rs-start = ((f32[32,4]{1,0}), f32[8,4]{1,0}) reduce-scatter-start(%ag)
  %rs = f32[8,4]{1,0} reduce-scatter-done(%rs-start)
  ROOT %t = (s32[], f32[8,4]{1,0}) tuple(%i, %rs)
}

%cond (p: (s32[], f32[8,4])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4]{1,0} parameter(0)
  %w = (s32[], f32[8,4]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  %ar = (bf16[16]{0}, bf16[2,2]{1,0}) all-reduce(%b, %c), to_apply=%add
  ROOT %r = f32[8,4]{1,0} get-tuple-element(%w), index=1
}
"""
    per_trip = 32 * 4 * 4 + 8 * 4 * 4      # the gather's result, the scatter's result
    assert collective_bytes(text) == 3 * per_trip + (16 + 4) * 2
    assert collective_bytes("HloModule empty\n") == 0
    # the TPU writes no known_trip_count: the bound is the constant the loop's
    # condition compares its counter with
    tpu = text.replace(', backend_config={"known_trip_count":{"n":"3"}}', "").replace(
        "  ROOT %lt = pred[] compare(%i, %n), direction=LT",
        "  %n = s32[]{:T(128)} constant(5)\n"
        "  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT")
    assert "known_trip_count" not in tpu
    assert collective_bytes(tpu) == 5 * per_trip + (16 + 4) * 2
    # a loop with collectives in its body and no trip count to be found is
    # not counted once (a scanned layer stack would read 1/depth): None
    uncounted = tpu.replace("direction=LT", "direction=NE")
    assert collective_bytes(uncounted) is None
    # the same loop with nothing to count in its body hides nothing
    quiet = re.sub(r"\n  %(ag|rs-start|rs) = [^\n]*", "", uncounted)
    assert "all-gather" not in quiet and "while(" in quiet
    assert collective_bytes(quiet) == (16 + 4) * 2
