"""End-to-end telemetry (ISSUE 3): registry determinism, histogram merge
algebra, span nesting/correlation, Prometheus rendering, attribution
(per-lane / per-pattern / hot-tier), and the chaos-trace acceptance
criterion — every recovery/escalation span carries the correlation id of
the batch it rolled back."""

import io
import json
import logging
import math
import os
import sys

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import EngineConfig
from kafkastreams_cep_tpu.engine.sizing import EscalationPolicy
from kafkastreams_cep_tpu.runtime import CEPBank, CEPProcessor, Record, Supervisor
from kafkastreams_cep_tpu.utils import failpoints as fp
from kafkastreams_cep_tpu.utils.logging import configure_logging
from kafkastreams_cep_tpu.utils.telemetry import (
    Histogram,
    InMemoryTraceSink,
    JsonlTraceSink,
    MetricsRegistry,
    Reporter,
    log_bucket_edges,
    merge_counter_dicts,
    positive_delta,
    render_prometheus,
    set_default_sink,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo


def stock_records():
    return [
        Record("s", {"price": e["price"], "volume": e["volume"]}, 1000 + i)
        for i, e in enumerate(stock_demo.STOCK_EVENTS)
    ]


def stock_cfg(**kw):
    base = dict(
        max_runs=8, slab_entries=16, slab_preds=4, dewey_depth=8, max_walk=8
    )
    base.update(kw)
    return EngineConfig(**base)


# -- registry / instruments ---------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(17)
    reg.histogram("h").observe(0.01)
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["g"] == 17
    assert snap["h"]["count"] == 1
    with pytest.raises(TypeError):
        reg.gauge("c")  # a name is one instrument type forever


def test_histogram_percentiles_deterministic():
    h = Histogram("lat", log_bucket_edges(1e-6, 10.0, 4))
    for v in [1e-4] * 98 + [5.0] * 2:
        h.observe(v)
    assert h.percentile(0.5) < 1e-3
    assert h.percentile(0.99) > 1.0
    # An empty histogram answers 0.0, not NaN.
    assert Histogram("e").percentile(0.99) == 0.0


def test_histogram_merge_associative_and_exact():
    def mk(vals):
        h = Histogram("x")
        for v in vals:
            h.observe(v)
        return h

    a, b, c = mk([1e-5, 0.2]), mk([0.3, 7.0, 150.0]), mk([1e-7])
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.snapshot() == right.snapshot()
    # Merge equals one histogram having seen every stream.
    assert left.snapshot() == mk([1e-5, 0.2, 0.3, 7.0, 150.0, 1e-7]).snapshot()
    with pytest.raises(ValueError):
        a.merge(Histogram("y", log_bucket_edges(1e-3, 1.0, 2)))


def test_registry_snapshot_deterministic():
    def run():
        reg = MetricsRegistry()
        reg.counter("records").value = 42
        reg.gauge("watermark").set(1234)
        for v in [0.001, 0.02, 0.3]:
            reg.histogram("lat").observe(v)
        return reg

    assert run().snapshot() == run().snapshot()
    assert json.dumps(run().snapshot()) == json.dumps(run().snapshot())


def test_registry_merge_and_delta():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").value = 3
    b.counter("n").value = 4
    b.counter("only_b").value = 1
    a.histogram("h").observe(0.1)
    b.histogram("h").observe(0.2)
    m = a.merge(b)
    assert m.snapshot()["n"] == 7
    assert m.snapshot()["only_b"] == 1
    assert m.snapshot()["h"]["count"] == 2
    assert m.delta({"n": 5}) == {"n": 2, "only_b": 1}
    assert positive_delta({"x": 5, "y": 2}, {"x": 5, "y": 3}) == {}
    assert merge_counter_dicts([{"a": 1}, {"a": 2, "b": 3}]) == {"a": 3, "b": 3}


def test_prometheus_rendering_golden():
    reg = MetricsRegistry()
    reg.counter("records_in").value = 12
    reg.gauge("lag ms").set(7)
    reg.histogram("lat", (0.1, 1.0)).observe(0.05)
    reg.histogram("lat", (0.1, 1.0)).observe(5.0)
    got = render_prometheus(reg.snapshot(), prefix="cep")
    assert got == (
        "# HELP cep_lag_ms runtime metric (see README metrics reference)\n"
        "# TYPE cep_lag_ms gauge\n"
        "cep_lag_ms 7\n"
        "# HELP cep_lat runtime metric (see README metrics reference)\n"
        "# TYPE cep_lat histogram\n"
        'cep_lat_bucket{le="0.1"} 1\n'
        'cep_lat_bucket{le="+Inf"} 2\n'
        "cep_lat_sum 5.05\n"
        "cep_lat_count 2\n"
        "# HELP cep_records_in runtime metric (see README metrics reference)\n"
        "# TYPE cep_records_in gauge\n"
        "cep_records_in 12\n"
    )


def test_prometheus_structural_labels():
    snap = {
        "run_drops": 1,
        "per_lane": {"run_drops": [0, 3]},
        "per_pattern": {"q0": {"run_drops": 1}},
        "phases": {
            "device": {
                "count": 1,
                "sum": 0.5,
                "p50": 0.5,
                "p99": 0.5,
                "buckets": [(1.0, 1)],
            }
        },
        "hbm": {"bytes_in_use": 64},
        "note": "skipped-string",
    }
    txt = render_prometheus(snap)
    assert 'cep_run_drops{lane="1"} 3' in txt
    assert 'cep_run_drops{lane="0"}' not in txt  # zero lanes elided
    assert 'cep_run_drops{pattern="q0"} 1' in txt
    assert 'cep_phase_seconds_bucket{phase="device",le="1.0"} 1' in txt
    assert "cep_hbm_bytes_in_use 64" in txt
    assert "skipped-string" not in txt


# -- span tracing -------------------------------------------------------------


def test_span_nesting_and_ids():
    sink = InMemoryTraceSink()
    with sink.span("outer", tag="a") as sp:
        with sink.span("inner"):
            sink.event("ping", k=1)
        sp["late"] = True
    inner, outer = sink.spans("inner")[0], sink.spans("outer")[0]
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] is None
    assert outer["late"] is True and outer["tag"] == "a"
    ping = [e for e in sink.events if e["name"] == "ping"][0]
    assert ping["parent_id"] == inner["span_id"]
    assert outer["duration_ms"] >= inner["duration_ms"]


def test_span_error_flagged():
    sink = InMemoryTraceSink()
    with pytest.raises(RuntimeError):
        with sink.span("boom"):
            raise RuntimeError("nope")
    assert "RuntimeError" in sink.spans("boom")[0]["error"]


def test_jsonl_sink_round_trips():
    buf = io.StringIO()
    sink = JsonlTraceSink(buf)
    with sink.span("s", n=1):
        pass
    evt = json.loads(buf.getvalue().strip())
    assert evt["type"] == "span" and evt["name"] == "s" and evt["n"] == 1


# -- processor integration ----------------------------------------------------


def test_processor_batch_and_phase_spans():
    sink = InMemoryTraceSink()
    proc = CEPProcessor(
        stock_demo.stock_pattern(), 1, stock_cfg(), trace_sink=sink
    )
    assert len(proc.process(stock_records())) == 4
    batch = sink.spans("batch")[0]
    assert batch["records"] == 8 and batch["matches"] == 4
    assert batch["lanes"] == 1 and batch["batch"] == 1
    kids = [
        s["name"]
        for s in sink.spans()
        if s["parent_id"] == batch["span_id"]
    ]
    assert kids == ["phase.pack", "phase.dispatch", "phase.device",
                    "phase.decode"]


def _children(sink, parent):
    return [
        s["name"] for s in sink.spans() if s["parent_id"] == parent["span_id"]
    ]


def _columns_run(sink, **kw):
    recs = stock_records()
    proc = CEPProcessor(
        stock_demo.stock_pattern(), 1, stock_cfg(), trace_sink=sink, **kw
    )
    matches = proc.process_columns(
        np.zeros(len(recs), np.int64),
        {f: np.asarray([r.value[f] for r in recs]) for f in ("price", "volume")},
        np.asarray([r.timestamp for r in recs]),
    )
    assert len(matches) == 4
    return proc


@pytest.mark.parametrize("gc_events_interval", [8, 1])
def test_processor_sub_phase_spans_nest_under_their_phase(gc_events_interval):
    """decode_wait and decode_build are children of decode, gc_pull of gc;
    the batch's own children stay the phases, and no sub-phase's seconds
    exceed its parent's."""
    sink = InMemoryTraceSink()
    proc = _columns_run(sink, gc_events_interval=gc_events_interval)
    batch = sink.spans("batch")[0]
    gc = gc_events_interval == 1
    assert _children(sink, batch) == [
        "phase.pack", "phase.dispatch", "phase.device", "phase.decode"
    ] + (["phase.gc"] if gc else [])
    decode = sink.spans("phase.decode")[0]
    assert _children(sink, decode) == ["phase.decode_wait",
                                       "phase.decode_build"]
    if gc:
        assert _children(sink, sink.spans("phase.gc")[0]) == ["phase.gc_pull"]
    else:
        assert not sink.spans("phase.gc_pull")
    m = proc.metrics
    assert m.decode_wait_seconds > 0 and m.decode_build_seconds > 0
    assert m.decode_wait_seconds + m.decode_build_seconds <= m.decode_seconds
    assert m.gc_pull_seconds <= m.gc_seconds
    assert (m.gc_pull_seconds > 0) == gc
    phases = m.phases()
    assert phases["decode_build"]["count"] == 1
    assert phases["gc_pull"]["count"] == int(gc)


def test_annotated_sink_emits_the_same_span_records():
    """``annotate=True`` adds profiler annotations and changes no record."""

    def records(sink):
        _columns_run(sink, gc_events_interval=1)
        return [
            {k: v for k, v in e.items() if k not in ("ts_ms", "duration_ms")}
            for e in sink.events
        ]

    plain, annotated = InMemoryTraceSink(), InMemoryTraceSink(annotate=True)
    assert annotated.annotate and not plain.annotate
    assert records(annotated) == records(plain)


def test_processor_snapshot_hot_counters_and_attribution():
    proc = CEPProcessor(
        stock_demo.stock_pattern(), 2, stock_cfg(slab_hot_entries=8)
    )
    proc.process(stock_records())
    snap = proc.metrics_snapshot()
    # Satellite 1: two-tier telemetry reachable from the runtime snapshot.
    hops = snap["slab_hot_hits"] + snap["slab_hot_misses"]
    assert hops > 0
    # Attribution: per-lane lists sized K, per-pattern keyed by name.
    assert len(snap["per_lane"]["run_drops"]) == 2
    assert sum(snap["per_lane"]["slab_hot_hits"]) == snap["slab_hot_hits"]
    assert snap["per_pattern"]["stream"]["records_in"] == 8
    # Watermark/lag gauges from batch timestamps.
    assert snap["watermark"] == 1007
    assert snap["event_time_lag_ms"] >= 0
    # Phase histograms carry per-batch observations.
    assert snap["phases"]["device"]["count"] == 1
    assert snap["phases"]["pack"]["p99"] > 0
    assert isinstance(snap["hbm"], dict)
    # per_lane is opt-out for light snapshots.
    assert "per_lane" not in proc.metrics_snapshot(per_lane=False)


TIMING_KEYS = (
    "device_seconds", "decode_seconds", "pack_seconds", "dispatch_seconds",
    "gc_seconds", "events_per_second_device", "event_time_lag_ms", "hbm",
    "decode_wait_seconds", "decode_build_seconds", "gc_pull_seconds",
    "pack_lanes_seconds", "phases",
    # Latency-ledger segment values are wall clock; observation COUNTS are
    # deterministic and asserted separately (tests/test_latency.py).
    "latency",
    # Process-global LRU warmth: the second identical run hits programs
    # the first one traced, so hits/misses are order-dependent by design.
    "trace_cache",
)


def _deterministic_view(snap):
    out = {k: v for k, v in snap.items() if k not in TIMING_KEYS}
    out["phase_counts"] = {
        name: h["count"] for name, h in snap["phases"].items()
    }
    return out


def test_processor_snapshot_determinism_across_runs():
    """Two identical runs produce identical snapshots once wall-clock
    values are projected out — counters, attribution, watermark, and every
    histogram's observation counts."""

    def run():
        proc = CEPProcessor(stock_demo.stock_pattern(), 2, stock_cfg())
        proc.process(stock_records()[:5])
        proc.process(stock_records()[5:])
        return _deterministic_view(proc.metrics_snapshot())

    a, b = run(), run()
    assert a == b
    assert json.dumps(a, default=str) == json.dumps(b, default=str)


# -- supervisor integration ---------------------------------------------------


def test_supervisor_snapshot_exposes_phases_and_attribution(tmp_path):
    sup = Supervisor(
        stock_demo.stock_pattern(), 1, stock_cfg(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=1,
        epoch=0,
    )
    sup.process(stock_records())
    snap = sup.metrics_snapshot()
    # Acceptance: per-phase latency histograms with p50/p99, per-lane and
    # per-pattern breakdowns, hot-tier counters — all from one call.
    for phase in ("pack", "dispatch", "device", "decode",
                  "checkpoint", "recover", "escalate"):
        assert {"count", "p50", "p99"} <= set(snap["phases"][phase])
    assert snap["phases"]["checkpoint"]["count"] == 1
    assert snap["phases"]["checkpoint"]["p99"] > 0
    assert snap["per_lane"]["run_drops"] == [0]
    assert "stream" in snap["per_pattern"]
    assert "slab_hot_hits" in snap
    assert snap["checkpoints"] == 1


def test_chaos_recovery_span_carries_batch_correlation(tmp_path):
    """Acceptance criterion: a fault-injected run's JSONL trace holds a
    recovery span whose ``corr`` is exactly the correlation id of the
    batch span it rolled back, plus the armed failpoint hit event."""
    buf = io.StringIO()
    sink = JsonlTraceSink(buf)
    prev = set_default_sink(sink)
    try:
        sup = Supervisor(
            sc.strict3(), 1, sc.default_config(),
            checkpoint_path=str(tmp_path / "c.ckpt"), checkpoint_every=2,
            trace_sink=sink,
        )
        with fp.FAILPOINTS.session({"device.result": [2]}):
            for i, v in enumerate([sc.A, sc.B, sc.C, sc.A, sc.B, sc.C]):
                sup.process([Record("k", v, 1000 + i, offset=i)])
    finally:
        set_default_sink(prev)
    assert sup.recoveries == 1
    events = [json.loads(l) for l in buf.getvalue().splitlines()]
    recs = [e for e in events if e.get("name") == "recover"]
    assert len(recs) == 1
    corr = recs[0]["corr"]
    rolled_back = [
        e for e in events
        if e.get("name") == "supervisor.batch" and e.get("corr") == corr
    ]
    assert len(rolled_back) == 1  # the batch the recovery replayed into
    assert rolled_back[0]["seq"] == int(corr.split("-")[1])
    # The fault landed right after a checkpoint, so the replay tail was
    # empty — the span still reports the restore source and replay size.
    assert recs[0]["replayed_records"] == 0
    assert recs[0]["from_checkpoint"] is True
    hits = [e for e in events if e.get("name") == "failpoint"]
    assert any(h["site"] == "device.result" and h["raised"] for h in hits)


def test_escalation_span_carries_batch_correlation(tmp_path):
    seed = EngineConfig(
        max_runs=4, slab_entries=16, slab_preds=2, dewey_depth=8, max_walk=8
    )
    ceiling = EngineConfig(
        max_runs=64, slab_entries=128, slab_preds=16, dewey_depth=32,
        max_walk=32,
    )
    sink = InMemoryTraceSink()
    sup = Supervisor(
        sc.skip_till_any(), 1, seed,
        checkpoint_path=str(tmp_path / "e.ckpt"), checkpoint_every=100,
        auto_escalate=EscalationPolicy(max_config=ceiling), gc_interval=0,
        trace_sink=sink,
    )
    values = [sc.A, sc.B] + [sc.C, sc.D] * 5
    for i, v in enumerate(values):
        sup.process([Record("k", v, 1000 + i, offset=i)])
    assert sup.escalations >= 1
    esc = sink.spans("escalate")
    assert len(esc) >= 1
    for e in esc:
        # Every escalation span names the tripping batch it rolled back.
        twin = [
            s for s in sink.spans("supervisor.batch")
            if s["corr"] == e["corr"]
        ]
        assert len(twin) == 1
        assert e["tripped"] and e["new_config"]["max_runs"] > 4
    snap = sup.metrics_snapshot()
    assert snap["phases"]["escalate"]["count"] == sup.escalations


def test_replan_span_and_stall_exemplar_carry_batch_correlation(tmp_path):
    """ISSUE 18 satellite: an adaptive replan's trace span AND the latency
    ledger's ``stall.replan`` exemplar both carry the correlation id of
    the batch boundary that triggered the swap — and the ledger itself
    survives the ``replan_processor`` rebuild."""
    import dataclasses

    from kafkastreams_cep_tpu.runtime.supervisor import AdaptPolicy

    cfg = dataclasses.replace(
        sc.default_config(), tiering=True, stage_attribution=True
    )
    sink = InMemoryTraceSink()
    sup = Supervisor(
        sc.strict3(), 1, cfg,
        checkpoint_path=str(tmp_path / "r.ckpt"), checkpoint_every=1,
        gc_interval=0, trace_sink=sink, latency=True,
        adapt_policy=AdaptPolicy(
            drift_threshold=0.05, min_evals=1, replan_streak=1, cooldown=0
        ),
    )
    ledger_before = sup.processor.ledger
    # Boundary 1 pins the selectivity baseline, boundary 2 opens the
    # window, boundary 3's flipped stream drifts past the threshold.
    streams = [[sc.A, sc.B, sc.C], [sc.A, sc.B, sc.C], [sc.X] * 6,
               [sc.X] * 6]
    t = 1000
    for vals in streams:
        sup.process([Record("k", v, t + j) for j, v in enumerate(vals)])
        t += 10
        if sup.replans:
            break
    assert sup.replans >= 1 and sup.replan_failures == 0
    span = sink.spans("replan")[0]
    corr = span["corr"]
    twins = [
        s for s in sink.spans("supervisor.batch") if s["corr"] == corr
    ]
    assert len(twins) == 1  # resolves to exactly one real batch span
    # The rebuilt processor carries the SAME ledger (continuity by
    # reference, like the metrics registry) with the stall attributed.
    assert sup.processor.ledger is ledger_before
    ex = sup.processor.ledger.exemplars["stall.replan"]
    assert ex["corr"] == corr and ex["seconds"] > 0
    snap = sup.metrics_snapshot(per_lane=False)
    assert snap["latency"]["stalls"]["replan"]["count"] == sup.replans


# -- bank / sharded / stacked attribution -------------------------------------


def test_bank_metrics_snapshot_merges_members():
    bank = CEPBank(
        {"stock": stock_demo.stock_pattern(),
         "strict": sc.strict3()},
        num_lanes=1, epoch=0,
    )
    recs = stock_records()
    bank.process(recs)
    snap = bank.metrics_snapshot()
    assert set(snap["per_pattern"]) == {"stock", "strict"}
    # Merged counters are the member sums; histograms aggregate exactly.
    assert snap["records_in"] == sum(
        m["records_in"] for m in snap["per_pattern"].values()
    ) == 2 * len(recs)
    assert snap["phases"]["device"]["count"] == 2
    assert snap["per_pattern"]["stock"]["matches_out"] == 4


def test_sharded_matcher_metrics_snapshot():
    from kafkastreams_cep_tpu.parallel import ShardedMatcher, key_mesh

    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = key_mesh()
    n = mesh.devices.size
    m = ShardedMatcher(sc.strict3(), n, mesh, sc.default_config())
    snap = m.metrics_snapshot(m.init_state())
    assert snap["run_drops"] == 0 and snap["alive_runs"] == n
    assert len(snap["per_lane"]["run_drops"]) == n
    assert "slab_hot_hits" in snap


def test_stacked_bank_metrics_snapshot():
    from kafkastreams_cep_tpu.parallel.stacked import StackedBankMatcher

    bank = StackedBankMatcher(
        [sc.strict3(), sc.strict3()], 2, sc.default_config()
    )
    snap = bank.metrics_snapshot(bank.init_state())
    assert set(snap["per_pattern"]) == {"q0", "q1"}
    for name, v in snap["per_pattern"]["q0"].items():
        assert snap["per_pattern"]["q0"][name] + snap["per_pattern"]["q1"][
            name
        ] == snap[name]


# -- reporter / logging / bench extra ----------------------------------------


def test_reporter_cadence_and_prometheus(tmp_path):
    buf = io.StringIO()
    sink = JsonlTraceSink(buf)
    reg = MetricsRegistry()
    reg.counter("n")
    prom = str(tmp_path / "metrics.prom")
    rep = Reporter(
        reg.snapshot, sink, every_batches=2, prometheus_path=prom
    )
    for _ in range(5):
        reg.counter("n").inc()
        rep.tick()
    assert rep.flushes == 2  # ticks 2 and 4
    rep.flush()
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert [l["snapshot"]["n"] for l in lines] == [2, 4, 5]
    assert open(prom).read() == (
        "# HELP cep_n runtime metric (see README metrics reference)\n"
        "# TYPE cep_n gauge\n"
        "cep_n 5\n"
    )


def test_configure_logging_json_lines():
    logger = configure_logging(json_lines=True)
    try:
        handler = next(
            h for h in logger.handlers
            if type(h) is logging.StreamHandler
        )
        buf = io.StringIO()
        old_stream = handler.setStream(buf)
        logger.info("hello %s", "world")
        handler.setStream(old_stream)
        evt = json.loads(buf.getvalue().strip())
        assert evt["type"] == "log" and evt["msg"] == "hello world"
        assert evt["level"] == "INFO"
        assert evt["logger"] == "kafkastreams_cep_tpu"
        # Idempotent: reconfiguring restores the human format in place.
        configure_logging(json_lines=False)
        assert (
            sum(
                1 for h in logger.handlers
                if type(h) is logging.StreamHandler
            )
            == 1
        )
    finally:
        configure_logging(json_lines=False)


def test_bench_metrics_extra_smoke():
    """Tier-1 wiring for the CEP_BENCH_METRICS extra: drive the exact
    bench function at tiny shapes so the extra cannot silently rot."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench

    block, n_events = bench.bench_metrics(K=4, T=8, n_batches=3)
    assert block["device"]["count"] == 3
    assert block["device"]["p99_ms"] > 0
    assert {"pack", "dispatch", "decode"} <= set(block)
    # Spans + reporter snapshots landed in the JSONL stream.
    assert n_events > 3
