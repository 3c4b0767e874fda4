"""End-to-end latency attribution (ISSUE 18): segment conservation,
ledger merge algebra, transactional commit across lazy drains, durability
through checkpoint/restore and migration, SLO burn math, and the
Prometheus rendering of the latency families.

Every test pins an injectable fake clock, so segment values are
deterministic — wall-clock flake cannot enter these assertions."""

import dataclasses
import json
import os
import sys

import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record
from kafkastreams_cep_tpu.runtime.checkpoint import (
    restore_processor,
    save_checkpoint,
)
from kafkastreams_cep_tpu.runtime.ingest import IngestPolicy
from kafkastreams_cep_tpu.runtime.migrate import migrate_processor
from kafkastreams_cep_tpu.utils.latency import (
    SEGMENTS,
    BatchLatency,
    LatencyLedger,
    SLOTracker,
)
from kafkastreams_cep_tpu.utils.telemetry import render_prometheus


class FakeClock:
    """Monotone fake wall clock: every read advances by ``step`` seconds,
    so identical call sequences produce identical stamp sequences."""

    def __init__(self, t0: float = 1000.0, step: float = 0.001):
        self.t = float(t0)
        self.step = float(step)

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def trace(vals, key="k", t0=1000):
    return [Record(key, v, t0 + i) for i, v in enumerate(vals)]


VALS = [sc.A, sc.B, sc.C, sc.X, sc.A, sc.B, sc.C, sc.X, sc.A, sc.B,
        sc.C, sc.X]


def seg_sums(snap):
    segs = snap["latency"]["segments"]
    return {name: segs[name]["sum"] for name in segs}


# -- conservation -------------------------------------------------------------


@pytest.mark.parametrize("grace,drain", [(0, 1), (3, 1), (0, 2), (3, 2)])
def test_segment_sums_reconcile_with_e2e_total(grace, drain):
    """Acceptance: reorder_hold + queue + device + drain_defer sums equal
    e2e_total's sum to float tolerance — conservation holds with and
    without the reorder guard, eager and deferred drains."""
    ingest = IngestPolicy(grace_ms=grace) if grace else None
    proc = CEPProcessor(
        sc.strict3(), 2, sc.default_config(), gc_interval=0,
        ingest=ingest, drain_interval=drain, clock=FakeClock(),
        latency=True,
    )
    for i in range(0, len(VALS), 3):
        proc.process(trace(VALS)[i:i + 3])
    proc.flush()
    if ingest is not None:
        proc.drain_ingest()
    snap = proc.metrics_snapshot(per_lane=False)
    lat = snap["latency"]
    sums = seg_sums(snap)
    total = sum(sums[name] for name in SEGMENTS)
    assert total == pytest.approx(sums["e2e_total"], rel=1e-9, abs=1e-9)
    # Every record observed exactly once in every segment histogram.
    counts = {
        name: lat["segments"][name]["count"] for name in lat["segments"]
    }
    assert len(set(counts.values())) == 1
    assert counts["e2e_total"] == lat["records"] == len(VALS)
    assert lat["deferred_batches"] == 0  # flush commits everything


def test_reorder_hold_measured_under_guard():
    """With a grace window armed, held records accrue reorder_hold time
    (admit stamps ride the guard heap); without one the segment is
    identically zero."""
    clock = FakeClock(step=0.01)
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        ingest=IngestPolicy(grace_ms=5), clock=clock, latency=True,
    )
    proc.process(trace([sc.A, sc.B, sc.C]))
    proc.drain_ingest()
    snap = proc.metrics_snapshot(per_lane=False)
    assert seg_sums(snap)["reorder_hold"] > 0
    bare = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        clock=FakeClock(step=0.01), latency=True,
    )
    bare.process(trace([sc.A, sc.B, sc.C]))
    assert seg_sums(bare.metrics_snapshot(per_lane=False))[
        "reorder_hold"
    ] == 0.0


def test_lazy_drain_deferral_is_transactional():
    """Under lazy extraction with a drain cadence, undrained batches park
    their bundles (deferred, uncommitted) and the drain that emits them
    commits every parked bundle at one emit stamp — the PR 4 deferral
    becomes measured ``drain_defer`` time."""
    cfg = sc.default_config(lazy_extraction=True)
    clock = FakeClock(step=0.005)
    proc = CEPProcessor(
        sc.strict3(), 1, cfg, gc_interval=0, drain_interval=4,
        clock=clock, latency=True,
    )
    proc.process(trace([sc.A, sc.B]))
    proc.process(trace([sc.C, sc.X], t0=1010))
    snap = proc.metrics_snapshot(per_lane=False)["latency"]
    assert snap["deferred_batches"] == 2  # no drain yet: nothing committed
    assert snap["records"] == 0
    proc.flush()
    snap = proc.metrics_snapshot(per_lane=False)["latency"]
    assert snap["deferred_batches"] == 0
    assert snap["records"] == 4
    # The deferral wait is real measured time, not zero.
    assert snap["segments"]["drain_defer"]["sum"] > 0
    sums = seg_sums({"latency": snap})
    assert sum(sums[n] for n in SEGMENTS) == pytest.approx(
        sums["e2e_total"], rel=1e-9
    )


# -- determinism / parity -----------------------------------------------------


def _run(latency, clock=None, env=None, num_lanes=2, vals=VALS):
    if env:
        os.environ[env[0]] = env[1]
    try:
        proc = CEPProcessor(
            sc.strict3(), num_lanes, sc.default_config(), gc_interval=0,
            clock=clock, latency=latency,
        )
        matches = []
        for i in range(0, len(vals), 3):
            matches += proc.process(trace(vals)[i:i + 3])
        matches += proc.flush()
    finally:
        if env:
            os.environ[env[0]] = "0"
    return proc, matches


def test_snapshot_determinism_under_pinned_clock():
    """Identical runs under identical fake clocks produce bit-identical
    latency snapshots — values included, not just counts."""

    def snap():
        proc, _ = _run(True, clock=FakeClock())
        return proc.metrics_snapshot(per_lane=False)["latency"]

    a, b = snap(), snap()
    assert a == b
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_ledger_on_off_parity_jnp():
    """Acceptance: arming the ledger changes no observable behavior —
    matches, emission order, and loss counters bit-identical on vs off."""
    p_on, m_on = _run(True, clock=FakeClock())
    p_off, m_off = _run(None)
    assert m_on == m_off  # content AND order
    assert p_on.batch.counters(p_on.state) == p_off.batch.counters(
        p_off.state
    )
    assert p_off.ledger is None
    assert p_on.ledger.records_committed == len(VALS)


@pytest.mark.parametrize("env,mode", [("CEP_WALK_KERNEL", "interpret")])
def test_ledger_on_off_parity_kernels(env, mode):
    """The same parity through the Pallas walk kernel (interpret mode;
    128-lane floor is the kernel's LANE_BLOCK).  Stamps are
    host-side, so the kernel path must be byte-for-byte unaffected."""
    vals = [sc.A, sc.B, sc.C, sc.X, sc.A, sc.B, sc.C]
    p_on, m_on = _run(
        True, clock=FakeClock(), env=(env, mode), num_lanes=128, vals=vals
    )
    p_off, m_off = _run(None, env=(env, mode), num_lanes=128, vals=vals)
    assert p_on.batch.uses_walk_kernel
    assert m_on == m_off and m_on  # non-vacuous
    assert p_on.batch.counters(p_on.state) == p_off.batch.counters(
        p_off.state
    )
    assert p_on.ledger.records_committed == len(vals)


# -- merge algebra ------------------------------------------------------------


def _ledger_with(corr, seconds, clock_t0=0.0, query=None, stall=None):
    led = LatencyLedger(clock=lambda: clock_t0)
    b = BatchLatency(corr, 2, None, release=clock_t0)
    b.dispatch = clock_t0 + seconds / 4
    b.complete = clock_t0 + seconds / 2
    led.commit(b, emit=clock_t0 + seconds)
    if query:
        led.observe_query(query, seconds)
    if stall:
        led.observe_stall(stall, seconds, corr=corr)
    return led


def test_merge_is_associative_and_commutative():
    a = _ledger_with("a-1", 0.004, query="q0", stall="recover")
    b = _ledger_with("b-1", 0.4, query="q0", stall="evacuate")
    c = _ledger_with("c-1", 4.0, query="q1")
    assert a.merge(b).merge(c).snapshot() == a.merge(
        b.merge(c)
    ).snapshot()
    ab, ba = a.merge(b).snapshot(), b.merge(a).snapshot()
    assert ab == ba
    assert ab["records"] == 4
    # The worst observation's exemplar wins the merge.
    assert a.merge(b).merge(c).exemplars["e2e_total"]["corr"] == "c-1"
    assert a.merge(b).exemplars["stall.recover"]["corr"] == "a-1"


def test_merge_rejects_mismatched_edges():
    a = LatencyLedger()
    b = LatencyLedger(edges=(0.1, 1.0))
    with pytest.raises(ValueError):
        a.merge(b)


# -- durability ---------------------------------------------------------------


def test_ledger_survives_checkpoint_restore_exactly_once(tmp_path):
    """The ledger rides the checkpoint header: a restore resumes the
    committed histograms, and replaying the post-checkpoint batch
    re-observes it exactly once on the restore timeline (no double
    counting, no loss)."""
    clock = FakeClock()
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        ingest=IngestPolicy(grace_ms=0), clock=clock, latency=True,
    )
    pre = trace([sc.A, sc.B, sc.C])
    post = trace([sc.A, sc.B, sc.C], t0=1010)
    proc.process(pre)
    path = str(tmp_path / "lat.ckpt")
    save_checkpoint(proc, path)
    want_state = proc.ledger.to_state()
    proc.process(post)  # lost with the crash below
    res = restore_processor(sc.strict3(), path)
    assert res.ledger is not None
    assert res.ledger.to_state() == want_state
    res.set_clock(clock)  # re-inject: clocks are wiring, never pickled
    res.process(post)  # replay
    assert res.ledger.records_committed == len(pre) + len(post)
    # Segment values on the replayed batch are honest wall clock under
    # the re-injected pinned clock — conservation still holds.
    snap = res.metrics_snapshot(per_lane=False)
    sums = seg_sums(snap)
    assert sum(sums[n] for n in SEGMENTS) == pytest.approx(
        sums["e2e_total"], rel=1e-9
    )


def test_ledger_rides_migration_by_reference(tmp_path):
    """migrate_processor carries the live ledger object itself — an
    escalation mid-stream never resets latency attribution."""
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        clock=FakeClock(), latency=True,
    )
    proc.process(trace([sc.A, sc.B, sc.C]))
    wider = dataclasses.replace(
        sc.default_config(), max_runs=32, slab_entries=64
    )
    moved = migrate_processor(sc.strict3(), proc, wider)
    assert moved.ledger is proc.ledger
    moved.process(trace([sc.A, sc.B, sc.C], t0=1010))
    assert moved.ledger.records_committed == 6


# -- SLO ----------------------------------------------------------------------


def test_slo_tracker_burn_math_and_window():
    t = SLOTracker(threshold_s=0.1, target=0.99, window=3)
    t.observe(1, 10)
    assert t.burn_rate() == pytest.approx((1 / 10) / 0.01)  # 10x budget
    for _ in range(5):
        t.observe(0, 10)
    assert len(t._pairs) == 3  # bounded window evicts the burn
    assert t.burn_rate() == 0.0
    with pytest.raises(ValueError):
        SLOTracker(threshold_s=0.1, target=1.5)
    with pytest.raises(ValueError):
        SLOTracker(threshold_s=0.0)


def test_slo_burn_exported_from_processor():
    """A threshold tighter than the fake clock's per-batch latency burns;
    the gauge reaches the snapshot and the Prometheus rendering."""
    led = LatencyLedger(
        clock=FakeClock(step=0.01), slo=SLOTracker(threshold_s=1e-6)
    )
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        clock=FakeClock(step=0.01), latency=led,
    )
    proc.process(trace([sc.A, sc.B, sc.C]))
    snap = proc.metrics_snapshot(per_lane=False)
    slo = snap["latency"]["slo"]
    assert slo["window_over"] == slo["window_records"] == 3
    assert slo["burn_rate"] == pytest.approx(100.0)  # 1.0 / (1 - 0.99)
    txt = render_prometheus(snap)
    assert "cep_slo_burn 100" in txt
    assert "# TYPE cep_slo_burn gauge" in txt


def test_slo_burn_window_survives_supervisor_resume(tmp_path):
    """Regression (ISSUE 20 satellite): the SLO tracker's rolling window
    rides the checkpoint header, AND ``Supervisor.resume`` re-injects
    the pinned clock into the restored processor — without the clock
    re-injection the restored burn window would mix pinned-clock history
    with wall-clock stamps and the overload controller would read a
    garbage burn signal after every crash."""
    from kafkastreams_cep_tpu.runtime import Supervisor

    clock = FakeClock(step=0.01)
    kw = dict(
        checkpoint_path=str(tmp_path / "slo.ckpt"),
        journal_path=str(tmp_path / "slo.jrnl"),
        checkpoint_every=1, gc_interval=0,
        ingest=IngestPolicy(grace_ms=0), clock=clock,
        latency=LatencyLedger(
            slo=SLOTracker(threshold_s=1e-6), clock=clock
        ),
    )
    sup = Supervisor(sc.strict3(), 1, sc.default_config(), **kw)
    for i, v in enumerate([sc.A, sc.B, sc.C]):
        sup.process([Record("k", v, 1000 + i, offset=i)])
    want_burn = sup.processor.ledger.slo.burn_rate()
    assert want_burn > 0  # the tight threshold is burning
    del sup  # crash
    sup2 = Supervisor.resume(sc.strict3(), 1, sc.default_config(), **kw)
    led = sup2.processor.ledger
    assert led.slo.burn_rate() == pytest.approx(want_burn)
    # Clocks are wiring, never pickled: resume re-pins them everywhere.
    assert led.clock is clock
    assert sup2.processor._guard._clock is clock
    # Post-resume batches keep observing on the pinned timeline.
    sup2.process([Record("k", sc.A, 2000, offset=3)])
    assert led.records_committed == 4
    assert led.slo.burn_rate() > 0


# -- rendering / exemplars ----------------------------------------------------


def test_prometheus_renders_latency_families():
    led = _ledger_with("stream-1", 0.4, query="q0", stall="recover")
    led.slo = SLOTracker(threshold_s=0.1)
    led.slo.observe(1, 2)
    txt = render_prometheus({"latency": led.snapshot()})
    assert 'cep_latency_seconds_bucket{segment="e2e_total",le=' in txt
    assert 'cep_latency_seconds_count{segment="queue"} 2' in txt
    assert 'cep_stall_seconds_count{cause="recover"} 1' in txt
    assert 'cep_latency_query_seconds_count{query="q0"} 1' in txt
    assert "cep_slo_burn 50" in txt
    assert "cep_latency_batches_total 1" in txt
    assert "cep_latency_records_total 2" in txt
    assert "# TYPE cep_latency_seconds histogram" in txt
    assert "# HELP cep_latency_seconds" in txt


def test_exemplars_resolve_to_batch_correlation_ids():
    """Every segment exemplar names the ``corr`` of the worst-observed
    batch — the same ``<name>-<seq>`` id the batch trace span carries."""
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), gc_interval=0,
        clock=FakeClock(), latency=True,
    )
    n_batches = 0
    for i in range(0, len(VALS), 3):
        proc.process(trace(VALS)[i:i + 3])
        n_batches += 1
    ex = proc.metrics_snapshot(per_lane=False)["latency"]["exemplars"]
    for seg in SEGMENTS + ("e2e_total",):
        name, seq = ex[seg]["corr"].rsplit("-", 1)
        assert name == proc.name
        assert 1 <= int(seq) <= n_batches
