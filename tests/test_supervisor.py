"""Failure detection & recovery (SURVEY §5): the supervisor restores the
last checkpoint and replays the journal after a device failure, landing in
exactly the pre-failure state — the Kafka Streams rebalance/changelog
contract (``CEPProcessor.java:117-134``) made explicit."""

import os
import sys

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record
from kafkastreams_cep_tpu.runtime.supervisor import (
    HealthReport,
    Supervisor,
    check_health,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo


def stock_records():
    return [
        Record("stocks", {"price": e["price"], "volume": e["volume"]}, 1000 + i)
        for i, e in enumerate(stock_demo.STOCK_EVENTS)
    ]


def stock_cfg():
    from kafkastreams_cep_tpu.engine import EngineConfig

    return EngineConfig(
        max_runs=32, slab_entries=64, slab_preds=8, dewey_depth=16, max_walk=16
    )


class FailOnce:
    """Monkeypatch hook: makes the Nth device dispatch raise once."""

    def __init__(self, scan, fail_on_call: int):
        self.scan = scan
        self.calls = 0
        self.fail_on_call = fail_on_call
        self.failed = False

    def __call__(self, state, events):
        self.calls += 1
        if self.calls == self.fail_on_call and not self.failed:
            self.failed = True
            raise RuntimeError("injected device failure")
        return self.scan(state, events)


def test_recovery_matches_uninterrupted_run(tmp_path):
    """Fail the device dispatch mid-stream; the supervisor recovers from
    checkpoint + journal replay and total emissions equal a clean run's."""
    records = stock_records()
    name_of = {i: e["name"] for i, e in enumerate(stock_demo.STOCK_EVENTS)}

    sup = Supervisor(
        stock_demo.stock_pattern(), 1, stock_cfg(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=2,
    )
    out = []
    out += sup.process(records[:3])
    out += sup.process(records[3:5])  # triggers a checkpoint (every 2)
    assert sup.checkpoints == 1

    # Inject a failure on the next dispatch.
    hook = FailOnce(sup.processor.batch.scan, fail_on_call=1)
    sup.processor.batch.scan = hook
    out += sup.process(records[5:])
    assert hook.failed
    assert sup.recoveries == 1

    lines = [stock_demo.format_match(seq, name_of) for _, seq in out]
    assert lines == stock_demo.EXPECTED


def test_recovery_without_checkpoint_replays_full_journal(tmp_path):
    """Before the first checkpoint the journal is the whole history: a
    fresh processor replays it and the stream continues correctly."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=100,
    )
    out = []
    out += sup.process([Record("k", sc.A, 1), Record("k", sc.B, 2)])
    hook = FailOnce(sup.processor.batch.scan, fail_on_call=1)
    sup.processor.batch.scan = hook
    out += sup.process([Record("k", sc.C, 3)])
    assert sup.recoveries == 1 and sup.checkpoints == 0
    assert len(out) == 1  # SEQ(A, B, C) completed across the failure


def test_recovery_does_not_duplicate_replayed_matches(tmp_path):
    """A match emitted before the failure is not re-emitted by replay."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=100,
    )
    first = sup.process(
        [Record("k", sc.A, 1), Record("k", sc.B, 2), Record("k", sc.C, 3)]
    )
    assert len(first) == 1
    hook = FailOnce(sup.processor.batch.scan, fail_on_call=1)
    sup.processor.batch.scan = hook
    later = sup.process([Record("k", sc.X, 4)])
    assert later == [] and sup.recoveries == 1
    # The completed match was extracted once; replay did not resurrect it.
    final = sup.process(
        [Record("k", sc.A, 5), Record("k", sc.B, 6), Record("k", sc.C, 7)]
    )
    assert len(final) == 1


def test_persistent_failure_raises(tmp_path, monkeypatch):
    """A failure that survives recovery (rebuilt processors fail on the
    same batch too) propagates once max_retries is exhausted."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"), max_retries=1,
    )
    sup.process([Record("k", sc.A, 1)])

    orig = CEPProcessor.process

    def poisoned(self, records):
        if any(r.value == sc.B for r in records):
            raise RuntimeError("permanent device loss")
        return orig(self, records)

    monkeypatch.setattr(CEPProcessor, "process", poisoned)
    with pytest.raises(RuntimeError, match="permanent device loss"):
        sup.process([Record("k", sc.B, 2)])
    assert sup.recoveries == 1  # it did try a recovery before giving up


def test_input_errors_do_not_trigger_recovery(tmp_path):
    """A deterministic input rejection (ValueError) propagates without a
    pointless restore-and-replay cycle."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"),
    )
    sup.process([Record("k", sc.A, 1)])
    with pytest.raises(ValueError, match="num_lanes"):
        sup.process([Record("other_key", sc.A, 2)])
    assert sup.recoveries == 0


def test_checkpoint_failure_does_not_lose_matches(tmp_path, monkeypatch):
    """If the snapshot write fails, the batch's matches still return and
    the journal keeps covering the gap."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=1,
    )
    from kafkastreams_cep_tpu.runtime import supervisor as sup_mod

    def broken_save(processor, path):
        raise OSError("disk full")

    monkeypatch.setattr(sup_mod.ckpt_mod, "save_checkpoint", broken_save)
    out = sup.process(
        [Record("k", sc.A, 1), Record("k", sc.B, 2), Record("k", sc.C, 3)]
    )
    assert len(out) == 1  # the match was not lost
    assert sup.checkpoint_failures == 1 and sup.checkpoints == 0
    assert len(sup._journal) == 1  # journal retained for future recovery


def test_default_checkpoint_paths_are_per_instance():
    a = Supervisor(sc.strict3(), 1, sc.default_config())
    b = Supervisor(sc.strict3(), 1, sc.default_config())
    assert a.checkpoint_path != b.checkpoint_path


def test_health_clean_processor():
    proc = CEPProcessor(sc.strict3(), 1, sc.default_config())
    proc.process([Record("k", sc.A, 1), Record("k", sc.B, 2)])
    report = check_health(proc)
    assert isinstance(report, HealthReport)
    assert report.healthy and not report.warnings and not report.errors


def test_health_flags_capacity_drops():
    """Overflowing the run queue is a warning (capacity policy), not an
    error: matching lost branches but state is consistent."""
    from kafkastreams_cep_tpu.engine import EngineConfig

    cfg = EngineConfig(
        max_runs=2, slab_entries=8, slab_preds=2, dewey_depth=4, max_walk=4
    )
    proc = CEPProcessor(sc.skip_till_any(), 1, cfg)
    proc.process(
        [Record("k", v, i) for i, v in enumerate([sc.A, sc.B, sc.B, sc.B, sc.B])]
    )
    report = check_health(proc)
    assert report.healthy  # drops are lossy but not corruption
    assert report.warnings


def test_health_detects_nan_fold_state():
    # NaN is only representable in float-typed fold state (agg is
    # typed-encoded int32; float states are stored as bit patterns), so
    # the probe needs a pattern with a float-dtype fold.
    from kafkastreams_cep_tpu import Query

    pattern = (
        Query()
        .select("a").where(lambda k, v, ts, st: v["price"] > 0)
        .fold("ema", lambda k, v, curr: 0.5 * curr + 0.5 * v["price"],
              init=0.0)
        .then()
        .select("b").where(lambda k, v, ts, st: v["price"] < 0)
        .build()
    )
    proc = CEPProcessor(pattern, 1, stock_cfg())
    proc.process(stock_records()[:2])
    nan_bits = np.float32(np.nan).view(np.int32)
    poisoned = proc.state._replace(
        agg=np.full_like(np.asarray(proc.state.agg), nan_bits)
    )
    proc.state = poisoned
    report = check_health(proc)
    assert not report.healthy
    assert any("NaN" in e for e in report.errors)

    # An int-typed pattern's agg can hold the same bits without being NaN.
    proc2 = CEPProcessor(stock_demo.stock_pattern(), 1, stock_cfg())
    proc2.process(stock_records()[:2])
    proc2.state = proc2.state._replace(
        agg=np.full_like(np.asarray(proc2.state.agg), nan_bits)
    )
    assert check_health(proc2).healthy


def test_pipelined_supervisor_checkpoints_and_loses_nothing(tmp_path):
    """ISSUE 2 satellite: periodic snapshots of a pipeline=True processor
    used to be perpetual checkpoint_failures (save_checkpoint refuses a
    pending undecoded batch).  The supervisor now flushes first and the
    flushed matches still reach the caller."""
    records = stock_records()
    sup = Supervisor(
        stock_demo.stock_pattern(), 1, stock_cfg(),
        checkpoint_path=str(tmp_path / "p.ckpt"), checkpoint_every=2,
        pipeline=True,
    )
    out = []
    for i in range(0, len(records), 2):
        out += sup.process(records[i:i + 2])
    out += sup.checkpoint()  # drains the final in-flight batch
    assert sup.checkpoint_failures == 0
    assert sup.checkpoints >= 2
    name_of = {i: e["name"] for i, e in enumerate(stock_demo.STOCK_EVENTS)}
    lines = [stock_demo.format_match(seq, name_of) for _, seq in out]
    assert lines == stock_demo.EXPECTED


def test_pipelined_checkpoint_failure_keeps_flushed_matches(tmp_path, monkeypatch):
    """If the snapshot fails AFTER the flush, the flushed matches are not
    lost with it — they ride out on the same process() call."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "pf.ckpt"), checkpoint_every=1,
        pipeline=True,
    )
    from kafkastreams_cep_tpu.runtime import supervisor as sup_mod

    def broken_save(processor, path, extra=None):
        raise OSError("disk full")

    monkeypatch.setattr(sup_mod.ckpt_mod, "save_checkpoint", broken_save)
    out = sup.process(
        [Record("k", sc.A, 1), Record("k", sc.B, 2), Record("k", sc.C, 3)]
    )
    assert sup.checkpoint_failures == 1
    assert len(out) == 1  # flushed match delivered despite the failed save


def test_plain_valueerror_from_device_triggers_recovery(tmp_path):
    """ISSUE 2 satellite: only the typed InputRejected short-circuits
    recovery; a bare ValueError out of the dispatch (how JAX surfaces
    some device faults) must restore-and-replay like any device loss."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "v.ckpt"),
    )
    sup.process([Record("k", sc.A, 1)])
    hook = FailOnce(sup.processor.batch.scan, fail_on_call=1)

    def value_error_scan(state, events):
        try:
            return hook(state, events)
        except RuntimeError:
            raise ValueError("INTERNAL: device connection dropped")

    sup.processor.batch.scan = value_error_scan
    out = sup.process([Record("k", sc.B, 2), Record("k", sc.C, 3)])
    assert sup.recoveries == 1
    assert len(out) == 1  # the match completed across the recovery


def test_input_rejected_is_a_valueerror():
    """Compat: callers catching ValueError for validation errors keep
    working; the supervisor distinguishes by the narrower type."""
    from kafkastreams_cep_tpu.runtime import InputRejected

    assert issubclass(InputRejected, ValueError)
    proc = CEPProcessor(sc.strict3(), 1, sc.default_config())
    proc.process([Record("k", sc.A, 1)])
    with pytest.raises(InputRejected, match="num_lanes"):
        proc.process([Record("other", sc.A, 2)])


def test_supervisor_metrics_snapshot(tmp_path):
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "s.ckpt"), checkpoint_every=1,
    )
    sup.process([Record("k", sc.A, 1)])
    snap = sup.metrics_snapshot()
    assert snap["checkpoints"] == 1
    assert snap["recoveries"] == 0
    assert snap["records_in"] == 1


# -- retry backoff (ISSUE 5 satellite) ---------------------------------------


def _failing_supervisor(tmp_path, monkeypatch, fail_times, **kw):
    """A supervisor whose processor faults on the first ``fail_times``
    dispatches of value B, with sleeps captured instead of slept."""
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "b.ckpt"), max_retries=4, **kw,
    )
    slept = []
    sup._sleep = slept.append
    state = {"left": fail_times}
    orig = CEPProcessor.process

    def flaky(self, records):
        if state["left"] > 0 and any(r.value == sc.B for r in records):
            state["left"] -= 1
            raise RuntimeError("transient device loss")
        return orig(self, records)

    monkeypatch.setattr(CEPProcessor, "process", flaky)
    return sup, slept


def test_retry_backoff_is_exponential_capped_and_counted(
    tmp_path, monkeypatch
):
    sup, slept = _failing_supervisor(
        tmp_path, monkeypatch, fail_times=3,
        retry_backoff_ms=100.0, retry_backoff_cap_ms=250.0,
    )
    sup.process([Record("k", sc.A, 1)])
    out = sup.process([Record("k", sc.B, 2)])
    assert sup.recoveries == 3
    assert len(slept) == 3
    # Exponential-with-jitter: each delay in [0.5, 1.0) x min(cap, base*2^n).
    for n, s in enumerate(slept):
        hi = min(250.0, 100.0 * 2 ** n) / 1000.0
        assert hi * 0.5 <= s < hi, (n, s)
    assert slept[2] < 0.250  # the cap bit (800 ms uncapped)
    assert sup.retry_backoff_ms_total == pytest.approx(
        sum(slept) * 1000.0, rel=1e-6
    )
    assert sup.metrics_snapshot(per_lane=False)[
        "retry_backoff_ms_total"
    ] == pytest.approx(sum(slept) * 1000.0, rel=1e-6)
    # The batch eventually succeeded and the C completes the match.
    out += sup.process([Record("k", sc.C, 3)])
    assert len(out) == 1


def test_retry_backoff_jitter_is_deterministic(tmp_path, monkeypatch):
    waits = []
    for _ in range(2):
        sup, slept = _failing_supervisor(
            tmp_path, monkeypatch, fail_times=2, retry_backoff_ms=40.0,
        )
        sup.process([Record("k", sc.A, 1)])
        sup.process([Record("k", sc.B, 2)])
        waits.append(tuple(slept))
        monkeypatch.undo()
    assert waits[0] == waits[1]  # (seq, attempt)-seeded jitter


def test_retry_backoff_zero_disables(tmp_path, monkeypatch):
    sup, slept = _failing_supervisor(
        tmp_path, monkeypatch, fail_times=1, retry_backoff_ms=0.0,
    )
    sup.process([Record("k", sc.A, 1)])
    sup.process([Record("k", sc.B, 2)])
    assert sup.recoveries == 1
    assert slept == []
    assert sup.retry_backoff_ms_total == 0.0
