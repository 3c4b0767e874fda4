"""Flight recorder + observability satellites (ISSUE 6).

Covers: the bounded per-batch flight ring and its JSONL dump schema; the
supervisor dump triggers (chaos crash, recovery, escalation) with batch
correlation ids; the quarantine-burst trigger; TraceSink JSONL rotation;
and the Reporter's atomic cadence write (a crash mid-report — the armed
``"report.write"`` failpoint — never leaves a torn line).
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import EngineConfig, EscalationPolicy
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record, Supervisor
from kafkastreams_cep_tpu.runtime.flight import FlightRecorder, read_dump
from kafkastreams_cep_tpu.runtime.ingest import IngestPolicy
from kafkastreams_cep_tpu.utils.failpoints import FAILPOINTS
from kafkastreams_cep_tpu.utils.telemetry import JsonlTraceSink, Reporter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

CFG = EngineConfig(
    max_runs=8, slab_entries=16, slab_preds=4, dewey_depth=8, max_walk=8
)


def stock_records(n, seed=0, t0=0, keys=4):
    rng = np.random.default_rng(seed)
    return [
        Record(
            int(rng.integers(0, keys)),
            {"price": int(rng.integers(90, 131)),
             "volume": int(rng.integers(600, 1101))},
            t0 + i,
        )
        for i in range(n)
    ]


# -- the ring -----------------------------------------------------------------


def test_flight_ring_is_bounded_and_dump_schema(tmp_path):
    fr = FlightRecorder(capacity=3, path=str(tmp_path / "fl"))
    proc = CEPProcessor(stock_demo.stock_pattern(), 4, CFG, epoch=0,
                        flight=fr)
    for b in range(5):
        proc.process(stock_records(16, seed=b, t0=b * 100))
    assert len(fr.records) == 3 and fr.dropped == 2
    path = fr.dump("demand", corr="manual-1")
    doc = read_dump(path)
    h = doc["header"]
    assert h["reason"] == "demand" and h["corr"] == "manual-1"
    assert h["records"] == 3 and h["dropped"] == 2
    # Records are the LAST N batches, oldest first, with the processor's
    # batch correlation ids and per-batch (not lifetime) deltas.
    assert [r["seq"] for r in doc["records"]] == [3, 4, 5]
    assert [r["corr"] for r in doc["records"]] == [
        "stream-3", "stream-4", "stream-5"
    ]
    for r in doc["records"]:
        assert r["records_in"] == 16  # the batch's delta, not 80
        assert "phase_seconds" in r and "slab_live" in r
    # Dumping again ships full context again (ring not cleared).
    assert read_dump(fr.dump("demand"))["header"]["records"] == 3


def test_flight_record_splits_gc_into_pull_and_scan():
    """A GC batch's record carries the liveness pull beside the whole GC,
    so a GC stall's dump shows transfer against per-lane scan."""
    fr = FlightRecorder(capacity=4)
    proc = CEPProcessor(stock_demo.stock_pattern(), 4, CFG, epoch=0,
                        flight=fr, gc_events_interval=1)
    proc.process(stock_records(16))
    ph = fr.records[-1]["phase_seconds"]
    assert 0 < ph["gc_pull"] <= ph["gc"]
    assert ph["decode_wait"] + ph.get("decode_build", 0.0) <= ph["decode"]


def test_flight_observe_without_path_returns_records():
    fr = FlightRecorder(capacity=8)
    proc = CEPProcessor(stock_demo.stock_pattern(), 2, CFG, epoch=0,
                        flight=fr)
    proc.process(stock_records(8, keys=2))
    out = fr.dump("demand")
    assert isinstance(out, list) and out[0]["type"] == "flight_dump"
    assert out[1]["type"] == "flight_record"


# -- supervisor triggers ------------------------------------------------------


def test_chaos_crash_and_recovery_dump_flight(tmp_path):
    """A device fault mid-stream: the recovery dump ships the last-N
    batch records with correct correlation ids; exhausted retries dump
    with reason=crash before the exception propagates."""
    fr = FlightRecorder(capacity=8, path=str(tmp_path / "fl"))
    sup = Supervisor(
        stock_demo.stock_pattern(), 4, CFG, epoch=0,
        checkpoint_path=str(tmp_path / "c.ckpt"),
        journal_path=str(tmp_path / "c.jrnl"),
        checkpoint_every=100, flight=fr, gc_interval=0,
    )
    for b in range(3):
        sup.process(stock_records(16, seed=b, t0=b * 100))
    with FAILPOINTS.session({"device.result": [0]}):
        sup.process(stock_records(16, seed=9, t0=900))
    assert sup.recoveries == 1
    dumps = [p for p in fr.dump_paths if "-recover-" in p]
    assert len(dumps) == 1
    doc = read_dump(dumps[0])
    assert doc["header"]["reason"] == "recover"
    # The supervisor's corr names the batch that provoked the recovery.
    assert doc["header"]["corr"] == "batch-4"
    # The ring holds the batches before the fault, with processor corrs
    # (the faulted batch itself never completed, so it has no record —
    # the dump runs before the rollback/replay overwrites the tail).
    corrs = [r["corr"] for r in doc["records"]]
    assert corrs == ["stream-1", "stream-2", "stream-3"]

    # Exhausted retries: dump reason=crash, then the exception surfaces.
    # Hits 1-4 are the recovery replay of the 4 journaled batches; hit 5
    # is the retry of the faulted batch — failing it exhausts
    # max_retries=1.
    with FAILPOINTS.session({"device.dispatch": [0, 5]}):
        with pytest.raises(Exception):
            sup.process(stock_records(16, seed=10, t0=1200))
    crash = [p for p in fr.dump_paths if "-crash-" in p]
    assert len(crash) == 1
    assert read_dump(crash[0])["header"]["reason"] == "crash"


def test_escalation_dumps_flight(tmp_path):
    seed_cfg = EngineConfig(
        max_runs=4, slab_entries=16, slab_preds=2, dewey_depth=8, max_walk=8
    )
    ceiling = EngineConfig(
        max_runs=64, slab_entries=128, slab_preds=16, dewey_depth=32,
        max_walk=32,
    )
    fr = FlightRecorder(capacity=8, path=str(tmp_path / "fl"))
    sup = Supervisor(
        sc.skip_till_any(), 1, seed_cfg,
        checkpoint_path=str(tmp_path / "e.ckpt"),
        checkpoint_every=100,
        auto_escalate=EscalationPolicy(max_config=ceiling),
        gc_interval=0, flight=fr,
    )
    values = [sc.A, sc.B] + [sc.C, sc.D] * 3
    for i, v in enumerate(values):
        sup.process([Record("k", v, 1000 + i, offset=i)])
    assert sup.escalations >= 1
    dumps = [p for p in fr.dump_paths if "-escalate-" in p]
    assert dumps, fr.dump_paths
    doc = read_dump(dumps[0])
    assert doc["header"]["reason"] == "escalate"
    assert doc["header"]["corr"].startswith("batch-")
    # The newest record carries the escalation annotation (note()).
    assert doc["records"][-1].get("tripped")


def test_quarantine_burst_dumps_flight(tmp_path):
    fr = FlightRecorder(capacity=8, path=str(tmp_path / "fl"),
                        quarantine_burst=4)
    proc = CEPProcessor(
        stock_demo.stock_pattern(), 4, CFG, epoch=0, flight=fr,
        ingest=IngestPolicy(grace_ms=0, on_bad_record="quarantine"),
    )
    proc.process(stock_records(8, seed=1, t0=0))
    # A burst of schema-defective records dead-letters in one batch.
    bad = [Record(0, {"wrong": 1}, 100 + i) for i in range(6)]
    proc.process(bad)
    bursts = [p for p in fr.dump_paths if "-quarantine_burst-" in p]
    assert bursts, fr.dump_paths
    doc = read_dump(bursts[0])
    assert doc["header"]["reason"] == "quarantine_burst"
    assert doc["records"][-1]["dead_letters"] >= 4


# -- TraceSink rotation (satellite) ------------------------------------------


def test_jsonl_sink_rotates_by_size(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = JsonlTraceSink(path, max_bytes=256)
    for i in range(40):
        sink.event("tick", i=i)
    sink.close()
    assert sink.rollovers > 0
    assert os.path.exists(path + ".1")
    # Every retained line (both generations) is complete JSON.
    n = 0
    for p in (path, path + ".1"):
        with open(p) as f:
            for line in f:
                json.loads(line)
                n += 1
    assert n > 0
    assert os.path.getsize(path) <= 256 + 200  # one line of slack


def test_jsonl_sink_rotates_by_age(tmp_path, monkeypatch):
    import kafkastreams_cep_tpu.utils.telemetry as tel

    t = [1000.0]
    monkeypatch.setattr(tel.time, "monotonic", lambda: t[0])
    path = str(tmp_path / "t.jsonl")
    sink = JsonlTraceSink(path, max_age_s=30.0)
    sink.event("a")
    t[0] += 60.0
    sink.event("b")  # crosses the age bound -> rollover then write
    sink.close()
    assert sink.rollovers == 1
    assert json.loads(open(path).read())["name"] == "b"
    assert json.loads(open(path + ".1").read())["name"] == "a"


# -- Reporter atomic cadence write (satellite) --------------------------------


def test_reporter_crash_mid_flush_leaves_no_torn_line(tmp_path):
    """Armed ``report.write`` fires in the serialized-but-unwritten
    window of Reporter.flush: the failing flush must contribute NOTHING
    to the JSONL file — every retained line parses, and the flush count
    of complete records matches the successful flushes exactly."""
    path = str(tmp_path / "metrics.jsonl")
    sink = JsonlTraceSink(path)
    reporter = Reporter(lambda: {"records_in": 7}, sink, every_batches=1)
    with FAILPOINTS.session({"report.write": [1]}):
        reporter.tick()  # hit 0: succeeds
        with pytest.raises(OSError):
            reporter.tick()  # hit 1: injected crash mid-report
        reporter.tick()  # hit 2: succeeds
    sink.close()
    with open(path) as f:
        lines = f.read().splitlines()
    assert len(lines) == 2
    for line in lines:
        rec = json.loads(line)  # complete JSON — no torn tail
        assert rec["type"] == "metrics"
        assert rec["snapshot"] == {"records_in": 7}
