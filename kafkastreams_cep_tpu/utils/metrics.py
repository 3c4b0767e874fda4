"""Metrics & tracing — the ``StreamsMetrics`` analog the reference skips.

The reference exposes Kafka Streams' metrics registry via the processor
context but never records anything (SURVEY §5).  :class:`Metrics` keeps the
runtime's counters (records, matches, batches, per-phase wall time) — now
backed by a :class:`~kafkastreams_cep_tpu.utils.telemetry.MetricsRegistry`
instead of ad-hoc dataclass fields, so every timed phase also lands in a
fixed-log-bucket latency histogram (p50/p99 in ``snapshot()["phases"]``)
and processor metrics merge across bank members (``registry.merge``).

The attribute API (``metrics.records_in += n``, ``metrics.timed(attr)``)
is unchanged; storage moved into the registry.  ``profile`` wraps
``jax.profiler`` so a processor window can be captured for
TensorBoard/XProf when tuning on real TPU hardware.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

from kafkastreams_cep_tpu.utils.telemetry import (
    LATENCY_EDGES_S,
    MetricsRegistry,
)

#: Integer runtime counters, in their historical snapshot order.  The
#: decode's event slots split two ways: ``decode_events_built`` were
#: materialized from the lazy column batches, ``decode_events_reused``
#: came from the mirror or an earlier row of the same call.
#: ``gc_carry_pinned`` counts the events the host event GC kept only
#: because a tiered matcher's stencil carry holds them in a partial
#: prefix (summed over GC passes); ``gc_events_materialized`` the live
#: rows the GC materialized from lazy column batches, and
#: ``gc_lanes_swept`` the lanes whose mirror its dead removal visited
#: (both summed over GC passes).  ``pack_lane_misses`` counts the
#: columnar rows whose key the pack's sorted key index did not hold (new
#: keys, and every row of a column the index does not serve).
COUNTER_ATTRS = (
    "records_in",
    "matches_out",
    "batches",
    "duplicates_dropped",
    "decode_fallbacks",
    "decode_events_built",
    "decode_events_reused",
    "gc_carry_pinned",
    "gc_events_materialized",
    "gc_lanes_swept",
    "pack_lane_misses",
)

#: Wall-time accumulators; each also feeds the phase histogram of the same
#: stem ("device_seconds" -> phases["device"]).  The last four are
#: sub-phases, timed inside their parent: ``decode_wait`` (the device wait
#: for the match compaction) and ``decode_build`` (Sequence/Event
#: construction) inside ``decode``, ``gc_pull`` (the liveness transfer)
#: inside ``gc``, ``pack_lanes`` (the key-to-lane lookup of a columnar
#: call) inside ``pack``; a parent's self time is its seconds minus its
#: children's.
SECONDS_ATTRS = (
    "device_seconds",
    "decode_seconds",
    "pack_seconds",
    "dispatch_seconds",
    "drain_seconds",
    "gc_seconds",
    "decode_wait_seconds",
    "decode_build_seconds",
    "gc_pull_seconds",
    "pack_lanes_seconds",
)

#: The batch phases every processor pre-registers, so snapshots of runs
#: that never hit a phase (e.g. gc off, eager extraction) still carry
#: identical key sets.
PHASE_NAMES = ("pack", "dispatch", "drain", "device", "decode", "gc",
               "decode_wait", "decode_build", "gc_pull", "pack_lanes")


def _counter_property(name: str) -> property:
    def get(self) -> float:
        return self.registry.counter(name).value

    def set(self, v) -> None:
        self.registry.counter(name).value = v

    return property(get, set)


class Metrics:
    """Mutable counters for one processor (or bank member), registry-backed.

    Counter attributes read/write registry counters; ``timed(attr)``
    accumulates wall seconds into the ``attr`` counter AND observes the
    corresponding phase latency histogram, so a single context manager
    yields both the lifetime total and the percentile view.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or MetricsRegistry()
        for n in COUNTER_ATTRS + SECONDS_ATTRS:
            self.registry.counter(n)
        for n in PHASE_NAMES:
            self.registry.histogram(f"phase.{n}", LATENCY_EDGES_S)

    def snapshot(self, engine_counters: Dict[str, int]) -> Dict[str, float]:
        """One flat dict: runtime counters + engine overflow counters +
        derived rates + per-phase latency histograms (``"phases"``)."""
        out: Dict[str, float] = {
            n: self.registry.counter(n).value for n in COUNTER_ATTRS
        }
        for n in SECONDS_ATTRS:
            out[n] = round(self.registry.counter(n).value, 6)
        if out["device_seconds"] > 0:
            out["events_per_second_device"] = round(
                out["records_in"] / out["device_seconds"], 1
            )
        out.update(engine_counters)
        out["phases"] = self.phases()
        return out

    def phases(self) -> Dict[str, dict]:
        """Per-phase latency histogram snapshots (count/sum/p50/p99)."""
        return {
            name[len("phase."):]: inst.snapshot()
            for name, inst in self.registry.items()
            if name.startswith("phase.")
        }

    @contextlib.contextmanager
    def timed(self, attr: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.registry.counter(attr).value += dt
            phase = attr[:-8] if attr.endswith("_seconds") else attr
            self.registry.histogram(f"phase.{phase}", LATENCY_EDGES_S).observe(
                dt
            )


for _n in COUNTER_ATTRS + SECONDS_ATTRS:
    setattr(Metrics, _n, _counter_property(_n))
del _n


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the enclosed block (viewable in
    TensorBoard/XProf); use around ``processor.process`` calls on TPU."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Name a host-side region inside an active profiler trace."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def device_memory_stats() -> Dict[str, int]:
    """HBM usage of the fullest local device: each byte stat is the
    largest over every local device, so a mesh whose state sits on one
    chip is visible (empty dict when the backend doesn't report) — sizing
    aid for lane-count / slab-shape capacity planning."""
    import jax

    out: Dict[str, int] = {}
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:
            continue
        for k, v in stats.items():
            if isinstance(v, (int, float)) and "bytes" in k:
                out[k] = max(out.get(k, 0), int(v))
    return out
