"""The stream-processor analog: micro-batched host→device record pump.

Reference: ``CEPProcessor.java:88-163``.  The reference receives one record
at a time from Kafka Streams, steps one NFA, and forwards matches.  Here a
*micro-batch* of records is grouped by key into device lanes (the partition
analog, SURVEY §2.2), padded to a rectangular ``[K, T]`` batch, scanned in
one device dispatch, and the completed matches are decoded and emitted in
exact arrival order — the order the reference would have forwarded them.

Lane ownership mirrors the reference's per-partition state contract
(``CEPProcessor.java:117-134``): each key owns one lane's run queue, slab,
and fold state for the processor's lifetime; checkpoints externalize those
arrays (``runtime/checkpoint.py``).

Time is int32 on device (the TPU-native width).  Epoch-millisecond
timestamps don't fit, so the processor subtracts a fixed ``epoch`` (default:
the first record's timestamp) from every record before transfer; windows
compare time *differences*, which rebasing preserves exactly.  Predicates
therefore observe rebased timestamps — pass ``epoch=0`` if a predicate
matches on absolute time and your timestamps are small.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import time
from typing import Any, Dict, Hashable, List, NamedTuple, Optional, Sequence as Seq, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kafkastreams_cep_tpu import native
from kafkastreams_cep_tpu.engine.matcher import (
    OFFSET_LIMIT,
    EngineConfig,
    EventBatch,
)
from kafkastreams_cep_tpu.engine.stencil import partial_prefix_mask
from kafkastreams_cep_tpu.engine.tiered import engine_view
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.runtime.ingest import (
    REASON_LANE_OVERFLOW,
    REASON_LATE,
    REASON_OVERLOAD_SHED,
    REASON_SCHEMA,
    REASON_TIME_RANGE,
    Defect,
    IngestGuard,
    IngestPolicy,
)
from kafkastreams_cep_tpu.runtime.overload import shed_keep as _shed_keep
from kafkastreams_cep_tpu.utils import tracecache
from kafkastreams_cep_tpu.utils.events import Event, Sequence
from kafkastreams_cep_tpu.utils.failpoints import fire as _failpoint
from kafkastreams_cep_tpu.utils.metrics import Metrics, device_memory_stats
from kafkastreams_cep_tpu.utils.telemetry import TraceSink, maybe_span

from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("runtime")

_I32 = np.iinfo(np.int32)
_I64 = np.iinfo(np.int64)


class InputRejected(ValueError):
    """Deterministic input rejection by processor validation.

    Raised *before* any lane bookkeeping or device state mutates (batch
    validation is atomic), so the batch is bad, not the engine: a
    restore-and-replay recovery cycle cannot help and must not run.  The
    supervisor keys on this exact type — a plain ``ValueError`` out of a
    device dispatch (JAX surfaces some device faults that way) still
    triggers recovery.  Subclasses ``ValueError`` so pre-existing callers'
    except clauses keep working.
    """


class Record(NamedTuple):
    """One input record, the host analog of a Kafka ``(key, value, ts)``.

    ``offset`` is the record's log position within its key's lane: pass the
    source offset (Kafka-style) to enable replay dedup, or leave ``None``
    for auto-assignment.  Mixing explicit and auto offsets within one lane
    is allowed but auto always continues past the highest seen.
    """

    key: Hashable
    value: Any
    timestamp: int
    offset: Optional[int] = None


def _bucket(t: int) -> int:
    """Round a batch length up to the next power of two so recompiles are
    bounded (one trace per bucket) instead of one per distinct length."""
    n = 1
    while n < t:
        n *= 2
    return n


@contextlib.contextmanager
def _gc_paused():
    """Hold the cyclic garbage collector off over a bulk build of live
    objects: every collection it would start there frees nothing, and its
    older generations walk the whole heap.  The objects stay tracked; the
    next collection after the block sees them."""
    if not gc.isenabled():
        yield
        return
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _slot_keys(mask: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """``lane * OFFSET_LIMIT + offset`` of every marked slot of a ``[K, n]``
    offset array that holds a device offset (``0 <= offset <
    OFFSET_LIMIT``), in lane order."""
    lane = np.nonzero(mask)[0]
    off = offs[mask].astype(np.int64)
    ok = (off >= 0) & (off < OFFSET_LIMIT)
    return lane[ok] * OFFSET_LIMIT + off[ok]


def _rows_in(batch, lanes: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """Mask of the ``(lanes[i], offs[i])`` device slots whose rows sit in
    lazy column ``batch`` (its per-lane ``start``, ``-1`` where the lane
    fed none, and row ``cnt``)."""
    start, cnt = batch[0][lanes], batch[1][lanes]
    return (start >= 0) & (start <= offs) & (offs < start + cnt)


class CEPProcessor:
    """Micro-batching processor: records in, :class:`Sequence` matches out.

    ``num_lanes`` bounds the number of distinct keys (the partition count
    analog); a new key claims a free lane and keeps it for the processor's
    lifetime — one more key than lanes raises, like an unassigned Kafka
    partition would.  Values must share one numeric pytree structure
    (scalars or nested dicts of scalars): they are stacked into device
    arrays and handed to predicates as traced pytrees.  The first record
    fixes the schema (leaf structure and int/float dtypes), like a serde; a
    later record with a float where the schema says int is rejected rather
    than silently truncated.

    Predicates receive the record key as a numeric scalar: integer keys
    pass through unchanged; any other key type is represented by its lane
    index (keys must then not be matched on — the reference's lambdas can
    close over arbitrary keys, a device program cannot).

    **At-least-once dedup (deviation — fixes reference README.md:108).**
    The reference corrupts runs when records replay; here each lane keeps a
    high-water mark, and a record whose explicit ``offset`` is below it is
    dropped (counted in ``metrics.duplicates_dropped``).  Pass
    ``dedup=False`` to reproduce the reference's replay behavior.

    ``process(records)`` accepts any number of records, splits them into
    per-lane queues, pads to the max queue length (bucketed to powers of
    two so jit retraces are bounded), scans the whole batch in one jitted
    dispatch, and returns ``(key, Sequence)`` pairs in the exact order the
    reference's per-record loop would have forwarded them
    (``CEPProcessor.java:154-163``): by arrival of the completing record,
    then run-queue order.
    """

    def __init__(
        self,
        pattern,
        num_lanes: int,
        config: Optional[EngineConfig] = None,
        topic: str = "stream",
        epoch: Optional[int] = None,
        gc_events: bool = True,
        dedup: bool = True,
        gc_interval: int = 16,
        gc_events_interval: int = 8,
        decode_budget: int = 131072,
        pipeline: bool = False,
        mesh=None,
        trace_sink: Optional[TraceSink] = None,
        name: Optional[str] = None,
        drain_interval: int = 1,
        ingest: Optional[IngestPolicy] = None,
        flight=None,
        profile=None,
        clock=None,
        latency=None,
    ):
        # ``profile``: an optional measured ``per_stage`` selectivity
        # snapshot (``stage_counters()`` of an attribution run) handed to
        # the tiered matcher's lazy-chain conjunct ordering; ignored
        # untiered.  The supervisor's adaptive replanner
        # (runtime/supervisor.py AdaptPolicy) rebuilds the processor with
        # a fresh measured profile when observed selectivity drifts.
        # ``mesh``: a ``jax.sharding.Mesh`` shards the lane axis over the
        # devices (state-follows-partition, ``CEPProcessor.java:117-134`` —
        # each lane's run queue/slab/folds live on exactly one device for
        # the processor's lifetime).  The rest of the runtime is identical:
        # checkpoints gather to host arrays (mesh-agnostic, so a restore
        # may re-place onto a different mesh — the rebalance analog).  A
        # whole number ``n`` is the mesh of the first ``n`` devices
        # (``parallel.sharding.as_mesh``), so a configuration file can
        # name one.
        from kafkastreams_cep_tpu.parallel.sharding import as_mesh

        mesh = as_mesh(mesh)
        self.mesh = mesh
        tiering = config is not None and getattr(config, "tiering", False)
        if mesh is not None:
            from kafkastreams_cep_tpu.parallel.sharding import ShardedMatcher

            if tiering:
                # The tiered matcher's host control flow (per-tier
                # dispatch selection) is not expressible under shard_map
                # today; refusing beats silently restoring a tiered
                # checkpoint into an untiered shape.
                raise ValueError(
                    "EngineConfig.tiering is single-chip: construct the "
                    "processor without a mesh (or without tiering)"
                )
            self.batch = ShardedMatcher(pattern, num_lanes, mesh, config)
        elif tiering:
            from kafkastreams_cep_tpu.parallel.tiered import (
                TieredBatchMatcher,
            )

            self.batch = TieredBatchMatcher(
                pattern, num_lanes, config, profile=profile
            )
        else:
            self.batch = BatchMatcher(pattern, num_lanes, config)
        self.topic = topic
        self.num_lanes = int(num_lanes)
        # Maintenance sweep every N batches (0 = off; on by default —
        # unbounded streams need it twice over).  Long streams strand
        # walk-bound-truncated paths in the slab (counted in ``trunc``);
        # the sweep frees entries no future buffer op can reach, holding
        # occupancy bounded at fixed slab_entries.  The same sweep also
        # renormalizes Dewey versions (EngineConfig.renorm_versions) so
        # straddling runs' per-event version growth (NFA.java:185-188)
        # doesn't exhaust the fixed dewey_depth.
        self.gc_interval = int(gc_interval)
        # Host-event GC cadence: _gc_events costs a full device_get of slab
        # keys + run state; amortizing it every N batches keeps the host
        # mirror bounded without a per-batch sync (VERDICT round-4 item 9).
        self.gc_events_interval = max(int(gc_events_interval), 1)
        # Total compacted match rows the decode pulls per batch (0 =
        # always pull the raw [K, T, R, W] grid); batches with more
        # matches than the budget fall back to the full pull, counted in
        # ``metrics.decode_fallbacks``.  See _decode / ops/decode.py.
        self.decode_budget = int(decode_budget)
        # Pipelined mode (SURVEY §2.2 PP row — the fetch-ahead overlap the
        # reference gets from Kafka Streams' poll loop): process() returns
        # the PREVIOUS batch's matches, so batch N's device scan overlaps
        # batch N+1's host packing and batch N-1's decode.  Call flush()
        # to drain the last batch.  Match content is identical to the
        # serial mode, one call later; the host-event GC cadence drains
        # the pipeline first (its liveness pull must not prune events a
        # pending decode still references).
        self.pipeline = bool(pipeline)
        self._pending: Optional[tuple] = None
        self.state = self.batch.init_state()
        # Lazy extraction (EngineConfig.lazy_extraction): completed matches
        # are compact device handles until the batched drain pass
        # materializes them.  ``drain_interval`` sets the drain cadence in
        # batches (1 = every batch, the default — matches the eager
        # engine's emission latency exactly; larger values trade latency
        # for fewer drain dispatches and need a handle ring sized for the
        # longer interval).  ``flush()`` and checkpoints always drain.
        self.lazy = bool(self.batch.matcher.config.lazy_extraction)
        self.drain_interval = max(int(drain_interval), 1)
        # step_seq value at the start of the current batch's scan — maps a
        # drained handle's absolute completion step back to this batch's
        # t-axis (arrival ordering); restored from device state on resume.
        self._step_base = 0
        self.epoch = epoch  # None = rebase to the first record's timestamp
        self.gc_events = gc_events
        self.dedup = dedup
        # Key -> lane, append-only; assigning the property also drops the
        # columnar path's sorted index of its integer keys (_int_key_index).
        self._lane_of = {}
        self._key_of: Dict[int, Hashable] = {}
        self._next_offset = np.zeros(self.num_lanes, dtype=np.int64)
        # Per-lane offset base: the engine sees offsets rebased to log
        # positions (device offsets must stay < 2^24 for the slab's f32
        # pointer packing, engine.matcher.OFFSET_LIMIT); the first record of
        # a lane fixes its base, like `epoch` does for timestamps.
        self._off_base = np.full(self.num_lanes, -1, dtype=np.int64)
        # Host event mirror, keyed by *device* (rebased) offset per lane.
        self._events: List[Dict[int, Event]] = [dict() for _ in range(self.num_lanes)]
        # Columnar-path batches (process_columns): events stay as packed
        # [K, T] columns until a decode or GC touches them — match-sparse
        # streams then never pay per-record Event construction.  Each entry
        # is (start [K], count [K], abs_ts [K, T], value leaves [K, T]...).
        self._col_batches: List[tuple] = []
        self._value_proto = None
        self.metrics = Metrics()
        # Telemetry (utils/telemetry.py): an optional span sink — every
        # process() call emits one "batch" span with nested phase spans
        # (pack -> dispatch -> device -> decode -> gc; a columnar pack
        # holds pack_lanes, decode holds decode_wait and decode_build, gc
        # holds gc_pull); None costs one attribute check per phase.
        # ``name`` labels this processor in per-pattern attribution (bank
        # members pass their query name).
        self.trace = trace_sink
        self.name = name or topic
        self._batch_seq = 0
        # Event-time watermark: the max record timestamp ingested (absolute
        # ms), for the watermark / event-time-lag gauges in
        # ``metrics_snapshot`` — the ``records-lag`` analog.
        self._watermark: Optional[int] = None
        # Ingestion guard (runtime/ingest.py): a watermark-driven reorder
        # buffer + per-record quarantine in front of the engine.  None (the
        # default) keeps the historical batch-atomic front door: any bad
        # record raises InputRejected for the whole batch, and arrival
        # order is the engine order.  With a policy, records are validated
        # per record (defects dead-lettered, or raised under
        # ``on_bad_record="raise"``), held until the watermark passes them,
        # and released to the engine in timestamp order with auto-assigned
        # engine offsets; source offsets drive replay dedup at admission.
        # Injectable wall clock (tests pin a fake): every host-side stamp —
        # the event-time-lag gauge and all latency-ledger boundaries —
        # reads it.  Wall clock (time.time), not perf_counter: stamps must
        # stay comparable across a checkpoint→restore process boundary.
        self._clock = clock if clock is not None else time.time
        # Latency-attribution ledger (utils/latency.py): ``True`` builds a
        # fresh ledger on this processor's clock, an existing ledger is
        # adopted as-is (supervisor restore / bank members sharing one),
        # None/False disarms it — one ``None`` check per call site, zero
        # device work either way.
        if latency is True:
            from kafkastreams_cep_tpu.utils.latency import LatencyLedger

            self.ledger = LatencyLedger(clock=self._clock)
        else:
            self.ledger = latency or None
        self._guard = (
            IngestGuard(ingest, clock=self._clock)
            if ingest is not None
            else None
        )
        # Flight recorder (runtime/flight.py): a bounded ring of per-batch
        # records (phase timings, counter deltas, occupancy) appended at
        # the end of every batch and dumped as JSONL on crash/escalation/
        # quarantine-burst — None costs one check per batch.
        self.flight = flight
        self._dlq_base = 0  # dead-letter total at last batch (burst detect)
        # Brownout actuators (runtime/overload.py, set by the supervisor's
        # OverloadController — never directly by callers):
        # ``overload_admit_fraction`` None = door open; otherwise the
        # fraction of admissible records kept at the ingest door, via a
        # deterministic within-batch Bresenham stride (0.0 = L4, refuse
        # all).  ``telemetry_defer`` skips the per-lane/per-key device
        # gathers in metrics_snapshot while browned out.
        self.overload_admit_fraction: Optional[float] = None
        self.telemetry_defer = False

    def set_clock(self, clock) -> None:
        """Re-inject the host clock everywhere it is read (processor
        stamps, guard admit stamps, ledger commits).  Clocks are not
        durable state — a restored processor runs on wall clock until the
        caller pins one (tests do, for deterministic stamps)."""
        self._clock = clock
        if self._guard is not None:
            self._guard._clock = clock
        if self.ledger is not None:
            self.ledger.clock = clock

    # -- key -> lane assignment (partition-assignment analog) ---------------

    def lane(self, key: Hashable) -> int:
        existing = self._lane_of.get(key)
        if existing is not None:
            return existing
        lane = len(self._lane_of)
        if lane >= self.num_lanes:
            raise InputRejected(
                f"key {key!r}: more than num_lanes={self.num_lanes} "
                "distinct keys; size the processor for the key "
                "cardinality it serves"
            )
        self._lane_of[key] = lane
        self._key_of[lane] = key
        logger.info("assigned key %r to lane %d", key, lane)
        return lane

    def _key_code(self, key: Hashable, lane: int) -> int:
        if isinstance(key, (int, np.integer)) and _I32.min <= key <= _I32.max:
            return int(key)
        return lane

    def _rebased_ts(self, timestamp: int, rank: int = -1, key=None) -> int:
        rel = int(timestamp) - self.epoch
        if not (_I32.min <= rel <= _I32.max):
            where = f"record {rank} (key {key!r}): " if rank >= 0 else ""
            raise InputRejected(
                f"{where}timestamp {timestamp} is {rel} ms from the "
                f"processor epoch {self.epoch}, outside int32 device time "
                "(~±24.8 days); construct the processor with an epoch near "
                "your stream's timestamps"
            )
        return rel

    # -- the per-batch hot path --------------------------------------------

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One batch phase: a nested trace span + the ``{name}_seconds``
        accumulator + the ``phases[name]`` latency histogram, in one."""
        with maybe_span(self.trace, f"phase.{name}"):
            with self.metrics.timed(f"{name}_seconds"):
                yield

    def process(self, records: Seq[Record]) -> List[Tuple[Hashable, Sequence]]:
        if not records:
            return []
        self._batch_seq += 1
        with maybe_span(
            self.trace, "batch", path="records", batch=self._batch_seq,
            records=len(records),
        ) as sp:
            # Release stamp for the latency ledger: batch entry (the guard
            # releases mid-pack; validation time counts as queue).
            lat_t0 = self._clock() if self.ledger is not None else None
            with self._phase("pack"):
                if self._guard is not None:
                    released = self._ingest(
                        list(records), f"{self.name}-{self._batch_seq}"
                    )
                    sp["released"] = len(released)
                    packed = (
                        self._pack_records(released) if released else None
                    )
                else:
                    packed = self._pack_records(records)
            if packed is None:
                # Nothing released/kept this batch — still a flight tick
                # (a quarantine burst can empty a batch entirely).
                self._flight_tick()
                return []
            events, rank_of, n_kept = packed
            sp["lanes"] = len(self._lane_of)
            lat = None
            if self.ledger is not None:
                lat = self.ledger.start_batch(
                    f"{self.name}-{self._batch_seq}", n_kept,
                    admit=(
                        self._guard.last_release_stamps
                        if self._guard is not None
                        else None
                    ),
                    release=lat_t0,
                )
            matches = self._dispatch(events, rank_of, n_kept, lat)
            sp["matches"] = len(matches)
            return matches

    # -- the ingestion guard (runtime/ingest.py) ---------------------------

    def _ingest(self, records: List[Record], corr: str) -> List[Record]:
        """Admit one raw batch through the guard; returns the released
        (watermark-passed, timestamp-ordered) records with engine offsets
        reset to auto — release order IS the engine's log order, and the
        source offsets already did their job (dedup at admission)."""
        guard = self._guard
        # Fault site: before any guard or lane bookkeeping mutates — the
        # batch is rejected wholesale, nothing half-admitted.
        _failpoint("ingest.admit")
        strict = guard.policy.on_bad_record == "raise"
        admit_frac = self.overload_admit_fraction
        n_admissible = 0
        for idx, rec in enumerate(records):
            defect = self._record_defect(rec)
            if defect is None:
                # Brownout shed (runtime/overload.py L3+): AFTER
                # validation and replay dedup — source_hw already
                # advanced, so a re-submitted shed record dedups silently
                # instead of double-counting — and the Bresenham index
                # runs over admissible records only, so replaying the
                # same batch sheds the same records.
                keep = admit_frac is None or _shed_keep(
                    n_admissible, admit_frac
                )
                n_admissible += 1
                if not keep:
                    # Fault site: the shed decision is made but not yet
                    # recorded — recovery replays the batch from the
                    # snapshot + journal and re-sheds deterministically.
                    _failpoint("overload.shed")
                    guard.quarantine(
                        rec, REASON_OVERLOAD_SHED,
                        f"brownout admit fraction {admit_frac}", corr,
                    )
                    # The shed record's event time is still observed:
                    # the watermark keeps advancing so the held backlog
                    # drains while the door is throttled/closed.
                    guard.observe_time(rec.timestamp)
                    continue
                guard.push(rec)
                continue
            if defect.silent:
                self.metrics.duplicates_dropped += 1
                continue
            if strict:
                raise InputRejected(
                    f"record {idx} (key {rec.key!r}): {defect.reason}: "
                    f"{defect.detail}"
                )
            guard.quarantine(rec, defect.reason, defect.detail, corr)
        released = guard.release()
        # Fault site: the adversarial window — the buffer already moved
        # (records admitted, releases popped) but the engine never saw
        # them.  Recovery must restore the buffer from the snapshot and
        # re-admit from the journal (chaos-tested).
        _failpoint("ingest.release")
        return [
            r._replace(offset=None) if r.offset is not None else r
            for r in released
        ]

    def _record_defect(self, rec: Record) -> Optional[Defect]:
        """Validate ONE record against the schema/lane/time contracts the
        batch path enforces atomically; commits schema, epoch, and lane
        assignment on first sight (the guard admits per record, so there
        is no batch to reject).  Returns None when admissible."""
        guard = self._guard
        if self._value_proto is None:
            leaves0, treedef0 = jax.tree_util.tree_flatten(rec.value)
            self._value_proto = jax.tree_util.tree_unflatten(
                treedef0,
                [
                    np.dtype(np.float32)
                    if np.issubdtype(np.asarray(l).dtype, np.floating)
                    else np.dtype(np.int32)
                    for l in leaves0
                ],
            )
        dtypes, treedef = jax.tree_util.tree_flatten(self._value_proto)
        leaves, rec_def = jax.tree_util.tree_flatten(rec.value)
        if rec_def != treedef:
            return Defect(
                REASON_SCHEMA,
                f"value structure {rec_def} differs from the schema "
                f"{treedef} fixed by the first record",
            )
        for field_i, (leaf, dt) in enumerate(zip(leaves, dtypes)):
            if np.issubdtype(np.asarray(leaf).dtype, np.floating) and not (
                np.issubdtype(dt, np.floating)
            ):
                return Defect(
                    REASON_SCHEMA,
                    f"field #{field_i}: float value {leaf!r} in a field "
                    "the schema (fixed by the first record) typed as int",
                )
        lane = self._lane_of.get(rec.key)
        if lane is None:
            if len(self._lane_of) >= self.num_lanes:
                return Defect(
                    REASON_LANE_OVERFLOW,
                    f"key {rec.key!r} would exceed num_lanes="
                    f"{self.num_lanes}; size the processor for the key "
                    "cardinality it serves",
                )
            lane = len(self._lane_of)
            self._lane_of[rec.key] = lane
            self._key_of[lane] = rec.key
            logger.info("assigned key %r to lane %d", rec.key, lane)
        if self.epoch is None:
            self.epoch = int(rec.timestamp)
        rel = int(rec.timestamp) - self.epoch
        if not (_I32.min <= rel <= _I32.max):
            return Defect(
                REASON_TIME_RANGE,
                f"timestamp {rec.timestamp} is {rel} ms from the processor "
                f"epoch {self.epoch}, outside int32 device time "
                "(~±24.8 days)",
            )
        if rec.offset is not None:
            hw = guard.source_hw.get(lane, 0)
            if self.dedup and rec.offset < hw:
                return Defect("duplicate", "", silent=True)
            guard.source_hw[lane] = max(hw, int(rec.offset) + 1)
        behind = guard.late_by(int(rec.timestamp))
        if behind is not None:
            return Defect(
                REASON_LATE,
                f"timestamp {rec.timestamp} is {behind} ms behind the "
                f"watermark {guard.watermark} (grace "
                f"{guard.policy.grace_ms} ms)",
            )
        return None

    def drain_ingest(self) -> List[Tuple[Hashable, Sequence]]:
        """End-of-stream drain of the reorder buffer: release every held
        record regardless of watermark (the stream is declared over, so
        nothing younger can still arrive) and run them through the
        engine.  A no-op without a guard or with an empty buffer.  Call
        :meth:`flush` afterwards for pipelined / lazy processors."""
        if self._guard is None:
            return []
        lat_t0 = self._clock() if self.ledger is not None else None
        released = self._guard.drain()
        if not released:
            return []
        released = [
            r._replace(offset=None) if r.offset is not None else r
            for r in released
        ]
        self._batch_seq += 1
        with maybe_span(
            self.trace, "batch", path="ingest-drain", batch=self._batch_seq,
            records=len(released),
        ) as sp:
            with self._phase("pack"):
                packed = self._pack_records(released)
            if packed is None:
                return []
            lat = None
            if self.ledger is not None:
                lat = self.ledger.start_batch(
                    f"{self.name}-{self._batch_seq}", packed[2],
                    admit=self._guard.last_release_stamps, release=lat_t0,
                )
            matches = self._dispatch(*packed, lat)
            sp["matches"] = len(matches)
            return matches

    def _pack_records(self, records: Seq[Record]):
        """Validate + lane-assign + pad one record batch to ``[K, T]``
        device columns; None when every record was a replay duplicate."""
        K = self.num_lanes
        if self.epoch is None:
            self.epoch = int(records[0].timestamp)
        if self._value_proto is None:
            # A pytree of dtypes with the records' value structure (kept as
            # plain picklable objects for the checkpoint header).
            leaves0, treedef0 = jax.tree_util.tree_flatten(records[0].value)
            self._value_proto = jax.tree_util.tree_unflatten(
                treedef0,
                [
                    np.dtype(np.float32)
                    if np.issubdtype(np.asarray(l).dtype, np.floating)
                    else np.dtype(np.int32)
                    for l in leaves0
                ],
            )
        dtypes, treedef = jax.tree_util.tree_flatten(self._value_proto)

        # Validate the whole batch BEFORE mutating any lane bookkeeping, so
        # a bad record rejects the batch atomically (nothing half-ingested).
        # Lane assignment is simulated first and committed only after
        # validation — a rejected batch must not consume lane slots.
        # Offsets are simulated the same way: explicit ones below the lane's
        # high-water mark are duplicates (at-least-once replay) and dropped.
        lane_sim = dict(self._lane_of)
        lanes = []
        for rank, rec in enumerate(records):
            lane = lane_sim.get(rec.key)
            if lane is None:
                lane = len(lane_sim)
                if lane >= self.num_lanes:
                    raise InputRejected(
                        f"record {rank} (key {rec.key!r}): more than "
                        f"num_lanes={self.num_lanes} distinct keys; size "
                        "the processor for the key cardinality it serves"
                    )
                lane_sim[rec.key] = lane
            lanes.append(lane)
        rel_ts = [
            self._rebased_ts(rec.timestamp, rank, rec.key)
            for rank, rec in enumerate(records)
        ]
        next_sim = self._next_offset.copy()
        base_sim = self._off_base.copy()
        offsets: List[Optional[int]] = []
        batch_leaves = []
        for rank, rec in enumerate(records):
            leaves = jax.tree_util.tree_leaves(rec.value)
            if len(leaves) != len(dtypes):
                raise InputRejected(
                    f"record {rank} (key {rec.key!r}): value structure "
                    f"({len(leaves)} fields) differs from the schema fixed "
                    f"by the first record ({len(dtypes)} fields)"
                )
            for field_i, (leaf, dt) in enumerate(zip(leaves, dtypes)):
                if np.issubdtype(np.asarray(leaf).dtype, np.floating) and not np.issubdtype(dt, np.floating):
                    raise InputRejected(
                        f"record {rank} (key {rec.key!r}): field #{field_i} "
                        f"float value {leaf!r} in a field the schema (fixed "
                        "by the first record) typed as int"
                    )
            batch_leaves.append(leaves)
            lane = lanes[rank]
            off = rec.offset if rec.offset is not None else int(next_sim[lane])
            if self.dedup and off < next_sim[lane]:
                offsets.append(None)  # duplicate — high-water mark drop
            else:
                if base_sim[lane] < 0:
                    base_sim[lane] = off  # first record fixes the lane base
                dev = off - int(base_sim[lane])
                if dev < 0:
                    raise InputRejected(
                        f"record {rank} (key {rec.key!r}): offset {off} is "
                        f"below lane {lane}'s base {int(base_sim[lane])} "
                        "(out-of-order replay below the first seen offset "
                        "needs dedup=True)"
                    )
                if dev >= OFFSET_LIMIT:
                    raise InputRejected(
                        f"record {rank} (key {rec.key!r}): offset {off} is "
                        f"{dev} past lane {lane}'s base — per-lane log "
                        "positions must stay below 2^24 (engine f32 "
                        "pointer packing)"
                    )
                offsets.append(off)
                next_sim[lane] = max(next_sim[lane], off + 1)

        # Validation passed — commit the simulated lane assignments.
        for key, lane in lane_sim.items():
            if key not in self._lane_of:
                self._lane_of[key] = lane
                self._key_of[lane] = key
                logger.info("assigned key %r to lane %d", key, lane)

        # Host-event bookkeeping (the decode mirror), one pass.  Events keep
        # their true source offsets; the mirror is keyed by device offset.
        self._off_base = base_sim
        dropped = 0
        for rank, rec in enumerate(records):
            off = offsets[rank]
            if off is None:
                dropped += 1
                continue
            lane = lanes[rank]
            self._next_offset[lane] = max(self._next_offset[lane], off + 1)
            event = Event(
                rec.key, rec.value, int(rec.timestamp), self.topic, lane, off
            )
            self._events[lane][off - int(self._off_base[lane])] = event
        self.metrics.duplicates_dropped += dropped
        if dropped:
            logger.info("dropped %d replayed records (high-water mark)", dropped)
        wm = max(int(rec.timestamp) for rec in records)
        self._watermark = wm if self._watermark is None else max(self._watermark, wm)
        if all(off is None for off in offsets):
            return None

        # Lane-queue positions + columnar [K, T] packing via the native
        # ingest kernels (NumPy fallbacks inside, ``native/``).
        n = len(records)
        lanes_arr = np.asarray(lanes, dtype=np.int32)
        keep = np.fromiter(
            (off is not None for off in offsets), dtype=np.uint8, count=n
        )
        pos, _qlen, max_len = native.queue_positions(lanes_arr, keep, K)
        T = _bucket(max_len)

        key_col = np.fromiter(
            (
                self._key_code(rec.key, lanes[rank])
                for rank, rec in enumerate(records)
            ),
            dtype=np.int32,
            count=n,
        )
        ts_col = np.asarray(rel_ts, dtype=np.int32)
        off_col = np.fromiter(
            (
                off - int(self._off_base[lanes[rank]]) if off is not None else 0
                for rank, off in enumerate(offsets)
            ),
            dtype=np.int32,
            count=n,
        )
        rank_col = np.arange(n, dtype=np.int64)

        # Pad to [K, T]; padding slots carry valid=False and leave lane
        # state untouched (engine contract, matcher.py step()).
        key_arr = np.zeros((K, T), dtype=np.int32)
        ts = np.zeros((K, T), dtype=np.int32)
        off = np.zeros((K, T), dtype=np.int32)
        valid = np.zeros((K, T), dtype=bool)
        rank_of = np.full((K, T), -1, dtype=np.int64)
        native.pack_column(key_arr, key_col, lanes_arr, pos, keep)
        native.pack_column(ts, ts_col, lanes_arr, pos, keep)
        native.pack_column(off, off_col, lanes_arr, pos, keep)
        native.pack_column(rank_of, rank_col, lanes_arr, pos, keep)
        native.pack_valid(valid, lanes_arr, pos, keep)
        val_leaves = [np.zeros((K, T), dtype=dt) for dt in dtypes]
        for i, dt in enumerate(dtypes):
            col = np.asarray([leaves[i] for leaves in batch_leaves], dtype=dt)
            native.pack_column(val_leaves[i], col, lanes_arr, pos, keep)

        events = self._event_batch(
            key_arr, treedef, val_leaves, ts, off, valid
        )
        return events, rank_of, len(records) - dropped

    def process_columns(
        self, keys, values, timestamps
    ) -> List[Tuple[Hashable, Sequence]]:
        """Columnar ingestion: ``[N]`` arrays instead of Record objects.

        The per-record :meth:`process` spends microseconds of Python per
        record (validation, Event construction) — fine at Kafka-consumer
        rates, the wall at engine rates.  This path validates and packs
        with array ops and defers Event construction until a match (or the
        GC) actually touches an event, so match-sparse streams never pay
        it (the packed columns themselves are the mirror).

        ``keys`` is an ``[N]`` array (numeric keys vectorize; object keys
        fall back to a Python mapping pass), ``values`` a pytree of ``[N]``
        arrays with the schema's structure, ``timestamps`` ``[N]`` ints.
        Offsets are always auto-assigned — explicit-offset replay/dedup
        needs the per-record path.  Emitted Events carry values rebuilt
        from the packed columns (schema dtypes), not the caller's original
        scalars."""
        if self._guard is not None:
            raise ValueError(
                "the ingestion guard runs on the per-record path only; "
                "process_columns bypasses per-record validation and the "
                "reorder buffer (construct the processor without "
                "ingest=... to use the columnar path)"
            )
        self._batch_seq += 1
        with maybe_span(
            self.trace, "batch", path="columns", batch=self._batch_seq,
        ) as sp:
            lat_t0 = self._clock() if self.ledger is not None else None
            with self._phase("pack"):
                packed = self._pack_columns(keys, values, timestamps)
            if packed is None:
                return []
            events, rank_of, n = packed
            sp["records"] = n
            sp["lanes"] = len(self._lane_of)
            lat = None
            if self.ledger is not None:
                lat = self.ledger.start_batch(
                    f"{self.name}-{self._batch_seq}", n, release=lat_t0,
                )
            matches = self._dispatch(events, rank_of, n, lat)
            sp["matches"] = len(matches)
            return matches

    def _pack_columns(self, keys, values, timestamps):
        keys_arr = np.asarray(keys)
        if keys_arr.ndim != 1:
            raise InputRejected(
                f"keys must be a 1-D column, got shape {keys_arr.shape}"
            )
        ts_arr = np.asarray(timestamps, dtype=np.int64)
        n = int(keys_arr.shape[0])
        # One timestamp per record, validated BEFORE the native pack path:
        # pack_column dereferences n column elements by row, so a short
        # timestamps column would be an out-of-bounds read, not an error.
        if ts_arr.shape != (n,):
            raise InputRejected(
                f"timestamps shape {ts_arr.shape} != ({n},); pass exactly "
                "one timestamp per record"
            )
        if n == 0:
            return None
        K = self.num_lanes
        if self.epoch is None:
            self.epoch = int(ts_arr[0])
        leaves_in, treedef_in = jax.tree_util.tree_flatten(values)
        leaves_in = [np.asarray(l) for l in leaves_in]
        if self._value_proto is None:
            self._value_proto = jax.tree_util.tree_unflatten(
                treedef_in,
                [
                    np.dtype(np.float32)
                    if np.issubdtype(l.dtype, np.floating)
                    else np.dtype(np.int32)
                    for l in leaves_in
                ],
            )
        dtypes, treedef = jax.tree_util.tree_flatten(self._value_proto)
        if treedef_in != treedef:
            raise InputRejected(
                "value columns structure differs from the schema fixed by "
                "the first batch"
            )
        for field_i, (l, dt) in enumerate(zip(leaves_in, dtypes)):
            if l.shape != (n,):
                raise InputRejected(
                    f"field #{field_i}: value column shape {l.shape} != "
                    f"({n},)"
                )
            if np.issubdtype(l.dtype, np.floating) and not np.issubdtype(
                dt, np.floating
            ):
                raise InputRejected(
                    f"field #{field_i}: float column in a field the "
                    "schema typed as int"
                )

        with self._phase("pack_lanes"):
            lanes_arr = self._column_lanes(keys_arr)

        rel = ts_arr - self.epoch
        if rel.size and (rel.min() < _I32.min or rel.max() > _I32.max):
            bad = int(
                np.argmax((rel < _I32.min) | (rel > _I32.max))
            )
            raise InputRejected(
                f"record {bad} (key {keys_arr[bad]!r}): timestamp "
                f"{int(ts_arr[bad])} outside int32 device time relative "
                f"to the processor epoch {self.epoch}"
            )
        wm = int(ts_arr.max())
        self._watermark = wm if self._watermark is None else max(self._watermark, wm)

        keep = np.ones(n, dtype=np.uint8)
        pos, qlen, max_len = native.queue_positions(lanes_arr, keep, K)
        # Auto offsets: lane l's batch rows take consecutive log positions
        # from its high-water mark; a fresh lane's base pins to it.
        fresh = (self._off_base < 0) & (qlen > 0)
        self._off_base[fresh] = self._next_offset[fresh]
        start_dev = self._next_offset - self._off_base  # [K] first dev off
        dev_off = (start_dev[lanes_arr] + pos).astype(np.int64)
        if dev_off.size and dev_off.max() >= OFFSET_LIMIT:
            raise InputRejected(
                "per-lane log positions past 2^24 (engine f32 pointer "
                "packing) — rotate the processor via checkpoint/restore"
            )
        self._next_offset += qlen

        T = _bucket(max_len)
        # Per-key decision, exactly like _key_code on the record path: an
        # int32-range integer key passes through, anything else is its
        # lane index (an out-of-range batch-mate must not change another
        # key's code).
        if np.issubdtype(keys_arr.dtype, np.integer):
            in_range = (keys_arr >= _I32.min) & (keys_arr <= _I32.max)
            key_codes = np.where(
                in_range, keys_arr.astype(np.int64),
                lanes_arr.astype(np.int64),
            ).astype(np.int32)
        elif keys_arr.dtype == object:
            # Object columns can mix int and non-int keys; each element
            # must take the code _key_code gives it on the record path (an
            # in-range int keeps its value, anything else its lane index),
            # or record- and column-ingested events of the SAME key would
            # see different ``key`` values in predicates.
            key_codes = np.fromiter(
                (
                    self._key_code(k, int(lanes_arr[i]))
                    for i, k in enumerate(keys_arr.tolist())
                ),
                dtype=np.int32,
                count=n,
            )
        else:
            key_codes = lanes_arr.astype(np.int32)
        key_arr = np.zeros((K, T), dtype=np.int32)
        ts = np.zeros((K, T), dtype=np.int32)
        off = np.zeros((K, T), dtype=np.int32)
        valid = np.zeros((K, T), dtype=bool)
        rank_of = np.full((K, T), -1, dtype=np.int64)
        abs_ts = np.zeros((K, T), dtype=np.int64)
        native.pack_column(key_arr, key_codes, lanes_arr, pos, keep)
        native.pack_column(ts, rel.astype(np.int32), lanes_arr, pos, keep)
        native.pack_column(off, dev_off.astype(np.int32), lanes_arr, pos, keep)
        native.pack_column(rank_of, np.arange(n, dtype=np.int64), lanes_arr, pos, keep)
        native.pack_column(abs_ts, ts_arr, lanes_arr, pos, keep)
        native.pack_valid(valid, lanes_arr, pos, keep)
        val_leaves = [np.zeros((K, T), dtype=dt) for dt in dtypes]
        for i, dt in enumerate(dtypes):
            native.pack_column(
                val_leaves[i], leaves_in[i].astype(dt), lanes_arr, pos, keep
            )

        # Lazy mirror: the packed columns ARE the event store until a
        # match or the GC touches a row.
        col_start = np.where(qlen > 0, start_dev, -1).astype(np.int64)
        self._col_batches.append(
            (col_start, qlen.astype(np.int64), abs_ts, val_leaves)
        )

        events = self._event_batch(
            key_arr, treedef, val_leaves, ts, off, valid
        )
        return events, rank_of, n

    def _event_batch(self, key, treedef, val_leaves, ts, off, valid):
        """The packed ``[K, T]`` host columns as the scan's ``EventBatch``:
        on the device here, or, on a mesh, left on the host for the
        dispatch to place each lane block straight onto its device."""
        put = np.asarray if self.mesh is not None else jnp.asarray
        return EventBatch(
            key=put(key),
            value=jax.tree_util.tree_unflatten(
                treedef, [put(v) for v in val_leaves]
            ),
            ts=put(ts),
            off=put(off),
            valid=put(valid),
        )

    # -- columnar lane resolution ------------------------------------------

    @property
    def _lane_of(self) -> Dict[Hashable, int]:
        return self._lanes

    @_lane_of.setter
    def _lane_of(self, lanes: Dict[Hashable, int]) -> None:
        # A replaced map (restore, migration) invalidates the key index; an
        # in-place write only appends, which the index notices by length.
        self._lanes = lanes
        self._key_index = None

    def _int_key_index(self) -> tuple:
        """``(keys, lanes, n)``: the ``_lane_of`` entries whose key is an
        int64-range integer, sorted by key, as of a map of ``n`` entries.
        Rebuilt whenever the map has grown since (lanes are append-only)."""
        idx = self._key_index
        n = len(self._lane_of)
        if idx is None or idx[2] != n:
            pairs = [
                (int(k), lane) for k, lane in self._lane_of.items()
                if isinstance(k, (int, np.integer))
                and not isinstance(k, bool)
                and _I64.min <= k <= _I64.max
            ]
            keys = np.fromiter(
                (k for k, _ in pairs), dtype=np.int64, count=len(pairs)
            )
            lanes = np.fromiter(
                (lane for _, lane in pairs), dtype=np.int32, count=len(pairs)
            )
            order = np.argsort(keys)
            idx = self._key_index = (keys[order], lanes[order], n)
        return idx

    def _assign_lanes(self, uniq: list) -> list:
        """Give each key of ``uniq`` (distinct, in first-appearance order)
        that has no lane the next free one, all or none: a batch that would
        overflow ``num_lanes`` is rejected before any is committed.
        Returns the newly assigned keys."""
        K = self.num_lanes
        new = [k for k in uniq if k not in self._lane_of]
        if len(self._lane_of) + len(new) > K:
            raise InputRejected(
                f"more than num_lanes={K} distinct keys (first overflowing "
                f"key: {new[K - len(self._lane_of)]!r}); size the "
                "processor for the key cardinality it serves"
            )
        for k in new:
            lane = len(self._lane_of)
            self._lane_of[k] = lane
            self._key_of[lane] = k
            logger.info("assigned key %r to lane %d", k, lane)
        return new

    def _column_lanes(self, keys_arr: np.ndarray) -> np.ndarray:
        """The lane of every row of a key column, new keys assigned in
        first-appearance order.

        An integer column that fits int64 is looked up in the sorted key
        index; only the rows it misses (keys new to the processor, or
        keys the index does not hold, such as a float ``5.0`` equal to an
        int row's ``5``) take the dict.  Other columns resolve through the
        dict alone.  ``pack_lane_misses`` counts the rows the index did
        not answer."""
        n = keys_arr.shape[0]
        dt = keys_arr.dtype
        if not (
            np.issubdtype(dt, np.signedinteger)
            or (np.issubdtype(dt, np.unsignedinteger) and dt.itemsize < 8)
        ):
            if dt == object:
                self._assign_lanes(list(dict.fromkeys(keys_arr.tolist())))
                lanes_arr = np.fromiter(
                    (self._lane_of[k] for k in keys_arr.tolist()),
                    dtype=np.int32, count=n,
                )
            else:
                vals, first = np.unique(keys_arr, return_index=True)
                self._assign_lanes(
                    [v.item() for v in vals[np.argsort(first)]]
                )
                ku = np.fromiter(self._lane_of.keys(), dtype=dt)
                lv = np.fromiter(self._lane_of.values(), dtype=np.int32)
                order = np.argsort(ku)
                lanes_arr = lv[order][
                    np.searchsorted(ku[order], keys_arr)
                ].astype(np.int32)
            self.metrics.pack_lane_misses += n
            return lanes_arr
        idx_keys, idx_lanes, _ = self._int_key_index()
        if idx_keys.size:
            at = np.searchsorted(idx_keys, keys_arr)
            np.minimum(at, idx_keys.size - 1, out=at)
            hit = idx_keys[at] == keys_arr
            lanes_arr = idx_lanes[at]
            if hit.all():
                return lanes_arr
            rows = np.flatnonzero(~hit)
        else:
            lanes_arr = np.empty(n, dtype=np.int32)
            rows = np.arange(n)
        vals, first, inv = np.unique(
            keys_arr[rows], return_index=True, return_inverse=True
        )
        new = self._assign_lanes([v.item() for v in vals[np.argsort(first)]])
        lanes_arr[rows] = np.fromiter(
            (self._lane_of[k] for k in vals.tolist()),
            dtype=np.int32, count=vals.size,
        )[inv]
        if new:
            new_keys = np.asarray(new, dtype=np.int64)
            new_lanes = np.fromiter(
                (self._lane_of[k] for k in new), dtype=np.int32,
                count=len(new),
            )
            order = np.argsort(new_keys)
            new_keys, new_lanes = new_keys[order], new_lanes[order]
            at = np.searchsorted(idx_keys, new_keys)
            self._key_index = (
                np.insert(idx_keys, at, new_keys),
                np.insert(idx_lanes, at, new_lanes),
                len(self._lane_of),
            )
        self.metrics.pack_lane_misses += rows.size
        return lanes_arr

    def _dispatch(self, events, rank_of, n_records, lat=None):
        # Fault-injection sites (utils/failpoints.py; no-ops unless a test
        # armed them): ``device.dispatch`` fails before the scan — state
        # untouched; ``device.result`` fails after ``self.state`` advanced
        # but before the batch's matches reach the caller — the adversarial
        # window the supervisor's restore-and-replay must cover.
        _failpoint("device.dispatch")
        if self.mesh is not None:
            # Shard fault site: the host→mesh transfer is where a dead
            # device first surfaces on the sharded path — state untouched,
            # so the supervisor's evacuation can restore-and-replay onto
            # the surviving sub-mesh (arm with ShardLost to drive it).
            _failpoint("shard.dispatch")

        base = self._step_base
        if lat is not None:
            lat.dispatch = self._clock()
        with self._phase("dispatch"):
            if self.mesh is not None:
                with self._phase("shard_put"):
                    # Host columns to each device's lane block, in one
                    # transfer.
                    events = self.batch.shard_events(events)
            # Enqueue only: the scan (and any due sweep) dispatch async;
            # the wait is attributed to the device phase below.
            self.state, out = self.batch.scan(self.state, events)
            self._step_base += int(events.ts.shape[1])
            if self.gc_interval and (self.metrics.batches + 1) % self.gc_interval == 0:
                # Pending lazy handles survive the sweep by construction:
                # they are mark-sweep liveness roots and renorm rows
                # (parallel/batch.py sweep_lanes).
                self.state = self.batch.sweep(self.state)
        drain_out = None
        if self.lazy and (
            (self.metrics.batches + 1) % self.drain_interval == 0
        ):
            with self._phase("drain"):
                # One batched pass materializes every pending handle —
                # the deferred analog of the eager in-step extraction
                # walks, off the per-step critical path.
                self.state, drain_out = self.batch.drain(self.state)
        with self._phase("device"):
            if not self.pipeline:
                # Serial mode: wait here so device_seconds is the real
                # device wall time.  Pipelined mode never blocks on the
                # fresh dispatch — the wait lands in the next call's
                # decode of THIS batch, overlapped with its device scan.
                jax.block_until_ready(
                    out.count if drain_out is None else drain_out.count
                )
        if lat is not None:
            # Device-completion stamp: rides the existing gates transfer —
            # no extra device_get.  Serial mode just blocked, so this is
            # real completion; pipelined mode observes the enqueue point
            # (the wait lands in the next call's decode, and so does the
            # stamp's tail — host-observed by design).
            lat.complete = self._clock()
        _failpoint("device.result")
        gc_due = self.gc_events and (
            (self.metrics.batches + 1) % self.gc_events_interval == 0
        )
        self.metrics.records_in += n_records
        self.metrics.batches += 1
        with self._phase("decode"):
            if self.pipeline:
                prev, self._pending = (
                    self._pending, (out, rank_of, drain_out, base, lat),
                )
                matches = self._decode(*prev[:4]) if prev is not None else []
                if prev is not None:
                    self._lat_finish(
                        prev[4], (not self.lazy) or prev[2] is not None
                    )
                if gc_due:
                    # The GC liveness pull must not prune events the
                    # still-pending decode references: drain first.
                    pend, self._pending = self._pending, None
                    matches += self._decode(*pend[:4])
                    self._lat_finish(
                        pend[4], (not self.lazy) or pend[2] is not None
                    )
            else:
                matches = self._decode(out, rank_of, drain_out, base)
                self._lat_finish(
                    lat, (not self.lazy) or drain_out is not None
                )
        if gc_due:
            self._gc_events()
        self.metrics.matches_out += len(matches)
        self._flight_tick()
        return matches

    def _lat_finish(self, lat, emitted: bool) -> None:
        """Commit or defer one batch's latency bundle at its decode.

        ``emitted`` means the batch's matches just left the device (eager
        decode, or this batch's drain carried its handles): the bundle —
        plus any parked earlier bundles whose handles rode the same drain
        — commits at one emit stamp.  Otherwise (lazy, drain not due) the
        bundle parks until the drain that emits it; a bundle that never
        commits because its batch failed dies with the rollback and is
        re-observed on replay — exactly-once counts, honest wall clock.
        """
        if lat is None or self.ledger is None:
            return
        if emitted:
            emit = self._clock()
            self.ledger.commit_deferred(emit)
            self.ledger.commit(lat, emit)
        else:
            self.ledger.defer(lat)

    def _flight_tick(self) -> None:
        """Record this batch in the flight ring (runtime/flight.py) and
        trigger a quarantine-burst dump when the guard dead-lettered a
        burst's worth of records in one batch.  One ``None`` check when
        no recorder is attached."""
        if self.flight is None:
            return
        corr = f"{self.name}-{self._batch_seq}"
        self.flight.observe(self, corr=corr)
        if self._guard is not None:
            total = int(sum(self._guard.reason_counts.values()))
            if total - self._dlq_base >= self.flight.quarantine_burst:
                self.flight.dump("quarantine_burst", corr=corr)
            self._dlq_base = total

    def flush(self) -> List[Tuple[Hashable, Sequence]]:
        """Drain the pipelined in-flight batch (no-op in serial mode or
        when nothing is pending), and — under lazy extraction — also
        drain any handles still pending on device (a ``drain_interval``
        > 1 leaves up to interval-1 batches' matches undrained).  Call
        before checkpointing a pipelined processor — a snapshot cannot
        carry undecoded device outputs."""
        matches: List[Tuple[Hashable, Sequence]] = []
        if self._pending is not None:
            pend, self._pending = self._pending, None
            with self._phase("decode"):
                matches = self._decode(*pend[:4])
            self._lat_finish(pend[4], (not self.lazy) or pend[2] is not None)
        if self.lazy:
            with self._phase("drain"):
                self.state, dout = self.batch.drain(self.state)
            with self._phase("decode"):
                # No rank_of: everything pending predates "now", so the
                # order key degrades to (completion step, lane, run row).
                matches += self._decode_drained(dout, None, self._step_base)
            if self.ledger is not None:
                # This drain emitted every parked batch's matches.
                self.ledger.commit_deferred(self._clock())
        self.metrics.matches_out += len(matches)
        return matches

    def _decode(
        self, out, rank_of, drain_out=None, base=0
    ) -> List[Tuple[Hashable, Sequence]]:
        """One batch's matches: the eager ``StepOutput`` grid (empty under
        lazy extraction) plus, when a drain ran, the drained handles."""
        matches = [] if self.lazy else self._decode_eager(out, rank_of)
        if drain_out is not None:
            matches = matches + self._decode_drained(
                drain_out, rank_of, base
            )
        return matches

    def _decode_drained(
        self, dout, rank_of, base
    ) -> List[Tuple[Hashable, Sequence]]:
        """Drained handles -> (key, Sequence) in the eager emission order.

        Handles completed in THIS batch (``seq >= base``) order exactly
        like the eager path — by arrival rank of the completing record,
        then run-queue row; handles deferred from earlier batches (only
        with ``drain_interval > 1`` or after a restore) emit first, by
        (completion step, lane, run row).

        Fast path mirrors the eager decode: the hit rows compact
        on-device (``ops/decode.py: compact_drained``) so the host pulls
        rows proportional to the match count, not ``lanes x ring``.
        """
        if self.decode_budget:
            from kafkastreams_cep_tpu.ops.decode import compact_drained

            K, HB = dout.count.shape
            with self._phase("decode_wait"):
                c_stage, c_off, c_count, c_seq, c_row, c_k, c_n, _ovf = (
                    compact_drained(dout, self.decode_budget)
                )
                n = int(c_n)
            if n <= min(self.decode_budget, K * HB):
                if n == 0:
                    return []
                m = 1
                while m < n:
                    m *= 2
                m = min(m, int(c_count.shape[0]))
                cnts, stages, offs, seqs, rows, ks = jax.device_get(
                    (c_count[:m], c_stage[:m], c_off[:m], c_seq[:m],
                     c_row[:m], c_k[:m])
                )
                return self._emit_drained(
                    ks[:n], cnts[:n], stages[:n], offs[:n], seqs[:n],
                    rows[:n], rank_of, base,
                )
            self.metrics.decode_fallbacks += 1
        count = np.asarray(jax.device_get(dout.count))  # [K, HB]
        ks, hs = np.nonzero(count)
        if ks.size == 0:
            return []
        stage, off, seqa, rowa = (
            np.asarray(jax.device_get(x))
            for x in (dout.stage, dout.off, dout.seq, dout.row)
        )
        return self._emit_drained(
            ks, count[ks, hs], stage[ks, hs], off[ks, hs], seqa[ks, hs],
            rowa[ks, hs], rank_of, base,
        )

    def _emit_drained(self, ks, cnts, stages, offs, seqs, rows, rank_of,
                      base):
        if rank_of is not None:
            cur = seqs >= base
            t_idx = np.clip(seqs - base, 0, rank_of.shape[1] - 1)
            key2 = np.where(cur, rank_of[ks, t_idx], seqs)
        else:
            cur = np.zeros(ks.shape, bool)
            key2 = seqs
        order = np.lexsort(
            (rows, np.where(cur, 0, ks), key2, cur.astype(np.int8))
        )
        return self._build_matches(
            ks[order], cnts[order], stages[order], offs[order]
        )

    def _decode_eager(self, out, rank_of) -> List[Tuple[Hashable, Sequence]]:
        """Device walk outputs -> (key, Sequence), in arrival order.

        Fast path: the batch's match rows compact on-device into a GLOBAL
        budget of ``decode_budget`` rows across all lanes
        (``ops/decode.py``), so the host pulls kilobytes-to-megabytes
        proportional to the actual match count instead of the raw
        ``[K, T, R, W]`` grid — gigabytes at production shapes, and the
        processor's former critical-path wall (SURVEY §2.2 PP row).  A
        batch with more total matches than the budget falls back to the
        full pull (counted in ``decode_fallbacks``; correctness never
        depends on the budget).
        """
        if self.decode_budget and self.mesh is not None:
            matches = self._decode_sharded(out, rank_of)
            if matches is not None:
                return matches
            self.metrics.decode_fallbacks += 1
        elif self.decode_budget:
            from kafkastreams_cep_tpu.ops.decode import compact_matches

            K, T, R = out.count.shape
            # The device wait inside decode: whatever is still queued
            # ahead of the compaction (the sweep) plus the compaction.
            with self._phase("decode_wait"):
                c_stage, c_off, c_count, c_k, c_t, c_r, c_n, _overflow = (
                    compact_matches(out, self.decode_budget)
                )
                # One scalar round-trip; overflow is host-derivable from
                # it (an extra device_get would be a second host sync).
                n = int(c_n)
            if n <= min(self.decode_budget, K * T * R):
                if n == 0:
                    return []
                # Second phase pulls only the hit rows — padded up to a
                # power of two so slice shapes (and their compiled
                # executables) are bounded at log2(budget) variants.
                m = 1
                while m < n:
                    m *= 2
                m = min(m, int(c_count.shape[0]))
                count, stage, off, k_arr, t_arr, r_arr = jax.device_get(
                    (c_count[:m], c_stage[:m], c_off[:m], c_k[:m],
                     c_t[:m], c_r[:m])
                )
                return self._emit(
                    k_arr[:n], t_arr[:n], r_arr[:n], count[:n],
                    stage[:n], off[:n], rank_of,
                )
            self.metrics.decode_fallbacks += 1
        stage = np.asarray(jax.device_get(out.stage))  # [K, T, R, W]
        off = np.asarray(jax.device_get(out.off))
        count = np.asarray(jax.device_get(out.count))  # [K, T, R]
        ks, ts, rs = np.nonzero(count)
        if ks.size == 0:
            return []
        return self._emit(
            ks, ts, rs, count[ks, ts, rs], stage[ks, ts, rs],
            off[ks, ts, rs], rank_of,
        )

    def _decode_sharded(self, out, rank_of):
        """The eager decode on a mesh: each shard compacts its own lanes'
        hit rows into its share of ``decode_budget``
        (``ShardedMatcher.compact_matches``, no exchange between chips);
        one transfer brings every shard's compacted rows to the host, and
        the hit rows, in shard order, are the global (k, t, r) order.
        ``None`` when a shard's hits overflow its share (the caller pulls
        the full grid).  Counts each call's largest and smallest shard
        match count in ``shard_matches_max`` / ``shard_matches_min``."""
        n_sh = int(self.mesh.devices.size)
        budget = max(self.decode_budget // n_sh, 1)
        with self._phase("decode_wait"):
            rows = self.batch.compact_matches(out, budget)
            hits = np.asarray(jax.device_get(rows[-1]))  # [n_sh]
        self.metrics.shard_matches_max += int(hits.max())
        self.metrics.shard_matches_min += int(hits.min())
        G = int(rows[2].shape[0]) // n_sh
        if (hits > G).any():
            return None
        if not hits.any():
            return []
        stage, off, count, k, t, r = jax.device_get(rows[:6])
        take = np.concatenate(
            [np.arange(s * G, s * G + h) for s, h in enumerate(hits)]
        )
        return self._emit(
            k[take], t[take], r[take], count[take], stage[take], off[take],
            rank_of,
        )

    def _emit(self, ks, ts, rs, cnts, stages, offs, rank_of):
        """Hit rows -> (key, Sequence) in arrival order (rank of the
        completing record), then run-queue order."""
        order = np.lexsort((rs, rank_of[ks, ts]))
        return self._build_matches(
            ks[order], cnts[order], stages[order], offs[order]
        )

    def _build_matches(self, ks, cnts, stages, offs):
        """Already-ordered hit rows -> (key, Sequence) objects.

        One batched pass: every row's first ``cnts[i]`` ``(lane, device
        offset)`` slots are gathered in walk order and deduplicated; each
        distinct slot is served from the materialized mirror first, and
        the rest are materialized from the lazy column batches (newest
        first) with one gather per column, then cached in the mirror in
        walk order.  Counted in ``decode_events_built`` (materialized
        here) and ``decode_events_reused`` (every other slot)."""
        names = self.batch.names
        with self._phase("decode_build"), _gc_paused():
            if np.size(ks) == 0:
                return []
            ks = np.asarray(ks, dtype=np.int64)
            offs = np.asarray(offs, dtype=np.int64)
            take = np.arange(offs.shape[1]) < np.asarray(cnts)[:, None]
            lane = np.broadcast_to(ks[:, None], offs.shape)[take]
            off = offs[take]
            _, first, inv = np.unique(
                lane * OFFSET_LIMIT + off, return_index=True,
                return_inverse=True,
            )
            u_lane, u_off = lane[first], off[first]
            mirror = self._events
            events = [
                mirror[l].get(o)
                for l, o in zip(u_lane.tolist(), u_off.tolist())
            ]
            miss = np.flatnonzero(
                np.fromiter((e is None for e in events), bool, len(events))
            )
            if miss.size:
                # Walk order of first use: the order the mirror caches them.
                miss = miss[np.argsort(first[miss])]
                for j, ev in zip(miss.tolist(), self._materialize_slots(
                    u_lane[miss], u_off[miss]
                )):
                    events[j] = ev
            self.metrics.decode_events_built += int(miss.size)
            self.metrics.decode_events_reused += int(off.size - miss.size)
            slot_events = [events[j] for j in inv.reshape(-1).tolist()]
            # Runs of slots with one row and one stage, each added to its
            # row's Sequence in one extend (walk order kept).
            row = np.nonzero(take)[0]
            stage = np.asarray(stages)[take]
            run_start = np.ones(row.size, dtype=bool)
            run_start[1:] = (row[1:] != row[:-1]) | (stage[1:] != stage[:-1])
            starts = np.flatnonzero(run_start)
            seqs = [Sequence() for _ in range(ks.size)]
            for r, s, a, z in zip(
                row[starts].tolist(), stage[starts].tolist(),
                starts.tolist(), starts[1:].tolist() + [row.size],
            ):
                seqs[r].extend(names[s], slot_events[a:z])
            key_of = self._key_of
            matches = [(key_of[k], seq) for k, seq in zip(ks.tolist(), seqs)]
        return matches

    def _materialize_slots(self, lanes, offs) -> List[Event]:
        """Events at distinct ``(lanes[i], offs[i])`` device slots, none in
        the mirror yet, from the lazy column batches (newest first, one
        fancy-index gather per column); each is cached in the mirror in
        the order given."""
        treedef = jax.tree_util.tree_structure(self._value_proto)
        out: List[Optional[Event]] = [None] * len(offs)
        todo = np.arange(len(offs))
        for batch in reversed(self._col_batches):
            if not todo.size:
                break
            hit = _rows_in(batch, lanes[todo], offs[todo])
            if not hit.any():
                continue
            at = todo[hit]
            for i, ev in zip(at.tolist(), self._events_at(
                batch, treedef, lanes[at], offs[at]
            )):
                out[i] = ev
            todo = todo[~hit]
        if todo.size:
            lane, off = int(lanes[todo[0]]), int(offs[todo[0]])
            raise KeyError(f"lane {lane} has no event at device offset {off}")
        for lv, ov, ev in zip(lanes.tolist(), offs.tolist(), out):
            self._events[lv][ov] = ev
        return out

    def _events_at(self, batch, treedef, lanes, offs) -> List[Event]:
        """The events of the rows at device slots ``(lanes[i], offs[i])``,
        all held by column ``batch``: one fancy-index gather and
        ``.tolist()`` per column, values unflattened with ``treedef``."""
        start, _, abs_ts, leaves = batch
        t = offs - start[lanes]
        key_of, topic = self._key_of, self.topic
        return [
            Event._of(key_of[lv], treedef.unflatten(vals), ts, topic, lv, src)
            for lv, ts, src, *vals in zip(
                lanes.tolist(), abs_ts[lanes, t].tolist(),
                (offs + self._off_base[lanes]).tolist(),
                *(leaf[lanes, t].tolist() for leaf in leaves),
            )
        ]

    def _gc_events(self) -> None:
        """Drop host events no longer reachable from device state.

        The device slab GCs entries by refcount exactly like the reference
        buffer (``KVSharedVersionedBuffer.java:147-171``); the host mirror
        only needs events still present in a lane's slab, pointed at by a
        live run, or held in a tiered matcher's stencil carry as a partial
        prefix a later batch may promote (counted in ``gc_carry_pinned``
        where nothing else holds them), so everything else is released
        here after each batch.

        One pass over whole arrays: the live ``(lane, device offset)``
        slots as sorted ``lane * OFFSET_LIMIT + offset`` keys; the mirror's
        dead entries dropped on the lanes whose mirror holds any (counted
        in ``gc_lanes_swept``); then the live rows still in lazy column
        batches and not in the mirror materialized, batch by batch, with
        one gather per column (counted in ``gc_events_materialized``)
        before the batches are dropped.  Dead rows never materialize, and
        mirror entries keep their identity.
        Timed as the ``gc`` phase wherever it runs (checkpoints call it
        too), with the liveness transfer as its ``gc_pull`` child.
        """
        with self._phase("gc"), _gc_paused():
            # Tiered processors wrap the engine state (engine/tiered.py);
            # liveness lives in the engine half, plus the stencil carry's
            # partial prefixes, which own no slab entry until promoted.
            eng = engine_view(self.state)
            carry = getattr(self.state, "carry", None)
            with self._phase("gc_pull"):
                slab_stage = np.asarray(jax.device_get(eng.slab.stage))  # [K, E]
                slab_off = np.asarray(jax.device_get(eng.slab.off))
                run_alive = np.asarray(jax.device_get(eng.alive))  # [K, R]
                run_off = np.asarray(jax.device_get(eng.event_off))
                if carry is not None:
                    c_bools, c_offs = (np.asarray(a) for a in jax.device_get(
                        (carry.bools, carry.offs)))  # [K, p-1, p], [K, p-1]
            live = np.union1d(_slot_keys(slab_stage >= 0, slab_off),
                              _slot_keys(run_alive, run_off))
            if carry is not None:
                held = np.unique(
                    _slot_keys(partial_prefix_mask(c_bools, c_offs), c_offs)
                )
                self.metrics.gc_carry_pinned += int(
                    held.size - np.isin(held, live, assume_unique=True).sum()
                )
                live = np.union1d(live, held)
            mirror = self._events
            sizes = np.fromiter(map(len, mirror), np.int64, len(mirror))
            cached = np.repeat(
                np.arange(len(mirror), dtype=np.int64) * OFFSET_LIMIT, sizes
            ) + np.fromiter(
                itertools.chain.from_iterable(mirror), np.int64, int(sizes.sum())
            )
            at = np.searchsorted(live, cached)
            kept = at < live.size
            kept[kept] = live[at[kept]] == cached[kept]
            dead_lane, dead_off = np.divmod(cached[~kept], OFFSET_LIMIT)
            for lv, ov in zip(dead_lane.tolist(), dead_off.tolist()):
                del mirror[lv][ov]
            self.metrics.gc_lanes_swept += int(np.count_nonzero(sizes))
            # Live rows still sitting in lazy column batches materialize
            # now (the batches are dropped below), from the oldest batch
            # that holds each.
            if self._col_batches:
                treedef = jax.tree_util.tree_structure(self._value_proto)
                todo = live[~np.isin(live, cached[kept], assume_unique=True)]
                lanes, offs = np.divmod(todo, OFFSET_LIMIT)
                for batch in self._col_batches:
                    if not lanes.size:
                        break
                    hit = _rows_in(batch, lanes, offs)
                    if not hit.any():
                        continue
                    l, o = lanes[hit], offs[hit]
                    for lv, ov, ev in zip(l.tolist(), o.tolist(),
                                          self._events_at(batch, treedef, l, o)):
                        mirror[lv][ov] = ev
                    self.metrics.gc_events_materialized += int(l.size)
                    lanes, offs = lanes[~hit], offs[~hit]
            self._col_batches.clear()

    def lane_shards(self) -> Optional[List[int]]:
        """The live lane→shard assignment (contiguous blocks over the
        mesh's lane axis), or ``None`` unmeshed.  Recorded in checkpoint
        headers so a snapshot states which mesh wrote it and a restore
        onto a different device count is an explicit, logged event
        (``runtime/checkpoint.py``)."""
        if self.mesh is None:
            return None
        per = self.num_lanes // int(self.mesh.devices.size)
        return [k // per for k in range(self.num_lanes)]

    def place(self, state):
        """Device placement for host-built state (mesh-aware) — used by
        checkpoint restore so snapshots re-place onto whatever mesh this
        processor runs on."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                state,
                NamedSharding(self.mesh, PartitionSpec(self.batch.axis)),
            )
        return jax.device_put(state)

    # -- diagnostics --------------------------------------------------------

    def counters(self) -> Dict[str, int]:
        """Lane-summed overflow/drop counters (all zero in healthy runs)."""
        return self.batch.counters(self.state)

    def hot_counters(self) -> Dict[str, int]:
        """Two-tier residency telemetry of the live state (lane-summed;
        all zero when ``slab_hot_entries == 0``)."""
        return self.batch.hot_counters(self.state)

    def walk_counters(self) -> Dict[str, int]:
        """Walk-cost telemetry of the live state (lane-summed hop counts
        by walker class — the reduce-width perf model's observables)."""
        return self.batch.walk_counters(self.state)

    def tier_counters(self) -> Dict[str, int]:
        """Compiler-tiering telemetry (events screened by the stencil
        prefix tier / prefix completions / NFA promotions); structural
        zeros on untiered processors."""
        from kafkastreams_cep_tpu.engine.matcher import TIER_COUNTER_NAMES

        fn = getattr(self.batch, "tier_counters", None)
        if fn is None:
            return {n: 0 for n in TIER_COUNTER_NAMES}
        return fn(self.state)

    def metrics_snapshot(self, per_lane: bool = True) -> Dict[str, Any]:
        """Runtime metrics + engine counters + attribution in one dict.

        Flat lifetime counters keep their historical keys; added on top:
        hot-tier counters (``slab_hot_hits``, ... — previously computed but
        unreachable from the snapshot), per-phase latency histograms under
        ``"phases"`` (count/sum/p50/p99 per batch phase), per-lane and
        per-pattern engine-counter breakdowns, the event-time watermark and
        lag gauges, and HBM byte gauges (``device_memory_stats``).  Pass
        ``per_lane=False`` to skip the per-lane host gather (banks do, to
        keep member snapshots light).
        """
        snap: Dict[str, Any] = self.metrics.snapshot(self.counters())
        hot = self.hot_counters()
        snap.update(hot)
        snap.update(self.walk_counters())
        tier = self.tier_counters()
        snap.update(tier)
        snap["watermark"] = self._watermark
        # Injectable clock (not inline time.time): deterministic under a
        # pinned test clock, and consistent with every latency stamp.
        snap["event_time_lag_ms"] = (
            int(self._clock() * 1000) - self._watermark
            if self._watermark is not None
            else None
        )
        if self.ledger is not None:
            # Latency-attribution ledger (utils/latency.py): segment/stall/
            # per-query histograms, exemplars, and the SLO burn gauge —
            # rendered as cep_latency_seconds{segment=} etc.
            snap["latency"] = self.ledger.snapshot()
        if self._guard is not None:
            # Guard telemetry: the three loss counters (all-zero ⇒
            # loss-free), hold depth/age gauges, and per-reason
            # dead-letter counts (rendered with reason labels by
            # utils/telemetry.render_prometheus).
            snap.update(self._guard.stats())
            snap["dead_letters"] = dict(self._guard.reason_counts)
        snap["per_pattern"] = {
            self.name: {
                **self.counters(),
                **hot,
                **tier,  # labeled cep_prefix_*/cep_tier_* series per query
                "records_in": self.metrics.records_in,
                "matches_out": self.metrics.matches_out,
            }
        }
        plan = getattr(self.batch, "plan", None)
        if plan is not None:
            # The compiler tiering decision (per-query ``tier=`` tag of
            # the profiler CLI; strings are skipped by the Prometheus
            # renderer, the counters above are the scrapeable series).
            snap["tier_plan"] = plan.describe()
        per_stage = self.batch.stage_counters(self.state)
        if per_stage:
            # Per-stage selectivity & cost attribution
            # (EngineConfig.stage_attribution) — the compiler-tiering /
            # lazy-chain-ordering signal, labeled by stage name in the
            # Prometheus rendering.
            snap["per_stage"] = per_stage
        # Brownout L1+ defers the per-lane/per-key device gathers — the
        # one part of the snapshot that costs device round-trips.
        if per_lane and not self.telemetry_defer:
            snap["per_lane"] = self.batch.per_lane_counters(self.state)
            snap["per_key"] = self.per_key_cost(
                per_lane_arrays=snap["per_lane"]
            )
        snap["hbm"] = device_memory_stats()
        # Compiled-program cache health (utils/tracecache.py): entry
        # count vs capacity plus hit/miss/eviction totals — an eviction
        # storm here is recompilation thrash, the first thing to check
        # when adaptive replans or escalations slow a stream down.
        snap["trace_cache"] = tracecache.stats()
        return snap

    def per_key_cost(
        self, top_k: int = 8, per_lane_arrays=None
    ) -> Dict[str, Any]:
        """Top-K heavy-hitter cost attribution by *key* (tentpole part 1,
        the hot-key-rebalancing signal): each lane's total device walk
        work (walk + extract + drain hops — the per-hop cost model's
        observable) mapped back through the key→lane assignment, ranked,
        with each hitter's share of the total.  Rendered as
        ``cep_key_hops{key=...,lane=...}`` gauges by
        ``utils/telemetry.render_prometheus``.  Works with attribution
        off — the per-lane hop counters always exist.
        """
        arrays = (
            per_lane_arrays
            if per_lane_arrays is not None
            else self.batch.per_lane_counters(self.state)
        )
        hops = (
            np.asarray(arrays["walk_hops"], dtype=np.int64)
            + np.asarray(arrays["extract_hops"], dtype=np.int64)
            + np.asarray(arrays["drain_hops"], dtype=np.int64)
        ).reshape(-1)
        total = int(hops.sum())
        order = np.argsort(hops, kind="stable")[::-1][: max(int(top_k), 1)]
        top = []
        for lane in order:
            lane = int(lane)
            if hops[lane] <= 0 or lane not in self._key_of:
                continue
            top.append(
                {
                    "key": str(self._key_of[lane]),
                    "lane": lane,
                    "hops": int(hops[lane]),
                    "share": (
                        round(float(hops[lane]) / total, 4) if total else 0.0
                    ),
                }
            )
        return {"total_hops": total, "top": top}
