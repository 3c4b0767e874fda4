"""Failure detection & recovery — the rebalance/changelog-restore analog.

The reference delegates fault tolerance entirely to Kafka Streams (SURVEY
§5): every store is changelog-backed, so when a task dies the partition is
reassigned and the new owner replays the changelog to rebuild run queue,
buffer, and aggregate state (``CEPProcessor.java:117-134,144-149``).  The
library's own contribution is keeping *all* engine state store-resident so
that recovery is possible at every record boundary.

The TPU analog splits the same contract in two:

* **checkpoint** = the changelog snapshot: the supervisor persists the
  processor's full state (``runtime/checkpoint.py``) every
  ``checkpoint_every`` batches — far cheaper than the reference's
  every-record run-queue serialization (``CEPProcessor.java:158-160``),
  with the gap covered by a record journal;
* **journal + replay** = the changelog tail: records processed since the
  last checkpoint are kept host-side; on failure the supervisor restores
  the checkpoint and replays the journal, which is deterministic (the
  engine is a pure function of state × records), so the recovered
  processor lands in exactly the pre-failure state.

Failure *detection* covers what a lost Kafka Streams task would surface:
any exception out of the device dispatch (device reset, OOM, lost host)
triggers recovery, and :meth:`Supervisor.health` exposes the engine's
overflow counters plus state-validity probes (NaN fold state, negative
refcounts) as a typed report — the counters exist precisely because
fixed-shape capacity overflow is this design's failure mode, with no
reference analog to inherit.

Matches replayed during recovery are suppressed (they were already
emitted), preserving exactly-once *emission* for everything the caller saw
before the failure — one better than the reference, whose at-least-once
replay duplicates and corrupts runs (``README.md:108``).

On a meshed processor (``mesh=`` kwarg) the same machinery covers **shard
failure**: a dead device (``ShardLost`` out of the dispatch, or a
``shard_probe`` report attached to any device error) triggers *evacuation*
— restore the last checkpoint and replay the journal onto the surviving
sub-mesh (``parallel.sharding.surviving_mesh``; lanes re-place through
``runtime.migrate.repartition_state``), pin the new assignment with an
immediate snapshot, and retry the batch degraded but exactly-once.
Straggler watermarks (:meth:`Supervisor.observe_shard_latency`, fed by the
deployment's per-host heartbeat) declare a lagging shard and evacuate it
at the next batch boundary; and at checkpoint boundaries the PR 6 per-key
heavy-hitter counters drive **hot-key rebalancing** — a pure lane
relabeling (``runtime.migrate.move_lanes``) that moves hot lanes off a
saturated shard with zero dropped or duplicated matches
(:class:`ShardPolicy` hysteresis keeps assignments from thrashing).
"""

from __future__ import annotations

import itertools
import os
import pickle
import tempfile
import time
from dataclasses import dataclass, field
from typing import Hashable, List, Optional, Sequence as Seq, Tuple

import numpy as np

from kafkastreams_cep_tpu.engine import sizing
from kafkastreams_cep_tpu.engine.matcher import EngineConfig
from kafkastreams_cep_tpu.engine.sizing import EscalationPolicy
from kafkastreams_cep_tpu.native.journal import Journal
from kafkastreams_cep_tpu.parallel.sharding import ShardLost, surviving_mesh
from kafkastreams_cep_tpu.runtime import checkpoint as ckpt_mod
from kafkastreams_cep_tpu.runtime import migrate as migrate_mod
from kafkastreams_cep_tpu.runtime.overload import (
    MAX_LEVEL as _OVERLOAD_MAX_LEVEL,
    OverloadController,
)
from kafkastreams_cep_tpu.runtime.processor import (
    CEPProcessor,
    InputRejected,
    Record,
)
from kafkastreams_cep_tpu.utils.events import Sequence
from kafkastreams_cep_tpu.utils.failpoints import fire as _failpoint
from kafkastreams_cep_tpu.utils.telemetry import (
    MetricsRegistry,
    maybe_span,
    positive_delta,
    timed_histogram,
)

from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("runtime.supervisor")


@dataclass
class HealthReport:
    """One health probe of a live processor."""

    healthy: bool
    warnings: List[str] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)
    counters: dict = field(default_factory=dict)


def check_health(processor: CEPProcessor) -> HealthReport:
    """Probe a processor's engine state for capacity loss and corruption.

    *Warnings* are capacity-policy events (bounded-shape drops: runs, slab
    entries, pointer lists, Dewey width, walk length) — matching may have
    silently lost branches, which the reference (unbounded heap) never
    does; *errors* are states no healthy execution can reach (NaN fold
    state, negative refcounts) and indicate corruption.
    """
    counters = processor.counters()
    warnings = [
        f"{name}={val} capacity drops" for name, val in counters.items() if val
    ]
    errors = []
    # Fold state is typed-encoded int32 (float32 states as bit patterns,
    # engine/matcher.py); only float-typed columns can hold NaN.
    # Tiered processors wrap the engine state (engine/tiered.py).
    eng = getattr(processor.state, "engine", processor.state)
    agg = np.asarray(eng.agg)
    dtypes = processor.batch.matcher.tables.state_dtypes
    flt = [i for i, d in enumerate(dtypes) if d == "float32"]
    if flt and np.isnan(
        np.ascontiguousarray(agg[..., flt]).view(np.float32)
    ).any():
        errors.append("NaN in fold-aggregate state")
    refs = np.asarray(eng.slab.refs)
    if (refs < 0).any():
        errors.append("negative slab refcount")
    return HealthReport(
        healthy=not errors, warnings=warnings, errors=errors, counters=counters
    )


@dataclass
class ShardPolicy:
    """When a meshed supervisor declares a shard sick and when it moves
    lanes — both sides deliberately hysteretic, because evacuation and
    rebalancing each cost a restore-or-move plus a pinning snapshot and
    must not thrash on noise.

    Straggler side (fed by :meth:`Supervisor.observe_shard_latency`): a
    shard whose step-latency watermark (max over the last
    ``straggler_window`` observations) exceeds ``straggler_factor`` × the
    median of the other shards' watermarks on ``straggler_streak``
    consecutive observations is declared lagging; with
    ``evacuate_stragglers`` it is evacuated at the next batch boundary,
    exactly like a dead shard (the slow host may be dying — and even if
    not, the whole mesh steps at the straggler's pace).

    Skew side (checked at checkpoint boundaries from the per-lane hop
    deltas behind ``CEPProcessor.per_key_cost``): a boundary *trips* when
    the window saw at least ``rebalance_min_hops`` total hops and the
    hottest shard carried more than ``rebalance_skew`` × the mean
    per-shard load.  After ``rebalance_streak`` consecutive tripping
    boundaries (and at least ``rebalance_cooldown`` boundaries since the
    last move), hot lanes are re-spread greedily
    (``runtime.migrate.plan_rebalance``) and moved via
    ``runtime.migrate.move_lanes`` — a pure relabeling, so the stream
    sees no dropped or duplicated matches.
    """

    straggler_factor: float = 3.0
    straggler_window: int = 8
    straggler_streak: int = 3
    evacuate_stragglers: bool = True
    rebalance_skew: float = 2.0
    rebalance_min_hops: int = 64
    rebalance_streak: int = 2
    rebalance_cooldown: int = 1


@dataclass
class AdaptPolicy:
    """When the supervisor re-derives the execution plan from *measured*
    selectivity — adaptive recompilation (ISSUE 16 tentpole part 3), the
    loop that closes profiler → compiler.

    The compiler's lazy-chain conjunct ordering and tier split
    (``compiler/tiering.py``) are derived once, from hints or from
    whatever profile existed at build time.  A stream whose selectivity
    drifts (the cheap gate stops being selective) leaves that plan
    stale — correct, but doing the expensive conjunct's work first.  At
    every checkpoint boundary the supervisor compares the *windowed*
    per-stage (and per-conjunct, when ``stage_attribution`` tallies
    them) accept fraction against the selectivity the live plan was
    derived from; sustained drift triggers
    ``runtime.migrate.replan_processor`` — re-running
    ``apply_lazy_order``/``plan_tiering`` over the measured profile and
    swapping the processor in place.  Conjunct reordering commutes and
    the state transfers verbatim, so matches, emission order, and loss
    counters are invariant to the swap point (chaos-tested in
    tests/test_chaos.py).

    Hysteresis mirrors :class:`ShardPolicy`: a boundary *trips* when any
    tracked selectivity that saw at least ``min_evals`` windowed
    evaluations moved more than ``drift_threshold`` (absolute) from its
    plan-time value; ``replan_streak`` consecutive tripping boundaries
    (with ``cooldown`` boundaries since the last swap) fire the replan.
    A swap that fails (``replan.swap`` fault site) leaves the old
    processor and plan fully intact and counts in ``replan_failures``.
    """

    drift_threshold: float = 0.25
    min_evals: int = 256
    replan_streak: int = 2
    cooldown: int = 1


class Supervisor:
    """Checkpointing, health-probing, auto-recovering processor wrapper.

    ``pattern`` must be re-compilable user code (predicates/folds live in
    code, never in checkpoints — the ``ComputationStageSerDe`` contract);
    the supervisor owns the processor it creates.

    ``process(records)`` behaves like :meth:`CEPProcessor.process`, plus:

    * every ``checkpoint_every`` batches the full state is checkpointed
      (atomic rename, so a crash mid-write keeps the previous snapshot);
    * if the underlying processor raises, the supervisor restores the
      latest checkpoint, replays the journaled records since it
      (suppressing their already-emitted matches), retries the failing
      batch once, and counts the recovery in ``recoveries``;
    * with ``journal_path`` set, every batch is also appended to a durable
      CRC-framed on-disk journal (``native/journal.py``, C++ write path) —
      then :meth:`Supervisor.resume` recovers from a full *process* crash:
      restore the snapshot, replay the journal's intact prefix, continue.
      ``journal_sync=True`` fsyncs per batch (machine-crash durable);
    * with ``auto_escalate`` set (``True`` for the default
      :class:`~kafkastreams_cep_tpu.engine.sizing.EscalationPolicy`, or a
      policy instance), a batch that trips a capacity-loss counter is
      *rolled back* (checkpoint restore + journal replay), the live state
      is migrated onto a strictly-wider config (``runtime/migrate.py`` —
      a pure embedding, so nothing already matched changes), and the
      batch re-processes at the new width — its dropped branches are
      recovered, not warned about.  Escalations are counted in
      ``escalations``; a post-escalation snapshot pins the wide config so
      later recoveries and resumes replay at the new width.
    """

    _instance_ids = itertools.count()

    def __init__(
        self,
        pattern,
        num_lanes: int,
        config: Optional[EngineConfig] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 16,
        max_retries: int = 1,
        journal_path: Optional[str] = None,
        journal_sync: bool = False,
        auto_escalate=False,
        retry_backoff_ms: float = 50.0,
        retry_backoff_cap_ms: float = 5000.0,
        processor: Optional[CEPProcessor] = None,
        shard_policy: Optional[ShardPolicy] = None,
        shard_probe=None,
        adapt_policy=None,
        overload_policy=None,
        _resuming: bool = False,
        **proc_kwargs,
    ):
        if auto_escalate is True:
            self._policy: Optional[EscalationPolicy] = EscalationPolicy()
        elif auto_escalate:
            self._policy = auto_escalate
        else:
            self._policy = None
        self._pattern = pattern
        self._proc_kwargs = dict(proc_kwargs)
        # ``processor`` injection lets resume() hand over an
        # already-restored processor instead of building one to discard.
        self.processor = processor or CEPProcessor(
            pattern, num_lanes, config, **self._proc_kwargs
        )
        # Per-instance default path: two supervisors in one process must
        # never clobber each other's snapshots.
        self.checkpoint_path = checkpoint_path or os.path.join(
            tempfile.gettempdir(),
            f"cep_supervisor_{os.getpid()}_{next(self._instance_ids)}.ckpt",
        )
        self.checkpoint_every = int(checkpoint_every)
        self.max_retries = int(max_retries)
        # Exponential retry backoff with deterministic jitter: a device
        # fault that survives the instant retry is usually environmental
        # (reset storm, flapping link), and hammering it back-to-back turns
        # one fault into a fault train.  Jitter derives from (seq,
        # attempt) so a given retry always waits the same time —
        # reproducible chaos runs.  Tests patch ``self._sleep``.
        self.retry_backoff_ms = float(retry_backoff_ms)
        self.retry_backoff_cap_ms = float(retry_backoff_cap_ms)
        self.retry_backoff_ms_total = 0.0
        self._sleep = time.sleep
        self._journal: List[List[Record]] = []  # batches since last ckpt
        self._disk_journal = (
            Journal(journal_path, sync=journal_sync) if journal_path else None
        )
        if not _resuming:
            # A fresh supervisor starting over a previous incarnation's
            # files: that history would otherwise leak into a later
            # resume() — the old checkpoint (with its higher seq) would be
            # restored and the new run's journal frames skipped.  Starting
            # fresh declares the old history abandoned — remove both
            # loudly.  (To continue it, use Supervisor.resume.)
            if (
                self._disk_journal is not None
                and os.path.exists(journal_path)
                and os.path.getsize(journal_path) > 0
            ):
                logger.warning(
                    "journal %s holds frames from a previous run; truncating "
                    "(use Supervisor.resume to continue that history)",
                    journal_path,
                )
                self._disk_journal.truncate()
            if self._disk_journal is not None and os.path.exists(
                journal_path + ".prev"
            ):
                os.remove(journal_path + ".prev")
            if os.path.exists(self.checkpoint_path):
                logger.warning(
                    "checkpoint %s belongs to a previous run; removing "
                    "(use Supervisor.resume to continue that history)",
                    self.checkpoint_path,
                )
                os.remove(self.checkpoint_path)
            if os.path.exists(self.checkpoint_path + ".prev"):
                os.remove(self.checkpoint_path + ".prev")
        self._has_checkpoint = False
        self._batches_since_ckpt = 0
        # Monotone batch sequence number: stamped into journal frames and
        # the checkpoint header so resume() can tell which frames a
        # snapshot already contains (a crash between snapshot and journal
        # truncation must not double-replay them).
        self._seq = 0
        self.recoveries = 0
        self.checkpoints = 0
        self.checkpoint_failures = 0
        self.journal_failures = 0
        self.escalations = 0
        self.ingest_escalations = 0
        # Ingest-loss escalation baseline (guard counters are cumulative,
        # like the engine capacity counters).
        self._ingest_base: Optional[dict] = None
        # Escalation bookkeeping: capacity counters are cumulative, so
        # trips are detected on the per-batch DELTA against this snapshot
        # (refreshed after every batch / recovery / migration).
        self._counter_base: Optional[dict] = None
        self._trip_streak = 0
        # Matches flushed out of a pipelined processor by a checkpoint but
        # not yet returned to the caller (drained at the end of process();
        # survives a checkpoint-save failure so nothing is ever lost).
        self._unclaimed: List[Tuple[Hashable, Sequence]] = []
        # Mesh fault tolerance (module docstring): on by default whenever
        # the processor is meshed — a dead shard with no policy would be a
        # hard crash, which is strictly worse than degraded continuation.
        # Pass ``shard_policy=False`` to opt out explicitly.
        if shard_policy is False:
            self._shard_policy: Optional[ShardPolicy] = None
        elif shard_policy is not None:
            self._shard_policy = shard_policy
        else:
            self._shard_policy = (
                ShardPolicy() if self._mesh() is not None else None
            )
        # Optional deployment hook: zero-arg callable returning the shard
        # indices an external health source (host heartbeat, PCIe error
        # telemetry) currently believes dead.  Consulted when a dispatch
        # fails with a *generic* device error — ShardLost needs no probe.
        self._shard_probe = shard_probe
        self.evacuations = 0
        self.rebalances = 0
        self.rebalance_failures = 0
        self.lanes_moved = 0
        self.stragglers = 0
        # Straggler bookkeeping: recent step latencies per shard index,
        # consecutive over-watermark counts, and shards declared lagging
        # (evacuated at the next batch boundary).  All cleared on
        # evacuation — shard indices are renumbered by the shrink.
        self._shard_lat: dict = {}
        self._lag_streak: dict = {}
        self._lagging: set = set()
        # SLO burn rising-edge latch (see _slo_tick): one flight dump per
        # excursion over burn 1.0, not one per batch while burning.
        self._slo_burning = False
        # Rebalance hysteresis: per-lane hop baseline for the windowed
        # delta, consecutive tripping boundaries, boundaries since the
        # last move.
        self._hops_base: Optional[np.ndarray] = None
        self._rebalance_streak = 0
        self._boundaries_since_move = 10**9  # no cooldown before 1st move
        # Adaptive recompilation (AdaptPolicy): ``True`` takes the
        # defaults, a policy instance tunes the hysteresis, None/False
        # disables.  Only a tiered processor with ``stage_attribution``
        # produces the measured signal — the check is a boundary-time
        # no-op otherwise, so enabling it on any processor is harmless.
        if adapt_policy is True:
            self._adapt_policy: Optional[AdaptPolicy] = AdaptPolicy()
        elif adapt_policy:
            self._adapt_policy = adapt_policy
        else:
            self._adapt_policy = None
        self.replans = 0
        self.replan_failures = 0
        # Selectivity the LIVE plan was derived from ({key: fraction};
        # None until the first boundary with >= min_evals measured), and
        # the cumulative (evals, accepts) snapshot at the previous
        # boundary for the windowed delta.  Both reset on any rollback
        # rebuild (_restore_tail) — restored processors carry the
        # default plan and reverted counters.
        self._plan_sel: Optional[dict] = None
        self._sel_prev: Optional[dict] = None
        self._replan_streak = 0
        self._boundaries_since_replan = 10**9  # no cooldown before 1st
        # After a failed append the on-disk journal is no longer a complete
        # history — appending later batches would leave a seq gap that a
        # resume would replay straight through into a wrong state.  Suspend
        # journaling until the next checkpoint re-establishes a clean base.
        self._journal_suspended = False
        # Telemetry: the supervisor shares the processor's trace sink (pass
        # ``trace_sink=`` like any processor kwarg) and owns the lifecycle
        # latency histograms — checkpoint/recover/escalate cost as
        # p50/p99, not just the bare integers above.
        self.trace = self._proc_kwargs.get("trace_sink")
        self.telemetry = MetricsRegistry()
        for _n in ("checkpoint", "recover", "escalate", "evacuate",
                   "rebalance", "replan"):
            self.telemetry.histogram(f"phase.{_n}")
        # Flight recorder (runtime/flight.py): pass ``flight=`` like any
        # processor kwarg; the supervisor owns the dump triggers — crash
        # (retries exhausted), recovery, escalation — and re-attaches the
        # recorder across restore/migrate (restored processors carry no
        # telemetry wiring, same rule as the trace sink).
        self.flight = self._proc_kwargs.get("flight")
        if self.flight is not None:
            self.processor.flight = self.flight
        # Brownout ladder (runtime/overload.py): ``True`` takes the
        # default OverloadPolicy, a policy instance tunes
        # thresholds/actuators, None/False disables.  The controller is
        # supervisor-owned durable state: its level rides the checkpoint
        # header (``extra["overload"]``) and every transition is pinned
        # with an immediate snapshot, so recovery/resume/migration land
        # in the same level and replay under the same actuators.
        if overload_policy is True:
            self._overload: Optional[OverloadController] = (
                OverloadController()
            )
        elif overload_policy:
            self._overload = OverloadController(overload_policy)
        else:
            self._overload = None
        # Optional caller-owned admission front door (runtime/tenant.py
        # TenantAdmission, or a bare AdmissionLimiter) the L2 actuator
        # squeezes — see attach_admission().
        self._admission = None
        if self._overload is not None:
            self._overload.base_drain = self.processor.drain_interval
            self._overload_wire()

    @classmethod
    def resume(
        cls,
        pattern,
        num_lanes: int,
        config: Optional[EngineConfig] = None,
        checkpoint_path: Optional[str] = None,
        journal_path: Optional[str] = None,
        **kwargs,
    ) -> "Supervisor":
        """Rebuild a supervisor after a process crash.

        Restores ``checkpoint_path`` if the file exists (else starts
        fresh), then replays the on-disk journal chain's intact prefix —
        deterministic, so the processor lands exactly where the crashed
        process left off; replayed matches are suppressed (the old process
        already emitted them).  Journal frames carry the batch sequence
        number, and frames at or below the checkpoint's sequence are
        skipped — so a crash *between* snapshotting and journal rotation
        cannot double-replay the snapshotted batches.

        A snapshot that fails its integrity check (``checkpoint.py``
        sha256 — bit rot, torn write) does not crash the resume: the
        previous-good ``.prev`` snapshot is restored instead (or a fresh
        processor when the corrupt one was the first), and the journal
        chain (``.prev`` frames + live frames, one generation retained
        per snapshot) replays the full gap.
        """
        proc = None
        base_seq = 0
        overload_state = None
        candidates = []
        if checkpoint_path:
            candidates = [
                p for p in (checkpoint_path, checkpoint_path + ".prev")
                if os.path.exists(p)
            ]
        for path in candidates:
            try:
                ckpt = ckpt_mod.load_checkpoint(path)
                proc = ckpt_mod.restore_processor(
                    pattern, path, ckpt=ckpt, mesh=kwargs.get("mesh"),
                )
                extra = ckpt["header"].get("extra", {})
                base_seq = int(extra.get("seq", 0))
                overload_state = extra.get("overload")
                break
            except ckpt_mod.CheckpointCorrupt:
                logger.exception(
                    "checkpoint %s is corrupt; falling back (journal-chain "
                    "replay covers the gap)", path,
                )
        sup = cls(
            pattern, num_lanes, config,
            checkpoint_path=checkpoint_path,
            journal_path=journal_path,
            processor=proc,
            _resuming=True,
            **kwargs,
        )
        sup._has_checkpoint = proc is not None
        sup._seq = base_seq
        # An injected (restored) processor carries no telemetry wiring.
        sup.processor.trace = sup.trace
        sup.processor.flight = sup.flight
        # The clock is wiring too (checkpoints carry no callables): a
        # pinned clock must keep ticking the restored guard and ledger —
        # without this the SLO tracker's burn-rate window (restored from
        # the checkpoint header) would observe wall-clock stamps against
        # pinned-clock history and the controller's input would be junk.
        clock = sup._proc_kwargs.get("clock")
        if clock is not None:
            sup.processor.set_clock(clock)
        # Load the pinned brownout level BEFORE the journal replay: every
        # journaled batch was processed at the pinned level (transitions
        # checkpoint immediately, truncating the journal), so replay must
        # run under the same actuators to shed the same records.
        if sup._overload is not None and overload_state:
            sup._overload.load_state(overload_state)
        sup._overload_wire()
        replayed = skipped = 0
        if sup._disk_journal is not None:
            # The chain: the retired ``.prev`` generation first (frames at
            # or below the LIVE snapshot's seq — needed only when that
            # snapshot was corrupt and the fallback rewound base_seq),
            # then the live journal.
            gap = False
            for jr in (
                Journal(journal_path + ".prev"), sup._disk_journal,
            ):
                for payload in jr.replay():
                    seq, batch = pickle.loads(payload)
                    if seq <= base_seq:
                        skipped += 1  # already inside the snapshot
                        continue
                    if seq != sup._seq + 1:
                        # Defense in depth: a seq gap means the journal is
                        # not a complete history (it should be impossible —
                        # a failed append suspends journaling).  Replaying
                        # past the gap would build a state that never saw
                        # the missing batches; stop at the last contiguous
                        # frame.
                        logger.error(
                            "journal seq gap (%d -> %d); stopping replay at "
                            "the last contiguous frame", sup._seq, seq,
                        )
                        gap = True
                        break
                    sup.processor.process(batch)  # matches already emitted
                    sup._overload_replay_tick()
                    sup._journal.append(batch)
                    sup._batches_since_ckpt += 1
                    sup._seq = seq
                    replayed += len(batch)
                if gap:
                    break
        # Pipelined replay leaves the last batch undecoded: drain it
        # (suppressed — the crashed process already emitted it) so it
        # cannot leak out of the first post-resume process() call.
        sup.processor.flush()
        if sup._policy is not None:
            sup._counter_base = sup._capacity_counters()
            sup._ingest_base = sup._ingest_loss_counters()
        logger.info(
            "resumed from %s + %s: %d journaled records replayed "
            "(%d pre-snapshot frames skipped)",
            checkpoint_path, journal_path, replayed, skipped,
        )
        return sup

    # -- checkpointing ------------------------------------------------------

    def checkpoint(self) -> List[Tuple[Hashable, Sequence]]:
        """Snapshot now (atomic) and truncate the journals.

        A pipelined processor is flushed first — a snapshot cannot carry
        an undecoded device batch (checkpoint.py refuses it), and before
        this flush every periodic snapshot of a ``pipeline=True``
        processor silently failed into ``checkpoint_failures``.  The
        flushed matches are returned (empty for serial processors); if
        the snapshot itself fails they are retained and the next
        :meth:`process` call returns them instead — flushing is
        observable emission and must never be dropped with the snapshot.
        """
        with maybe_span(self.trace, "checkpoint", seq=self._seq), \
                timed_histogram(self.telemetry, "phase.checkpoint"):
            if self.processor.pipeline:
                self._unclaimed.extend(self.processor.flush())
            tmp = self.checkpoint_path + ".tmp"
            extra = {"seq": self._seq}
            if self._overload is not None:
                extra["overload"] = self._overload.to_state()
            ckpt_mod.save_checkpoint(self.processor, tmp, extra=extra)
            # Fault site: the crash window between writing the tmp snapshot
            # and atomically installing it (utils/failpoints.py).
            _failpoint("checkpoint.rename")
            # One-generation retention: the outgoing snapshot survives as
            # ``.prev`` and the outgoing journal as ``.prev`` frames, so
            # a snapshot that later fails its integrity check (bit rot —
            # checkpoint.py sha256) can fall back to the previous-good
            # snapshot with the journal CHAIN covering the full gap.
            if os.path.exists(self.checkpoint_path):
                os.replace(
                    self.checkpoint_path, self.checkpoint_path + ".prev"
                )
            os.replace(tmp, self.checkpoint_path)
            self._has_checkpoint = True
            self._journal.clear()
            if self._disk_journal is not None:
                self._rotate_journal()
                self._journal_suspended = False  # clean base re-established
            self._batches_since_ckpt = 0
            self.checkpoints += 1
        return self._drain_unclaimed()

    def _rotate_journal(self) -> None:
        """Retire the journal's frames into ``.prev`` (all covered by the
        snapshot just installed; kept one generation for the corrupt-
        snapshot fallback) and start the live journal empty."""
        jr = self._disk_journal.path
        if os.path.exists(jr):
            os.replace(jr, jr + ".prev")
        else:
            # Nothing to retire, but a stale .prev from two checkpoints
            # ago must not linger past its snapshot.
            try:
                os.remove(jr + ".prev")
            except FileNotFoundError:
                pass

    def _drain_unclaimed(self) -> List[Tuple[Hashable, Sequence]]:
        out, self._unclaimed = self._unclaimed, []
        return out

    def drain_ingest(self) -> List[Tuple[Hashable, Sequence]]:
        """End-of-stream drain of the ingestion guard's reorder buffer,
        made durable: the drain dispatch is not journaled (it has no
        input batch a replay could reproduce), so the post-drain state is
        pinned with an immediate snapshot — a crash after this call
        resumes with the buffer empty and the drained matches already
        emitted, never double-emitted.  Terminal by convention: call when
        the stream is declared over."""
        matches = self.processor.drain_ingest()
        matches += self.processor.flush()
        try:
            matches = matches + self.checkpoint()
        except Exception:
            self.checkpoint_failures += 1
            logger.exception(
                "post-drain checkpoint failed; a resume will re-drain "
                "(the drained matches were already emitted — re-submit "
                "nothing, the journal still covers the pre-drain state)"
            )
        return matches

    # -- the supervised hot path -------------------------------------------

    def process(
        self, records: Seq[Record]
    ) -> List[Tuple[Hashable, Sequence]]:
        records = list(records)
        # Correlation id: the journal seq this batch WILL get on success.
        # Recovery/escalation spans fired while handling it carry the same
        # id, so a trace walks from a fault to the batch that provoked it.
        corr = f"batch-{self._seq + 1}"
        with maybe_span(
            self.trace, "supervisor.batch", corr=corr, seq=self._seq + 1,
            records=len(records),
        ) as sp:
            matches = self._process_supervised(records, corr)
            sp["matches"] = len(matches)
            return matches

    def _process_supervised(
        self, records: List[Record], corr: str
    ) -> List[Tuple[Hashable, Sequence]]:
        # Shards declared lagging by observe_shard_latency() are evacuated
        # at the batch boundary — before the dispatch, where the restore
        # and replay are cheapest and nothing is in flight.
        if (
            self._lagging
            and self._shard_policy is not None
            and self._shard_policy.evacuate_stragglers
        ):
            mesh = self._mesh()
            if mesh is not None and int(mesh.devices.size) > 1:
                lagging = sorted(self._lagging)
                logger.warning(
                    "evacuating lagging shard(s) %s at the batch boundary",
                    lagging,
                )
                self._evacuate(lagging, corr)
        for attempt in range(self.max_retries + 1):
            try:
                # Captured per attempt (a recovery resets the pipeline):
                # whether the batch before this one is still undecoded —
                # escalation must then recompute it too, since its matches
                # ride the lossy attempt's (discarded) return value.
                had_pending = (
                    getattr(self.processor, "_pending", None) is not None
                )
                matches = self.processor.process(records)
                break
            except InputRejected:
                # Deterministic input rejection (schema, lane overflow,
                # timestamp range): the batch is bad, not the device —
                # restore-and-replay cannot help and state was untouched
                # (processor validation is atomic).  Only the typed
                # exception short-circuits: JAX surfaces some real device
                # faults as bare ValueError, and those must recover.
                raise
            except ShardLost as e:
                # A typed shard loss out of the meshed dispatch: the
                # device is gone, so restore-and-replay onto the SAME mesh
                # (plain recovery) would re-dispatch straight into the
                # dead device.  Evacuate instead: shrink to the surviving
                # sub-mesh and retry there.  Unmeshed or single-device,
                # there is nothing to evacuate onto — crash.
                mesh = self._mesh()
                if (
                    mesh is None
                    or int(mesh.devices.size) < 2
                    or attempt >= self.max_retries
                ):
                    if self.flight is not None:
                        self.flight.dump("crash", corr=corr)
                    raise
                logger.exception(
                    "shard %d lost on a %d-record batch; evacuating onto "
                    "the surviving sub-mesh", e.shard, len(records),
                )
                self._evacuate([e.shard], corr)
                self._backoff(attempt)
            except Exception:
                if attempt >= self.max_retries:
                    # Crash: retries exhausted, the exception propagates
                    # to the caller — ship the last-N-batches context
                    # first so the post-mortem has it.
                    if self.flight is not None:
                        self.flight.dump("crash", corr=corr)
                    raise
                # A generic device error does not say WHICH device (JAX
                # surfaces resets as bare RuntimeError); ask the optional
                # external probe before falling back to same-mesh
                # recovery.
                dead = self._probe_dead_shards()
                if dead:
                    logger.exception(
                        "processor failed and the shard probe reports "
                        "shard(s) %s dead; evacuating", sorted(dead),
                    )
                    self._evacuate(dead, corr)
                else:
                    logger.exception(
                        "processor failed on a %d-record batch; recovering",
                        len(records),
                    )
                    self._recover(corr)
                self._backoff(attempt)
        if self._policy is not None:
            matches = self._maybe_escalate(records, matches, had_pending, corr)
        self._journal.append(records)
        self._seq += 1
        if self._disk_journal is not None:
            # Journal after success, before returning matches.  A process
            # crash in the tiny window before this append loses the batch
            # from recovery (the caller should re-submit unacknowledged
            # batches; replay dedup absorbs them); a crash after it replays
            # the batch with emissions suppressed.  Either way state and
            # the match stream stay consistent — the reference's Kafka
            # commit boundary has the same at-least-once window
            # (README.md:108), without the dedup.
            #
            # An append *failure* (disk full) must not raise here: state
            # already advanced, and a caller retry would double-apply the
            # batch.  Count it and SUSPEND journaling until the next
            # checkpoint — later frames after a missing seq would otherwise
            # replay into a state that never saw this batch.  The in-memory
            # journal still covers device-failure recovery; process-crash
            # durability is degraded until the next snapshot.
            if not self._journal_suspended:
                try:
                    self._disk_journal.append(
                        pickle.dumps((self._seq, records))
                    )
                except Exception:
                    self.journal_failures += 1
                    self._journal_suspended = True
                    logger.exception(
                        "journal append failed; journaling suspended until "
                        "the next checkpoint (batch %d+ not crash-durable)",
                        self._seq,
                    )
        self._batches_since_ckpt += 1
        # Overload/SLO observation BEFORE the cadence snapshot below: a
        # batch's tick must be pinned together with the batch itself, or
        # a crash landing right after the snapshot restores streaks that
        # are one observation behind the crash-free run — and since the
        # batch is inside the checkpoint it is never re-submitted, so the
        # lost tick can never be replayed (the ladder would then exit a
        # brownout level one batch late and shed records an uncrashed
        # run admits).  A transition taken here pins its own snapshot,
        # which also resets the cadence counter.
        self._slo_tick(corr)
        self._overload_tick(corr)
        # A suspended journal means acknowledged batches are NOT in the
        # crash history — don't wait out the cadence, close the window by
        # snapshotting immediately (a successful snapshot contains the
        # un-journaled batch and re-arms journaling).
        force_ckpt = self._journal_suspended
        if force_ckpt or self._batches_since_ckpt >= self.checkpoint_every:
            # Hot-key rebalance check BEFORE the snapshot: a move landing
            # here is immediately pinned by the checkpoint below, so every
            # recovery and resume replays under the new lane assignment.
            if self._shard_policy is not None:
                self._maybe_rebalance()
            # Adaptive replan check, same placement for the same reason:
            # a plan swap landing here is pinned by the snapshot below.
            if self._adapt_policy is not None:
                self._maybe_replan(corr)
            # A failed snapshot (disk full, ...) must not lose the batch's
            # matches: the journal still covers everything since the last
            # good snapshot, so log, count, and retry next batch.
            try:
                matches = matches + self.checkpoint()
            except Exception:
                self.checkpoint_failures += 1
                logger.exception("checkpoint failed; journal retained")
        if self._policy is not None:
            self._maybe_escalate_ingest()
        if self._unclaimed:
            # A failed snapshot above still flushed the pipeline; those
            # matches belong to the caller either way.
            matches = matches + self._drain_unclaimed()
        return matches

    def _backoff(self, attempt: int) -> None:
        """Sleep before re-dispatching a faulted batch: exponential in the
        attempt, capped, with deterministic jitter — ``(seq, attempt)``
        seeds the jitter so a replayed chaos schedule waits identically.
        ``retry_backoff_ms=0`` disables (the historical immediate retry).
        """
        if self.retry_backoff_ms <= 0:
            return
        delay_ms = min(
            self.retry_backoff_cap_ms,
            self.retry_backoff_ms * (2.0 ** attempt),
        )
        rng = np.random.default_rng((self._seq + 1, attempt))
        delay_ms *= 0.5 + 0.5 * float(rng.random())  # jitter in [0.5, 1.0)
        self.retry_backoff_ms_total += delay_ms
        logger.info(
            "retry backoff: %.1f ms before attempt %d", delay_ms, attempt + 2
        )
        self._sleep(delay_ms / 1000.0)

    def _restore_tail(self) -> int:
        """Restore the last checkpoint and replay the journal tail.

        Replay is deterministic, so the processor lands in exactly the
        state it had after the last successful batch; replayed matches are
        dropped (already emitted).  With no checkpoint yet, the journal is
        the full history and replay starts from a fresh processor.
        Shared by failure recovery and escalation rollback.
        """
        if self._has_checkpoint:
            try:
                self.processor = ckpt_mod.restore_processor(
                    self._pattern, self.checkpoint_path,
                    mesh=self._proc_kwargs.get("mesh"),
                )
            except ckpt_mod.CheckpointCorrupt:
                # Same fallback order as resume(): the previous-good
                # snapshot; the in-memory journal of a supervisor that
                # restored from .prev covers everything since it.
                logger.exception(
                    "checkpoint %s is corrupt during recovery; restoring "
                    "the previous-good snapshot", self.checkpoint_path,
                )
                self.processor = ckpt_mod.restore_processor(
                    self._pattern, self.checkpoint_path + ".prev",
                    mesh=self._proc_kwargs.get("mesh"),
                )
            # Checkpoints carry no telemetry wiring: reattach the trace
            # sink so post-recovery batches keep emitting spans.  The
            # clock is wiring too (checkpoints carry no callables) — a
            # pinned test clock must keep ticking the restored ledger.
            self.processor.trace = self.trace
            self.processor.flight = self.flight
            clock = self._proc_kwargs.get("clock")
            if clock is not None:
                self.processor.set_clock(clock)
        else:
            num_lanes = self.processor.num_lanes
            config = self.processor.batch.matcher.config
            self.processor = CEPProcessor(
                self._pattern, num_lanes, config, **self._proc_kwargs
            )
        # Re-wire the brownout actuators BEFORE the replay: every
        # journaled batch ran at the pinned level (transitions snapshot
        # immediately), so replay must shed under the same actuators.
        self._overload_wire()
        replayed = 0
        for batch in self._journal:
            self.processor.process(batch)  # matches already emitted
            replayed += len(batch)
        # Pipelined replay leaves the last batch undecoded; drain it here
        # (suppressed — already emitted) or it would leak into the next
        # real process() call as a duplicate emission.
        self.processor.flush()
        # Every rollback rebuild (recovery, evacuation, escalation) lands
        # on the checkpoint-restored processor, which carries the DEFAULT
        # execution plan and reverted attribution counters — the adaptive
        # replanner's plan baseline and window snapshot are both stale.
        self._plan_sel = None
        self._sel_prev = None
        self._replan_streak = 0
        return replayed

    def _observe_stall(
        self, cause: str, seconds: float, corr: Optional[str]
    ) -> None:
        """Attribute one lifecycle stall (recover/evacuate/replan wall
        time) to the latency ledger, tagged with the ``corr`` id of the
        batch the rollback was handling — a stall exemplar then resolves
        to the same trace span as the recovery span itself.  The live
        (post-rebuild) processor's ledger takes the observation: the
        pre-failure ledger rolled back with the state it described."""
        ledger = getattr(self.processor, "ledger", None)
        if ledger is not None:
            ledger.observe_stall(cause, seconds, corr=corr)

    def _slo_tick(self, corr: str) -> None:
        """Rising-edge SLO-burn annotation: when the ledger's burn rate
        first crosses 1.0 (burning faster than the error budget), note the
        rate in the flight ring and dump it — the post-mortem then carries
        the batches that spent the budget.  Re-arms when burn falls back
        under 1.0."""
        ledger = getattr(self.processor, "ledger", None)
        if ledger is None or ledger.slo is None:
            return
        burn = ledger.slo.burn_rate()
        if burn > 1.0 and not self._slo_burning:
            self._slo_burning = True
            logger.warning(
                "SLO burn rate %.3f exceeds budget (corr=%s)", burn, corr
            )
            if self.flight is not None:
                self.flight.note(slo_burn=round(burn, 3))
                self.flight.dump("slo_burn", corr=corr)
        elif burn <= 1.0 and self._slo_burning:
            self._slo_burning = False

    # -- overload control (runtime/overload.py) ------------------------------

    def attach_admission(self, admission) -> None:
        """Register the caller-owned tenant admission front door
        (runtime/tenant.py ``TenantAdmission``, or a bare
        ``AdmissionLimiter``) so the L2 actuator can squeeze its token
        buckets proportionally to measured tenant cost.  Idempotent —
        re-applies the current pinned pressure immediately, so callers
        re-attach after their own restore."""
        self._admission = admission
        self._overload_wire()

    def _overload_limiter(self):
        adm = self._admission
        if adm is None:
            return None
        return getattr(adm, "limiter", adm)

    def _overload_wire(self) -> None:
        """Re-apply the pinned level's actuators — after any processor
        rebuild or swap (restore, resume, migration, rebalance, replan)
        the new processor carries default actuators and must be re-wired
        before it processes (or replays) anything."""
        if self._overload is not None:
            self._overload_apply()

    def _overload_apply(self) -> None:
        ctl = self._overload
        proc = self.processor
        base = max(int(ctl.base_drain), 1)
        proc.drain_interval = max(1, base * ctl.drain_widen())
        proc.telemetry_defer = ctl.telemetry_defer()
        proc.overload_admit_fraction = ctl.admit_fraction()
        lim = self._overload_limiter()
        if lim is not None:
            scale, shares = ctl.admission_pressure
            lim.set_pressure(scale, shares)

    def _overload_signals(self) -> dict:
        """The pressure inputs, all host-side (no per-batch device
        reads): SLO burn rate, reorder hold depth/age, ingest-queue
        segment p99, and the deferred-drain backlog (the host proxy for
        handle-ring occupancy).  Missing subsystems contribute nothing —
        a processor without a guard or ledger reads pressure 0."""
        sig: dict = {}
        proc = self.processor
        guard = getattr(proc, "_guard", None)
        if guard is not None:
            depth = guard.policy.reorder_depth
            if depth:
                sig["hold_frac"] = guard.held / depth
            grace = guard.policy.grace_ms
            if grace > 0:
                sig["hold_age_frac"] = guard.hold_age_ms() / grace
        ledger = getattr(proc, "ledger", None)
        if ledger is not None:
            if ledger.slo is not None:
                sig["burn_rate"] = ledger.slo.burn_rate()
            hist = ledger._hists.get("queue")
            if hist is not None:
                sig["queue_p99_s"] = hist.percentile(0.99)
            sig["ring_depth"] = len(ledger._deferred)
        return sig

    def _overload_shares(self) -> dict:
        """Per-tenant cost shares from the heavy-hitter attribution
        (per_key_cost top list), mapped through the admission policy's
        key→tenant function — the L2 squeeze is proportional to measured
        cost, not record count.  One device gather, paid only on an L2+
        transition (never per batch)."""
        adm = self._admission
        if adm is None:
            return {}
        policy = getattr(adm, "policy", None)
        key_tenant = getattr(policy, "key_tenant", None) or str
        try:
            top = self.processor.per_key_cost().get("top") or []
        except Exception:
            logger.exception(
                "per-key cost attribution failed; squeezing all tenants "
                "uniformly"
            )
            return {}
        shares: dict = {}
        for row in top:
            tenant = str(key_tenant(row["key"]))
            shares[tenant] = shares.get(tenant, 0.0) + float(row["share"])
        return shares

    def _overload_replay_tick(self) -> None:
        """Advance the controller's observation streaks for one REPLAYED
        batch without taking transitions.  The crashed process ticked
        once per journaled batch after the last pin; a cold resume
        restores the PINNED streaks, so replay must re-run those
        observations or the resumed ladder would trail the crash-free
        trajectory by the journal window (holding a brownout level — and
        shedding — for extra batches an uncrashed run would not).  A
        transition cannot legitimately arise here: a committed
        transition pins a snapshot that truncates the journal, so every
        replayed batch was a no-transition tick in the original run.  A
        proposal (possible only from nondeterministic wall-clock
        signals) is deferred, not dropped — streaks are retained at
        threshold, so the first live batch re-proposes and commits it
        under the full transition protocol."""
        ctl = self._overload
        if ctl is None:
            return
        guard = getattr(self.processor, "_guard", None)
        if guard is not None:
            ctl.shed_total = guard.overload_shed
        ctl.tick(self._overload_signals())

    def _overload_tick(self, corr: str) -> None:
        """One controller observation per batch (after _slo_tick, before
        the unclaimed drain).  A proposal runs the transition protocol;
        no proposal costs a few host float compares."""
        ctl = self._overload
        if ctl is None:
            return
        guard = getattr(self.processor, "_guard", None)
        if guard is not None:
            ctl.shed_total = guard.overload_shed
        proposal = ctl.tick(self._overload_signals())
        if proposal is not None:
            self._overload_transition(proposal[0], proposal[1], corr)

    def _overload_transition(
        self, from_level: int, to_level: int, corr: str
    ) -> None:
        """The supervisor-owned transition protocol: failpoint →
        tentative level → actuators → pin checkpoint → commit.  ANY
        failure (armed failpoint, pin-snapshot failure) reverts level
        and actuators — the previous level stays authoritative, keeping
        the invariant that the in-memory level always equals the
        last-pinned level (so recovery replay never spans a
        transition)."""
        ctl = self._overload
        entering = to_level > from_level
        site = "overload.enter" if entering else "overload.exit"
        try:
            with maybe_span(
                self.trace, "overload.transition", corr=corr,
                from_level=from_level, to_level=to_level,
                pressure=round(ctl.last_pressure, 4),
            ):
                # Fault site: before actuators apply or the level pins —
                # a crash here must leave the previous level live.
                _failpoint(site)
                ctl.begin(to_level)
                scale = ctl.admission_scale(to_level)
                ctl.admission_pressure = (
                    float(scale),
                    dict(self._overload_shares()) if scale < 1.0 else {},
                )
                self._overload_apply()
                if entering and to_level >= _OVERLOAD_MAX_LEVEL:
                    # Emergency entry: flush pinned drains so the pin
                    # snapshot carries them.  Flushed matches are
                    # observable emission — they ride _unclaimed out.
                    self._unclaimed.extend(self.processor.flush())
                # Pin: the transition exists only once snapshotted — a
                # replayed crash must land in the same level.
                self._unclaimed.extend(self.checkpoint())
        except Exception:
            ctl.abort()
            self._overload_apply()
            logger.exception(
                "overload transition L%d -> L%d failed; L%d stays "
                "authoritative", from_level, to_level, from_level,
            )
            return
        ctl.commit()
        if self.flight is not None:
            self.flight.note(
                overload_level=to_level,
                overload_pressure=round(ctl.last_pressure, 4),
            )
            if entering and to_level >= 3:
                # L3+ entry is the incident boundary: ship the last-N
                # batches of context while the ring still holds the
                # flood that forced the shed.
                self.flight.dump("overload", corr=corr)

    def _recover(self, corr: Optional[str] = None) -> None:
        # ``corr`` correlates the recovery span with the batch span whose
        # failure provoked it (None when driven outside process(), e.g.
        # a manual probe); the restore-and-replay cost lands in the
        # ``recover`` latency histogram either way.
        if self.flight is not None:
            # Dump BEFORE the rollback: the ring still holds the faulted
            # batch's context (the restore rebuilds the processor, and
            # replayed batches would overwrite the interesting tail).
            self.flight.dump("recover", corr=corr)
        t0 = time.perf_counter()
        with maybe_span(
            self.trace, "recover", corr=corr, seq=self._seq,
        ) as sp, timed_histogram(self.telemetry, "phase.recover"):
            replayed = self._restore_tail()
            sp["replayed_records"] = replayed
            sp["from_checkpoint"] = self._has_checkpoint
        self._observe_stall("recover", time.perf_counter() - t0, corr)
        self.recoveries += 1
        # Counters reverted with the state; re-snapshot the escalation
        # baseline BEFORE the retry re-runs the failing batch, or its
        # delta would be measured against the pre-failure accumulation.
        if self._policy is not None:
            self._counter_base = self._capacity_counters()
            self._ingest_base = self._ingest_loss_counters()
        logger.info(
            "recovered: checkpoint=%s, %d journaled records replayed",
            self._has_checkpoint, replayed,
        )
        # The rebalance baseline indexes lanes in the *live* processor's
        # order; a rollback may precede the last move, so re-measure.
        self._hops_base = None

    # -- mesh fault tolerance ------------------------------------------------

    def _mesh(self):
        """The mesh the NEXT (re)built processor will land on — the
        ``mesh`` proc kwarg, which evacuation rewrites; falls back to the
        live processor's mesh for an injected (resumed) processor."""
        mesh = self._proc_kwargs.get("mesh")
        if mesh is None:
            mesh = getattr(self.processor, "mesh", None)
        return mesh

    def _probe_dead_shards(self) -> set:
        if self._shard_probe is None or self._shard_policy is None:
            return set()
        mesh = self._mesh()
        if mesh is None or int(mesh.devices.size) < 2:
            return set()
        try:
            return {int(s) for s in (self._shard_probe() or ())}
        except Exception:
            logger.exception("shard probe failed; treating as no report")
            return set()

    def _evacuate(self, dead, corr: Optional[str] = None) -> None:
        """Move the lost shard(s)' lanes onto the surviving sub-mesh.

        Same rollback spine as :meth:`_recover` — restore the last
        checkpoint and replay the journal tail, deterministic and
        emission-suppressed — but the rebuilt processor is placed on
        ``surviving_mesh(mesh, dead)`` (``_proc_kwargs["mesh"]`` is
        rewritten first, so ``_restore_tail`` and every later rebuild
        land there; ``checkpoint.restore_processor`` routes the lane
        re-placement through ``migrate.repartition_state``).  The shrunk
        assignment is pinned with an immediate snapshot: a recovery or
        resume between here and the next periodic snapshot must not
        re-place lanes on the dead device.  Processing continues
        *degraded* — fewer devices, same lanes, exactly-once emission.
        """
        mesh = self._mesh()
        dead = sorted({int(d) for d in dead})
        new_mesh = surviving_mesh(mesh, dead, self.processor.num_lanes)
        if self.flight is not None:
            self.flight.note(
                evacuation=self.evacuations + 1, dead_shards=dead
            )
            self.flight.dump("evacuate", corr=corr)
        t0 = time.perf_counter()
        with maybe_span(
            self.trace, "evacuate", corr=corr, seq=self._seq,
            dead_shards=dead, survivors=int(new_mesh.devices.size),
        ) as sp, timed_histogram(self.telemetry, "phase.evacuate"):
            self._proc_kwargs["mesh"] = new_mesh
            replayed = self._restore_tail()
            sp["replayed_records"] = replayed
            sp["from_checkpoint"] = self._has_checkpoint
            try:
                self._unclaimed.extend(self.checkpoint())
            except Exception:
                self.checkpoint_failures += 1
                logger.exception(
                    "post-evacuation checkpoint failed; a resume before "
                    "the next good snapshot re-places lanes itself "
                    "(restore_processor repartitions on mesh-size change)"
                )
        self._observe_stall("evacuate", time.perf_counter() - t0, corr)
        self.evacuations += 1
        # Shard indices are renumbered by the shrink: every piece of
        # straggler and skew bookkeeping keyed by the old numbering is
        # meaningless now.
        self._shard_lat.clear()
        self._lag_streak.clear()
        self._lagging.clear()
        self._hops_base = None
        if self._policy is not None:
            self._counter_base = self._capacity_counters()
            self._ingest_base = self._ingest_loss_counters()
        logger.warning(
            "shard(s) %s evacuated: %d lanes now on %d device(s), "
            "%d journaled records replayed (degraded but exactly-once)",
            dead, self.processor.num_lanes, int(new_mesh.devices.size),
            replayed,
        )

    def observe_shard_latency(self, shard: int, seconds: float) -> bool:
        """Feed one shard's step-latency watermark (per-host heartbeat in
        a real deployment; the bench and chaos harness call it directly).

        A shard whose watermark — max over the last
        ``ShardPolicy.straggler_window`` observations — exceeds
        ``straggler_factor`` × the median of the other shards' watermarks
        on ``straggler_streak`` consecutive observations is declared
        lagging.  Returns True when ``shard`` is currently declared; with
        ``evacuate_stragglers`` the declaration triggers evacuation at
        the next batch boundary.
        """
        policy = self._shard_policy
        if policy is None:
            return False
        shard = int(shard)
        lat = self._shard_lat.setdefault(shard, [])
        lat.append(float(seconds))
        del lat[: -int(policy.straggler_window)]
        others = [
            max(v) for s, v in self._shard_lat.items() if s != shard and v
        ]
        if not others:
            return shard in self._lagging
        med = float(np.median(others))
        if med > 0.0 and max(lat) > policy.straggler_factor * med:
            self._lag_streak[shard] = self._lag_streak.get(shard, 0) + 1
        else:
            self._lag_streak[shard] = 0
        if (
            self._lag_streak[shard] >= policy.straggler_streak
            and shard not in self._lagging
        ):
            self._lagging.add(shard)
            self.stragglers += 1
            if self.trace is not None:
                self.trace.event(
                    "straggler", shard=shard, watermark_s=max(lat),
                    peer_median_s=med,
                )
            logger.warning(
                "shard %d declared lagging (watermark %.4fs vs peer "
                "median %.4fs); evacuation at the next batch boundary",
                shard, max(lat), med,
            )
        return shard in self._lagging

    def _maybe_rebalance(self) -> None:
        """Move hot lanes off a saturated shard at a checkpoint boundary.

        The signal is the windowed per-lane hop DELTA (walk + extract +
        drain — the counters behind ``CEPProcessor.per_key_cost``) since
        the last boundary: cumulative totals would forever punish a key
        that was hot an hour ago.  Trip + streak + cooldown hysteresis
        per :class:`ShardPolicy`; the move itself is
        ``migrate.move_lanes`` with the greedy ``plan_rebalance``
        permutation — a pure relabeling, pinned by the checkpoint that
        immediately follows in ``_process_supervised``.  A move that
        fails (``rebalance.move`` fault site) leaves the old processor
        and assignment fully intact.
        """
        policy = self._shard_policy
        mesh = self._mesh()
        if policy is None or mesh is None:
            return
        n = int(mesh.devices.size)
        k = self.processor.num_lanes
        if n < 2 or k % n != 0:
            return
        self._boundaries_since_move += 1
        arrays = {
            name: np.asarray(vals, dtype=np.int64).reshape(-1)
            for name, vals in self.processor.batch.per_lane_counters(
                self.processor.state
            ).items()
            if name in ("walk_hops", "extract_hops", "drain_hops")
        }
        if not arrays:
            return
        hops = sum(arrays.values())
        base = self._hops_base
        if base is None or base.shape != hops.shape:
            self._hops_base = hops
            self._rebalance_streak = 0
            return
        window = hops - base
        self._hops_base = hops
        total = int(window.sum())
        shard_loads = window.reshape(n, k // n).sum(axis=1)
        mean = total / n
        tripped = (
            total >= policy.rebalance_min_hops
            and float(shard_loads.max()) > policy.rebalance_skew * mean
        )
        if not tripped:
            self._rebalance_streak = 0
            return
        self._rebalance_streak += 1
        if (
            self._rebalance_streak < policy.rebalance_streak
            or self._boundaries_since_move <= policy.rebalance_cooldown
        ):
            return
        perm = migrate_mod.plan_rebalance(window, n)
        if perm is None:
            self._rebalance_streak = 0
            return
        # The PR 6 heavy-hitter attribution over the same window names
        # the keys being moved — operator-facing (span + log), the
        # decision above is already made from the identical arrays.
        hot = self.processor.per_key_cost(
            top_k=4,
            per_lane_arrays={
                "walk_hops": window,
                "extract_hops": np.zeros_like(window),
                "drain_hops": np.zeros_like(window),
            },
        )
        moved = int(np.sum(perm != np.arange(k)))
        with maybe_span(
            self.trace, "rebalance", seq=self._seq, lanes_moved=moved,
            hot_keys=[h["key"] for h in hot["top"]],
            shard_loads=[int(x) for x in shard_loads],
        ), timed_histogram(self.telemetry, "phase.rebalance"):
            if self.processor.pipeline:
                # An undecoded device batch cannot be permuted host-side;
                # flushing is observable emission, kept for the caller.
                self._unclaimed.extend(self.processor.flush())
            try:
                self.processor = migrate_mod.move_lanes(
                    self._pattern, self.processor, perm, mesh=mesh
                )
            except Exception:
                self.rebalance_failures += 1
                # move_lanes mutates nothing before it succeeds — the old
                # processor and lane assignment are intact; skip this
                # boundary and re-measure (the baseline still indexes the
                # unmoved lane order).
                logger.exception(
                    "lane rebalance failed; keeping the current assignment"
                )
                return
            self.processor.trace = self.trace
            self.processor.flight = self.flight
            self._overload_wire()
            self.rebalances += 1
            self.lanes_moved += moved
            # The baseline must follow its lanes to the new positions.
            self._hops_base = hops[perm]
            self._rebalance_streak = 0
            self._boundaries_since_move = 0
        logger.warning(
            "hot-key rebalance #%d: moved %d lanes (window loads per "
            "shard %s; hottest keys %s)",
            self.rebalances, moved,
            [int(x) for x in shard_loads],
            [h["key"] for h in hot["top"]],
        )

    # -- adaptive recompilation ---------------------------------------------

    @staticmethod
    def _sel_counts(per_stage: dict) -> dict:
        """Flatten a ``stage_counters`` snapshot into cumulative
        ``{key: (evals, accepts)}`` rows — one ``(stage,)`` row per stage
        and one ``(stage, conjunct_key)`` row per measured conjunct (the
        exact selectivities ``apply_lazy_order`` would rank by)."""
        counts: dict = {}
        for name, row in per_stage.items():
            if not isinstance(row, dict):
                continue
            counts[(name,)] = (
                int(row.get("stage_evals", 0) or 0),
                int(row.get("stage_accepts", 0) or 0),
            )
            cj = row.get("conjuncts")
            if isinstance(cj, dict):
                for key, crow in cj.items():
                    if isinstance(crow, dict):
                        counts[(name, key)] = (
                            int(crow.get("evals", 0) or 0),
                            int(crow.get("accepts", 0) or 0),
                        )
        return counts

    def _maybe_replan(self, corr: Optional[str] = None) -> None:
        """Swap the processor onto a re-derived execution plan when the
        measured selectivity has drifted from the plan's assumptions.

        Runs at checkpoint boundaries only (see :class:`AdaptPolicy` for
        the signal and hysteresis).  The swap is
        ``migrate.replan_processor`` — config unchanged, state verbatim,
        matches/emission order/loss counters invariant — and is pinned by
        the checkpoint that immediately follows in
        ``_process_supervised``, so recoveries and resumes replay under a
        *consistent* plan either side of the boundary.  A failed swap
        (``replan.swap`` fault site) keeps the old processor and plan.
        """
        policy = self._adapt_policy
        if policy is None:
            return
        config = self.processor.batch.matcher.config
        if not getattr(config, "tiering", False):
            return  # replan_processor requires the tiered matcher
        per_stage = self.processor.batch.stage_counters(
            self.processor.state
        )
        if not per_stage:
            return  # stage_attribution off: no measured signal
        counts = self._sel_counts(per_stage)
        prev, self._sel_prev = self._sel_prev, counts
        self._boundaries_since_replan += 1
        if self._plan_sel is None:
            # First boundary with measured data: pin the plan baseline
            # (keys below min_evals stay unpinned until they have seen
            # enough evaluations to mean anything).
            self._plan_sel = {
                key: ac / ev
                for key, (ev, ac) in counts.items()
                if ev >= policy.min_evals
            }
            return
        # Late-warming keys join the baseline as they cross min_evals.
        for key, (ev, ac) in counts.items():
            if key not in self._plan_sel and ev >= policy.min_evals:
                self._plan_sel[key] = ac / ev
        if prev is None:
            return  # no window yet (first boundary after a rollback)
        drifted = []
        for key, (ev, ac) in counts.items():
            pev, pac = prev.get(key, (0, 0))
            wev, wac = ev - pev, ac - pac
            base = self._plan_sel.get(key)
            # wev < 0: the cumulative tally restarted under this key (a
            # prior replan resets the conjunct accumulator) — skip until
            # the window is meaningful again.
            if base is None or wev < policy.min_evals:
                continue
            wsel = wac / wev
            if abs(wsel - base) > policy.drift_threshold:
                drifted.append((key, round(base, 4), round(wsel, 4)))
        if not drifted:
            self._replan_streak = 0
            return
        self._replan_streak += 1
        if (
            self._replan_streak < policy.replan_streak
            or self._boundaries_since_replan <= policy.cooldown
        ):
            return
        t0 = time.perf_counter()
        with maybe_span(
            self.trace, "replan", corr=corr, seq=self._seq,
            drifted=[
                {"key": "/".join(k), "plan": b, "window": w}
                for k, b, w in drifted
            ],
        ), timed_histogram(self.telemetry, "phase.replan"):
            if self.processor.pipeline:
                # An undecoded device batch belongs to the OLD plan's
                # dispatch; flushing is observable emission, kept for
                # the caller (same rule as rebalance/checkpoint).
                self._unclaimed.extend(self.processor.flush())
            try:
                self.processor = migrate_mod.replan_processor(
                    self._pattern, self.processor, per_stage
                )
            except Exception:
                self.replan_failures += 1
                # replan_processor mutates nothing before it succeeds —
                # the old processor, plan, and state are fully intact;
                # skip this boundary and re-measure.
                logger.exception(
                    "adaptive replan failed; keeping the current plan"
                )
                self._replan_streak = 0
                return
            self.processor.trace = self.trace
            self.processor.flight = self.flight
            self._overload_wire()
            self.replans += 1
            self._replan_streak = 0
            self._boundaries_since_replan = 0
            # The new plan was derived from exactly this profile: its
            # baseline is the cumulative selectivity at the swap.  The
            # window snapshot resets — the rebuilt matcher restarts the
            # per-conjunct accumulator from zero.
            self._plan_sel = {
                key: ac / ev
                for key, (ev, ac) in counts.items()
                if ev >= policy.min_evals
            }
            self._sel_prev = None
        self._observe_stall("replan", time.perf_counter() - t0, corr)
        logger.warning(
            "adaptive replan #%d: selectivity drift %s (plan -> window); "
            "plan re-derived from the measured profile",
            self.replans,
            [(("/".join(k)), b, w) for k, b, w in drifted],
        )

    # -- elastic capacity escalation ----------------------------------------

    def _capacity_counters(self) -> dict:
        return sizing.capacity_counters(self.processor.counters())

    def _ingest_loss_counters(self) -> dict:
        guard = getattr(self.processor, "_guard", None)
        if guard is None:
            return {}
        return sizing.ingest_capacity_counters(guard.loss_counters())

    def _maybe_escalate(
        self, records, matches, had_pending: bool = False,
        corr: Optional[str] = None,
    ) -> List[Tuple[Hashable, Sequence]]:
        """Detect capacity loss in the batch just processed and recover it.

        Loss counters are cumulative, so a trip is a positive DELTA over
        the post-previous-batch snapshot.  On a trip (after ``hysteresis``
        consecutive tripping batches): roll the processor back to the
        pre-batch state (the drop already cost this batch branches, and
        those branches exist only in the pre-batch world), migrate the
        live state onto the next wider config, snapshot it (so later
        recoveries and resumes replay at the new width), and re-process
        the batch — returning the re-run's matches, which supersede the
        lossy attempt's (never emitted).  Repeats up to
        ``policy.max_rounds`` if the re-run still trips; degrades to the
        historical warn-and-count behavior at the policy ceiling.
        """
        policy = self._policy
        counters = self._capacity_counters()
        base = self._counter_base
        if base is None:
            # First observation (fresh/restored processor): no delta yet.
            base = {k: 0 for k in counters} if self._seq == 0 else counters
        tripped = positive_delta(counters, base)
        if not tripped:
            self._counter_base = counters
            self._trip_streak = 0
            return matches
        self._trip_streak += 1
        if self._trip_streak < policy.hysteresis:
            logger.warning(
                "capacity trip %s tolerated (%d/%d before escalation); "
                "this batch's lost branches are NOT recovered",
                tripped, self._trip_streak, policy.hysteresis,
            )
            self._counter_base = counters
            return matches
        # Serial mode: ``matches`` is the lossy attempt's output, fully
        # superseded by the re-run.  Pipeline mode: the attempt's return
        # can mix the PREVIOUS batch's clean matches with this batch's
        # lossy ones (the gc cadence drains both), so splitting it is not
        # reliable — instead, when the previous batch was still in flight
        # (``had_pending``), it is popped from the journal tail and
        # recomputed from the rollback point alongside the tripping batch;
        # both re-runs are flushed so everything returns synchronously.
        pipeline = self.processor.pipeline
        kept: List[Tuple[Hashable, Sequence]] = []
        rerun = [] if pipeline else matches
        # (had_pending implies the previous batch is the journal tail: a
        # checkpoint or escalation would have flushed the pipeline, and
        # both clear the pending marker — the bool() is belt-and-braces.)
        redo_prev = pipeline and had_pending and bool(self._journal)
        rolled = False
        for _round in range(policy.max_rounds):
            cfg = self.processor.batch.matcher.config
            new_cfg = sizing.escalate(cfg, tripped, policy)
            if new_cfg is None:
                logger.warning(
                    "escalation exhausted at the policy ceiling (counters "
                    "%s); degrading to warn-and-count", tripped,
                )
                self._counter_base = counters
                return (kept + rerun) if rolled else matches
            new_dims = {
                k: getattr(new_cfg, k)
                for k in ("max_runs", "slab_entries", "slab_preds",
                          "dewey_depth", "max_walk")
            }
            with maybe_span(
                self.trace, "escalate", corr=corr, round=_round,
                tripped=dict(tripped), new_config=new_dims,
            ) as esp, timed_histogram(self.telemetry, "phase.escalate"):
                if self.flight is not None:
                    # Context of the batches that led to the trip, before
                    # the rollback discards them.
                    self.flight.note(escalation=self.escalations + 1,
                                     tripped=dict(tripped))
                    self.flight.dump("escalate", corr=corr)
                if redo_prev:
                    prev_batch = self._journal.pop()
                # Roll back to the pre-batch state; a pending pipelined
                # decode belongs to the lossy attempt and dies with the
                # old processor.
                self._restore_tail()
                self.processor = migrate_mod.migrate_processor(
                    self._pattern, self.processor, new_cfg,
                    mesh=self._proc_kwargs.get("mesh"),
                )
                self.processor.trace = self.trace
                self.processor.flight = self.flight
                self._overload_wire()
                self.escalations += 1
                logger.warning(
                    "capacity escalation #%d: %s after counters %s; "
                    "re-processing the %d-record batch at the new width",
                    self.escalations, new_dims, tripped, len(records),
                )
                if redo_prev:
                    # The in-flight previous batch: its matches rode the
                    # discarded lossy return, so emit them from this re-run
                    # (a wider config never drops where the narrow one
                    # didn't, so this re-run is clean by construction).
                    kept = list(self.processor.process(prev_batch))
                    kept += self.processor.flush()
                    self._journal.append(prev_batch)
                    redo_prev = False
                # Pin the wide config on disk before re-processing: a
                # recovery or resume between here and the next periodic
                # snapshot must replay at the new width, not the old one.
                try:
                    self.checkpoint()
                except Exception:
                    self.checkpoint_failures += 1
                    logger.exception(
                        "post-escalation checkpoint failed; a recovery "
                        "before the next good snapshot replays at the "
                        "OLD width"
                    )
                pre = self._capacity_counters()
                rerun = self.processor.process(records)
                if pipeline:
                    rerun = rerun + self.processor.flush()
                rolled = True
                counters = self._capacity_counters()
                tripped = positive_delta(counters, pre)
                esp["still_tripped"] = bool(tripped)
            if not tripped:
                break
        else:
            logger.warning(
                "batch still trips %s after %d escalation rounds; "
                "keeping the widest result", tripped, policy.max_rounds,
            )
        self._counter_base = counters
        self._trip_streak = 0
        return kept + rerun

    def _maybe_escalate_ingest(self) -> None:
        """Grow the ingestion-guard policy when a batch tripped an
        ingest loss counter (``sizing.escalate_ingest`` rows: late drops
        grow the grace, evictions grow the buffer depth).

        Forward-only, unlike engine escalation: the dropped records are
        already dead-lettered (recoverable by the caller from the DLQ),
        and re-processing them would require re-ordering history the
        engine has moved past — widening stops the loss for the rest of
        the stream.  The widened policy is pinned with an immediate
        snapshot so recoveries and resumes replay under it.
        """
        guard = getattr(self.processor, "_guard", None)
        if guard is None:
            return
        counters = self._ingest_loss_counters()
        base = self._ingest_base
        if base is None:
            base = {k: 0 for k in counters}
        tripped = positive_delta(counters, base)
        self._ingest_base = counters
        if not tripped:
            return
        new_policy = sizing.escalate_ingest(
            guard.policy, tripped, growth=self._policy.growth
        )
        if new_policy is None:
            logger.warning(
                "ingest loss %s but the guard policy cannot grow; records "
                "remain in the dead-letter queue", tripped,
            )
            return
        old = guard.policy
        guard.policy = new_policy
        self.ingest_escalations += 1
        logger.warning(
            "ingest escalation #%d: grace_ms %d -> %d, reorder_depth "
            "%d -> %d after loss %s (already-dropped records stay in the "
            "dead-letter queue)",
            self.ingest_escalations, old.grace_ms, new_policy.grace_ms,
            old.reorder_depth, new_policy.reorder_depth, tripped,
        )
        try:
            # checkpoint() returns any pipeline-flush matches; they belong
            # to the caller via the _unclaimed drain in process().
            self._unclaimed.extend(self.checkpoint())
        except Exception:
            self.checkpoint_failures += 1
            logger.exception(
                "post-ingest-escalation checkpoint failed; a recovery "
                "before the next good snapshot replays under the OLD "
                "ingest policy"
            )

    # -- diagnostics --------------------------------------------------------

    def health(self) -> HealthReport:
        return check_health(self.processor)

    def metrics_snapshot(self, per_lane: bool = True) -> dict:
        """The processor snapshot (per-phase latency histograms, per-lane
        and per-pattern counter breakdowns, hot-tier counters, watermark
        and HBM gauges) + supervisor lifecycle telemetry: the bare event
        counts AND their latency histograms (``phases`` gains
        ``checkpoint`` / ``recover`` / ``escalate`` with p50/p99) — when
        they fired and what they cost, not just how many."""
        out = self.processor.metrics_snapshot(per_lane=per_lane)
        out["recoveries"] = self.recoveries
        out["checkpoints"] = self.checkpoints
        out["checkpoint_failures"] = self.checkpoint_failures
        out["journal_failures"] = self.journal_failures
        out["escalations"] = self.escalations
        out["ingest_escalations"] = self.ingest_escalations
        out["evacuations"] = self.evacuations
        out["rebalances"] = self.rebalances
        out["rebalance_failures"] = self.rebalance_failures
        out["replans"] = self.replans
        out["replan_failures"] = self.replan_failures
        out["lanes_moved"] = self.lanes_moved
        out["stragglers"] = self.stragglers
        if self.flight is not None:
            out["flight_dumps"] = self.flight.dumps
        if self._overload is not None:
            # cep_overload_level / _pressure / _transitions /
            # _transition_failures gauges (README metrics reference).
            out.update(self._overload.metrics())
        out["retry_backoff_ms_total"] = round(self.retry_backoff_ms_total, 3)
        phases = dict(out.get("phases") or {})
        phases.update(
            {
                name[len("phase."):]: inst.snapshot()
                for name, inst in self.telemetry.items()
                if name.startswith("phase.")
            }
        )
        out["phases"] = phases
        return out
