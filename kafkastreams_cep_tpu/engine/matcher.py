"""The TPU array NFA engine — batched, jittable SASE+ matching.

This is the device counterpart of the host oracle (``nfa/oracle.py``) and the
reason this project exists: the per-event evaluator of the reference
(``nfa/NFA.java:94-289``) re-expressed as fixed-shape masked array programs so
it jits, vmaps over keys, and shards over a TPU mesh.

Representation
--------------
The run queue (``NFA.java:75``, a ``LinkedBlockingQueue``) becomes ``R`` fixed
run slots.  Every queued run in the reference is either the *seed* run (the
non-epsilon BEGIN stage re-added every event, ``NFA.java:148-157``) or an
epsilon wrapper ``eps(identity, target)`` (``Stage.java:42-46``), so a run slot
stores:

* ``id_pos``    — canonical identity position of the wrapper (``-1`` = seed,
  i.e. ``previous == null`` in ``NFA.evaluate``);
* ``eval_pos``  — the wrapper's PROCEED target, where edge evaluation happens;
* ``ver/vlen``  — fixed-width Dewey version (``ops/dewey_ops.py``);
* ``event_off`` — pointer-event offset (``ComputationStage.getEvent``);
* ``start_ts``  — window start; ``branching`` — the branch flag
  (``ComputationStage.java:91-97``);
* ``agg``       — per-run fold state.  Fold state can live *per slot* because
  at any time each live run has a distinct sequence id: branch runs and
  re-seeds always take fresh ids, and one run yields at most one same-id
  successor per event (a frame either recurses on PROCEED or emits its one
  local successor).

Per-event step (semantics matched to ``NFA.java:162-250``)
----------------------------------------------------------
1. all predicates are evaluated for every run against its pre-event fold
   state — exact because within one event all predicate evaluations happen
   before all folds (folds run on recursion unwind, ``NFA.java:248``), and
   runs never share fold state;
2. each run walks its PROCEED chain, statically unrolled to the pattern's
   ``max_hops``: masked BEGIN/TAKE/PROCEED/IGNORE dispatch, the 4-pair
   branching rule (``NFA.java:280-289``), stage-digit appends on non-branching
   stage crossings (``NFA.java:185-188``), producing at most one survivor,
   one branch run per frame, and the seed re-add;
3. folds apply innermost-frame-first (the unwind order), with branch-time
   fold-state copies capturing exactly the reference's
   copy-before-current-frame's-fold semantics (``NFA.java:243,248``);
4. shared-buffer mutations (``ops/slab.py``) run sequentially in the
   reference's op order: per run in queue order — consuming puts in frame
   order, then branch walks deepest-first, then dead-run removal — and match
   extraction for final states after all runs (``NFA.java:102-123``);
5. survivors/branches/re-seeds are compacted into the next queue in exactly
   the order the reference appends them; overflow beyond ``R`` is counted,
   never silent.

Windows: the reference's epsilon wrappers never carry ``windowMs``
(``Stage.newEpsilonState``, ``Stage.java:41-46``), and every non-seed run is
an epsilon wrapper, so ``isOutOfWindow`` (``ComputationStage.java:98-100``)
can never fire — ``within()`` does not prune in the reference.  The engine
reproduces that faithfully by default; ``EngineConfig.enforce_windows=True``
opts into functional pruning using the evaluation stage's window.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kafkastreams_cep_tpu.compiler.tables import (
    OP_BEGIN,
    OP_TAKE,
    TYPE_BEGIN,
    TransitionTables,
    lower,
    stackable,
)
from kafkastreams_cep_tpu.ops import dewey_ops
from kafkastreams_cep_tpu.ops import slab as slab_mod
from kafkastreams_cep_tpu.ops.onehot import get_at, get_at2, put_at
from kafkastreams_cep_tpu.pattern.pattern import Pattern
from kafkastreams_cep_tpu.utils.events import Event, Sequence

from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("engine")


class ArrayStates:
    """Read-only fold-state view handed to predicates on device.

    Mirrors ``pattern/States.java:46-68``; values are traced scalars.  Unlike
    the host view, state is always "present" (initialized to the declared
    ``init``), so ``get_or_else`` only falls back for unknown names.
    """

    __slots__ = ("_values",)

    def __init__(self, values: Dict[str, Any]):
        self._values = values

    def get(self, name: str):
        return self._values[name]

    def get_or_else(self, name: str, default):
        if name in self._values:
            return self._values[name]
        return default

    def __getitem__(self, name: str):
        return self.get(name)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shape/feature knobs for one compiled matcher."""

    max_runs: int = 16  # R — run-queue slots (overflow counted in run_drops)
    slab_entries: int = 64  # E — shared-buffer slots per key
    # E_hot — hot-tier slots of the two-tier slab layout (0 = legacy single
    # tier).  Slots [0, E_hot) hold the most recent entries (new entries
    # always allocate hot; the least-recent hot entry demotes to the
    # overflow tier when the hot tier fills), and the walk passes resolve
    # each hop against the hot rows first, touching the overflow rows only
    # on a miss — in the Pallas kernels the common hop pays an E_hot-sized
    # reduce instead of an E-sized one (PERF.md, walk-pass cost model).
    # Capacity semantics are unchanged: every drop counter is bit-identical
    # to the single-tier engine, and matches/slab contents agree modulo which
    # slot (tier) an entry occupies.  Must be a multiple of 8 (TPU sublane tile)
    # strictly below slab_entries.  Residency telemetry rides the
    # slab_hot_hits / slab_hot_misses / slab_overflow_walks /
    # slab_demotions counters (HOT_COUNTER_NAMES).
    slab_hot_entries: int = 0
    slab_preds: int = 8  # MP — predecessor pointers per buffer entry
    dewey_depth: int = 12  # D — fixed Dewey width (overflow counted)
    max_walk: int = 16  # W — buffer walk bound = max match length
    # Width of the compacted walker pool the jnp walk pass runs over.
    # Typically only ~1-2 of the step's 3R+ candidate walkers are enabled;
    # the pass drains enabled walkers in queue-order batches of this width.
    # 1 (default) = exactly the reference's sequential per-walker order.
    # Wider batches run walkers of a batch in lockstep — near-sequential and
    # faster when many walkers fire, but when two removal walkers meet at
    # one entry in the same hop, prune/delete attribution can deviate from
    # sequential (a refs==0 entry may survive with a stale pointer).  That
    # trigger is counted per occurrence in the ``walk_collisions`` counter:
    # a run whose walk_collisions stays 0 matched sequential order exactly;
    # nonzero means the match set may have diverged.  The fused Pallas
    # kernel path is always sequential-exact (and collision-free) regardless.
    walker_budget: int = 1
    # Delete provably-dead zero positions from all versions in a lane at
    # sweep time (ops/renorm.py) — keeps the fixed dewey_depth sufficient
    # on unbounded streams whose straddling runs append a digit per event
    # (NFA.java:185-188).  Semantics-preserving by construction; the switch
    # exists for differential testing.  Only effective when sweeps actually
    # run: BatchMatcher/ShardedMatcher ``sweep()`` between scans, which
    # ``CEPProcessor`` schedules every ``gc_interval`` batches (on by
    # default there); bare ``MatcherSession`` never sweeps.
    renorm_versions: bool = True
    enforce_windows: bool = False  # deviation: functional within() pruning
    # Apply slab ops one run at a time (the reference's literal op order)
    # instead of the batched per-step passes.  The batched path reproduces
    # the same per-entry op order (see ops/slab.py) and is ~2 orders of
    # magnitude faster on TPU; this switch exists for differential testing.
    sequential_slab: bool = False
    # Lazy match extraction (PROFILE_r06 "next leverage" item 1): when True, a
    # run reaching the final stage no longer dispatches its W-hop extraction
    # walk inside the per-step walk pass — the dominant walker class and the
    # main source of two-tier hot misses on match-dense traces (PERF.md,
    # walk-pass cost model).  Instead the step emits a fixed-width *handle*
    # (root stage, root offset, Dewey version, completion step + run row +
    # timestamp) into a per-lane handle ring and *pins* the referenced chain
    # (refcount +1 at the root, so no removal walk can delete it before drain;
    # the maintenance sweep additionally roots pending handles).
    # Materialization moves to the batched drain pass (``TPUMatcher.drain`` /
    # ``BatchMatcher.drain``) that unpins and walks all pending handles
    # together, off the per-step critical path.  The drained match set is
    # identical to the eager engine's
    # (tests/test_lazy_extraction.py); eager mode remains the differential
    # oracle.
    lazy_extraction: bool = False
    # HB — per-lane handle-ring slots (multiple of 8, TPU sublane tile).
    # Must hold every match completed between drains; a full ring drops the
    # match and counts ``handle_overflows`` (a loss counter: all-zero means
    # loss-free, like every other capacity knob — sizing.suggest derives it
    # from the probe's per-chunk match maxima).
    handle_ring: int = 16
    # Continuous profiling (PROFILE/ISSUE 6): per-stage selectivity and
    # cost attribution.  When True the engine carries per-stage tallies —
    # frames evaluated / accepted (TAKE|BEGIN fired) / ignored / rejected
    # per stage (``EngineState.stage_counts``, the lazy-chain stage-
    # ordering signal of arxiv 1612.05110) plus per-stage walk-hop costs
    # (``SlabState.stage_hops``, keyed by the walker's current stage) —
    # threaded identically through the jnp path and both Pallas kernels,
    # so the three paths agree bit-exactly.  Off (the default) every
    # attribution array has zero size and every tally is skipped at trace
    # time: zero new device work.  Not a capacity knob; migration must
    # not flip it (runtime/migrate.py _SEMANTIC_FLAGS).
    stage_attribution: bool = False
    # Compiler tiering (ROADMAP "route pattern prefixes onto the stencil
    # path"): when True the runtime builds a TieredBatchMatcher
    # (parallel/tiered.py) that runs each query's maximal strict-
    # contiguity prefix on the branch-free stencil tier over the whole
    # [K, T] batch and promotes runs into this NFA+slab engine only at
    # events where the prefix completes (compiler/tiering.py).  Matches,
    # emission order, and loss counters are bit-identical to the untiered
    # engine on loss-free workloads (tests/test_tiering.py); patterns
    # with no usable prefix fall back to whole-NFA execution unchanged.
    # Semantic for state *shape* (the tiered state carries the stencil
    # carry), so migration must not flip it (runtime/migrate.py).
    tiering: bool = False
    # Hybrid-tier gating granularity (events per device-gated segment of
    # the chunked hybrid scan, parallel/tiered.py): the [K, T] batch is
    # segmented at promotion boundaries and each segment's NFA work runs
    # under a device-side ``lax.cond`` — a segment with no live suffix
    # run and no prefix completion is skipped on device (step_seq += C in
    # one op), so the scan issues zero host syncs.  Pure performance
    # knob: any value yields bit-identical results (the skip is exact),
    # so migration/replanning may change it freely (NOT in
    # _SEMANTIC_FLAGS).  Smaller chunks skip more NFA work on screened
    # traffic; larger chunks amortize the per-segment gate.
    gate_chunk: int = 32


class EventBatch(NamedTuple):
    """One event (or a [T]-stacked batch) for a single key lane.

    ``value`` is an arbitrary pytree of numeric scalars — the same object the
    predicates receive.  ``valid`` masks padding steps.
    """

    key: jnp.ndarray
    value: Any
    ts: jnp.ndarray
    off: jnp.ndarray
    valid: jnp.ndarray


class EngineState(NamedTuple):
    """Full per-key engine state (run queue + slab + counters)."""

    alive: jnp.ndarray  # [R] bool
    id_pos: jnp.ndarray  # [R] int32 — -1 = seed run
    eval_pos: jnp.ndarray  # [R] int32
    ver: jnp.ndarray  # [R, D] int32
    vlen: jnp.ndarray  # [R] int32
    event_off: jnp.ndarray  # [R] int32 — -1 = none
    start_ts: jnp.ndarray  # [R] int32
    branching: jnp.ndarray  # [R] bool
    agg: jnp.ndarray  # [R, NS] int32 — typed-encoded fold state (float32
    #   states stored as their bit pattern; see _build_step)
    slab: slab_mod.SlabState
    run_drops: jnp.ndarray  # scalar int32 — queue-overflow drops
    ver_overflows: jnp.ndarray  # scalar int32 — Dewey add_stage overflows
    # --- lazy-extraction handle ring (EngineConfig.lazy_extraction; all
    #     fields inert under the eager engine).  Slots [0, hr_count) hold
    #     pending match handles in completion order; drain clears them.
    hr_stage: jnp.ndarray  # [HB] int32 — root identity stage (-1 free)
    hr_off: jnp.ndarray  # [HB] int32 — root event offset (walk origin)
    hr_ver: jnp.ndarray  # [HB, D] int32 — walk version at completion
    hr_vlen: jnp.ndarray  # [HB] int32
    hr_ts: jnp.ndarray  # [HB] int32 — completing event's (rebased) ts
    hr_seq: jnp.ndarray  # [HB] int32 — step_seq at completion (ordering)
    hr_row: jnp.ndarray  # [HB] int32 — completing run row (queue order)
    hr_count: jnp.ndarray  # scalar int32 — pending handles
    step_seq: jnp.ndarray  # scalar int32 — monotone per-lane step counter
    handle_overflows: jnp.ndarray  # scalar int32 — ring-full match drops
    # --- per-stage selectivity tallies (EngineConfig.stage_attribution;
    #     shape [4, 0] when off — inert).  Row order is STAGE_TALLY_NAMES:
    #     frames evaluated / accepted / ignored / rejected per stage.
    stage_counts: jnp.ndarray  # [4, S] int32


class StepOutput(NamedTuple):
    """Matches completed by one event, in emission order.

    ``stage[r, w]``/``off[r, w]`` hold the backward buffer walk of run slot
    ``r``'s match (final stage first, like ``Sequence`` insertion order);
    ``count[r]`` is 0 for slots that completed nothing.
    """

    stage: jnp.ndarray  # [R, W] int32 — identity positions
    off: jnp.ndarray  # [R, W] int32 — event offsets
    count: jnp.ndarray  # [R] int32


class DrainOutput(NamedTuple):
    """One drain pass's materialized matches, in ring (completion) order.

    Row ``h`` is handle ``h`` of the ring at drain time: ``count[h] == 0``
    past the pending prefix.  ``seq``/``row`` recover the eager engine's
    emission order ((completing step, run-queue row) — the processor sorts
    drained matches by them), ``ts`` the completing event's timestamp.
    All leading axes batch ([K] under the lane-batched matchers).
    """

    stage: jnp.ndarray  # [HB, W] int32
    off: jnp.ndarray  # [HB, W] int32
    count: jnp.ndarray  # [HB] int32
    seq: jnp.ndarray  # [HB] int32
    row: jnp.ndarray  # [HB] int32
    ts: jnp.ndarray  # [HB] int32


def _as_bool(x) -> jnp.ndarray:
    return jnp.asarray(x, dtype=bool).reshape(())


# The batched slab walks extract pointer rows with f32 matmuls (ops/slab.py
# ``_pack_ptrs``), so event offsets must stay exactly representable in
# float32.  Host entry points enforce this; the runtime's per-lane offsets
# are log positions, so the bound is 16.7M events per lane.
OFFSET_LIMIT = 1 << 24


def check_offset(offset: int) -> int:
    if offset < 0:
        raise ValueError(
            f"event offset {offset} is negative; -1 is the engine's "
            "null-pointer sentinel, so offsets must be >= 0"
        )
    if offset >= OFFSET_LIMIT:
        raise ValueError(
            f"event offset {offset} >= 2^24; the engine's f32 pointer packing "
            "requires per-lane offsets below 16,777,216 — rebase source "
            "offsets to per-lane log positions (the runtime's auto-assignment "
            "does this) before feeding the engine"
        )
    return int(offset)


# Single source of truth for the engine's overflow/drop diagnostics; every
# aggregator (matcher, batch, sharded) derives its reporting from this pair
# so names and order can never drift.
COUNTER_NAMES = (
    "run_drops",
    "ver_overflows",
    "slab_full_drops",
    "slab_pred_drops",
    "slab_missing",
    "slab_trunc",
    "walk_collisions",
    "handle_overflows",
)

# Two-tier residency telemetry (EngineConfig.slab_hot_entries) — kept OUT of
# COUNTER_NAMES on purpose: those are overflow/drop counters whose all-zero
# state means "loss-free" (bench.py, sizing.py rely on that), while these
# only describe where walk hops resolved and are nonzero on any two-tier
# run.  Same single-source discipline: every reporter derives from this
# pair.
HOT_COUNTER_NAMES = (
    "slab_hot_hits",
    "slab_hot_misses",
    "slab_overflow_walks",
    "slab_demotions",
)

# Walk-cost telemetry (PERF.md walk-pass cost model, PROFILE_r06: the walk pass
# is compute-bound on per-hop reduces x lockstep trip counts) — like
# HOT_COUNTER_NAMES these are NOT loss indicators and live outside
# COUNTER_NAMES; they make the reduce-width perf model measurable on CPU CI.
# ``extract_hops`` counts eager in-step extraction walk hops; ``drain_hops``
# the deferred drain pass's (lazy_extraction); ``walk_hops`` everything else
# (branch refcount walks, dead-run removals).
WALK_COUNTER_NAMES = (
    "walk_hops",
    "extract_hops",
    "drain_hops",
)

# Per-stage selectivity tallies (EngineConfig.stage_attribution), in the
# row order of ``EngineState.stage_counts``.  Like the walk counters these
# are NOT loss indicators; they exist so the compiler-tiering and
# lazy-chain stage-ordering work (ROADMAP) can read per-stage selectivity
# (accepts / evals) and cost without hand-run scripts.  The per-stage
# walk-hop cost rides ``SlabState.stage_hops`` and reports beside these
# as ``stage_walk_hops``.
STAGE_TALLY_NAMES = (
    "stage_evals",
    "stage_accepts",
    "stage_ignores",
    "stage_rejects",
)

# Compiler-tiering telemetry (EngineConfig.tiering): how much traffic the
# stencil prefix tier absorbed before the NFA tier saw anything.  NOT loss
# indicators (like the hot/walk counters) — ``prefix_events_screened``
# counts every valid event the prefix evaluated, ``prefix_fires`` the
# prefix completions, ``tier_promotions`` the runs actually injected into
# the NFA tier (fires minus queue-overflow drops).  Untiered matchers
# report them as structural zeros so dashboards need no per-tier schema.
TIER_COUNTER_NAMES = (
    "prefix_events_screened",
    "prefix_fires",
    "tier_promotions",
)


def counter_values(state: "EngineState") -> Tuple[jnp.ndarray, ...]:
    """The counters of ``state`` in ``COUNTER_NAMES`` order."""
    return (
        state.run_drops,
        state.ver_overflows,
        state.slab.full_drops,
        state.slab.pred_drops,
        state.slab.missing,
        state.slab.trunc,
        state.slab.collisions,
        state.handle_overflows,
    )


def hot_counter_values(state: "EngineState") -> Tuple[jnp.ndarray, ...]:
    """The two-tier counters of ``state`` in ``HOT_COUNTER_NAMES`` order."""
    return (
        state.slab.hot_hits,
        state.slab.hot_misses,
        state.slab.overflow_walks,
        state.slab.demotions,
    )


def walk_counter_values(state: "EngineState") -> Tuple[jnp.ndarray, ...]:
    """The walk-cost counters of ``state`` in ``WALK_COUNTER_NAMES``
    order."""
    return (
        state.slab.walk_hops,
        state.slab.extract_hops,
        state.slab.drain_hops,
    )


def per_lane_counter_arrays(state: "EngineState") -> Dict[str, Any]:
    """Un-summed counter arrays (drop + hot + walk-cost), one host int64
    array per name, for per-lane attribution (telemetry pillar 3): a
    ``[K]``-batched state yields ``[K]`` arrays — which lane is burning
    capacity — while a single-lane state yields scalars.  One
    ``device_get`` for all of them.
    """
    names = COUNTER_NAMES + HOT_COUNTER_NAMES + WALK_COUNTER_NAMES
    vals = jax.device_get(
        counter_values(state)
        + hot_counter_values(state)
        + walk_counter_values(state)
    )
    return {
        n: np.asarray(v).astype(np.int64) for n, v in zip(names, vals)
    }


def stage_counter_arrays(state: "EngineState") -> Dict[str, Any]:
    """Per-stage attribution arrays as host int64 ndarrays: the four
    selectivity tallies (``STAGE_TALLY_NAMES``, each ``[..., S]``) plus
    ``stage_walk_hops`` from the slab.  Leading batch axes (lanes) are
    preserved so callers can attribute per lane *and* per stage; empty
    dict when attribution is off (zero-size arrays).  One ``device_get``
    for all of them."""
    if int(state.stage_counts.shape[-1]) == 0:
        return {}
    sc, sh = jax.device_get((state.stage_counts, state.slab.stage_hops))
    sc = np.asarray(sc).astype(np.int64)
    out = {
        n: sc[..., i, :] for i, n in enumerate(STAGE_TALLY_NAMES)
    }
    out["stage_walk_hops"] = np.asarray(sh).astype(np.int64)
    return out


def stage_report(
    arrays: Dict[str, Any], names: Sequence[str]
) -> Dict[str, Dict[str, int]]:
    """``stage_counter_arrays`` output -> ``{stage_name: {metric: total}}``
    with leading (lane) axes summed away and a derived ``selectivity``
    (accepts / evals) per stage — the roll-up ``metrics_snapshot``
    publishes under ``per_stage``."""
    if not arrays:
        return {}
    S = next(iter(arrays.values())).shape[-1]
    out: Dict[str, Dict[str, int]] = {}
    for s in range(S):
        name = names[s] if s < len(names) else f"stage{s}"
        row = {
            metric: int(np.asarray(arr).reshape(-1, S)[:, s].sum())
            for metric, arr in arrays.items()
        }
        ev = row.get("stage_evals", 0)
        row["selectivity"] = (
            round(row.get("stage_accepts", 0) / ev, 6) if ev else 0.0
        )
        out[name] = row
    return out


class StepPhases(NamedTuple):
    """The step's per-lane phase functions, exposed so batched callers can
    run the walk pass over the full lane batch (the fused Pallas kernel
    operates on ``[K]``-batched slabs and cannot live under ``vmap``)."""

    eval_chain: Any
    build_puts: Any
    build_walkers: Any
    finish: Any
    out_base: int
    out_rows: int
    max_walk: int
    hot_entries: int
    pred_stats: Any = None  # merged-dispatch dedup stats (multitenant)


class _ChainRecord(NamedTuple):
    """Everything one run's chain produced, consumed by the slab pass."""

    surv_alive: jnp.ndarray
    surv_final: jnp.ndarray
    surv_id: jnp.ndarray
    surv_eval: jnp.ndarray
    surv_ver: jnp.ndarray
    surv_vlen: jnp.ndarray
    surv_event: jnp.ndarray
    surv_start: jnp.ndarray
    surv_branching: jnp.ndarray
    put_en: jnp.ndarray  # [H]
    put_cur: jnp.ndarray  # [H]
    put_prev: jnp.ndarray  # [H] — -1 = put_first
    put_ver: jnp.ndarray  # [H, D]
    put_vlen: jnp.ndarray  # [H]
    br_en: jnp.ndarray  # [H]
    br_prev: jnp.ndarray  # [H] — walk origin stage
    br_ver: jnp.ndarray  # [H, D] — walk version (pre-add_run)
    br_vlen: jnp.ndarray  # [H]
    br_run_ver: jnp.ndarray  # [H, D] — branch-run version (add_run)
    br_run_vlen: jnp.ndarray  # [H]
    br_id: jnp.ndarray  # [H] — branch-run identity (= prev)
    br_eval: jnp.ndarray  # [H] — branch-run eval (= frame stage)
    br_event: jnp.ndarray  # [H]
    br_start: jnp.ndarray  # [H]
    br_agg: jnp.ndarray  # [H, NS] — typed-encoded
    final_agg: jnp.ndarray  # [NS] — survivor fold state (all folds applied)
    has_succ: jnp.ndarray
    dead: jnp.ndarray
    ovf: jnp.ndarray  # int32 — Dewey overflows in this chain
    stage_tally: jnp.ndarray  # [4, S] int32 — per-stage selectivity tallies
    #   in STAGE_TALLY_NAMES row order ([4, 0] when attribution is off)


def _build_step(tables, cfg: EngineConfig):
    """Compile the per-event step — a pure jittable fn.

    ``tables`` is one :class:`TransitionTables` or a LIST of them sharing
    the compiled table shape: a *stacked bank* (BASELINE.json config 4).
    Stacked tables ride a leading query axis selected per lane by a traced
    ``qid``; per-query predicates and folds are statically merged, so N
    same-shape queries run as one compiled program over ``N x K`` lanes
    instead of N dispatches.
    """
    tlist = list(tables) if isinstance(tables, (list, tuple)) else [tables]
    tables = tlist[0]
    Q = len(tlist)
    if not stackable(tlist):
        raise ValueError(
            "stacked patterns must share the compiled table shape "
            "(stage count, chain depth, begin/final positions); "
            "fall back to one matcher per query otherwise"
        )
    R, D, W = cfg.max_runs, cfg.dewey_depth, cfg.max_walk
    EH = cfg.slab_hot_entries
    if EH:
        if EH % 8 or not 0 < EH < cfg.slab_entries:
            raise ValueError(
                f"slab_hot_entries={EH} must be a multiple of 8 strictly "
                f"below slab_entries={cfg.slab_entries} (0 disables the "
                "two-tier layout)"
            )
    HB = cfg.handle_ring
    if HB <= 0 or HB % 8:
        raise ValueError(
            f"handle_ring={HB} must be a positive multiple of 8 (TPU "
            "sublane tile; the ring is engine state even under the eager "
            "engine)"
        )
    H = tables.max_hops
    NS = max(max(t.num_states for t in tlist), 1)
    S_CAND = 1 + H + 1  # survivor, branch per hop, re-seed
    # Per-stage attribution width: the pattern's stage count when enabled,
    # 0 (zero-size arrays, zero device work) when not.
    S_AT = tables.num_stages if cfg.stage_attribution else 0

    # Merged predicate dispatch table: the union of all queries'
    # predicates deduplicated and split into an event-level half (proven
    # independent of per-run fold state — evaluated once per event, the
    # dense predicate-matrix rows) and a run-level half (evaluated per
    # run under the owner query's decode).  compiler/multitenant.py owns
    # the proofs; per-query table entries remap into the merged ids.
    from kafkastreams_cep_tpu.compiler.multitenant import (
        plan_step_predicates,
    )

    pred_plan = plan_step_predicates(tlist)
    _remaps = pred_plan.remaps

    def stk(get, offset=False):
        rows = []
        for q, t in enumerate(tlist):
            a = np.asarray(get(t))
            if offset and len(_remaps[q]):
                a = np.where(a >= 0, _remaps[q][np.maximum(a, 0)], a)
            rows.append(a)
        return jnp.asarray(np.stack(rows))  # [Q, S]

    ident = stk(lambda t: t.ident)
    types = stk(lambda t: t.types)
    consume_op = stk(lambda t: t.consume_op)
    consume_pred = stk(lambda t: t.consume_pred, offset=True)
    consume_target = stk(lambda t: t.consume_target)
    ignore_pred = stk(lambda t: t.ignore_pred, offset=True)
    proceed_pred = stk(lambda t: t.proceed_pred, offset=True)
    proceed_target = stk(lambda t: t.proceed_target)
    # Device time is int32 (TPU-native width; callers rebase epoch-ms via
    # the runtime's `epoch`, runtime/processor.py).  Windows must fit too.
    for t in tlist:
        if t.window_ms.max(initial=-1) > np.iinfo(np.int32).max:
            raise ValueError(
                f"window of {int(t.window_ms.max())} ms exceeds int32 device "
                "time; windows up to ~24.8 days are supported"
            )
    window_ms = stk(lambda t: t.window_ms.astype(np.int32))
    final_pos = int(tables.final_pos)
    begin_pos = int(tables.begin_pos)
    # Typed fold state (the array analog of the reference's generic
    # ``Aggregator<K, V, T>``, ``Aggregator.java:22-25``): every state is
    # STORED as int32 — float32 states as their bit pattern — so the
    # structural machinery (branch copies, queue compaction, checkpoints)
    # is dtype-blind and bit-exact, and int32 folds stay exact past
    # float32's 2^24 integer range.  Values are decoded/encoded only at
    # the fold and predicate boundaries.  Per query when stacked.
    is_float_q = [
        [d == "float32" for d in t.state_dtypes]
        + [False] * (NS - t.num_states)
        for t in tlist
    ]

    def _enc_host(x, flt):
        if flt:
            return int(np.float32(x).view(np.int32))
        return int(np.int32(x))

    inits = jnp.asarray(
        [
            [
                _enc_host(x, f)
                for x, f in zip(
                    list(t.state_inits) + [0] * (NS - t.num_states),
                    is_float_q[q],
                )
            ]
            or [0]
            for q, t in enumerate(tlist)
        ],
        dtype=jnp.int32,
    )  # [Q, NS]

    def dec(v, flt):
        return jax.lax.bitcast_convert_type(v, jnp.float32) if flt else v

    def enc(v, flt):
        if flt:
            return jax.lax.bitcast_convert_type(
                jnp.asarray(v, jnp.float32), jnp.int32
            )
        return jnp.asarray(v, jnp.int32)

    def inits_of(qid):
        return inits[0] if Q == 1 else get_at(inits, qid)

    G0, G1 = pred_plan.num_event, pred_plan.num_run

    def eval_preds_event(key, value, ts):
        """The event-level half of the merged dispatch table: predicates
        proven independent of per-run fold state (``compiler/multitenant.
        reads_states``), deduplicated across stacked queries, evaluated
        ONCE per event instead of once per run per query.  The ``states``
        argument is provably never observed; an empty view is passed."""
        empty = ArrayStates({})
        return jnp.stack(
            [
                _as_bool(e.pred(key, value, ts, empty))
                for e in pred_plan.event_entries
            ]
        )

    def eval_preds_run(key, value, ts, agg_row):
        """The run-level half: each fold-state-reading predicate against
        the lane's agg row decoded through its OWNER query's
        names/dtypes.

        Stacked-bank contract: a lane's agg row is also decoded under
        *other* queries' dtype conventions (every run-level predicate
        evaluates on every lane); those values are never selected — the
        per-query remap keeps each lane on its own query's predicate ids
        — but the evaluation itself happens.  Predicates must therefore
        be pure array functions — no side effects, no host callbacks,
        total over garbage inputs.  jit tracing already enforces the
        first two; NaN- or overflow-sensitive user code must tolerate
        off-query rows."""
        env: Dict[int, ArrayStates] = {}
        vals = []
        for e in pred_plan.run_entries:
            states = env.get(e.owner)
            if states is None:
                t = tlist[e.owner]
                states = ArrayStates(
                    {
                        n: dec(agg_row[i], is_float_q[e.owner][i])
                        for i, n in enumerate(t.state_names)
                    }
                )
                env[e.owner] = states
            vals.append(_as_bool(e.pred(key, value, ts, states)))
        return jnp.stack(vals)

    # All traced-index reads below go through one-hot selects (ops/onehot)
    # instead of gathers/scatters so the whole chain fuses on TPU — see the
    # implementation note in ops/slab.py.  Tables carry a leading query
    # axis; Q == 1 resolves it statically.
    def tbl(table, idx, qid):
        """``table[qid][idx]`` for a static table and traced indices."""
        if Q == 1:
            return get_at(table[0], idx)
        return get_at2(table, qid, idx)

    def pv(preds, pid):
        """Predicate value by id; ``-1`` (absent edge) is False."""
        return jnp.where(pid >= 0, get_at(preds, jnp.maximum(pid, 0)), False)

    def chain_one(
        alive, id_pos, eval_pos, ver, vlen, event_off, start_ts0, branching, agg,
        preds, key, value, ts, off, qid,
    ) -> _ChainRecord:
        """One run's full evaluation chain (``NFA.evaluate``, recursion
        unrolled to the pattern depth)."""
        i32 = jnp.int32
        seed = id_pos < 0
        idc = jnp.maximum(id_pos, 0)
        # getFirstPatternTimestamp (NFA.java:347-349): BEGIN-typed runs reset
        # the window start to the current event's timestamp.
        id_type_begin = seed | (tbl(types, idc, qid) == TYPE_BEGIN)
        start = jnp.where(id_type_begin, ts, start_ts0)

        if cfg.enforce_windows:
            w = tbl(window_ms, eval_pos, qid)
            out_w = (~id_type_begin) & (w != -1) & (ts - start_ts0 > w)
        else:
            # Faithful: epsilon wrappers carry windowMs == -1
            # (Stage.java:41-46), so no run is ever out of window.
            out_w = jnp.bool_(False)
        active = alive & ~out_w

        # Epsilon-hop stage digit (NFA.java:185-188): crossing into a new
        # stage off a non-branching run appends ".0".  A branching run never
        # appends (its flag survives the whole chain because setVersion — the
        # only thing that clears it — is itself gated on not-branching).
        cross0 = tbl(ident, eval_pos, qid) != idc
        do_add0 = active & ~seed & cross0 & ~branching
        _, vlen_a, ovf0 = dewey_ops.add_stage(ver, vlen)
        vl = jnp.where(do_add0, vlen_a, vlen)
        vv = ver
        ovf = jnp.where(do_add0 & ovf0, 1, 0).astype(i32)

        cur = eval_pos
        prev = jnp.where(seed, i32(-1), id_pos)

        zero_ver = jnp.zeros((D,), i32)
        surv_alive = jnp.bool_(False)
        surv_final = jnp.bool_(False)
        surv_id = i32(0)
        surv_eval = i32(0)
        surv_ver = zero_ver
        surv_vlen = i32(0)
        surv_event = i32(0)
        surv_start = i32(0)
        surv_branching = jnp.bool_(False)

        put_en, put_cur, put_prev, put_ver, put_vlen = [], [], [], [], []
        br_en, br_prev, br_ver, br_vlen = [], [], [], []
        br_run_ver, br_run_vlen, br_id, br_eval, br_event, br_start = [], [], [], [], [], []
        consumed_h, frame_pos = [], []
        tally = jnp.zeros((4, S_AT), i32)

        for _h in range(H):
            cs = jnp.maximum(cur, 0)
            cop = tbl(consume_op, cs, qid)
            cp = pv(preds, tbl(consume_pred, cs, qid))
            take_m = active & (cop == OP_TAKE) & cp
            begin_m = active & (cop == OP_BEGIN) & cp
            ig_m = active & pv(preds, tbl(ignore_pred, cs, qid))
            pr_m = active & pv(preds, tbl(proceed_pred, cs, qid))
            # The 4-pair nondeterministic branching rule (NFA.java:280-289).
            branch_m = (pr_m & take_m) | (ig_m & take_m) | (ig_m & begin_m) | (ig_m & pr_m)
            branch_m = branch_m & (prev >= 0)  # unreachable for seeds; guard
            consumed = take_m | begin_m
            if S_AT:
                # Per-stage selectivity: every frame that ran predicate
                # dispatch at stage ``cs`` tallies one eval, plus one
                # accept (consumed), ignore, or reject (nothing fired —
                # the run dead-ends here) as applicable.
                rejected = active & ~consumed & ~ig_m & ~pr_m
                oh_s = jnp.arange(S_AT, dtype=i32) == cs
                tally = tally + (
                    oh_s[None, :]
                    & jnp.stack([active, consumed, ig_m, rejected])[:, None]
                ).astype(i32)

            # Survivor: at most one across the chain — a frame either
            # recurses on PROCEED or emits its single local successor.
            st = take_m & ~branch_m  # self-loop re-add (NFA.java:196-205)
            sb = begin_m  # advance (NFA.java:210-222), kept even when branching
            si = ig_m & ~branch_m  # unchanged re-add (NFA.java:223-227)
            fire = st | sb | si
            tgt = tbl(consume_target, cs, qid)
            surv_id = jnp.where(fire, jnp.where(si, id_pos, tbl(ident, cs, qid)), surv_id)
            surv_eval = jnp.where(
                fire, jnp.where(st, cs, jnp.where(sb, tgt, eval_pos)), surv_eval
            )
            surv_ver = jnp.where(fire, vv, surv_ver)
            surv_vlen = jnp.where(fire, vl, surv_vlen)
            surv_event = jnp.where(fire, jnp.where(si, event_off, off), surv_event)
            surv_start = jnp.where(fire, jnp.where(si, start_ts0, start), surv_start)
            surv_branching = jnp.where(fire, si & branching, surv_branching)
            surv_final = jnp.where(fire, sb & (tgt == final_pos), surv_final)
            surv_alive = surv_alive | fire

            # Consuming put; on a branching TAKE the event is recorded under
            # the bumped version and no successor is emitted (NFA.java:206-208).
            put_en.append(consumed)
            put_cur.append(tbl(ident, cs, qid))
            put_prev.append(jnp.where(prev >= 0, tbl(ident, jnp.maximum(prev, 0), qid), i32(-1)))
            put_ver.append(jnp.where(take_m & branch_m, dewey_ops.add_run(vv, vl), vv))
            put_vlen.append(vl)

            # Branch run (NFA.java:231-246): eps(previous, current), version
            # addRun, pointer event = previous when the frame also ignored.
            br_en.append(branch_m)
            br_prev.append(tbl(ident, jnp.maximum(prev, 0), qid))
            br_ver.append(vv)
            br_vlen.append(vl)
            br_run_ver.append(dewey_ops.add_run(vv, vl))
            br_run_vlen.append(vl)
            br_id.append(tbl(ident, jnp.maximum(prev, 0), qid))
            br_eval.append(cs)
            br_event.append(jnp.where(ig_m, event_off, off))
            br_start.append(start)
            consumed_h.append(consumed)
            frame_pos.append(cs)

            # PROCEED recursion (NFA.java:182-190).
            ptgt = tbl(proceed_target, cs, qid)
            ptc = jnp.maximum(ptgt, 0)
            do_add = pr_m & (tbl(ident, ptc, qid) != tbl(ident, cs, qid)) & ~branching
            _, vlen_b, ovf_b = dewey_ops.add_stage(vv, vl)
            vl = jnp.where(do_add, vlen_b, vl)
            ovf = ovf + jnp.where(do_add & ovf_b, 1, 0).astype(i32)
            prev = jnp.where(pr_m, cs, prev)
            cur = jnp.where(pr_m, ptc, cur)
            active = pr_m

        # Fold pass, innermost frame first (folds run on recursion unwind,
        # NFA.java:248); branch-time copies capture the state *before* the
        # branching frame's own fold but *after* deeper frames'
        # (NFA.java:243 runs before :248), restricted to the states declared
        # at the branching stage (ValueStore.branch copies only those).
        s = agg
        inits_l = inits_of(qid)
        br_agg: List[Any] = [None] * H
        for h in range(H - 1, -1, -1):
            copy_mask = jnp.zeros((NS,), bool)
            for q, t in enumerate(tlist):
                qm = True if Q == 1 else (qid == q)
                for slot in t.aggs:
                    copy_mask = copy_mask.at[slot.state].set(
                        copy_mask[slot.state]
                        | ((frame_pos[h] == slot.stage) & qm)
                    )
            br_agg[h] = jnp.where(copy_mask, s, inits_l)
            for q, t in enumerate(tlist):
                qm = True if Q == 1 else (qid == q)
                for slot in t.aggs:
                    cond = consumed_h[h] & (frame_pos[h] == slot.stage) & qm
                    flt = is_float_q[q][slot.state]
                    val = enc(
                        slot.fn(key, value, dec(s[slot.state], flt)), flt
                    )
                    s = s.at[slot.state].set(
                        jnp.where(cond, val, s[slot.state])
                    )
        final_agg = s

        any_br = jnp.any(jnp.stack(br_en)) if H else jnp.bool_(False)
        has_succ = surv_alive | any_br
        dead = alive & ~seed & ~has_succ

        stk = jnp.stack
        return _ChainRecord(
            surv_alive, surv_final, surv_id, surv_eval, surv_ver, surv_vlen,
            surv_event, surv_start, surv_branching,
            stk(put_en), stk(put_cur), stk(put_prev), stk(put_ver), stk(put_vlen),
            stk(br_en), stk(br_prev), stk(br_ver), stk(br_vlen),
            stk(br_run_ver), stk(br_run_vlen), stk(br_id), stk(br_eval),
            stk(br_event), stk(br_start),
            stk(br_agg), final_agg, has_succ, dead, ovf, tally,
        )

    RH = R * H

    def eval_chain(
        state: EngineState, ev: EventBatch, qid=None
    ) -> _ChainRecord:
        """Predicate evaluation + every run's unrolled chain (per lane).
        ``qid`` selects the lane's query in a stacked bank (None = 0)."""
        i32 = jnp.int32
        if qid is None:
            qid = jnp.zeros((), i32)
        key, value = ev.key, ev.value
        ts, off = jnp.asarray(ev.ts, i32), jnp.asarray(ev.off, i32)
        # The merged [R, G] predicate frame: the event-level block is one
        # evaluation broadcast over runs; only state-reading predicates
        # pay the per-run vmap.
        parts = []
        if G0:
            parts.append(
                jnp.broadcast_to(
                    eval_preds_event(key, value, ts), (R, G0)
                )
            )
        if G1:
            parts.append(
                jax.vmap(lambda a: eval_preds_run(key, value, ts, a))(
                    state.agg
                )
            )
        if len(parts) == 2:
            preds = jnp.concatenate(parts, axis=-1)
        elif parts:
            preds = parts[0]
        else:
            preds = jnp.zeros((R, 0), jnp.bool_)
        return jax.vmap(
            chain_one,
            in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None, None, None, None,
                     None),
        )(
            state.alive, state.id_pos, state.eval_pos, state.ver, state.vlen,
            state.event_off, state.start_ts, state.branching, state.agg,
            preds, key, value, ts, off, qid,
        )

    def build_puts(state: EngineState, rec: _ChainRecord, ev: EventBatch):
        """The step's consuming-put ops (per lane), in reference order:
        run-major, frame-ascending (``NFA.java`` queue order)."""
        prev_off_rep = jnp.repeat(state.event_off, H)
        return slab_mod.PutOps(
            en=rec.put_en.reshape(RH),
            first=rec.put_prev.reshape(RH) < 0,
            cur_stage=rec.put_cur.reshape(RH),
            prev_stage=rec.put_prev.reshape(RH),
            prev_off=prev_off_rep,
            ver=rec.put_ver.reshape(RH, D),
            vlen=rec.put_vlen.reshape(RH),
        )

    def build_walkers(state: EngineState, rec: _ChainRecord, ev: EventBatch):
        """The step's walker-candidate queue (per lane; no slab mutation).

        Queue layout (reference op order): branch frames deepest-first per
        run ([RH]), dead-run removals ([R]), final extractions ([R]) —
        ``out_base = RH + R``, ``out_rows = R``.
        """
        i32 = jnp.int32
        off = jnp.asarray(ev.off, i32)
        valid = _as_bool(ev.valid)
        final_en = rec.surv_alive & rec.surv_final & valid
        if cfg.lazy_extraction:
            # Lazy extraction: completed matches become ring handles
            # (finish()) instead of W-hop extraction walkers — the final
            # segment keeps its rows (layout is static) but never enables.
            final_en = jnp.zeros_like(final_en)

        prev_off_rep = jnp.repeat(state.event_off, H)

        def rev(f):
            return f[:, ::-1].reshape((RH,) + f.shape[2:])

        dead_en = rec.dead & (state.event_off >= 0)
        w_en = jnp.concatenate([rev(rec.br_en), dead_en, final_en])
        w_stage = jnp.concatenate(
            [rev(rec.br_prev), jnp.maximum(state.id_pos, 0), rec.surv_id]
        )
        w_off = jnp.concatenate(
            [prev_off_rep, state.event_off, jnp.broadcast_to(off, (R,))]
        )
        w_ver = jnp.concatenate([rev(rec.br_ver), state.ver, rec.surv_ver])
        w_vlen = jnp.concatenate(
            [rev(rec.br_vlen), state.vlen, rec.surv_vlen]
        )
        w_remove = jnp.concatenate(
            [jnp.zeros((RH,), bool), jnp.ones((2 * R,), bool)]
        )
        w_out = jnp.concatenate(
            [jnp.zeros((RH + R,), bool), jnp.ones((R,), bool)]
        )
        return (w_en, w_stage, w_off, w_ver, w_vlen, w_remove, w_out)

    def step(
        state: EngineState, ev: EventBatch, qid=None
    ) -> Tuple[EngineState, StepOutput]:
        i32 = jnp.int32
        off = jnp.asarray(ev.off, i32)
        valid = _as_bool(ev.valid)

        rec = eval_chain(state, ev, qid)

        # --- Shared-buffer mutations, in the reference's exact op order:
        # per run (queue order): consuming puts frame-by-frame, branch walks
        # deepest-first (they run on recursion unwind), then dead-run path
        # removal (NFA.java:102-103,117-123).  The batched path applies the
        # same ops phase-by-phase with identical per-entry ordering
        # (ops/slab.py batched kernels); the sequential path below executes
        # them literally one run at a time.
        final_en = rec.surv_alive & rec.surv_final & valid

        def run_body(r, slab):
            # Row extraction by one-hot (r is a traced loop index); the ``h``
            # indexing below is static.
            prev_off = get_at(state.event_off, r)
            put_en = get_at(rec.put_en, r)
            put_cur = get_at(rec.put_cur, r)
            put_prev = get_at(rec.put_prev, r)
            put_ver = get_at(rec.put_ver, r)
            put_vlen = get_at(rec.put_vlen, r)
            for h in range(H):
                en = put_en[h]
                first = en & (put_prev[h] < 0)
                chained = en & (put_prev[h] >= 0)
                slab = slab_mod.put_first(
                    slab, put_cur[h], off,
                    put_ver[h], put_vlen[h], enable=first, hot_entries=EH,
                )
                slab = slab_mod.put(
                    slab, put_cur[h], off, put_prev[h], prev_off,
                    put_ver[h], put_vlen[h], enable=chained, hot_entries=EH,
                )
            br_en = get_at(rec.br_en, r)
            br_prev = get_at(rec.br_prev, r)
            br_ver = get_at(rec.br_ver, r)
            br_vlen = get_at(rec.br_vlen, r)
            for h in range(H - 1, -1, -1):
                slab = slab_mod.branch(
                    slab, br_prev[h], prev_off,
                    br_ver[h], br_vlen[h], W,
                    enable=br_en[h], hot_entries=EH,
                )
            dead_en = get_at(rec.dead, r) & (prev_off >= 0)
            slab, _, _, _ = slab_mod.peek(
                slab, jnp.maximum(get_at(state.id_pos, r), 0), prev_off,
                get_at(state.ver, r), get_at(state.vlen, r), W,
                remove=True, enable=dead_en, hot_entries=EH,
                hop_kind="walk",
            )
            return slab

        def fin_body(r, carry):
            slab, out_stage, out_off, out_count = carry
            fe = get_at(final_en, r)
            slab, st_row, off_row, cnt = slab_mod.peek(
                slab, get_at(rec.surv_id, r), off, get_at(rec.surv_ver, r),
                get_at(rec.surv_vlen, r), W, remove=True, enable=fe,
                hot_entries=EH,
            )
            out_stage = put_at(out_stage, r, st_row[None, :], enable=fe)
            out_off = put_at(out_off, r, off_row[None, :], enable=fe)
            out_count = put_at(out_count, r, cnt, enable=fe)
            return slab, out_stage, out_off, out_count

        if cfg.sequential_slab:
            slab = jax.lax.fori_loop(0, R, run_body, state.slab)
            if cfg.lazy_extraction:
                # Lazy: finish() appends handles instead; no in-step
                # extraction walks at all.
                out_stage = jnp.full((R, W), -1, i32)
                out_off = jnp.full((R, W), -1, i32)
                out_count = jnp.zeros((R,), i32)
            else:
                # Match construction for final states, after all runs
                # (NFA.java:111-115), in queue order.
                slab, out_stage, out_off, out_count = jax.lax.fori_loop(
                    0, R, fin_body,
                    (
                        slab,
                        jnp.full((R, W), -1, i32),
                        jnp.full((R, W), -1, i32),
                        jnp.zeros((R,), i32),
                    ),
                )
        else:
            # One walk pass serves every walker of the step — branch
            # refcount walks (deepest-first per run, NFA.java:231-246),
            # dead-run removals (NFA.java:102-103,117-123), and final-match
            # extraction (NFA.java:111-115) — compacted in queue-order rank
            # into a small pool (PROFILE_r04.md: carrying all 3R+ slots
            # through every hop was ~90% of the step).
            # (Rank-compacting the puts like the walk pass was measured
            # net-negative in jnp: the vmapped batch loop costs every lane
            # the busiest lane's batch count.  puts_batched's O(RH^2)
            # masks fuse well under XLA; the fused kernel path applies
            # puts in-kernel instead.)
            slab = slab_mod.puts_batched(
                state.slab, build_puts(state, rec, ev), off, hot_entries=EH
            )
            wk = build_walkers(state, rec, ev)
            slab, out_stage, out_off, out_count = slab_mod.walks_compacted(
                slab, *wk, W,
                budget=cfg.walker_budget, out_base=RH + R, out_rows=R,
                hot_entries=EH,
            )

        return finish(state, ev, rec, slab, out_stage, out_off, out_count,
                      qid)

    def finish(
        state: EngineState,
        ev: EventBatch,
        rec: _ChainRecord,
        slab,
        out_stage,
        out_off,
        out_count,
        qid=None,
        slab_gated: bool = False,
    ) -> Tuple[EngineState, StepOutput]:
        """Queue compaction + padding masking (per lane).

        ``slab_gated``: the caller's slab pass already left the slab of a
        padding step untouched (no put or walker enabled there), so the
        slab skips the padding select: selecting it would keep the step's
        input slab alive beside its output, a second copy of the largest
        part of the state."""
        i32 = jnp.int32
        if qid is None:
            qid = jnp.zeros((), i32)
        valid = _as_bool(ev.valid)
        inits_l = inits_of(qid)

        # --- Next queue: per run [survivor, branches deepest-first, re-seed],
        # flattened in queue order, compacted into R slots (overflow counted).
        seed_mask = state.alive & (state.id_pos < 0)
        reseed_ver = jnp.where(
            rec.has_succ[:, None],
            jax.vmap(dewey_ops.add_run)(state.ver, state.vlen),
            state.ver,
        )

        def cand(field_surv, field_br, field_seed):
            # [R] / [R, H] / [R] -> [R, S_CAND]; branches deepest-first.
            parts = [field_surv[:, None]]
            if H:
                parts.append(field_br[:, ::-1])
            parts.append(field_seed[:, None])
            return jnp.concatenate(parts, axis=1)

        c_alive = cand(
            rec.surv_alive & ~rec.surv_final,
            rec.br_en,
            seed_mask,
        )
        c_id = cand(rec.surv_id, rec.br_id, jnp.full((R,), -1, i32))
        c_eval = cand(rec.surv_eval, rec.br_eval, jnp.full((R,), begin_pos, i32))
        c_ver = jnp.concatenate(
            [rec.surv_ver[:, None, :]]
            + ([rec.br_run_ver[:, ::-1, :]] if H else [])
            + [reseed_ver[:, None, :]],
            axis=1,
        )
        c_vlen = cand(rec.surv_vlen, rec.br_run_vlen, state.vlen)
        c_event = cand(rec.surv_event, rec.br_event, jnp.full((R,), -1, i32))
        c_start = cand(rec.surv_start, rec.br_start, jnp.full((R,), -1, i32))
        c_branching = cand(
            rec.surv_branching,
            jnp.ones((R, H), bool) if H else jnp.zeros((R, 0), bool),
            jnp.zeros((R,), bool),
        )
        c_agg = jnp.concatenate(
            [rec.final_agg[:, None, :]]
            + ([rec.br_agg[:, ::-1, :]] if H else [])
            + [jnp.broadcast_to(inits_l, (R, NS))[:, None, :]],
            axis=1,
        )

        RS = R * S_CAND
        flat_alive = c_alive.reshape(RS)
        idx = jnp.cumsum(flat_alive.astype(i32)) - 1
        keep = flat_alive & (idx < R)
        dropped = jnp.sum((flat_alive & (idx >= R)).astype(i32))

        # Scatter-free compaction: each kept candidate's one-hot destination
        # row, reduced over the candidate axis (at most one source per slot).
        ohm = keep[:, None] & (idx[:, None] == jnp.arange(R, dtype=i32)[None, :])

        def compact(field, fill=0):
            flat = field.reshape((RS,) + field.shape[2:])
            m = ohm.reshape((RS, R) + (1,) * (flat.ndim - 1))
            if flat.dtype == jnp.bool_:
                return jnp.any(m & flat[:, None], axis=0)
            vals = jnp.sum(jnp.where(m, flat[:, None], 0), axis=0).astype(flat.dtype)
            got = jnp.any(m, axis=0).reshape((R,) + (1,) * (flat.ndim - 1))
            return jnp.where(got, vals, jnp.asarray(fill, flat.dtype))

        new_alive = jnp.any(ohm & flat_alive[:, None], axis=0)

        # --- Lazy extraction: append completed matches to the handle ring
        # and pin each root (refs +1) so no removal walk can delete the
        # chain's root entry before the drain pass unpins and walks it.
        hr = dict(
            hr_stage=state.hr_stage, hr_off=state.hr_off,
            hr_ver=state.hr_ver, hr_vlen=state.hr_vlen,
            hr_ts=state.hr_ts, hr_seq=state.hr_seq, hr_row=state.hr_row,
            hr_count=state.hr_count,
            handle_overflows=state.handle_overflows,
        )
        if cfg.lazy_extraction:
            off = jnp.asarray(ev.off, i32)
            ts = jnp.asarray(ev.ts, i32)
            final_en = rec.surv_alive & rec.surv_final & valid
            rank = jnp.cumsum(final_en.astype(i32)) - 1
            dst = state.hr_count + rank
            fit = final_en & (dst < HB)
            m = fit[:, None] & (
                jnp.arange(HB, dtype=i32)[None, :] == dst[:, None]
            )  # [R, HB] — at most one True per row and per column
            got = jnp.any(m, axis=0)

            def ring_set(cur, val):
                if val.ndim == 1:
                    upd = jnp.sum(jnp.where(m, val[:, None], 0), axis=0)
                    return jnp.where(got, upd.astype(cur.dtype), cur)
                upd = jnp.sum(
                    jnp.where(m[:, :, None], val[:, None, :], 0), axis=0
                )
                return jnp.where(got[:, None], upd.astype(cur.dtype), cur)

            pin = jnp.sum(
                (
                    (slab.stage[None, :] == rec.surv_id[:, None])
                    & (slab.off[None, :] == off)
                    & fit[:, None]
                ).astype(i32),
                axis=0,
            )
            slab = slab._replace(refs=slab.refs + pin)
            hr = dict(
                hr_stage=ring_set(state.hr_stage, rec.surv_id),
                hr_off=ring_set(
                    state.hr_off, jnp.broadcast_to(off, (R,))
                ),
                hr_ver=ring_set(state.hr_ver, rec.surv_ver),
                hr_vlen=ring_set(state.hr_vlen, rec.surv_vlen),
                hr_ts=ring_set(state.hr_ts, jnp.broadcast_to(ts, (R,))),
                hr_seq=ring_set(
                    state.hr_seq, jnp.broadcast_to(state.step_seq, (R,))
                ),
                hr_row=ring_set(state.hr_row, jnp.arange(R, dtype=i32)),
                hr_count=state.hr_count + jnp.sum(fit.astype(i32)),
                handle_overflows=state.handle_overflows
                + jnp.sum((final_en & ~fit).astype(i32)),
            )

        new_state = EngineState(
            alive=new_alive,
            id_pos=compact(c_id, -1),
            eval_pos=compact(c_eval),
            ver=compact(c_ver),
            vlen=compact(c_vlen),
            event_off=compact(c_event, -1),
            start_ts=compact(c_start, -1),
            branching=compact(c_branching, False),
            agg=compact(c_agg),
            slab=slab,
            run_drops=state.run_drops + dropped,
            ver_overflows=state.ver_overflows + jnp.sum(rec.ovf),
            step_seq=state.step_seq,
            stage_counts=state.stage_counts
            + jnp.sum(rec.stage_tally, axis=0),
            **hr,
        )

        # Padding steps leave the state untouched and emit nothing.
        new_slab = new_state.slab
        new_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                jnp.reshape(valid, (1,) * n.ndim), n, o
            ) if n.ndim else jnp.where(valid, n, o),
            new_state, state,
        )
        if slab_gated:
            new_state = new_state._replace(slab=new_slab)
        # The step counter ticks on every step, padding included — it is
        # the StepOutput ``t`` index (handle ordering), not match state.
        new_state = new_state._replace(step_seq=state.step_seq + 1)
        out = StepOutput(
            stage=jnp.where(valid, out_stage, -1),
            off=jnp.where(valid, out_off, -1),
            count=jnp.where(valid, out_count, 0),
        )
        return new_state, out

    def init_state(q: int = 0) -> EngineState:
        i32 = jnp.int32
        ver = jnp.zeros((R, D), i32).at[0, 0].set(1)
        return EngineState(
            alive=jnp.zeros((R,), bool).at[0].set(True),
            id_pos=jnp.full((R,), -1, i32),
            eval_pos=jnp.full((R,), begin_pos, i32),
            ver=ver,
            vlen=jnp.zeros((R,), i32).at[0].set(1),
            event_off=jnp.full((R,), -1, i32),
            start_ts=jnp.full((R,), -1, i32),
            branching=jnp.zeros((R,), bool),
            agg=jnp.broadcast_to(inits[q], (R, NS)),
            slab=slab_mod.make(
                cfg.slab_entries, cfg.slab_preds, D, num_stages=S_AT
            ),
            run_drops=jnp.zeros((), i32),
            ver_overflows=jnp.zeros((), i32),
            hr_stage=jnp.full((HB,), -1, i32),
            hr_off=jnp.full((HB,), -1, i32),
            hr_ver=jnp.zeros((HB, D), i32),
            hr_vlen=jnp.zeros((HB,), i32),
            hr_ts=jnp.zeros((HB,), i32),
            hr_seq=jnp.zeros((HB,), i32),
            hr_row=jnp.zeros((HB,), i32),
            hr_count=jnp.zeros((), i32),
            step_seq=jnp.zeros((), i32),
            handle_overflows=jnp.zeros((), i32),
            stage_counts=jnp.zeros((4, S_AT), i32),
        )

    phases = StepPhases(
        eval_chain=eval_chain,
        build_puts=build_puts,
        build_walkers=build_walkers,
        finish=finish,
        out_base=RH + R,
        out_rows=R,
        max_walk=W,
        hot_entries=EH,
        pred_stats=dict(pred_plan.stats),
    )
    return step, init_state, phases


def build_drain(cfg: EngineConfig):
    """The per-lane batched drain pass for ``cfg`` — a pure jittable
    ``drain(state) -> (state, DrainOutput)``.

    Unpins every pending handle's root (the emission-time refcount +1,
    ``finish``), then walks all handles together through the step walk
    machinery (``ops/slab.py: walks_compacted`` with ``drain=True`` hop
    accounting) with full removal semantics — exactly the walks the eager
    engine would have run in-step, in the same per-handle order (ring
    order = completion order; ``budget=1`` default runs each alone).  The
    ring is cleared.  A no-op on an empty ring (and under the eager
    engine), so callers may drain unconditionally.  Table-free: one drain
    works for any pattern compiled at the same shapes, stacked banks
    included.
    """
    HB, W, EH, D = (
        cfg.handle_ring, cfg.max_walk, cfg.slab_hot_entries,
        cfg.dewey_depth,
    )
    i32 = jnp.int32

    def drain(state: EngineState) -> Tuple[EngineState, DrainOutput]:
        pending = jnp.arange(HB, dtype=i32) < state.hr_count
        slab = state.slab
        unpin = jnp.sum(
            (
                (slab.stage[None, :] == state.hr_stage[:, None])
                & (slab.off[None, :] == state.hr_off[:, None])
                & pending[:, None]
            ).astype(i32),
            axis=0,
        )
        slab = slab._replace(refs=jnp.maximum(slab.refs - unpin, 0))
        ones = jnp.ones((HB,), bool)
        slab, out_stage, out_off, count = slab_mod.walks_compacted(
            slab, pending, state.hr_stage, state.hr_off, state.hr_ver,
            state.hr_vlen, ones, ones, W,
            budget=cfg.walker_budget, out_base=0, out_rows=HB,
            hot_entries=EH, drain=True,
        )
        out = DrainOutput(
            stage=out_stage,
            off=out_off,
            count=jnp.where(pending, count, 0),
            seq=jnp.where(pending, state.hr_seq, -1),
            row=jnp.where(pending, state.hr_row, -1),
            ts=jnp.where(pending, state.hr_ts, -1),
        )
        state = state._replace(
            slab=slab,
            hr_stage=jnp.full((HB,), -1, i32),
            hr_off=jnp.full((HB,), -1, i32),
            hr_ver=jnp.zeros((HB, D), i32),
            hr_vlen=jnp.zeros((HB,), i32),
            hr_ts=jnp.zeros((HB,), i32),
            hr_seq=jnp.zeros((HB,), i32),
            hr_row=jnp.zeros((HB,), i32),
            hr_count=jnp.zeros((), i32),
        )
        return state, out

    return drain


def _build_programs(tables: TransitionTables, cfg: EngineConfig):
    """Build the full program bundle one :class:`TPUMatcher` needs.

    Returned as a tuple so :mod:`utils.tracecache` can share it across
    matcher instances with structurally identical (tables, config): the
    jitted callables carry their trace/compile caches with them, so a
    cache hit skips both the Python re-trace and the XLA compile.
    """
    step, init_state, phases = _build_step(tables, cfg)

    def scan(state: EngineState, events: EventBatch):
        """Run a [T]-stacked batch of events; returns [T]-stacked outputs."""
        return jax.lax.scan(step, state, events)

    drain_fn = build_drain(cfg)
    return (
        step, init_state, phases, jax.jit(step), jax.jit(scan), drain_fn,
        jax.jit(drain_fn),
    )


class TPUMatcher:
    """A compiled array matcher for one pattern.

    The core object is a pure jitted ``step(state, event) -> (state, output)``
    over a single key lane; ``scan`` runs a [T]-batch of events under
    ``lax.scan``, and both vmap cleanly over a leading key axis (see
    ``parallel/``).  Differential conformance against :class:`OracleNFA` is
    enforced by ``tests/test_engine*.py``.
    """

    def __init__(
        self,
        pattern,
        config: Optional[EngineConfig] = None,
    ):
        self.tables: TransitionTables = (
            pattern if isinstance(pattern, TransitionTables) else lower(pattern)
        )
        self.config = config or EngineConfig()
        logger.info(
            "building matcher: %d stages %s, max_hops=%d, %s",
            self.tables.num_stages, self.tables.names,
            self.tables.max_hops, self.config,
        )
        # The traced/jitted programs are structural functions of
        # (tables, config): identical fingerprints share one build —
        # including the jit caches behind ``step``/``scan``/``drain`` —
        # so re-instantiating a matcher for an already-compiled pattern
        # (tests, evacuation restores, supervisor recovery) costs a dict
        # lookup instead of a 2-5s re-trace.
        from kafkastreams_cep_tpu.compiler.multitenant import tables_key
        from kafkastreams_cep_tpu.utils import tracecache

        tkey = tables_key(self.tables)
        cache_key = (
            None
            if tkey is None
            else (tkey, dataclasses.astuple(self.config))
        )
        (
            self._step_fn, self._init_fn, self._phases, self.step,
            self.scan, self._drain_fn, self.drain,
        ) = tracecache.lookup(
            "engine.programs",
            cache_key,
            lambda: _build_programs(self.tables, self.config),
        )

    @property
    def names(self) -> List[str]:
        return self.tables.names

    def init_state(self) -> EngineState:
        return self._init_fn()

    def _scan(self, state: EngineState, events: EventBatch):
        """Run a [T]-stacked batch of events; returns [T]-stacked outputs."""
        return jax.lax.scan(self._step_fn, state, events)

    def counters(self, state: EngineState) -> Dict[str, int]:
        """Host-side diagnostic snapshot of all overflow/drop counters."""
        return {
            n: int(v) for n, v in zip(COUNTER_NAMES, counter_values(state))
        }

    def hot_counters(self, state: EngineState) -> Dict[str, int]:
        """Two-tier residency telemetry (all zero when
        ``slab_hot_entries == 0``) — reported separately from
        :meth:`counters` because these are not loss indicators."""
        return {
            n: int(v)
            for n, v in zip(HOT_COUNTER_NAMES, hot_counter_values(state))
        }

    def walk_counters(self, state: EngineState) -> Dict[str, int]:
        """Walk-cost telemetry (per-hop device work by walker class) —
        like :meth:`hot_counters`, not loss indicators."""
        return {
            n: int(v)
            for n, v in zip(WALK_COUNTER_NAMES, walk_counter_values(state))
        }

    def stage_counters(self, state: EngineState) -> Dict[str, Dict[str, int]]:
        """Per-stage selectivity/cost attribution
        (``EngineConfig.stage_attribution``): ``{stage_name: {tally:
        total, ..., selectivity}}`` summed over any leading lane axes;
        empty dict when attribution is off."""
        return stage_report(stage_counter_arrays(state), self.names)


class MatcherSession:
    """Stateful single-partition wrapper with the oracle's ``match()`` API.

    Feeds events one at a time through the jitted step, keeps the raw
    :class:`Event` objects host-side keyed by offset, and decodes completed
    matches back into :class:`Sequence` objects — the engine analog of
    ``OracleNFA.match`` for conformance tests and small-scale use.  Event
    values must be numeric pytrees (scalars or dicts of scalars).
    """

    def __init__(self, matcher: TPUMatcher):
        self.matcher = matcher
        self.state = matcher.init_state()
        self._events: Dict[int, Event] = {}
        self._offset = 0

    def match(
        self,
        key,
        value,
        timestamp: int,
        topic: str = "test",
        partition: int = 0,
        offset: Optional[int] = None,
    ) -> List[Sequence]:
        if offset is None:
            offset = self._offset
        check_offset(offset)
        self._offset = max(self._offset, offset + 1)
        event = Event(key, value, timestamp, topic, partition, offset)
        self._events[offset] = event
        ev = EventBatch(
            key=jnp.asarray(0 if key is None else key),
            value=value,
            ts=jnp.asarray(timestamp, jnp.int32),
            off=jnp.asarray(offset, jnp.int32),
            valid=jnp.asarray(True),
        )
        self.state, out = self.matcher.step(self.state, ev)
        if self.matcher.config.lazy_extraction:
            # Per-event sessions drain immediately so the oracle-style
            # match() contract (matches returned by the completing event)
            # holds; batch callers drain at scan cadence instead.
            self.state, drained = self.matcher.drain(self.state)
            return self.decode_drained(drained)
        return self.decode(out)

    def decode(self, out: StepOutput) -> List[Sequence]:
        """Materialize one step's matches as :class:`Sequence` objects."""
        stage, off, count = (np.asarray(jax.device_get(x)) for x in out)
        names = self.matcher.names
        matches: List[Sequence] = []
        for r in range(count.shape[0]):
            n = int(count[r])
            if n == 0:
                continue
            seq = Sequence()
            for w in range(n):
                seq.add(names[int(stage[r, w])], self._events[int(off[r, w])])
            matches.append(seq)
        return matches

    def decode_drained(self, out: DrainOutput) -> List[Sequence]:
        """Materialize a drain pass's matches (already in completion
        order — ring order)."""
        stage, off, count = (
            np.asarray(jax.device_get(x))
            for x in (out.stage, out.off, out.count)
        )
        names = self.matcher.names
        matches: List[Sequence] = []
        for h in range(count.shape[0]):
            n = int(count[h])
            if n == 0:
                continue
            seq = Sequence()
            for w in range(n):
                seq.add(names[int(stage[h, w])], self._events[int(off[h, w])])
            matches.append(seq)
        return matches

    def counters(self) -> Dict[str, int]:
        return self.matcher.counters(self.state)
