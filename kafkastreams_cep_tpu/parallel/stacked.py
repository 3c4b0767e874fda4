"""Stacked multi-query bank: N same-shape queries in ONE compiled dispatch.

The reference composes multi-query topologies as one ``CEPProcessor`` per
pattern over the same topic; the serial device analog (``runtime/bank.py``)
pays one dispatch per query.  When queries lower to the same table shape
(stage count, chain depth — typical for banks of parameterized variants of
one query), their tables stack on a leading query axis and a per-lane
``qid`` selects each lane's query inside the engine step
(``engine/matcher.py: _build_step`` stacked mode).  N queries x K lanes run
as ``N*K`` lanes of one program — BASELINE.json config 4's "multi-pattern
NFA bank, batched".

Identical predicates across the stack (shared stages of parameterized
variants) are interned by bytecode structure before tracing
(``compiler/multitenant.py: plan_step_predicates``), so the fused step
evaluates each distinct predicate once per event rather than once per
query — ``StackedBankMatcher.pred_stats`` reports the measured dedup
ratio.  For banks with shared strict-contiguity *prefixes*, the
multi-tenant matcher (``parallel/tenantbank.py``) goes further and
screens the whole bank with one stencil pass.

Use :func:`stackable` to test compatibility and fall back to
``runtime/bank.py: CEPBank``'s per-query loop otherwise.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from kafkastreams_cep_tpu.compiler.tables import (
    TransitionTables,
    lower,
    stackable,
)
from kafkastreams_cep_tpu.engine.matcher import (
    COUNTER_NAMES,
    EngineConfig,
    EngineState,
    EventBatch,
    _build_step,
    counter_values,
)
from kafkastreams_cep_tpu.parallel.batch import (
    _select_walk_kernel,
    kernel_lane_scan,
    kernel_lane_step,
)
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("parallel.stacked")


class StackedBankMatcher:
    """``Q`` same-shape queries x ``K`` lanes each, one compiled program.

    Lane layout: query-major — lane ``q * K + k`` runs query ``q`` over key
    lane ``k``.  ``scan`` takes per-key events shaped ``[K, T]`` and
    replicates them across queries (every query sees every record, like the
    reference's one-processor-per-pattern topology); outputs come back
    ``[Q, K, T, R, W]`` so callers decode per query with that query's stage
    names (``names_of``).
    """

    def __init__(
        self,
        patterns: Sequence,
        lanes_per_query: int,
        config: Optional[EngineConfig] = None,
    ):
        self.tables_list: List[TransitionTables] = [
            p if isinstance(p, TransitionTables) else lower(p)
            for p in patterns
        ]
        if not stackable(self.tables_list):
            raise ValueError(
                "queries do not share a stackable table shape; use "
                "runtime.bank.CEPBank's per-query loop instead"
            )
        self.config = config or EngineConfig()
        self.Q = len(self.tables_list)
        self.K = int(lanes_per_query)
        self.num_lanes = self.Q * self.K
        logger.info(
            "stacked bank: %d queries x %d lanes in one dispatch",
            self.Q, self.K,
        )
        step, init_state, phases = _build_step(self.tables_list, self.config)
        self._step_fn = step
        self._init_fn = init_state
        self._phases = phases
        # _build_step interns predicates by bytecode identity across the
        # whole stack (compiler/multitenant.py: plan_step_predicates):
        # a bank of N parameterized variants of one query evaluates each
        # *distinct* predicate once per event instead of N times per lane.
        self.pred_stats = dict(phases.pred_stats or {})
        if self.pred_stats:
            logger.info(
                "stacked bank predicate dedup: %d -> %d distinct "
                "(%d event-level, %d run-level; ratio %.2f)",
                self.pred_stats.get("total_predicates", 0),
                self.pred_stats.get("distinct_predicates", 0),
                self.pred_stats.get("event_level", 0),
                self.pred_stats.get("run_level", 0),
                self.pred_stats.get("dedup_ratio", 1.0),
            )
        qids = jnp.repeat(
            jnp.arange(self.Q, dtype=jnp.int32), self.K
        )  # [Q*K]
        self._qids = qids

        use_kernel, interpret = _select_walk_kernel(
            self.config, self.num_lanes
        )
        self.uses_walk_kernel = use_kernel
        if use_kernel:
            bstep = kernel_lane_step(phases, interpret, qids=qids)
            scan = kernel_lane_scan(bstep)
        else:

            def scan(state: EngineState, events: EventBatch):
                return jax.vmap(
                    lambda s, e, q: jax.lax.scan(
                        lambda c, x: step(c, x, q), s, e
                    )
                )(state, events, qids)

        def scan_rep(state: EngineState, events: EventBatch):
            # Replicate [K, T] events across queries INSIDE the jit so XLA
            # fuses the broadcast instead of copying Q x [K, T] per call.
            ev = jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x] * self.Q, axis=0), events
            )
            return scan(state, ev)

        self._scan_fn = scan
        self.scan_flat = jax.jit(scan_rep)
        self._drain_jit = None  # built on first drain() (lazy configs)

    def names_of(self, q: int) -> List[str]:
        return self.tables_list[q].names

    def init_state(self) -> EngineState:
        """Per-query initial state tiled to the [Q*K] lane axis."""
        per_q = [self._init_fn(q) for q in range(self.Q)]
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(
                [jnp.broadcast_to(x, (self.K,) + x.shape) for x in xs]
            ),
            *per_q,
        )

    def scan(self, state: EngineState, events: EventBatch):
        """Events ``[K, T]`` -> replicated across queries (inside the
        jit) -> outputs reshaped ``[Q, K, T, ...]``."""
        state, out = self.scan_flat(state, events)
        out = jax.tree_util.tree_map(
            lambda x: x.reshape((self.Q, self.K) + x.shape[1:]), out
        )
        return state, out

    def counters(self, state: EngineState) -> Dict[str, int]:
        return {
            n: int(jnp.sum(v))
            for n, v in zip(COUNTER_NAMES, counter_values(state))
        }

    def hot_counters(self, state: EngineState) -> Dict[str, int]:
        """Two-tier residency telemetry summed over all lanes."""
        from kafkastreams_cep_tpu.engine.matcher import (
            HOT_COUNTER_NAMES,
            hot_counter_values,
        )

        return {
            n: int(jnp.sum(v))
            for n, v in zip(HOT_COUNTER_NAMES, hot_counter_values(state))
        }

    def walk_counters(self, state: EngineState) -> Dict[str, int]:
        """Walk-cost telemetry summed over all lanes."""
        from kafkastreams_cep_tpu.engine.matcher import (
            WALK_COUNTER_NAMES,
            walk_counter_values,
        )

        return {
            n: int(jnp.sum(v))
            for n, v in zip(WALK_COUNTER_NAMES, walk_counter_values(state))
        }

    def drain(self, state: EngineState):
        """Materialize pending lazy-extraction handles for every lane of
        the stacked ``[Q*K]`` axis in one pass (the drain is table-free,
        so one pass serves all bank members)."""
        if self._drain_jit is None:
            from kafkastreams_cep_tpu.engine.matcher import build_drain

            self._drain_jit = jax.jit(jax.vmap(build_drain(self.config)))
        return self._drain_jit(state)

    def stage_counters(self, state: EngineState) -> Dict[str, Dict[str, int]]:
        """Per-stage attribution summed over the whole ``[Q*K]`` lane axis
        (stage *positions* are shared by construction — stackable tables
        have one stage shape — so the roll-up uses query 0's names);
        empty when attribution is off."""
        from kafkastreams_cep_tpu.engine.matcher import (
            stage_counter_arrays,
            stage_report,
        )

        return stage_report(
            stage_counter_arrays(state), self.tables_list[0].names
        )

    def per_query_counters(self, state: EngineState) -> Dict[str, Dict[str, int]]:
        """Per-pattern attribution: drop + hot counters summed over each
        query's ``K``-lane block of the ``[Q*K]`` lane axis (lane layout is
        query-major) — which bank member is burning capacity inside the
        one fused dispatch."""
        from kafkastreams_cep_tpu.engine.matcher import per_lane_counter_arrays

        arrays = per_lane_counter_arrays(state)
        return {
            f"q{q}": {
                n: int(v.reshape(self.Q, self.K)[q].sum())
                for n, v in arrays.items()
            }
            for q in range(self.Q)
        }

    def metrics_snapshot(self, state: EngineState) -> Dict[str, object]:
        """Bank-wide engine telemetry: the per-member registries merged
        (summed drop + hot counters) beside the ``per_pattern`` breakdown
        that attributes them to individual queries."""
        from kafkastreams_cep_tpu.engine.matcher import TIER_COUNTER_NAMES

        out: Dict[str, object] = {}
        out.update(self.counters(state))
        out.update(self.hot_counters(state))
        out.update(self.walk_counters(state))
        # Stacked banks run whole-NFA (same-shape stacking is the point);
        # tier counters are structural zeros for schema uniformity.
        out.update({n: 0 for n in TIER_COUNTER_NAMES})
        out["per_pattern"] = self.per_query_counters(state)
        per_stage = self.stage_counters(state)
        if per_stage:
            out["per_stage"] = per_stage
        return out


def choose_bank(
    patterns: Sequence,
    config: Optional[EngineConfig] = None,
    sample_events: Optional[EventBatch] = None,
    reps: int = 2,
) -> Tuple[str, Dict[str, float]]:
    """Serial vs stacked, decided the way capacity is (engine/sizing.py):
    by measurement, not a cost model.

    The tradeoff is real in both directions: stacking runs the bank in one
    dispatch (one compile, one launch, better utilization at small
    per-query widths) but the stacked step evaluates *every* query's
    predicates on every lane (``engine/matcher.py eval_preds``), so wide
    lane counts with pred-heavy queries can favor the serial loop.  Where
    the crossover falls depends on Q, K, T, the pattern, and the backend —
    so when ``sample_events`` (a ``[K_s, T]`` batch, small ``K_s``) is
    given, both variants are timed on it and the faster wins.  Without a
    sample: non-stackable banks are serial by necessity, stackable ones
    default to stacked (the single-compile saving alone is decisive for
    short streams — a serial bank compiles once per query).

    Measured finding (v5e, BENCH_r05): at production widths (>=6400
    lanes/query) serial wins steady-state at every benched bank width
    (fused at 0.79-0.91x for N=2/8/16) — per-dispatch overhead is
    negligible at those widths while the stacked step pays every query's
    predicate work on every lane; fused wins compile time 2-4x (one
    program vs N).  Size the sample near the deployment's per-query
    width: a 128-lane sample once picked stacked for an N=8 bank whose
    12800-lane-per-query deployment favored serial, because dispatch
    overhead dominates at sample width.

    Returns ``(mode, details)`` with measured rates in ``details`` when a
    sample was timed."""
    import time

    from kafkastreams_cep_tpu.parallel.batch import BatchMatcher

    tlist = [
        p if isinstance(p, TransitionTables) else lower(p) for p in patterns
    ]
    if not stackable(tlist):
        return "serial", {"reason": "not stackable"}
    if sample_events is None:
        return "stacked", {"reason": "no sample; one compile beats Q"}

    K_s = int(sample_events.ts.shape[0])

    def best_of(fn):
        fn()  # compile + warm
        t = float("inf")
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    serial_ms = [BatchMatcher(t, K_s, config) for t in tlist]
    serial_states = [m.init_state() for m in serial_ms]

    def run_serial():
        # Each scan consumes its state: carry the result to the next run.
        outs = [
            m.scan(s, sample_events)
            for m, s in zip(serial_ms, serial_states)
        ]
        serial_states[:] = [o[0] for o in outs]
        jax.block_until_ready([o[1].count for o in outs])

    t_serial = best_of(run_serial)

    stacked = StackedBankMatcher(tlist, K_s, config)
    st0 = stacked.init_state()

    def run_stacked():
        _, out = stacked.scan(st0, sample_events)
        jax.block_until_ready(out.count)

    t_stacked = best_of(run_stacked)
    details = {
        "serial_s": t_serial,
        "stacked_s": t_stacked,
        "speedup_stacked": t_serial / t_stacked,
    }
    mode = "stacked" if t_stacked <= t_serial else "serial"
    logger.info("choose_bank: %s (%s)", mode, details)
    return mode, details
