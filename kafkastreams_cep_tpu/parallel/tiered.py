"""Tiered single-chip matcher: stencil prefix tier + NFA suffix tier.

Drop-in for :class:`~kafkastreams_cep_tpu.parallel.batch.BatchMatcher`
(same scan/sweep/drain/counters surface, ``CEPProcessor`` selects it when
``EngineConfig.tiering`` is set) that executes the compiler tiering plan
(``compiler/tiering.py``):

* ``nfa``     — no usable prefix: pure delegation to the inner
  :class:`BatchMatcher`, state still wrapped in :class:`TieredState` so
  every config compiles to one state shape.
* ``stencil`` — the whole pattern is a strict sequence: the prefix tier
  IS the matcher; completions are rendered as the engine's ``StepOutput``
  grid (``engine/tiered.py: stencil_step_output``) and the NFA engine is
  never dispatched (its ``step_seq`` still ticks, keeping drain/handle
  ordering invariants intact).
* ``hybrid``  — the stencil screens the whole ``[K, T]`` batch first
  (fully parallel over keys *and* time), then the NFA tier scans the
  batch with a promotion step fused after every engine step
  (``engine/tiered.py: build_promote``).  When the stencil reports no
  completions **and** no suffix run is alive anywhere, the NFA dispatch
  is skipped outright — on screened (production-monitoring-shaped)
  traffic most batches never pay a single NFA step.  The skip is exact:
  a stepped empty queue changes nothing but ``step_seq``, which the skip
  path advances by ``T`` in one op.

Gating is *chunk-level and fully on device*: the ``[K, T]`` batch is
segmented into ``EngineConfig.gate_chunk``-sized chunks and each chunk's
NFA work runs under a ``lax.cond`` — a chunk with no live suffix run and
no prefix completion advances ``step_seq`` in one op and emits a zero
output block.  The scan issues **zero per-scan host syncs**: dispatch
accounting accumulates on device and reaches the host only at telemetry
reads (:attr:`TieredBatchMatcher.nfa_dispatches`, whose only
host-counted part is a pure-NFA plan's whole-batch dispatches), so
pipelined processors keep full dispatch/decode overlap under tiering (the
old design paid one scalar ``device_get`` per scan to decide the skip on
host).  The skip is exact for any ``gate_chunk``: promotion happens
*after* the completing step — exactly the untiered schedule — so a
completion in chunk ``i`` has its first observable NFA effect inside
chunk ``i`` itself, which the gate (``any(alive) | any(fire)`` over the
chunk) never skips.

Parity: matches, emission order, and loss counters are bit-identical to
the untiered engine on loss-free workloads across the jnp and Pallas
walk-kernel paths (tests/test_tiering.py).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from kafkastreams_cep_tpu.compiler.tables import TransitionTables, lower
from kafkastreams_cep_tpu.compiler.tiering import (
    TIER_NFA,
    TIER_STENCIL,
    TieringPlan,
    apply_lazy_order,
    plan_tiering,
)
from kafkastreams_cep_tpu.engine.matcher import (
    TIER_COUNTER_NAMES,
    EngineConfig,
    EngineState,
    EventBatch,
    StepOutput,
)
from kafkastreams_cep_tpu.engine.stencil import PrefixCarry, StencilPrefix
from kafkastreams_cep_tpu.engine.tiered import (
    TieredState,
    build_promote,
    seedless_init,
    stencil_step_output,
)
from kafkastreams_cep_tpu.parallel.batch import (
    BatchMatcher,
    broadcast_state,
    kernel_lane_step,
    lane_step,
)
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("parallel.tiered")


@functools.lru_cache(maxsize=1)
def _bump_engine_jit():
    """Process-wide singleton (pattern-free: pure pytree surgery)."""
    return jax.jit(lambda eng, t: eng._replace(step_seq=eng.step_seq + t))


class TieredBatchMatcher:
    """``K`` lanes matched under a compiler tiering plan (one chip).

    ``profile`` is an optional measured ``per_stage`` snapshot
    (``metrics_snapshot()["per_stage"]`` from a ``stage_attribution``
    run) consumed by the lazy-chain predicate ordering; without it the
    static cost model orders the conjuncts.  ``reorder=False`` skips the
    ordering pass entirely (differential baseline).
    """

    def __init__(
        self,
        pattern,
        num_lanes: int,
        config: Optional[EngineConfig] = None,
        profile: Optional[Dict] = None,
        reorder: bool = True,
    ):
        tables = (
            pattern
            if isinstance(pattern, TransitionTables)
            else lower(pattern)
        )
        config = config or EngineConfig()
        if reorder:
            tables, self.lazy_order = apply_lazy_order(tables, profile)
        else:
            self.lazy_order = {}
        self.plan: TieringPlan = plan_tiering(tables, config, profile)
        self.tables = tables
        self.num_lanes = int(num_lanes)
        self.inner = BatchMatcher(tables, num_lanes, config)
        self.matcher = self.inner.matcher
        self.uses_walk_kernel = self.inner.uses_walk_kernel
        logger.info(
            "tiered matcher: %s (%s), %d lanes",
            self.plan.tier, self.plan.reason, self.num_lanes,
        )
        # Dispatch accounting.  ``scan_calls`` and ``gate_chunks`` are
        # host integers (pure Python bookkeeping); chunk-level NFA
        # dispatches accumulate *on device* (``_nfa_chunks_dev``) so the
        # gated scan stays sync-free — :attr:`nfa_dispatches` folds them
        # in with a single transfer at telemetry-read time.
        self.scan_calls = 0
        self.gate_chunks = 0  # device-gated chunks offered (bench denom)
        self._nfa_dispatch_host = 0  # whole-batch dispatches (nfa tier)
        self._nfa_chunks_dev = None  # [*] i32 — chunks that ran NFA work
        p = self.plan.prefix_len
        if self.plan.tier == TIER_NFA:
            self._prefix = None
        else:
            self._prefix = StencilPrefix(tables, num_lanes, p)
            self._promote = build_promote(tables, config, p)
            if self.plan.tier == TIER_STENCIL:
                self._synth = self._cached(
                    "tiered.synth", (p,),
                    lambda: jax.jit(
                        stencil_step_output(tables, config, p)
                    ),
                )

    # -- state ---------------------------------------------------------------

    @property
    def names(self) -> List[str]:
        return self.inner.names

    def _empty_carry(self) -> PrefixCarry:
        K = self.num_lanes
        i32 = jnp.int32
        z = jnp.zeros((K,), i32)
        return PrefixCarry(
            bools=jnp.zeros((K, 0, 0), bool),
            offs=jnp.zeros((K, 0), i32),
            ts=jnp.zeros((K, 0), i32),
            sver=jnp.zeros((K, 0), i32),
            cnt=z, screened=z, fires=z, promotions=z,
        )

    def init_state(self) -> TieredState:
        if self.plan.tier == TIER_NFA:
            return TieredState(
                engine=self.inner.init_state(), carry=self._empty_carry()
            )
        # The begin stage lives on the stencil tier: the NFA queue starts
        # empty and only promotions populate it.
        eng = broadcast_state(
            seedless_init(self.matcher._init_fn), self.num_lanes
        )
        return TieredState(engine=eng, carry=self._prefix.init_carry())

    # -- the scan ------------------------------------------------------------

    def _cached(self, namespace, tag, build):
        """Trace-cache lookup keyed by this matcher's (tables, config)
        fingerprint plus ``tag`` (utils/tracecache.py)."""
        import dataclasses as _dc

        from kafkastreams_cep_tpu.compiler.multitenant import tables_key
        from kafkastreams_cep_tpu.utils import tracecache

        tkey = tables_key(self.tables)
        key = (
            None
            if tkey is None
            else (tkey, _dc.astuple(self.matcher.config)) + tuple(tag)
        )
        return tracecache.lookup(namespace, key, build)

    @property
    def _bump_jit(self):
        """Advance ``step_seq`` by T without stepping: the exact effect a
        full scan of an empty, promotion-free queue would have had."""
        return _bump_engine_jit()

    @functools.cached_property
    def _hybrid_scan_jit(self):
        """The chunk-gated hybrid scan: ``(eng, events, promo) -> (eng,
        outs, promoted [K], dispatched)`` — ``dispatched`` the i32 count
        of chunks whose NFA work actually ran.  Entirely on device: the
        gate is a ``lax.cond`` per ``gate_chunk``-sized segment, so the
        host never syncs to decide a skip."""
        if self.inner.uses_walk_kernel:
            base_step = kernel_lane_step(
                self.matcher._phases, self.inner._kernel_interpret
            )
        else:
            base_step = lane_step(self.matcher._step_fn)
        promote_b = jax.vmap(self._promote)
        cfg = self.matcher.config
        C = max(int(cfg.gate_chunk), 1)
        K, R, W = self.num_lanes, cfg.max_runs, cfg.max_walk
        i32 = jnp.int32
        tmap = jax.tree_util.tree_map

        def body(s, x):
            ev, pr = x
            # Step first, then promote: the prefix completes *at* event
            # t, and the promoted run first evaluates at t+1 — exactly
            # the untiered run's schedule.
            s, out = base_step(s, ev)
            s, n = promote_b(s, pr.fire, pr.offs, pr.anchor_ts, pr.sver)
            return s, (out, n)

        def run_chunk(args):
            s, ev_t, pr_t = args
            s, (outs, ns) = jax.lax.scan(body, s, (ev_t, pr_t))
            return s, outs, jnp.sum(ns, axis=0)  # ns: [Tc, K] -> [K]

        def skip_chunk(args):
            # Exact: a scanned empty, promotion-free queue changes
            # nothing but step_seq, advanced here in one op.
            s, ev_t, _pr_t = args
            Tc = ev_t.ts.shape[0]
            outs = StepOutput(
                stage=jnp.full((Tc, K, R, W), -1, i32),
                off=jnp.full((Tc, K, R, W), -1, i32),
                count=jnp.zeros((Tc, K, R), i32),
            )
            s = s._replace(step_seq=s.step_seq + i32(Tc))
            return s, outs, jnp.zeros((K,), i32)

        def gated_chunk(s, ev_t, pr_t):
            # The chunk can observe NFA state iff a suffix run is live
            # at entry or the prefix completes inside it (promotion is
            # post-step, so a completion's first effect is in-chunk).
            needed = jnp.any(s.alive) | jnp.any(pr_t.fire)
            s, outs, n = jax.lax.cond(
                needed, run_chunk, skip_chunk, (s, ev_t, pr_t)
            )
            return s, outs, n, needed.astype(i32)

        def tiered_suffix_scan(eng: EngineState, events: EventBatch, promo):
            # Named for the profiler trace (``jit_tiered_suffix_scan``),
            # apart from the untiered matcher's ``jit_scan``.
            swap = lambda x: jnp.swapaxes(x, 0, 1)
            ev_t = tmap(swap, events)  # leaves [T, K, ...]
            pr_t = tmap(swap, promo)
            T = ev_t.ts.shape[0]
            m, r = divmod(T, C)
            promoted = jnp.zeros((K,), i32)
            dispatched = i32(0)
            parts = []
            if m:
                # All full chunks through ONE traced cond body: reshape
                # to [m, C, ...] and scan chunk-at-a-time.
                chunked = tmap(
                    lambda x: x[: m * C].reshape((m, C) + x.shape[1:]),
                    (ev_t, pr_t),
                )

                def outer(s, x):
                    ev, pr = x
                    s, outs, n, d = gated_chunk(s, ev, pr)
                    return s, (outs, n, d)

                eng, (outs_c, ns, ds) = jax.lax.scan(outer, eng, chunked)
                parts.append(
                    tmap(
                        lambda x: x.reshape((m * C,) + x.shape[2:]),
                        outs_c,
                    )
                )
                promoted = promoted + jnp.sum(ns, axis=0)
                dispatched = dispatched + jnp.sum(ds)
            if r:
                # Genuine ragged tail — never padded (padding would tick
                # step_seq past the batch and break bit-parity).
                ev_r, pr_r = tmap(lambda x: x[m * C :], (ev_t, pr_t))
                eng, outs_r, n_r, d_r = gated_chunk(eng, ev_r, pr_r)
                parts.append(outs_r)
                promoted = promoted + n_r
                dispatched = dispatched + d_r
            outs = (
                parts[0]
                if len(parts) == 1
                else tmap(
                    lambda *xs: jnp.concatenate(xs, axis=0), *parts
                )
            )
            outs = tmap(swap, outs)  # back to [K, T, ...]
            return eng, outs, promoted, dispatched

        return self._cached(
            "tiered.hybrid_scan_chunked",
            (
                self.plan.prefix_len, self.inner.uses_walk_kernel,
                self.inner._kernel_interpret,
            ),
            lambda: jax.jit(tiered_suffix_scan, donate_argnums=0),
        )

    @property
    def nfa_dispatches(self) -> int:
        """NFA-tier dispatch count: whole-batch dispatches of a pure-NFA
        plan (the only host-counted ones) plus device-gated chunks that
        actually ran NFA work.  Reading it is the only host sync in the
        dispatch accounting (telemetry/bench only — never on the scan
        path)."""
        n = self._nfa_dispatch_host
        if self._nfa_chunks_dev is not None:
            n += int(jax.device_get(self._nfa_chunks_dev))
        return n

    def scan(self, state: TieredState, events: EventBatch):
        """One ``[K, T]`` batch through the tier plan.  Same output
        contract as :meth:`BatchMatcher.scan`.  Sync-free: every tier
        decision is either host-static (the plan) or a device-side
        ``lax.cond`` (the chunk gate), so pipelined callers keep full
        dispatch/decode overlap."""
        T = int(events.ts.shape[1])
        self.scan_calls += 1
        if self.plan.tier == TIER_NFA:
            self._nfa_dispatch_host += 1
            eng, out = self.inner.scan(state.engine, events)
            return TieredState(eng, state.carry), out
        # Stencil/hybrid tiers never reach inner.scan, so the measured
        # conjunct tally (stage_attribution) accumulates here — same
        # once-per-batch schedule as the untiered matcher.
        self.inner._accumulate_conjuncts(events)
        carry, promo = self._prefix.scan(state.carry, events)
        if self.plan.tier == TIER_STENCIL:
            out = self._synth(promo)
            eng = self._bump_jit(state.engine, jnp.int32(T))
            return TieredState(eng, carry), out
        eng, out, promoted, dispatched = self._hybrid_scan_jit(
            state.engine, events, promo
        )
        C = max(int(self.matcher.config.gate_chunk), 1)
        self.gate_chunks += -(-T // C)
        # Add from the first call on, so the accumulation compiles in
        # the call that warms the scan up, not in the one after it.
        prev = self._nfa_chunks_dev
        if prev is None:
            prev = jnp.zeros_like(dispatched)
        self._nfa_chunks_dev = prev + dispatched
        carry = carry._replace(promotions=carry.promotions + promoted)
        return TieredState(eng, carry), out

    # -- maintenance / drains ------------------------------------------------

    def sweep(self, state: TieredState) -> TieredState:
        """Engine-tier maintenance sweep; the stencil carry holds no slab
        references (partial prefixes own no entries) so it rides along
        untouched."""
        return state._replace(engine=self.inner.sweep(state.engine))

    def drain(self, state: TieredState):
        eng, out = self.inner.drain(state.engine)
        return state._replace(engine=eng), out

    # -- telemetry -----------------------------------------------------------

    def counters(self, state: TieredState) -> Dict[str, int]:
        return self.inner.counters(state.engine)

    def hot_counters(self, state: TieredState) -> Dict[str, int]:
        return self.inner.hot_counters(state.engine)

    def walk_counters(self, state: TieredState) -> Dict[str, int]:
        return self.inner.walk_counters(state.engine)

    def per_lane_counters(self, state: TieredState) -> Dict[str, list]:
        return self.inner.per_lane_counters(state.engine)

    def stage_counters(self, state: TieredState):
        return self.inner.stage_counters(state.engine)

    def tier_counters(self, state: TieredState) -> Dict[str, int]:
        """Lane-summed tier telemetry in ``TIER_COUNTER_NAMES`` order:
        events screened by the prefix tier, prefix completions, and runs
        promoted into the NFA tier."""
        c = state.carry
        vals = jax.device_get(
            (jnp.sum(c.screened), jnp.sum(c.fires), jnp.sum(c.promotions))
        )
        return {n: int(v) for n, v in zip(TIER_COUNTER_NAMES, vals)}

    def metrics_snapshot(self, state: TieredState) -> Dict[str, object]:
        out = self.inner.metrics_snapshot(state.engine)
        out.update(self.tier_counters(state))
        # Dispatch-gate telemetry (host + one device read, never on the
        # scan path): how much NFA work the chunk gate actually elided.
        out["tier_scan_calls"] = self.scan_calls
        out["tier_gate_chunks"] = self.gate_chunks
        out["tier_nfa_dispatches"] = self.nfa_dispatches
        return out
