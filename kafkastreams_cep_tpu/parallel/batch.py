"""Single-chip key-batched matcher: ``vmap`` of the engine over lanes.

The reference runs one independent NFA per Kafka partition
(``CEPProcessor.java:117-134``); here each *lane* of a ``[K]`` leading axis
is one such independent matcher (state + slab), stepped in lockstep by one
compiled dispatch.  This is the unit the mesh layer shards.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kafkastreams_cep_tpu.engine.matcher import (
    COUNTER_NAMES,
    HOT_COUNTER_NAMES,
    WALK_COUNTER_NAMES,
    DrainOutput,
    EngineConfig,
    EngineState,
    EventBatch,
    StepOutput,
    TPUMatcher,
    counter_values,
    hot_counter_values,
    walk_counter_values,
)
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("parallel.batch")

def broadcast_state(state: EngineState, num_lanes: int) -> EngineState:
    """Tile one lane's engine state to a ``[K]`` leading axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (num_lanes,) + x.shape), state
    )


def lane_step(step_one):
    """Lift a per-lane step to a ``[K]``-batched step (shared by the batch
    and sharded matchers so lane semantics can never diverge)."""

    def step(state: EngineState, ev: EventBatch):
        return jax.vmap(step_one)(state, ev)

    return step


def lane_scan(step_one):
    """Lift a per-lane step to a ``[K, T]`` scanned batch."""

    def scan(state: EngineState, events: EventBatch):
        return jax.vmap(lambda s, e: jax.lax.scan(step_one, s, e))(
            state, events
        )

    return scan


def kernel_lane_step(phases, interpret: bool = False, qids=None):
    """A ``[K]``-batched step whose walk pass runs the fused Pallas kernel.

    The chain and puts phases stay vmapped jnp; the walk pass — ~90% of the
    step in the all-jnp engine (PROFILE_r04.md) — runs once over the whole
    lane batch with each block's slab resident in VMEM
    (``ops/walk_kernel.py``).  Semantically identical to
    ``lane_step(matcher._step_fn)`` (same phase order, same sequential
    queue-order walk semantics); differentially tested in
    ``tests/test_walk_kernel.py`` and the engine A/B test.
    """
    from kafkastreams_cep_tpu.ops.walk_kernel import walk_pass_kernel

    ph = phases

    def step(state: EngineState, ev: EventBatch):
        if qids is None:
            rec = jax.vmap(ph.eval_chain)(state, ev)
        else:
            # Stacked bank: each lane evaluates its own query's tables.
            rec = jax.vmap(ph.eval_chain)(state, ev, qids)
        # A padding step enables no put and no walker, so the kernel leaves
        # its slab as it was and ``finish`` need not select the old slab
        # back: the kernel then updates the slab in place.
        valid = ev.valid.astype(bool)
        ops = jax.vmap(ph.build_puts)(state, rec, ev)
        ops = ops._replace(en=ops.en & valid[:, None])
        wk = jax.vmap(ph.build_walkers)(state, rec, ev)
        wk = (wk[0] & valid[:, None],) + tuple(wk[1:])
        # Both slab phases (consuming puts, then all walks) run inside one
        # Pallas call: the slab crosses HBM once per step instead of twice.
        # (Lane-load sorting was tried here and measured net-negative: in
        # load-sorted blocks every batch runs the full hop bound, erasing
        # the batch-count win, and the permutation gathers add traffic.)
        slab, out_stage, out_off, out_count = walk_pass_kernel(
            state.slab, *wk,
            max_walk=ph.max_walk, out_base=ph.out_base,
            out_rows=ph.out_rows, interpret=interpret,
            put_ops=ops, ev_off=ev.off,
            hot_entries=ph.hot_entries,
        )
        finish = functools.partial(ph.finish, slab_gated=True)
        if qids is None:
            return jax.vmap(finish)(
                state, ev, rec, slab, out_stage, out_off, out_count
            )
        return jax.vmap(finish)(
            state, ev, rec, slab, out_stage, out_off, out_count, qids
        )

    return step


def kernel_lane_scan(step):
    """Scan a kernel-backed batched step over the time axis of ``[K, T]``
    events (time-major under the hood; the public layout is unchanged)."""

    def scan(state: EngineState, events: EventBatch):
        ev_t = jax.tree_util.tree_map(
            lambda x: jnp.swapaxes(x, 0, 1), events
        )
        state, outs = jax.lax.scan(step, state, ev_t)
        return state, jax.tree_util.tree_map(
            lambda x: jnp.swapaxes(x, 0, 1), outs
        )

    return scan


def sweep_lanes(state: EngineState, depth: int, do_renorm: bool) -> EngineState:
    """Per-lane maintenance sweep shared by the batch and sharded matchers
    (single source, like :func:`lane_step`, so their sweep semantics can
    never diverge): slab mark-sweep (frees entries unreachable from live
    run state) then, when enabled, Dewey version renormalization
    (``ops/renorm.py`` — deletes provably-dead zero positions so the fixed
    ``dewey_depth`` stays sufficient on unbounded straddling streams).

    Pending lazy-extraction handles (``EngineState.hr_*``) are first-class
    liveness roots: a pinned-but-undrained match chain must survive the
    mark-sweep, and its walk version must renormalize together with the
    pointer versions it will be compared against at drain time — handles
    ride the renorm as extra non-seed run rows.  Under the eager engine
    ``hr_count`` is always 0 and both extensions are inert.
    """
    from kafkastreams_cep_tpu.ops import renorm as renorm_mod
    from kafkastreams_cep_tpu.ops import slab as slab_mod

    blocks = _sweep_blocks(state)
    if blocks > 1:
        return _by_lane_blocks(
            functools.partial(
                sweep_lanes, depth=depth, do_renorm=do_renorm
            ),
            state, blocks,
        )
    HB = state.hr_stage.shape[-1]
    R = state.alive.shape[-1]
    pending = (
        jnp.arange(HB, dtype=jnp.int32)[None, :]
        < state.hr_count[:, None]
    )
    run_off = jnp.concatenate(
        [
            jnp.where(state.alive, state.event_off, -1),
            jnp.where(pending, state.hr_off, -1),
        ],
        axis=-1,
    )
    slab = jax.vmap(
        lambda s, ro: slab_mod.mark_sweep(s, None, ro, depth)
    )(state.slab, run_off)
    state = state._replace(slab=slab)
    if do_renorm:
        ver_all = jnp.concatenate([state.ver, state.hr_ver], axis=-2)
        vlen_all = jnp.concatenate([state.vlen, state.hr_vlen], axis=-1)
        alive_all = jnp.concatenate([state.alive, pending], axis=-1)
        # Handles are never seed runs (a match consumed events): id 0.
        id_all = jnp.concatenate(
            [state.id_pos, jnp.zeros_like(state.hr_vlen)], axis=-1
        )
        ver2, vlen2, slab, _ = jax.vmap(renorm_mod.renorm_lane)(
            ver_all, vlen_all, alive_all, id_all, state.slab
        )
        state = state._replace(
            ver=ver2[..., :R, :],
            vlen=vlen2[..., :R],
            hr_ver=ver2[..., R:, :],
            hr_vlen=vlen2[..., R:],
            slab=slab,
        )
    return state


#: The sweep's temporaries are one to two times the state it sweeps.  A
#: state of more than twice this many bytes sweeps lane block by lane
#: block, each of at most this many bytes, written back in place: 131,072
#: stock lanes (5.5e9 B a chip) then sweep in four blocks beside their
#: state and a call's match grid on one 16 GiB chip.  Smaller states (every
#: one-chip cell's) sweep whole, as one pass.
SWEEP_BLOCK_BYTES = 2 * 2**30


def _sweep_blocks(state: EngineState) -> int:
    """The number of lane blocks the sweep of ``state`` runs in: 1 up to
    ``2 * SWEEP_BLOCK_BYTES`` of state, else the fewest, a power of two
    dividing the lanes, of at most :data:`SWEEP_BLOCK_BYTES` each."""
    leaves = jax.tree_util.tree_leaves(state)
    K = int(leaves[0].shape[0])
    size = sum(int(x.size) * x.dtype.itemsize for x in leaves)
    if size <= 2 * SWEEP_BLOCK_BYTES:
        return 1
    n = 1
    while size > n * SWEEP_BLOCK_BYTES and K % (2 * n) == 0:
        n *= 2
    return n


def _by_lane_blocks(fn, state, blocks: int):
    """``fn`` (lane-local) over ``blocks`` equal lane blocks of ``state``, one
    after another, each block's result written over its own lanes."""
    B = int(jax.tree_util.tree_leaves(state)[0].shape[0]) // blocks

    def body(i, st):
        at = i * B
        blk = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, at, B), st
        )
        return jax.tree_util.tree_map(
            lambda x, b: jax.lax.dynamic_update_slice_in_dim(x, b, at, 0),
            st, fn(blk),
        )

    return jax.lax.fori_loop(0, blocks, body, state)


def _select_walk_kernel(config: EngineConfig, num_lanes: int):
    """Decide (use_kernel, interpret) for this batch shape.

    ``CEP_WALK_KERNEL``: ``auto`` (default — kernel on TPU backends when the
    lane count allows), ``0`` (never), ``1`` (force compiled), ``interpret``
    (force interpreter mode — CPU-testable).
    """
    from kafkastreams_cep_tpu.ops.walk_kernel import LANE_BLOCK

    mode = os.environ.get("CEP_WALK_KERNEL", "auto")
    feasible = (
        not config.sequential_slab and num_lanes % LANE_BLOCK == 0
    )
    if not feasible and mode in ("1", "interpret"):
        logger.warning(
            "CEP_WALK_KERNEL=%s requested but infeasible for this matcher "
            "(num_lanes=%d %% %d != 0 or sequential_slab) — falling back "
            "to the jnp walk pass",
            mode, num_lanes, LANE_BLOCK,
        )
    if mode == "0" or not feasible:
        return False, False
    if mode == "interpret":
        return True, True
    if mode == "1":
        return True, False
    return jax.default_backend() == "tpu", False


class BatchMatcher:
    """``K`` independent per-key matchers stepped as one array program.

    ``step`` consumes one event per lane (``EventBatch`` leaves shaped
    ``[K, ...]``); ``scan`` consumes a ``[K, T]`` time-stacked batch and runs
    the whole window in a single ``lax.scan`` dispatch — the shape the
    micro-batcher (``runtime/processor.py``) and the benchmarks feed.

    ``scan``, ``sweep`` and ``drain`` take the state donated: its buffers
    are reused for the state they return, and the state passed in is
    deleted, so a call holds one copy of the state, not two.
    """

    def __init__(
        self,
        pattern,
        num_lanes: int,
        config: Optional[EngineConfig] = None,
    ):
        self.matcher = TPUMatcher(pattern, config)
        self.num_lanes = int(num_lanes)
        use_kernel, interpret = _select_walk_kernel(
            self.matcher.config, self.num_lanes
        )
        self.uses_walk_kernel = use_kernel
        self._kernel_interpret = interpret
        # Like TPUMatcher, the lane-lifted jitted programs are structural
        # functions of (tables, config, kernel mode): share them across
        # instances so re-building a batch matcher for a known pattern
        # skips the vmap/scan re-trace (utils/tracecache.py).  The lane
        # count K is deliberately NOT in the key — vmap programs retrace
        # per input shape inside jit anyway, so one cached callable
        # serves every K in the same kernel-feasibility class.
        import dataclasses as _dc

        from kafkastreams_cep_tpu.compiler.multitenant import tables_key

        _tk = tables_key(self.matcher.tables)
        self._cache_key = (
            None
            if _tk is None
            else (_tk, _dc.astuple(self.matcher.config))
        )
        if use_kernel:
            logger.info(
                "batch matcher: fused walk kernel enabled (%d lanes%s)",
                self.num_lanes, ", interpret" if interpret else "",
            )
            self._step_fn = kernel_lane_step(self.matcher._phases, interpret)
            self._scan_fn = kernel_lane_scan(self._step_fn)
            self._mode_tag = ("kernel", interpret)
        else:
            self._step_fn = lane_step(self.matcher._step_fn)
            self._scan_fn = lane_scan(self.matcher._step_fn)
            self._mode_tag = ("jnp",)
        self.step = self._cached(
            "batch.step", self._mode_tag, lambda: jax.jit(self._step_fn)
        )
        self.scan = self._cached(
            "batch.scan", self._mode_tag,
            lambda: jax.jit(self._scan_fn, donate_argnums=0),
        )
        # Measured per-conjunct selectivity: under stage_attribution every
        # consuming-edge conjunct is tallied unconditionally over each
        # scanned batch (compiler/tiering.py: build_conjunct_tally) so
        # apply_lazy_order can rank lazy chains on measurement alone.
        # Accumulation is device-side and asynchronous; the counts sync to
        # host only at telemetry reads (conjunct_counters).  The slot-key
        # tuple joins the cache tag because the tally closes over this
        # instance's conjunct order, which lazy reordering permutes.
        self._conjunct_slots: list = []
        self._conjunct_counts = None
        if self.matcher.config.stage_attribution:
            from kafkastreams_cep_tpu.compiler.tiering import (
                build_conjunct_tally,
            )

            slots, tally = build_conjunct_tally(self.matcher.tables)
            if slots:
                self._conjunct_slots = slots
                self._conjunct_tally_jit = self._cached(
                    "batch.conjunct_tally",
                    ("tally",) + tuple(k for _, k, _ in slots),
                    lambda: jax.jit(tally),
                )
                inner_scan = self.scan

                def _scan_tallied(state, events):
                    self._accumulate_conjuncts(events)
                    return inner_scan(state, events)

                self.scan = _scan_tallied

    def _cached(self, namespace: str, tag, build):
        """Jitted-program lookup in the process trace cache, keyed by this
        matcher's (tables fingerprint, config) plus ``tag`` — unkeyable
        patterns build uncached."""
        from kafkastreams_cep_tpu.utils import tracecache

        key = None if self._cache_key is None else self._cache_key + (tag,)
        return tracecache.lookup(namespace, key, build)

    @property
    def names(self):
        return self.matcher.names

    def init_state(self) -> EngineState:
        return broadcast_state(self.matcher.init_state(), self.num_lanes)

    def sweep(self, state: EngineState) -> EngineState:
        """Free slab entries unreachable from live run state (the deferred
        compaction scan, SURVEY §7 step 4) — see ``ops/slab.py:mark_sweep``
        for the observably-equivalent argument.  Call between batches on
        long streams; ``CEPProcessor(gc_interval=N)`` does so automatically.
        """
        return self._sweep_jit(state)

    @functools.cached_property
    def _sweep_jit(self):
        from kafkastreams_cep_tpu.utils import tracecache

        depth = self.matcher.config.max_walk
        do_renorm = self.matcher.config.renorm_versions
        # Table-free: one sweep program serves every pattern at the same
        # (max_walk, renorm) — key on just those, not the pattern.
        return tracecache.lookup(
            "batch.sweep", (depth, do_renorm),
            lambda: jax.jit(
                lambda state: sweep_lanes(state, depth, do_renorm),
                donate_argnums=0,
            ),
        )

    def drain(self, state: EngineState):
        """Materialize every pending lazy-extraction handle in one batched
        pass (``engine/matcher.py: build_drain``) — the deferred analog of
        the eager in-step extraction walks, off the per-step critical
        path.  Returns ``(state, DrainOutput)`` with ``[K]``-leading
        outputs; a no-op on eager or already-drained state."""
        return self._drain_jit(state)

    @functools.cached_property
    def _drain_jit(self):
        import dataclasses as _dc

        from kafkastreams_cep_tpu.utils import tracecache

        cfg = self.matcher.config
        # The drain program is table-free (build_drain) — key on config
        # plus kernel mode only, shared across all patterns.
        dkey = (_dc.astuple(cfg), self.uses_walk_kernel,
                self._kernel_interpret)
        if not self.uses_walk_kernel:
            drain_fn = self.matcher._drain_fn
            return tracecache.lookup(
                "batch.drain", dkey,
                lambda: jax.jit(jax.vmap(drain_fn), donate_argnums=0),
            )
        from kafkastreams_cep_tpu.ops.walk_kernel import walk_pass_kernel

        HB, W, EH, D = (
            cfg.handle_ring, cfg.max_walk, cfg.slab_hot_entries,
            cfg.dewey_depth,
        )
        interpret = self._kernel_interpret

        def drain(state: EngineState):
            i32 = jnp.int32
            pending = (
                jnp.arange(HB, dtype=i32)[None, :]
                < state.hr_count[:, None]
            )  # [K, HB]
            slab = state.slab
            unpin = jnp.sum(
                (
                    (slab.stage[:, None, :] == state.hr_stage[:, :, None])
                    & (slab.off[:, None, :] == state.hr_off[:, :, None])
                    & pending[:, :, None]
                ).astype(i32),
                axis=1,
            )  # [K, E]
            slab = slab._replace(refs=jnp.maximum(slab.refs - unpin, 0))
            ones = jnp.ones_like(pending)
            slab, out_stage, out_off, count = walk_pass_kernel(
                slab, pending, state.hr_stage, state.hr_off,
                state.hr_ver, state.hr_vlen, ones, ones,
                max_walk=W, out_base=0, out_rows=HB,
                interpret=interpret, hot_entries=EH, drain=True,
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
                hr_stage=jnp.full_like(state.hr_stage, -1),
                hr_off=jnp.full_like(state.hr_off, -1),
                hr_ver=jnp.zeros_like(state.hr_ver),
                hr_vlen=jnp.zeros_like(state.hr_vlen),
                hr_ts=jnp.zeros_like(state.hr_ts),
                hr_seq=jnp.zeros_like(state.hr_seq),
                hr_row=jnp.zeros_like(state.hr_row),
                hr_count=jnp.zeros_like(state.hr_count),
            )
            return state, out

        return tracecache.lookup(
            "batch.drain", dkey, lambda: jax.jit(drain, donate_argnums=0)
        )

    def counters(self, state: EngineState) -> Dict[str, int]:
        """Aggregate overflow/drop counters summed over all lanes."""
        return {
            n: int(jnp.sum(v))
            for n, v in zip(COUNTER_NAMES, counter_values(state))
        }

    def hot_counters(self, state: EngineState) -> Dict[str, int]:
        """Two-tier residency telemetry summed over all lanes (all zero
        when ``slab_hot_entries == 0``)."""
        return {
            n: int(jnp.sum(v))
            for n, v in zip(HOT_COUNTER_NAMES, hot_counter_values(state))
        }

    def walk_counters(self, state: EngineState) -> Dict[str, int]:
        """Walk-cost telemetry summed over all lanes (hop counts by
        walker class; not loss indicators)."""
        return {
            n: int(jnp.sum(v))
            for n, v in zip(WALK_COUNTER_NAMES, walk_counter_values(state))
        }

    def per_lane_counters(self, state: EngineState) -> Dict[str, list]:
        """Per-lane (un-summed) drop + hot counters: ``{name: [K ints]}``
        — which lane is burning capacity, beside the summed view."""
        from kafkastreams_cep_tpu.engine.matcher import per_lane_counter_arrays

        return {
            n: v.reshape(-1).tolist()
            for n, v in per_lane_counter_arrays(state).items()
        }

    def _accumulate_conjuncts(self, events: EventBatch) -> None:
        """Fold one batch into the device-side conjunct tally.  Pure
        async device work — no host sync (``conjunct_counters`` syncs)."""
        if not self._conjunct_slots:
            return
        if self._conjunct_counts is None:
            self._conjunct_counts = jnp.zeros(
                (2, len(self._conjunct_slots)), jnp.int32
            )
        self._conjunct_counts = self._conjunct_tally_jit(
            self._conjunct_counts, events
        )

    def conjunct_counters(self) -> Dict[str, Dict[str, Dict[str, Any]]]:
        """Measured per-conjunct tallies: ``{stage: {conjunct_key:
        {evals, accepts, selectivity}}}``.  Selectivity is the marginal
        (order-independent) accept fraction — the ranking signal
        ``apply_lazy_order`` consumes; ``None`` before any batch.  Empty
        unless ``stage_attribution`` is on."""
        import numpy as np

        if not self._conjunct_slots:
            return {}
        if self._conjunct_counts is None:
            counts = np.zeros((2, len(self._conjunct_slots)), np.int64)
        else:
            counts = np.asarray(jax.device_get(self._conjunct_counts))
        report: Dict[str, Dict[str, Dict[str, Any]]] = {}
        for i, (stage, key, _m) in enumerate(self._conjunct_slots):
            ev, ac = int(counts[0, i]), int(counts[1, i])
            report.setdefault(stage, {})[key] = {
                "evals": ev,
                "accepts": ac,
                "selectivity": (ac / ev) if ev else None,
            }
        return report

    def stage_counters(self, state: EngineState) -> Dict[str, Dict[str, int]]:
        """Per-stage selectivity/cost attribution summed over all lanes
        (``{stage_name: {tally: total, selectivity}}``, plus a
        ``"conjuncts"`` sub-report of measured per-conjunct tallies);
        empty when ``EngineConfig.stage_attribution`` is off."""
        from kafkastreams_cep_tpu.engine.matcher import (
            stage_counter_arrays,
            stage_report,
        )

        report = stage_report(stage_counter_arrays(state), self.names)
        for stage, rows in self.conjunct_counters().items():
            report.setdefault(stage, {})["conjuncts"] = rows
        return report

    def metrics_snapshot(self, state: EngineState) -> Dict[str, object]:
        """Engine-level telemetry of ``state`` in one dict: summed drop and
        hot-tier counters plus the per-lane breakdown (and the per-stage
        attribution roll-up when enabled)."""
        from kafkastreams_cep_tpu.engine.matcher import TIER_COUNTER_NAMES

        out: Dict[str, object] = {}
        out.update(self.counters(state))
        out.update(self.hot_counters(state))
        out.update(self.walk_counters(state))
        # Untiered: the tier counters are structural zeros so dashboards
        # see one schema (TieredBatchMatcher overrides with real values).
        out.update({n: 0 for n in TIER_COUNTER_NAMES})
        out["per_lane"] = self.per_lane_counters(state)
        per_stage = self.stage_counters(state)
        if per_stage:
            out["per_stage"] = per_stage
        return out
