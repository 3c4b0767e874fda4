"""Multi-chip execution: the key axis sharded over a ``jax.sharding.Mesh``.

This is the distributed backend replacing the reference's Kafka-broker
fabric (SURVEY §2.2): partition assignment becomes a sharded lane axis,
"changelog replication" becomes host-side checkpoint of the sharded state
(``runtime/checkpoint.py``), and cross-partition diagnostics ride XLA
collectives (``psum``) over ICI within a slice and DCN across hosts.  Lanes
never exchange data during matching — exactly like the reference's
partitions (``CEPProcessor.java:160``) — so the hot path is collective-free
by construction and scales linearly by design.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kafkastreams_cep_tpu.engine.matcher import (
    COUNTER_NAMES,
    HOT_COUNTER_NAMES,
    WALK_COUNTER_NAMES,
    EngineConfig,
    EngineState,
    EventBatch,
    TPUMatcher,
    counter_values,
    hot_counter_values,
    walk_counter_values,
)
from kafkastreams_cep_tpu.parallel.batch import (
    _select_walk_kernel,
    broadcast_state,
    kernel_lane_scan,
    kernel_lane_step,
    lane_scan,
    lane_step,
)


class ShardLost(RuntimeError):
    """A mesh shard (device) is dead or unreachable.

    Raised by deployment probes / injected at the ``shard.dispatch``
    failpoint; the supervisor's evacuation path catches it, shrinks the
    mesh to the survivors (:func:`surviving_mesh`), and restores-and-
    replays onto the sub-mesh (``runtime/supervisor.py``).
    ``shard`` is the dead shard's index along the mesh's lane axis.
    """

    def __init__(self, msg: str = "shard lost", shard: int = 0):
        super().__init__(msg)
        self.shard = int(shard)


def surviving_mesh(mesh: Mesh, dead, num_lanes: int) -> Optional[Mesh]:
    """The degraded-mode mesh after losing the shards in ``dead``.

    Keeps the largest prefix of surviving devices whose count divides
    ``num_lanes`` (the ``ShardedMatcher`` contiguous-block constraint) —
    documented degraded-mode policy: capacity may shrink below the
    survivor count to keep lane blocks equal-sized, and a single-device
    mesh (``n=1``) is always reachable since every ``K`` divides by 1.
    Raises when every shard is dead.
    """
    dead = {int(d) for d in dead}
    survivors = [
        d for i, d in enumerate(mesh.devices.flat) if i not in dead
    ]
    if not survivors:
        raise ValueError("no surviving devices: every mesh shard is dead")
    m = len(survivors)
    while num_lanes % m:
        m -= 1
    return key_mesh(survivors[:m], axis=mesh.axis_names[0])


def key_mesh(devices: Optional[Sequence] = None, axis: str = "keys") -> Mesh:
    """A 1-D mesh over ``devices`` (default: all) sharding the key axis.

    Multi-host meshes need no special casing: key lanes are independent, so
    the same spec lays shards over ICI within a slice and DCN across hosts.
    """
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (axis,))


def as_mesh(mesh):
    """``mesh`` as a :class:`Mesh`: a whole number ``n`` is
    :func:`key_mesh` over the first ``n`` devices (so a configuration file
    can name a mesh); a ``Mesh`` or ``None`` is returned as it is."""
    import numpy as np

    if isinstance(mesh, bool) or not isinstance(mesh, (int, np.integer)):
        return mesh
    devices = jax.devices()
    if not 1 <= mesh <= len(devices):
        raise ValueError(
            f"mesh={mesh}: a mesh of {mesh} devices, but {len(devices)} "
            "are visible"
        )
    return key_mesh(devices[:mesh])


class ShardedMatcher:
    """``K`` key lanes sharded over a device mesh via ``jax.shard_map``.

    ``K`` must be divisible by the mesh size; each device steps ``K/n``
    lanes with the same compiled per-lane program as :class:`BatchMatcher`.
    ``stats`` is the one collective op — a ``psum`` of the overflow counters
    and per-step match counts across shards.

    The scan, the sweep and the drain take the state donated (its buffers
    are reused for the result, and the caller's state is deleted) and
    return it, and take the events, laid out as :meth:`init_state` and
    :meth:`shard_events` lay them: one compiled program per call shape,
    whatever produced its arguments.  They compile as ``jit_sharded_scan``,
    ``jit_sharded_sweep`` and ``jit_sharded_drain`` (the decode's
    per-shard compaction as ``jit_sharded_compact``), so a device trace
    tells them from the one-chip programs.
    """

    def __init__(
        self,
        pattern,
        num_lanes: int,
        mesh: Mesh,
        config: Optional[EngineConfig] = None,
    ):
        self.matcher = TPUMatcher(pattern, config)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        n = mesh.devices.size
        if num_lanes % n:
            raise ValueError(
                f"num_lanes={num_lanes} not divisible by mesh size {n}"
            )
        self.num_lanes = int(num_lanes)
        spec = P(self.axis)
        self._spread = NamedSharding(mesh, spec)
        self._compact_jits = {}
        # Each shard steps K/n lanes with the same code as BatchMatcher —
        # including the fused walk kernel when the per-shard lane count
        # allows it (Pallas composes with shard_map; lanes never cross
        # shards, so the kernel sees an ordinary lane batch).
        use_kernel, interpret = _select_walk_kernel(
            self.matcher.config, self.num_lanes // n
        )
        self.uses_walk_kernel = use_kernel
        if use_kernel:
            local_step = kernel_lane_step(self.matcher._phases, interpret)
            local_scan = kernel_lane_scan(local_step)
        else:
            local_step = lane_step(self.matcher._step_fn)
            local_scan = lane_scan(self.matcher._step_fn)

        def local_stats(state):
            local = jnp.stack(
                [jnp.sum(v) for v in counter_values(state)]
                + [jnp.sum(state.alive)]
                + [jnp.sum(v) for v in hot_counter_values(state)]
                + [jnp.sum(v) for v in walk_counter_values(state)]
            )
            return jax.lax.psum(local, self.axis)

        # check_vma off: constants born inside fori_loop carries are
        # device-invariant and trip the varying-axes check; the hot path has
        # no collectives, so the replication analysis buys nothing here.
        shard = lambda f, out_specs: jax.shard_map(
            f, mesh=mesh, in_specs=spec, out_specs=out_specs, check_vma=False
        )
        self.step = jax.jit(shard(local_step, spec))
        scan = shard(local_scan, spec)

        def sharded_scan(state, events):
            return scan(state, events)

        self.scan = self._donating(sharded_scan, 2)
        self._stats = jax.jit(shard(local_stats, P()))

    def _donating(self, fn, n_args: int):
        """``fn`` jitted with its first argument (the state) donated and
        every argument and result laid out as ``NamedSharding(mesh,
        P(axis))``, so a call's results are laid out exactly as the next
        call's arguments and the compiled program is found again."""
        return jax.jit(
            fn, donate_argnums=0,
            in_shardings=(self._spread,) * n_args,
            out_shardings=self._spread,
        )

    @property
    def names(self):
        return self.matcher.names

    def init_state(self) -> EngineState:
        """The initial state, built on each device as its own lane block
        (never whole on one device: at a chip's full key share the state
        is larger than one chip)."""
        return jax.jit(
            lambda: broadcast_state(self.matcher.init_state(), self.num_lanes),
            out_shardings=self._spread,
        )()

    def shard_events(self, events: EventBatch) -> EventBatch:
        """Place a ``[K, ...]`` event batch onto the mesh: host arrays go
        straight to each device's block in one transfer."""
        return jax.device_put(events, self._spread)

    def compact_matches(self, out, budget: int):
        """Each shard's hit rows of a scan's ``StepOutput``, compacted on
        its own device into ``budget`` rows (``ops/decode.py``
        ``compact_matches`` over its lane block): ``(stage [n*G, W], off
        [n*G, W], count [n*G], k [n*G], t [n*G], r [n*G], hits [n])``,
        shard ``s``'s rows at ``[s*G, s*G + hits[s])``, ``k`` the global
        lane.  Lane-local, so no grid crosses between chips (a global
        prefix sum over the sharded grid would gather it)."""
        budget = int(budget)
        fn = self._compact_jits.get(budget)
        if fn is None:
            fn = self._compact_jits[budget] = self._build_compact(budget)
        return fn(out)

    def _build_compact(self, budget: int):
        from kafkastreams_cep_tpu.ops.decode import compact_matches

        per = self.num_lanes // int(self.mesh.devices.size)
        axis = self.axis

        def local(out):
            stage, off, count, k, t, r, hits, _ = compact_matches(
                out, budget
            )
            k = k + jax.lax.axis_index(axis) * per
            return stage, off, count, k, t, r, hits.reshape(1)

        spec = P(axis)
        local = jax.shard_map(
            local, mesh=self.mesh, in_specs=spec, out_specs=spec,
            check_vma=False,
        )

        def sharded_compact(out):
            return local(out)

        return jax.jit(
            sharded_compact, in_shardings=self._spread,
            out_shardings=self._spread,
        )

    def stats(self, state: EngineState) -> Dict[str, int]:
        """Mesh-global counter totals (one ``psum`` across all shards)."""
        vals = jax.device_get(self._stats(state))
        keys = (
            COUNTER_NAMES + ("alive_runs",) + HOT_COUNTER_NAMES
            + WALK_COUNTER_NAMES
        )
        return {k: int(v) for k, v in zip(keys, vals)}

    def counters(self, state: EngineState) -> Dict[str, int]:
        """Overflow/drop counters summed over all lanes — the
        :class:`BatchMatcher` interface, so the runtime layer (processor,
        supervisor, checkpoint) is matcher-agnostic."""
        stats = self.stats(state)
        return {k: stats[k] for k in COUNTER_NAMES}

    def hot_counters(self, state: EngineState) -> Dict[str, int]:
        """Two-tier residency telemetry totals (BatchMatcher interface)."""
        stats = self.stats(state)
        return {k: stats[k] for k in HOT_COUNTER_NAMES}

    def walk_counters(self, state: EngineState) -> Dict[str, int]:
        """Walk-cost telemetry totals (BatchMatcher interface)."""
        stats = self.stats(state)
        return {k: stats[k] for k in WALK_COUNTER_NAMES}

    def drain(self, state: EngineState):
        """Materialize pending lazy-extraction handles on every shard
        (lane-elementwise, collective-free — the BatchMatcher interface;
        see ``engine/matcher.py: build_drain``)."""
        return self._drain_jit(state)

    @functools.cached_property
    def _drain_jit(self):
        spec = P(self.axis)
        local = jax.shard_map(
            jax.vmap(self.matcher._drain_fn), mesh=self.mesh, in_specs=spec,
            out_specs=(spec, spec), check_vma=False,
        )

        def sharded_drain(state):
            return local(state)

        return self._donating(sharded_drain, 1)

    @functools.cached_property
    def _stage_stats(self):
        """Mesh-global per-stage attribution: each shard reduces its lane
        block to ``[5, S]`` (the four selectivity tallies + stage hops)
        and one ``psum`` merges the shards — associative by construction
        (integer addition), exactly like the scalar-counter psum."""
        spec = P(self.axis)

        def local(state: EngineState):
            sc = jnp.sum(state.stage_counts, axis=0)  # [4, S]
            sh = jnp.sum(state.slab.stage_hops, axis=0)[None, :]  # [1, S]
            return jax.lax.psum(
                jnp.concatenate([sc, sh], axis=0), self.axis
            )

        return jax.jit(
            jax.shard_map(
                local, mesh=self.mesh, in_specs=spec, out_specs=P(),
                check_vma=False,
            )
        )

    def stage_counters(self, state: EngineState) -> Dict[str, Dict[str, int]]:
        """Per-stage attribution totals psum-merged across every shard
        (BatchMatcher interface); empty when attribution is off."""
        from kafkastreams_cep_tpu.engine.matcher import (
            STAGE_TALLY_NAMES,
            stage_report,
        )

        if int(state.stage_counts.shape[-1]) == 0:
            return {}
        import numpy as np

        merged = np.asarray(jax.device_get(self._stage_stats(state)))
        arrays = {
            n: merged[i].astype(np.int64)
            for i, n in enumerate(STAGE_TALLY_NAMES)
        }
        arrays["stage_walk_hops"] = merged[4].astype(np.int64)
        return stage_report(arrays, self.names)

    def per_lane_counters(self, state: EngineState) -> Dict[str, list]:
        """Per-lane drop + hot counters gathered from every shard:
        ``{name: [K ints]}`` with global lane indices (the lane axis is
        sharded, so lane ``k`` lives on device ``k // (K/n)``) — which
        lane, and therefore which shard, is burning capacity."""
        from kafkastreams_cep_tpu.engine.matcher import per_lane_counter_arrays

        return {
            n: v.reshape(-1).tolist()
            for n, v in per_lane_counter_arrays(state).items()
        }

    def metrics_snapshot(
        self,
        state: EngineState,
        watermark=None,
        clock=None,
        ledgers=None,
    ) -> Dict[str, object]:
        """Mesh-global engine telemetry in one dict — the per-shard
        registries merged: the summed view rides the one-``psum`` ``stats``
        collective (each shard's counter block is its local registry; the
        psum IS the merge), the per-lane breakdown a host gather.

        ``watermark`` (absolute ms) adds the watermark / event-time-lag
        gauges the unmeshed processor surfaces — through the caller's
        injectable ``clock`` — which the meshed wrapper historically
        omitted.  ``ledgers`` is an iterable of per-host
        :class:`~kafkastreams_cep_tpu.utils.latency.LatencyLedger` to fold
        into one ``latency`` entry (ledgers are host-side, so the
        multi-host merge is the associative ``merge``, not a psum)."""
        from kafkastreams_cep_tpu.engine.matcher import TIER_COUNTER_NAMES

        out: Dict[str, object] = dict(self.stats(state))
        # Tiering is single-chip today (the hybrid scan host-gates the NFA
        # dispatch, which shard_map cannot): the tier counters ride the
        # merged snapshot as structural zeros so the fleet schema is one.
        out.update({n: 0 for n in TIER_COUNTER_NAMES})
        out["per_lane"] = self.per_lane_counters(state)
        per_stage = self.stage_counters(state)
        if per_stage:
            out["per_stage"] = per_stage
        if watermark is not None:
            now = clock if clock is not None else time.time
            out["watermark"] = int(watermark)
            out["event_time_lag_ms"] = int(now() * 1000) - int(watermark)
        if ledgers:
            merged = None
            for led in ledgers:
                merged = led if merged is None else merged.merge(led)
            out["latency"] = merged.snapshot()
        return out

    def sweep(self, state: EngineState) -> EngineState:
        """Slab mark-sweep over every shard (lane-elementwise — XLA keeps
        the existing sharding; no collectives)."""
        return self._sweep_jit(state)

    @functools.cached_property
    def _sweep_jit(self):
        from kafkastreams_cep_tpu.parallel.batch import sweep_lanes

        depth = self.matcher.config.max_walk
        do_renorm = self.matcher.config.renorm_versions

        def local(state: EngineState) -> EngineState:
            return sweep_lanes(state, depth, do_renorm)

        spec = P(self.axis)
        local = jax.shard_map(
            local, mesh=self.mesh, in_specs=spec, out_specs=spec,
            check_vma=False,
        )

        def sharded_sweep(state):
            return local(state)

        return self._donating(sharded_sweep, 1)
