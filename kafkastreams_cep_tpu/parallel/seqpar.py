"""Sequence parallelism: one long trace split over the mesh's time axis.

The reference handles long streams only by windowed pruning — events are
strictly sequential per partition (``NFA.java:94-109``).  The general NFA
inherits that sequential dependence (run state at event ``t`` depends on
``t-1``), but the strict-SEQ stencil fragment (``engine/stencil.py``) does
not: a match at position ``t`` reads only the ``n`` events ending at ``t``.
That makes the time axis shardable — the CEP analog of
sequence/context parallelism, with a *halo exchange* instead of ring
attention: each device evaluates its chunk's predicate booleans locally and
receives the previous chunk's trailing ``n-1`` columns via one
``lax.ppermute`` hop over ICI.  Communication per step is ``O(K·n)``
booleans, independent of chunk length.

Device 0's halo arrives as ``ppermute`` zeros — exactly "no preceding
events", so a fresh trace needs no special casing.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kafkastreams_cep_tpu.engine.matcher import ArrayStates, EventBatch
from kafkastreams_cep_tpu.engine.stencil import StencilMatcher, StencilOutput


class TimeShardedStencil:
    """Strict-SEQ matching with the time axis sharded over a mesh.

    ``match(events)`` consumes a ``[K, T]`` batch with ``T`` divisible by
    the mesh size (padding slots are masked via ``valid``, exactly like the
    single-device scan); every device stencils its own ``T/n_dev`` chunk
    after one boundary exchange.  Output shapes equal the single-device
    :class:`StencilMatcher` scan on the same batch — verified equal
    element-for-element in ``tests/test_seqpar.py``.
    """

    def __init__(self, pattern, num_lanes: int, mesh: Mesh):
        self.inner = StencilMatcher(pattern, num_lanes)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_dev = int(mesh.devices.size)
        self.num_lanes = int(num_lanes)
        n = self.inner.n
        preds = self.inner._preds
        axis = self.axis

        def local(key, value, ts, off, valid):
            # [K, Tc] local chunk -> per-stage bools, halo, stencil.
            K = key.shape[0]
            Tc = key.shape[1]
            states = ArrayStates({})
            bools = jnp.stack(
                [
                    jnp.broadcast_to(
                        jnp.asarray(p(key, value, ts, states), bool), (K, Tc)
                    )
                    & valid
                    for p in preds
                ],
                axis=-1,
            )  # [K, Tc, n]
            offs = jnp.asarray(off, jnp.int32)
            if n == 1:
                return bools[..., 0], offs[..., None]

            perm = [(i, i + 1) for i in range(self.n_dev - 1)]
            halo_b = jax.lax.ppermute(bools[:, Tc - (n - 1) :, :], axis, perm)
            halo_o = jax.lax.ppermute(
                offs[:, Tc - (n - 1) :], axis, perm
            )
            ext_b = jnp.concatenate([halo_b, bools], axis=1)  # [K, Tc+n-1, n]
            ext_o = jnp.concatenate([halo_o, offs], axis=1)
            hit = ext_b[:, 0:Tc, 0]
            for i in range(1, n):
                hit = hit & ext_b[:, i : i + Tc, i]
            match_offs = jnp.stack(
                [ext_o[:, i : i + Tc] for i in range(n)], axis=-1
            )
            return hit, match_offs

        spec_in = (
            P(None, axis), P(None, axis), P(None, axis), P(None, axis),
            P(None, axis),
        )
        spec_out = (P(None, axis), P(None, axis, None))
        self._match = jax.jit(
            jax.shard_map(
                local,
                mesh=mesh,
                in_specs=spec_in,
                out_specs=spec_out,
                check_vma=False,
            )
        )

    def shard_events(self, events: EventBatch) -> EventBatch:
        """Place a host-built fully-valid [K, T] batch, T sharded."""
        sh = NamedSharding(self.mesh, P(None, self.axis))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), events
        )

    def match(self, events: EventBatch) -> StencilOutput:
        T = events.ts.shape[-1]
        if T % self.n_dev:
            raise ValueError(
                f"time axis {T} not divisible by mesh size {self.n_dev}"
            )
        hit, offs = self._match(
            events.key, events.value, events.ts, events.off, events.valid
        )
        return StencilOutput(hit=hit, offs=offs)
