"""Stencil matcher — the vectorized-over-time fast path for strict SEQ.

For a branch-free pattern (every stage cardinality ONE, strict contiguity,
no folds — ``TransitionTables.is_strict_seq``), the reference NFA's
semantics collapse to a stencil: the begin stage re-seeds a run at every
event (``NFA.java:148-157``), strict contiguity kills a run on the first
non-matching event (no IGNORE edges, ``StatesFactory.java:93-96``), so a
match completes at event ``t`` **iff** stage ``i``'s predicate holds on
event ``t-n+1+i`` for all ``i``.  No run queue, no shared buffer, no
versions — just ``n`` boolean arrays ANDed under relative shifts, fully
parallel over keys *and* time (the general engine is sequential over time).

``within()`` windows need no handling here for parity: in the reference all
non-seed runs are epsilon wrappers that never carry ``windowMs``
(``Stage.java:41-46``), so windows never prune (see ``engine/matcher.py``).
That invariant is no longer merely noted: the tiering pass *asserts* it at
compile time (``compiler/tiering.py: check_no_prune``) and refuses to route
a windowed prefix onto this tier when ``EngineConfig.enforce_windows``
breaks the proof.

A carry of the last ``n-1`` events' per-stage booleans and offsets makes
matching exact across micro-batch boundaries.  Conformance: differential
tests against :class:`OracleNFA` in ``tests/test_stencil.py``.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kafkastreams_cep_tpu.compiler.tables import (
    OP_BEGIN,
    TransitionTables,
    lower,
)
from kafkastreams_cep_tpu.engine.matcher import ArrayStates, EventBatch


def _cached_scan_jit(namespace, tables, shape_key, scan_fn):
    """Jit ``scan_fn`` through the process trace cache keyed by the
    pattern fingerprint + lane/prefix shape — stencil matchers for the
    same pattern (tests, tenant banks instantiating per-query screens,
    recovery rebuilds) share one traced program."""
    from kafkastreams_cep_tpu.compiler.multitenant import tables_key
    from kafkastreams_cep_tpu.utils import tracecache

    tkey = tables_key(tables)
    key = None if tkey is None else (tkey,) + tuple(shape_key)
    return tracecache.lookup(namespace, key, lambda: jax.jit(scan_fn))


class StencilState(NamedTuple):
    """Carry across micro-batches: the trailing ``n-1`` valid events."""

    bools: jnp.ndarray  # [K, n-1, n] bool — per-stage predicate values
    offs: jnp.ndarray  # [K, n-1] int32 — event offsets (-1 = none yet)


class StencilOutput(NamedTuple):
    """``hit[k, t]`` = a match completed at batch slot ``t``;
    ``offs[k, t, i]`` = the offset of the stage-``i`` event of that match."""

    hit: jnp.ndarray  # [K, T] bool
    offs: jnp.ndarray  # [K, T, n] int32


class StencilMatcher:
    """Compiled stencil matcher for one strict-SEQ pattern over ``K`` lanes.

    ``scan(state, events)`` consumes a ``[K, T]`` :class:`EventBatch` whose
    valid slots form a per-lane prefix (the processor's padding shape) and
    returns every completed match.  Unlike :class:`TPUMatcher` there is no
    sequential dependence on the time axis, so throughput is bounded by
    memory bandwidth, not step latency.
    """

    def __init__(self, pattern, num_lanes: int):
        self.tables: TransitionTables = (
            pattern if isinstance(pattern, TransitionTables) else lower(pattern)
        )
        if not self.tables.is_strict_seq():
            raise ValueError(
                "pattern is not a branch-free strict sequence; use TPUMatcher"
            )
        self.num_lanes = int(num_lanes)
        # Chain positions 0..n-1 each consume via a BEGIN edge; final is last.
        n = self.tables.num_stages - 1
        assert np.all(self.tables.consume_op[:n] == OP_BEGIN)
        self.n = n
        # Stage names in chain order, for decoding matches.
        self.stage_names: List[str] = self.tables.names[:n]
        self._preds = [
            self.tables.predicates[self.tables.consume_pred[i]] for i in range(n)
        ]
        self.scan = _cached_scan_jit(
            "stencil.scan", self.tables, (self.num_lanes,), self._scan
        )

    def init_state(self) -> StencilState:
        K, n = self.num_lanes, self.n
        return StencilState(
            bools=jnp.zeros((K, max(n - 1, 0), n), bool),
            offs=jnp.full((K, max(n - 1, 0)), -1, jnp.int32),
        )

    def _scan(
        self, state: StencilState, ev: EventBatch
    ) -> Tuple[StencilState, StencilOutput]:
        K, n = self.num_lanes, self.n
        T = ev.ts.shape[-1]
        states = ArrayStates({})
        # [K, T, n]: every stage predicate on every event, one fused pass.
        bools = jnp.stack(
            [
                jnp.broadcast_to(
                    jnp.asarray(p(ev.key, ev.value, ev.ts, states), bool),
                    (K, T),
                )
                & ev.valid
                for p in self._preds
            ],
            axis=-1,
        )
        offs = jnp.asarray(ev.off, jnp.int32)

        if n == 1:
            out = StencilOutput(hit=bools[..., 0], offs=offs[..., None])
            return state, out

        ext_bools = jnp.concatenate([state.bools, bools], axis=1)  # [K, T+n-1, n]
        ext_offs = jnp.concatenate([state.offs, offs], axis=1)  # [K, T+n-1]

        # hit[k, t] = AND_i ext_bools[k, t+i, i]  (stage i saw event t-n+1+i).
        hit = ext_bools[:, 0:T, 0]
        for i in range(1, n):
            hit = hit & ext_bools[:, i : i + T, i]
        match_offs = jnp.stack(
            [ext_offs[:, i : i + T] for i in range(n)], axis=-1
        )

        # New carry: the last n-1 *valid* columns.  Valid slots are a prefix
        # of each lane's row, so they occupy ext columns [c, c+n-2] where c
        # is the lane's valid count.
        c = jnp.sum(ev.valid, axis=1).astype(jnp.int32)  # [K]
        carry_bools = jax.vmap(
            lambda row, start: jax.lax.dynamic_slice(
                row, (start, 0), (n - 1, n)
            )
        )(ext_bools, c)
        carry_offs = jax.vmap(
            lambda row, start: jax.lax.dynamic_slice(row, (start,), (n - 1,))
        )(ext_offs, c)

        return StencilState(carry_bools, carry_offs), StencilOutput(hit, match_offs)

    def decode(self, out: StencilOutput, events_by_offset, lane_keys=None):
        """Host-side: materialize matches as ``Sequence`` objects per lane.

        ``events_by_offset`` is a list (per lane) of ``{offset: Event}``.
        Stages are inserted final-first, matching the reference's backward
        buffer walk (``KVSharedVersionedBuffer.java:161``).
        """
        from kafkastreams_cep_tpu.utils.events import Sequence

        hit = np.asarray(jax.device_get(out.hit))
        offs = np.asarray(jax.device_get(out.offs))
        matches = []
        for k, t in zip(*np.nonzero(hit)):
            seq = Sequence()
            for i in range(self.n - 1, -1, -1):
                seq.add(
                    self.stage_names[i],
                    events_by_offset[k][int(offs[k, t, i])],
                )
            matches.append((int(k), int(t), seq))
        return matches


# ---------------------------------------------------------------------------
# Prefix mode — the stencil as the first tier of a hybrid matcher
# ---------------------------------------------------------------------------


class PrefixCarry(NamedTuple):
    """Cross-batch carry of the stencil *prefix* tier (compiler tiering).

    Beyond :class:`StencilState`'s trailing-window booleans/offsets, the
    prefix tier must be able to *promote* a completing window into the
    NFA tier with exactly the state an untiered run would carry, so the
    carry also tracks per-event timestamps (window anchors), the seed
    Dewey version each window root was born under, and the running
    begin-accept count that generates those versions.  The three trailing
    fields are the tier telemetry counters — device state so they
    checkpoint/migrate/merge like every engine counter.
    """

    bools: jnp.ndarray  # [K, p-1, p] bool — per-stage predicate values
    offs: jnp.ndarray  # [K, p-1] int32 — event offsets (-1 = none yet)
    ts: jnp.ndarray  # [K, p-1] int32 — rebased event timestamps
    sver: jnp.ndarray  # [K, p-1] int32 — seed version at each event
    cnt: jnp.ndarray  # [K] int32 — begin-accepts seen (seed ver - 1)
    screened: jnp.ndarray  # [K] int32 — valid events the prefix screened
    fires: jnp.ndarray  # [K] int32 — prefix completions
    promotions: jnp.ndarray  # [K] int32 — runs injected into the NFA tier


def partial_prefix_mask(bools: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """Host-side ``[K, p-1]`` mask of the carry events a later batch can
    still complete a prefix with: carry slot ``i`` is pending iff some
    window start ``a <= i`` has every carry event from ``a`` on matching
    its stage (``bools[k, m, m - a]``).  A promotion reads exactly these
    events' offsets, so their host events must outlive the batch."""
    K, n = offs.shape
    chain = np.ones((K, n), bool)  # chain[:, a]: a partial prefix from a
    for a in range(n):
        for m in range(a, n):
            chain[:, a] &= bools[:, m, m - a]
    return np.logical_or.accumulate(chain, axis=1) & (offs >= 0)


class PromoOutput(NamedTuple):
    """Per-step promotion feed for the NFA tier: at every batch slot where
    the prefix completed (``fire``), the p prefix-event offsets, the
    window-anchor timestamp, and the first Dewey digit the promoted run
    must carry (the seed version at the window root)."""

    fire: jnp.ndarray  # [K, T] bool
    offs: jnp.ndarray  # [K, T, p] int32
    anchor_ts: jnp.ndarray  # [K, T] int32
    sver: jnp.ndarray  # [K, T] int32


class StencilPrefix:
    """Stencil evaluation of a query's strict-contiguity *prefix*.

    Generalizes :class:`StencilMatcher` from whole patterns to the leading
    ``prefix_len`` stages chosen by ``compiler/tiering.py``: ``scan``
    consumes a ``[K, T]`` :class:`EventBatch` fully parallel over keys and
    time and emits, per step, whether the prefix completed there plus
    everything the NFA tier needs to seed the suffix run — the exact
    Dewey root (``1 + begin-accepts before the window root``, the version
    the untiered seed would have handed that run), the window anchor
    (the reference resets the window start while a run's identity stage
    is BEGIN-typed, so the anchor is the window's second event for
    ``p >= 2`` and its only event for ``p == 1``), and the p event
    offsets whose shared-buffer chain the promotion writes.

    Predicates are evaluated against the declared fold-state *inits*:
    prefix stages carry no folds (by definition of the split), so every
    untiered prefix run evaluates against exactly those values.
    """

    def __init__(self, tables, num_lanes: int, prefix_len: int):
        self.tables: TransitionTables = (
            tables if isinstance(tables, TransitionTables) else lower(tables)
        )
        p = int(prefix_len)
        n = self.tables.num_stages - 1
        if not 0 < p <= n:
            raise ValueError(f"prefix_len={p} outside 1..{n}")
        if np.any(self.tables.consume_op[:p] != OP_BEGIN) or np.any(
            self.tables.ignore_pred[:p] >= 0
        ) or np.any(self.tables.proceed_pred[:p] >= 0) or any(
            slot.stage < p for slot in self.tables.aggs
        ):
            raise ValueError(
                f"stages [0, {p}) are not a strict-contiguity prefix; run "
                "compiler.tiering.plan_tiering first"
            )
        self.num_lanes = int(num_lanes)
        self.p = p
        self._preds = [
            self.tables.predicates[self.tables.consume_pred[j]]
            for j in range(p)
        ]
        # Fold-state inits (decoded to each state's declared dtype): the
        # exact ArrayStates view an untiered prefix run evaluates against.
        self._states = ArrayStates(
            {
                name: (
                    jnp.asarray(init, jnp.float32)
                    if dt == "float32"
                    else jnp.asarray(init, jnp.int32)
                )
                for name, init, dt in zip(
                    self.tables.state_names,
                    self.tables.state_inits,
                    self.tables.state_dtypes,
                )
            }
        )
        self.scan = _cached_scan_jit(
            "stencil.prefix_scan", self.tables,
            (self.num_lanes, self.p), self.stencil_prefix_scan,
        )

    def init_carry(self) -> PrefixCarry:
        K, p = self.num_lanes, self.p
        i32 = jnp.int32
        z = jnp.zeros((K,), i32)
        return PrefixCarry(
            bools=jnp.zeros((K, p - 1, p), bool),
            offs=jnp.full((K, p - 1), -1, i32),
            ts=jnp.zeros((K, p - 1), i32),
            sver=jnp.ones((K, p - 1), i32),
            cnt=z,
            screened=z,
            fires=z,
            promotions=z,
        )

    def stencil_prefix_scan(
        self, carry: PrefixCarry, ev: EventBatch
    ) -> Tuple[PrefixCarry, PromoOutput]:
        """The body ``scan`` jits; its name names the device program
        (``jit_stencil_prefix_scan``) in a profiler trace."""
        K, p = self.num_lanes, self.p
        i32 = jnp.int32
        T = ev.ts.shape[-1]
        bools = jnp.stack(
            [
                jnp.broadcast_to(
                    jnp.asarray(
                        pr(ev.key, ev.value, ev.ts, self._states), bool
                    ),
                    (K, T),
                )
                & ev.valid
                for pr in self._preds
            ],
            axis=-1,
        )  # [K, T, p]
        offs = jnp.asarray(ev.off, i32)
        ts = jnp.asarray(ev.ts, i32)
        b0 = bools[..., 0]
        # Seed version at each batch slot: 1 + begin-accepts strictly
        # before it (the version the untiered seed hands the run it
        # creates there — the seed bumps on every accept, not only on
        # completed prefixes).
        sver = 1 + carry.cnt[:, None] + (
            jnp.cumsum(b0.astype(i32), axis=1) - b0.astype(i32)
        )

        ext_b = jnp.concatenate([carry.bools, bools], axis=1)
        ext_off = jnp.concatenate([carry.offs, offs], axis=1)
        ext_ts = jnp.concatenate([carry.ts, ts], axis=1)
        ext_sver = jnp.concatenate([carry.sver, sver], axis=1)

        # fire[k, t] = AND_j ext_b[k, t+j, j]: stage j saw event t-p+1+j.
        fire = ext_b[:, 0:T, 0]
        for j in range(1, p):
            fire = fire & ext_b[:, j : j + T, j]
        offs_out = jnp.stack(
            [ext_off[:, j : j + T] for j in range(p)], axis=-1
        )
        # Window anchor: the event the untiered run's start_ts settles on
        # (the second window event for p >= 2 — re-anchored while the run
        # identity is the BEGIN-typed stage — else the root itself).
        a = min(1, p - 1)
        anchor = ext_ts[:, a : a + T]
        sver_out = ext_sver[:, 0:T]

        # New carry: the trailing p-1 *valid* columns (valid slots form a
        # per-lane prefix, so they end at column c = carry + valid count).
        c = jnp.sum(ev.valid, axis=1).astype(i32)
        carry_b = jax.vmap(
            lambda row, start: jax.lax.dynamic_slice(
                row, (start, 0), (p - 1, p)
            )
        )(ext_b, c)
        slice1 = lambda row, start: jax.lax.dynamic_slice(
            row, (start,), (p - 1,)
        )
        new_carry = PrefixCarry(
            bools=carry_b,
            offs=jax.vmap(slice1)(ext_off, c),
            ts=jax.vmap(slice1)(ext_ts, c),
            sver=jax.vmap(slice1)(ext_sver, c),
            cnt=carry.cnt + jnp.sum(b0.astype(i32), axis=1),
            screened=carry.screened
            + jnp.sum(ev.valid.astype(i32), axis=1),
            fires=carry.fires + jnp.sum(fire.astype(i32), axis=1),
            promotions=carry.promotions,
        )
        return new_carry, PromoOutput(fire, offs_out, anchor, sver_out)
