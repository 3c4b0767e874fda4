"""Dense predicate matrix: every distinct bank predicate once per batch.

The multi-tenant bank (``parallel/tenantbank.py``) screens N queries'
strict-contiguity prefixes over one shared ``[K, T]`` batch.  Naively that
is ``sum_q prefix_len(q)`` predicate evaluations per event; after the
bank compile pass (``compiler/multitenant.py: plan_bank``) the distinct
prefix predicates form a *column table*, and this module evaluates that
table as one dense ``[K, T, C]`` boolean matrix in a single fused pass —
each distinct predicate touches the batch exactly once, no matter how
many queries reference it.  Every query's prefix is then a gather of
``p`` columns (``group_bools``), and the whole frontier advances with
one vmapped stencil recurrence (``bank_prefix_scan``).

Bit-identity contract: ``single_prefix_scan`` is the post-predicate math
of ``engine/stencil.py: StencilPrefix.stencil_prefix_scan``, verbatim —
integer and boolean ops only, so vmapping it over a query axis is exact, and a
tenant bank's per-query promotions equal the promotions ``StencilPrefix``
would have produced for that query alone.  Column values are exact too:
a *shared* column is provably state-independent (``reads_states``), so
evaluating it under an empty states env equals evaluating it under any
owner's fold-state inits; a *private* (stateful or unkeyable) column is
evaluated under its owning query's decoded init env — exactly
``StencilPrefix._states``.

The residual (NFA-tier) analog of this matrix lives inside the engine
step itself: ``engine/matcher.py: _build_step`` splits the merged
dispatch table into event-level entries (evaluated once per event and
broadcast across runs — the per-step rows of the same conceptual matrix)
and run-level entries, on the jnp path and both Pallas kernels.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kafkastreams_cep_tpu.compiler.multitenant import PrefixColumn
from kafkastreams_cep_tpu.compiler.tables import TransitionTables
from kafkastreams_cep_tpu.engine.matcher import ArrayStates, EventBatch
from kafkastreams_cep_tpu.engine.stencil import PrefixCarry, PromoOutput


def owner_states(tables: TransitionTables) -> ArrayStates:
    """The fold-state *init* environment a prefix predicate evaluates
    against (``engine/stencil.py: StencilPrefix`` builds the same view):
    prefix stages precede every fold update, so an untiered run still in
    its prefix always sees exactly these values."""
    return ArrayStates(
        {
            name: (
                jnp.asarray(init, jnp.float32)
                if dt == "float32"
                else jnp.asarray(init, jnp.int32)
            )
            for name, init, dt in zip(
                tables.state_names, tables.state_inits, tables.state_dtypes
            )
        }
    )


def build_matrix(
    columns: Sequence[PrefixColumn],
    owner_tables: Sequence[TransitionTables],
    disabled: Sequence[int] = (),
):
    """A fused evaluator ``matrix(ev) -> [K, T, C]`` for the bank's
    prefix column table.

    Each column is one distinct predicate; shared columns get an empty
    states env (state-independence is proven, so the env is
    unobservable), private ones their owner's init env.  Values are
    ANDed with ``ev.valid`` so padded slots never fire — the same
    masking ``StencilPrefix.stencil_prefix_scan`` applies per stage.

    ``disabled`` columns (tenant quarantine — ``parallel/tenantbank.py``
    gates out every column used *only* by quarantined queries) are
    emitted as constant ``False`` without calling the predicate at all:
    a quarantined tenant's poisoned predicate can neither raise at trace
    time nor consume screen work, and its users gather only ``False`` —
    bit-identical to the screen of a bank that never contained them.
    """
    dis = frozenset(int(c) for c in disabled)
    envs = [
        ArrayStates({}) if col.shared else owner_states(
            owner_tables[col.owner]
        )
        for col in columns
    ]

    def matrix(ev: EventBatch) -> jnp.ndarray:
        K, T = ev.valid.shape
        dark = jnp.zeros((K, T), bool)
        return jnp.stack(
            [
                dark
                if ci in dis
                else (
                    jnp.broadcast_to(
                        jnp.asarray(
                            col.pred(ev.key, ev.value, ev.ts, env), bool
                        ),
                        (K, T),
                    )
                    & ev.valid
                )
                for ci, (col, env) in enumerate(zip(columns, envs))
            ],
            axis=-1,
        )

    return matrix


def group_bools(matrix: jnp.ndarray, sigs: np.ndarray) -> jnp.ndarray:
    """Gather one prefix group's stage booleans from the dense matrix.

    ``sigs`` is the group's ``[Nq, p]`` column-id table (every member has
    the same prefix length); returns ``[Nq, K, T, p]`` — query-major so
    the leading axis vmaps straight into :func:`bank_prefix_scan`.
    """
    cols = jnp.asarray(np.asarray(sigs, dtype=np.int32))
    return jnp.transpose(matrix[:, :, cols], (2, 0, 1, 3))


def single_prefix_scan(p: int):
    """The prefix recurrence for one query, predicates already evaluated.

    ``scan(carry, bools, offs, ts, valid) -> (carry, PromoOutput)`` is
    ``StencilPrefix.stencil_prefix_scan`` from its ``bools`` line down,
    verbatim — see the module docstring for why that equivalence is the
    whole correctness argument.
    """
    i32 = jnp.int32

    def scan(
        carry: PrefixCarry,
        bools: jnp.ndarray,  # [K, T, p], valid-masked
        offs: jnp.ndarray,  # [K, T] int32
        ts: jnp.ndarray,  # [K, T] int32
        valid: jnp.ndarray,  # [K, T] bool
    ) -> Tuple[PrefixCarry, PromoOutput]:
        T = ts.shape[-1]
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
        c = jnp.sum(valid, axis=1).astype(i32)
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
            screened=carry.screened + jnp.sum(valid.astype(i32), axis=1),
            fires=carry.fires + jnp.sum(fire.astype(i32), axis=1),
            promotions=carry.promotions,
        )
        return new_carry, PromoOutput(fire, offs_out, anchor, sver_out)

    return scan


def bank_prefix_scan(p: int):
    """The recurrence for a whole prefix group: ``scan(carries, bools_q,
    ev) -> (carries, PromoOutput)`` with carries/bools/outputs carrying a
    leading ``[Nq]`` query axis and the event batch shared.  One fused
    dispatch advances every member query's screen.
    """
    one = single_prefix_scan(p)

    def scan(carries: PrefixCarry, bools_q: jnp.ndarray, ev: EventBatch):
        offs = jnp.asarray(ev.off, jnp.int32)
        ts = jnp.asarray(ev.ts, jnp.int32)
        return jax.vmap(one, in_axes=(0, 0, None, None, None))(
            carries, bools_q, offs, ts, ev.valid
        )

    return scan


def init_carries(num_queries: int, num_lanes: int, p: int) -> PrefixCarry:
    """``[Nq]``-stacked :class:`PrefixCarry` — per query, exactly
    ``StencilPrefix.init_carry`` (fresh-screen seed version 1)."""
    Nq, K = int(num_queries), int(num_lanes)
    i32 = jnp.int32
    z = jnp.zeros((Nq, K), i32)
    return PrefixCarry(
        bools=jnp.zeros((Nq, K, p - 1, p), bool),
        offs=jnp.full((Nq, K, p - 1), -1, i32),
        ts=jnp.zeros((Nq, K, p - 1), i32),
        sver=jnp.ones((Nq, K, p - 1), i32),
        cnt=z,
        screened=z,
        fires=z,
        promotions=z,
    )
