"""Device shared versioned buffer — the SASE match DAG as a fixed slab.

Array equivalent of the host dict buffer (``nfa/buffer.py``) and the
reference ``nfa/buffer/impl/KVSharedVersionedBuffer.java``.  One slab holds
the buffer for ONE key/partition; the engine ``vmap``s these functions over
the key axis.

Representation (``E`` entries × ``MP`` predecessor pointers × depth ``D``):

* an *entry* is keyed by ``(stage, off)`` — the stage's canonical identity
  position (``compiler/tables.py``) and the event offset, the array form of
  ``StackEventKey`` (``StackEventKey.java:28-54``); ``stage == -1`` marks a
  free slot;
* each entry carries a refcount and an ordered list of Dewey-versioned
  predecessor pointers (``TimedKeyValue.java:27-45``); a pointer with
  ``pstage == -1`` is the null-predecessor run origin
  (``KVSharedVersionedBuffer.java:117-128``).

Semantics preserved exactly (differentially tested against the host buffer):

* ``put`` requires the predecessor entry to exist — the reference throws
  (``KVSharedVersionedBuffer.java:86-89``); under ``jit`` we count it in
  ``missing`` and drop the write;
* ``put_first`` overwrites unconditionally (``:117-128``);
* walks select, at each hop, the **first** pointer (insertion order) whose
  version is compatible with the walk version, then adopt that pointer's
  version (``TimedKeyValue.java:83-92``);
* refcount decrements floor at zero (``TimedKeyValue.java:59-61``); an entry
  is deleted only when ``remove`` and ``refs == 0`` and it has at most one
  predecessor; the traversed pointer is pruned when ``refs == 0``
  (``KVSharedVersionedBuffer.java:147-171``);
* capacity limits (slab full, pointer list full, walk bound) have no
  reference analog; overflows are counted, never raised.

Implementation note: no traced-index scatters/gathers/dynamic-slices.
Every indexed read/write goes through one-hot masked selects (``_oh`` /
``_get_e`` / ``_get_ej``), which XLA fuses into the surrounding
elementwise work.  On TPU, batched-index scatter/gather ops do not fuse —
each becomes a standalone kernel whose launch overhead, times the
thousands of tiny slab ops per step, dominated the engine's early runtime
by ~50x (and scaled linearly with the vmapped lane count).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from kafkastreams_cep_tpu.ops import dewey_ops
from kafkastreams_cep_tpu.ops.onehot import (
    get_at as _get_e,
    get_at2 as _get_ej,
    oh as _oh,
)


class SlabState(NamedTuple):
    stage: jnp.ndarray  # [E] int32 — identity stage position; -1 free
    off: jnp.ndarray  # [E] int32 — event offset
    refs: jnp.ndarray  # [E] int32
    npreds: jnp.ndarray  # [E] int32
    pstage: jnp.ndarray  # [E, MP] int32 — -1 = null pointer (run origin)
    poff: jnp.ndarray  # [E, MP] int32
    pver: jnp.ndarray  # [E, MP, D] int32
    pvlen: jnp.ndarray  # [E, MP] int32
    full_drops: jnp.ndarray  # scalar int32 — entry allocation failures
    pred_drops: jnp.ndarray  # scalar int32 — pointer-list overflow drops
    missing: jnp.ndarray  # scalar int32 — lookups the reference would NPE on
    trunc: jnp.ndarray  # scalar int32 — walks cut short by the walk bound
    collisions: jnp.ndarray  # scalar int32 — same-entry same-hop meetings of
    #   two lockstep remove-walkers: the exact trigger for prune/delete
    #   attribution deviating from the reference's sequential order.  Always
    #   0 on the default paths (walker_budget=1 runs walkers alone; the
    #   Pallas kernel is sequential by construction); nonzero means a
    #   walker_budget>1 run may have diverged (see EngineConfig).
    # --- two-tier telemetry (zero when hot_entries == 0; see module note
    #     "Two-tier layout" below).  Not capacity counters: they never
    #     indicate loss, only where walk hops resolved.
    hot_hits: jnp.ndarray  # scalar int32 — walk hops resolved in the hot tier
    hot_misses: jnp.ndarray  # scalar int32 — walk hops not resolved hot
    overflow_walks: jnp.ndarray  # scalar int32 — walk hops resolved overflow
    demotions: jnp.ndarray  # scalar int32 — hot -> overflow entry moves
    # --- walk-cost telemetry (never loss indicators): every active hop of
    #     every walker is classified exactly once by walker class, so the
    #     reduce-width perf model (PERF.md walk-pass cost model, PROFILE_r06:
    #     per-hop masked reduces x lockstep trip counts) is measurable on CPU
    #     CI without a chip.
    walk_hops: jnp.ndarray  # scalar int32 — branch/dead-removal walker hops
    extract_hops: jnp.ndarray  # scalar int32 — eager in-step extraction hops
    drain_hops: jnp.ndarray  # scalar int32 — deferred drain-pass hops (lazy)
    # --- per-stage walk-cost attribution (EngineConfig.stage_attribution):
    #     hop tallies keyed by the walker's CURRENT stage at each hop, the
    #     per-stage half of the continuous-profiling layer.  Shape [S]
    #     (S = the pattern's stage count) when attribution is on, [0] when
    #     off — a zero-size array adds no device work and no kernel
    #     plumbing (both Pallas kernels skip it at trace time).  Never a
    #     loss indicator.
    stage_hops: jnp.ndarray  # [S] int32 — walk hops by current stage


def make(
    num_entries: int, max_preds: int, depth: int, num_stages: int = 0
) -> SlabState:
    E, MP, D = num_entries, max_preds, depth
    i32 = jnp.int32
    return SlabState(
        stage=jnp.full((E,), -1, dtype=i32),
        off=jnp.full((E,), -1, dtype=i32),
        refs=jnp.zeros((E,), dtype=i32),
        npreds=jnp.zeros((E,), dtype=i32),
        pstage=jnp.full((E, MP), -1, dtype=i32),
        poff=jnp.full((E, MP), -1, dtype=i32),
        pver=jnp.zeros((E, MP, D), dtype=i32),
        pvlen=jnp.zeros((E, MP), dtype=i32),
        full_drops=jnp.zeros((), dtype=i32),
        pred_drops=jnp.zeros((), dtype=i32),
        missing=jnp.zeros((), dtype=i32),
        trunc=jnp.zeros((), dtype=i32),
        collisions=jnp.zeros((), dtype=i32),
        hot_hits=jnp.zeros((), dtype=i32),
        hot_misses=jnp.zeros((), dtype=i32),
        overflow_walks=jnp.zeros((), dtype=i32),
        demotions=jnp.zeros((), dtype=i32),
        walk_hops=jnp.zeros((), dtype=i32),
        extract_hops=jnp.zeros((), dtype=i32),
        drain_hops=jnp.zeros((), dtype=i32),
        stage_hops=jnp.zeros((num_stages,), dtype=i32),
    )


def find(slab: SlabState, stage, off) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Entry index for ``(stage, off)`` and whether it exists."""
    hit = (slab.stage == stage) & (slab.off == off)
    return jnp.argmax(hit), jnp.any(hit)


def _alloc(slab: SlabState):
    free = slab.stage < 0
    return jnp.argmax(free), jnp.any(free)


# ---------------------------------------------------------------------------
# Two-tier layout (``hot_entries`` static knob, 0 = legacy single tier)
#
# Slots ``[0, hot_entries)`` are the *hot tier*, the rest the *overflow
# tier*.  New entries always land in the hot tier: a free hot slot if one
# exists, else the least-recent hot entry (minimum event offset — offsets
# are monotone per lane, so the offset IS the recency; ties break to the
# lowest index) is *demoted* into a free overflow slot and its hot slot
# reused.  An allocation fails only when the WHOLE slab is full — exactly
# the single-tier drop condition — so ``full_drops`` and every other
# overflow counter stay bit-identical to the single-tier engine; only the
# slot an entry occupies (its tier placement) may differ.
#
# Lookups key on ``(stage, off)``, which is unique across the whole slab,
# so results are placement-independent; this jnp path therefore keeps its
# full-slab masked lookups (under XLA both tiers would be computed anyway)
# and only *accounts* tier residency via the hot_hits / hot_misses /
# overflow_walks counters.  The Pallas walk kernel (``ops/walk_kernel.py``)
# exploits the same layout structurally: the per-hop reduce runs over the
# hot rows only and the overflow rows are touched under a block-level
# ``pl.when`` that skips entirely when every lane of the block resolved
# hot — the E-linear hop cost drops to E_hot-linear on the common path
# (PERF.md, walk-pass cost model).
# ---------------------------------------------------------------------------


def _alloc_slot(slab: SlabState, hot_entries: int, want):
    """Allocation slot for one new entry, two-tier aware.

    Returns ``(slab, e, ok)``.  ``want`` gates the (slab-mutating)
    demotion: pass ``enable & ~found`` so lookups that reuse an existing
    entry never demote.  With ``hot_entries == 0`` this is :func:`_alloc`.
    """
    free = slab.stage < 0
    if not hot_entries:
        return slab, jnp.argmax(free), jnp.any(free)
    E = slab.stage.shape[0]
    EH = hot_entries
    i32 = jnp.int32
    idx = jnp.arange(E, dtype=i32)
    is_hot = idx < EH
    free_hot = free & is_hot
    free_ov = free & ~is_hot
    any_fh = jnp.any(free_hot)
    any_fo = jnp.any(free_ov)
    e_hot = jnp.argmax(free_hot).astype(i32)
    e_ov = jnp.argmax(free_ov).astype(i32)
    # Demotion victim: least-recent (min event offset) occupied hot entry,
    # first index on ties — deterministic, matched by both Pallas kernels.
    occ_hot = ~free & is_hot
    okey = jnp.where(occ_hot, slab.off, i32(1 << 30))
    victim = jnp.argmin(okey).astype(i32)
    demote = jnp.asarray(want) & ~any_fh & any_fo

    vm = _oh(victim, E) & demote
    om = _oh(e_ov, E) & demote

    def mv(field):
        m_v = vm.reshape((E,) + (1,) * (field.ndim - 1))
        m_o = om.reshape((E,) + (1,) * (field.ndim - 1))
        row = jnp.sum(jnp.where(m_v, field, 0), axis=0)
        return jnp.where(m_o, row[None].astype(field.dtype), field)

    slab = slab._replace(
        stage=jnp.where(vm, -1, mv(slab.stage)),
        off=jnp.where(vm, -1, mv(slab.off)),
        refs=mv(slab.refs),
        npreds=mv(slab.npreds),
        pstage=mv(slab.pstage),
        poff=mv(slab.poff),
        pver=mv(slab.pver),
        pvlen=mv(slab.pvlen),
        demotions=slab.demotions + jnp.where(demote, 1, 0),
    )
    e = jnp.where(any_fh, e_hot, victim)
    return slab, e, any_fh | any_fo


def _tier_counts(slab: SlabState, active, found_hot, found):
    """Walk-hop tier accounting: ``active`` walkers whose entry resolved in
    the hot tier / did not / resolved in the overflow tier.  Works on any
    matching bool shapes (scalar per-walker or ``[P]`` lockstep)."""
    i32 = jnp.int32
    return slab._replace(
        hot_hits=slab.hot_hits
        + jnp.sum((active & found_hot).astype(i32)),
        hot_misses=slab.hot_misses
        + jnp.sum((active & ~found_hot).astype(i32)),
        overflow_walks=slab.overflow_walks
        + jnp.sum((active & ~found_hot & found).astype(i32)),
    )


def _hop_counts(
    slab: SlabState, active, want_out=None, kind: str = "walk", stage=None
):
    """Classify one hop's active walkers into the walk-cost counters.

    ``want_out`` (when given) splits the pool: emitting walkers count to
    the ``kind`` class ("extract" eager in-step, "drain" deferred pass),
    non-emitting walkers to ``walk_hops``.  Without it, every active
    walker counts to ``kind``.  Static ``kind`` keeps the counter choice
    trace-time, mirroring the Pallas kernels' static routing.

    ``stage`` (the walkers' current stage, scalar or ``[P]``) additionally
    attributes every active hop to its ``stage_hops[stage]`` row when the
    slab carries stage attribution (``stage_hops.shape[-1] > 0``); with
    attribution off the tally is skipped at trace time.
    """
    i32 = jnp.int32
    if want_out is None:
        n_emit = jnp.sum(jnp.asarray(active).astype(i32))
        n_walk = jnp.zeros((), i32)
    else:
        n_emit = jnp.sum((active & want_out).astype(i32))
        n_walk = jnp.sum((active & ~want_out).astype(i32))
    upd = {"walk_hops": slab.walk_hops + n_walk}
    if kind == "walk":
        upd["walk_hops"] = upd["walk_hops"] + n_emit
    elif kind == "extract":
        upd["extract_hops"] = slab.extract_hops + n_emit
    elif kind == "drain":
        upd["drain_hops"] = slab.drain_hops + n_emit
    else:  # pragma: no cover - trace-time misuse
        raise ValueError(f"unknown hop kind {kind!r}")
    S = int(slab.stage_hops.shape[-1])
    if S and stage is not None:
        oh = (
            jnp.asarray(stage, i32)[..., None]
            == jnp.arange(S, dtype=i32)
        ) & jnp.asarray(active)[..., None]
        upd["stage_hops"] = slab.stage_hops + jnp.sum(
            oh.astype(i32).reshape(-1, S), axis=0
        )
    return slab._replace(**upd)


def _select_pointer(slab: SlabState, e, qver, qlen):
    """First version-compatible predecessor pointer of entry ``e``
    (``TimedKeyValue.java:83-92``)."""
    mp = slab.pstage.shape[1]
    valid = jnp.arange(mp, dtype=jnp.int32) < _get_e(slab.npreds, e)
    compat = jax.vmap(dewey_ops.is_compatible, in_axes=(None, None, 0, 0))(
        qver, qlen, _get_e(slab.pver, e), _get_e(slab.pvlen, e)
    )
    hit = compat & valid
    return jnp.argmax(hit), jnp.any(hit)


def _append_pointer(slab: SlabState, e, pstage, poff, ver, vlen, enable):
    """Append a pointer to entry ``e``'s list; drops (counted) when full."""
    E, mp = slab.pstage.shape
    n = _get_e(slab.npreds, e)
    full = n >= mp
    do = enable & ~full
    slot = jnp.minimum(n, mp - 1)
    m2 = (_oh(e, E)[:, None] & _oh(slot, mp)[None, :]) & do

    return slab._replace(
        pstage=jnp.where(m2, pstage, slab.pstage),
        poff=jnp.where(m2, poff, slab.poff),
        pver=jnp.where(m2[:, :, None], ver[None, None, :], slab.pver),
        pvlen=jnp.where(m2, vlen, slab.pvlen),
        npreds=slab.npreds + jnp.where(_oh(e, E) & do, 1, 0),
        pred_drops=slab.pred_drops + jnp.where(enable & full, 1, 0),
    )


def _prune_pointer(slab: SlabState, e, j, enable):
    """Remove pointer ``j`` of entry ``e``, shifting later pointers left to
    keep insertion order (``TimedKeyValue.removePredecessor``)."""
    E, mp = slab.pstage.shape
    idx = jnp.arange(mp, dtype=jnp.int32)
    # Shift-by-one as a static roll + mask: slot i >= j takes slot i+1's
    # value (the last slot keeps its own — matching min(i+1, mp-1)).
    m2 = (_oh(e, E)[:, None] & (idx[None, :] >= j)) & enable

    def shift(field, m):
        nxt = jnp.concatenate([field[:, 1:], field[:, -1:]], axis=1)
        return jnp.where(m, nxt, field)

    return slab._replace(
        pstage=shift(slab.pstage, m2),
        poff=shift(slab.poff, m2),
        pvlen=shift(slab.pvlen, m2),
        pver=shift(slab.pver, m2[:, :, None]),
        npreds=slab.npreds - jnp.where(_oh(e, E) & enable, 1, 0),
    )


def put_first(
    slab: SlabState, stage, off, ver, vlen, enable=True, hot_entries: int = 0
) -> SlabState:
    """First-stage put: fresh entry whose single null-predecessor pointer
    records the run version; overwrites any existing entry
    (``KVSharedVersionedBuffer.java:117-128``)."""
    enable = jnp.asarray(enable)
    existing, found = find(slab, stage, off)
    slab, free, has_free = _alloc_slot(slab, hot_entries, enable & ~found)
    e = jnp.where(found, existing, free)
    ok = enable & (found | has_free)
    m1 = _oh(e, slab.stage.shape[0]) & ok

    slab = slab._replace(
        stage=jnp.where(m1, stage, slab.stage),
        off=jnp.where(m1, off, slab.off),
        refs=jnp.where(m1, 1, slab.refs),
        npreds=jnp.where(m1, 0, slab.npreds),
        full_drops=slab.full_drops + jnp.where(enable & ~found & ~has_free, 1, 0),
    )
    return _append_pointer(slab, e, jnp.int32(-1), jnp.int32(-1), ver, vlen, ok)


def put(slab: SlabState, cur_stage, cur_off, prev_stage, prev_off, ver, vlen, enable=True, hot_entries: int = 0) -> SlabState:
    """Append a versioned predecessor pointer to ``(cur_stage, cur_off)``.

    The predecessor entry must exist (``KVSharedVersionedBuffer.java:86-89``);
    a miss is counted and the write dropped.
    """
    enable = jnp.asarray(enable)
    _, prev_found = find(slab, prev_stage, prev_off)
    slab = slab._replace(missing=slab.missing + jnp.where(enable & ~prev_found, 1, 0))
    enable = enable & prev_found

    existing, found = find(slab, cur_stage, cur_off)
    slab, free, has_free = _alloc_slot(slab, hot_entries, enable & ~found)
    e = jnp.where(found, existing, free)
    create = enable & ~found & has_free
    ok = enable & (found | has_free)
    m1 = _oh(e, slab.stage.shape[0]) & create

    slab = slab._replace(
        stage=jnp.where(m1, cur_stage, slab.stage),
        off=jnp.where(m1, cur_off, slab.off),
        refs=jnp.where(m1, 1, slab.refs),
        npreds=jnp.where(m1, 0, slab.npreds),
        full_drops=slab.full_drops + jnp.where(enable & ~found & ~has_free, 1, 0),
    )
    return _append_pointer(slab, e, prev_stage, prev_off, ver, vlen, ok)


def branch(slab: SlabState, stage, off, ver, vlen, max_walk: int, enable=True, hot_entries: int = 0) -> SlabState:
    """Refcount-increment walk so shared prefixes survive sibling removal
    (``KVSharedVersionedBuffer.java:99-110``)."""

    def body(_, carry):
        slab, stage, off, qver, qlen, active = carry
        e, found = find(slab, stage, off)
        if hot_entries:
            slab = _tier_counts(
                slab, active, found & (e < hot_entries), found
            )
        slab = _hop_counts(slab, active, stage=stage)
        slab = slab._replace(missing=slab.missing + jnp.where(active & ~found, 1, 0))
        active = active & found
        slab = slab._replace(
            refs=slab.refs + jnp.where(_oh(e, slab.refs.shape[0]) & active, 1, 0)
        )
        j, sel = _select_pointer(slab, e, qver, qlen)
        nxt_stage = _get_ej(slab.pstage, e, j)
        active = active & sel & (nxt_stage >= 0)
        stage = jnp.where(active, nxt_stage, stage)
        off = jnp.where(active, _get_ej(slab.poff, e, j), off)
        qver = jnp.where(active, _get_ej(slab.pver, e, j), qver)
        qlen = jnp.where(active, _get_ej(slab.pvlen, e, j), qlen)
        return slab, stage, off, qver, qlen, active

    init = (
        slab,
        jnp.asarray(stage, jnp.int32),
        jnp.asarray(off, jnp.int32),
        jnp.asarray(ver, jnp.int32),
        jnp.asarray(vlen, jnp.int32),
        jnp.asarray(enable),
    )
    out = jax.lax.fori_loop(0, max_walk, body, init)
    slab, still_active = out[0], out[5]
    # A walk still active after max_walk hops was truncated: refcounts along
    # the untraversed tail were not incremented (no reference analog).
    return slab._replace(trunc=slab.trunc + jnp.where(still_active, 1, 0))


def peek(
    slab: SlabState,
    stage,
    off,
    ver,
    vlen,
    max_walk: int,
    remove: bool,
    enable=True,
    hot_entries: int = 0,
    hop_kind: str = "extract",
):
    """Backward pointer walk assembling a match, final stage first.

    Returns ``(slab, out_stage[max_walk], out_off[max_walk], count)``; hops
    beyond the walk bound are dropped (no reference analog — counted via the
    returned ``count`` saturating at ``max_walk``).  With ``remove`` this is
    ``SharedVersionedBuffer.remove`` (refcount GC + pointer pruning);
    without, ``get`` — which still decrements refcounts, a preserved quirk of
    ``KVSharedVersionedBuffer.peek`` (``:156``).
    """
    L = max_walk
    out_stage = jnp.full((L,), -1, dtype=jnp.int32)
    out_off = jnp.full((L,), -1, dtype=jnp.int32)

    def body(i, carry):
        slab, stage, off, qver, qlen, active, out_stage, out_off, count = carry
        E = slab.stage.shape[0]
        e, found = find(slab, stage, off)
        if hot_entries:
            slab = _tier_counts(
                slab, active, found & (e < hot_entries), found
            )
        slab = _hop_counts(slab, active, kind=hop_kind, stage=stage)
        slab = slab._replace(missing=slab.missing + jnp.where(active & ~found, 1, 0))
        active = active & found
        m1 = _oh(e, E) & active

        refs_left = jnp.maximum(_get_e(slab.refs, e) - 1, 0)  # floors at zero
        slab = slab._replace(refs=jnp.where(m1, refs_left, slab.refs))
        delete = (
            active & remove & (refs_left == 0) & (_get_e(slab.npreds, e) <= 1)
        )
        md = _oh(e, E) & delete
        slab = slab._replace(
            stage=jnp.where(md, -1, slab.stage),
            off=jnp.where(md, -1, slab.off),
        )

        mi = _oh(i, out_stage.shape[0]) & active
        out_stage = jnp.where(mi, stage, out_stage)
        out_off = jnp.where(mi, off, out_off)
        count = count + jnp.where(active, 1, 0)

        j, sel = _select_pointer(slab, e, qver, qlen)
        sel = sel & active
        prune = sel & remove & (refs_left == 0)
        nxt_stage = _get_ej(slab.pstage, e, j)
        nxt_off = _get_ej(slab.poff, e, j)
        nxt_ver = _get_ej(slab.pver, e, j)
        nxt_len = _get_ej(slab.pvlen, e, j)
        slab = _prune_pointer(slab, e, j, prune)

        active = sel & (nxt_stage >= 0)
        stage = jnp.where(active, nxt_stage, stage)
        off = jnp.where(active, nxt_off, off)
        qver = jnp.where(active, nxt_ver, qver)
        qlen = jnp.where(active, nxt_len, qlen)
        return slab, stage, off, qver, qlen, active, out_stage, out_off, count

    init = (
        slab,
        jnp.asarray(stage, jnp.int32),
        jnp.asarray(off, jnp.int32),
        jnp.asarray(ver, jnp.int32),
        jnp.asarray(vlen, jnp.int32),
        jnp.asarray(enable),
        out_stage,
        out_off,
        jnp.zeros((), dtype=jnp.int32),
    )
    slab, _, _, _, _, still_active, out_stage, out_off, count = jax.lax.fori_loop(
        0, L, body, init
    )
    # Truncated extraction: the untraversed tail keeps its refcounts (a leak
    # the caller can see via this counter) and the returned hops are partial.
    slab = slab._replace(trunc=slab.trunc + jnp.where(still_active, 1, 0))
    return slab, out_stage, out_off, count


def live_entries(slab: SlabState) -> jnp.ndarray:
    """Number of occupied slots (host/diagnostic helper)."""
    return jnp.sum(slab.stage >= 0)


def mark_sweep(slab: SlabState, run_stage, run_off, depth: int) -> SlabState:
    """Free every entry unreachable from live run state — the deferred
    compaction scan of SURVEY §7 step 4.

    The reference never needs this: its refcount GC
    (``KVSharedVersionedBuffer.java:147-171``) runs over unbounded walks.
    This engine's walks are bounded by ``max_walk``, so a truncated removal
    walk strands its untraversed tail with elevated refcounts (counted in
    ``trunc``) and the slab fills over long streams.  The sweep is
    *observably equivalent* to the reference's state: every future buffer
    operation starts from live run state — consuming puts reference a run's
    pointer event, branch/removal/extraction walks start at a run's pointer
    event or the current event — and walks take at most ``max_walk`` hops,
    so an entry not reachable within ``depth >= max_walk`` pointer hops of
    any live run can never be read or written again.  Freeing it changes no
    future output and no counter.

    ``run_off`` is the ``[N]`` array of the live runs' pointer-event
    offsets (``off < 0`` rows ignored); ``run_stage`` is accepted for
    signature symmetry but roots are keyed by offset alone — buffer
    operations may start at any *stage* carrying a run's pointer offset
    (e.g. a branch walk starts at the branching frame's predecessor stage,
    a chained put references the same offset under the put frame's stage).
    Marking follows ALL pointers (not version-filtered) — conservative
    over every possible future walk version.  Vmappable over a leading
    lane axis.
    """
    del run_stage  # roots are offset-keyed; see docstring
    E, MP = slab.pstage.shape
    run_off = jnp.asarray(run_off, jnp.int32)

    # Roots: every entry at any live run's pointer-event offset.
    root_hit = (slab.off[:, None] == run_off[None, :]) & (
        run_off[None, :] >= 0
    )  # [E, N]
    marked = jnp.any(root_hit, axis=1) & (slab.stage >= 0)

    # Adjacency: entry e reaches e' when any live pointer of e keys
    # (stage, off)[e'].  Reduced over MP up front — marking ignores which
    # pointer hit, and [E, E] is MP-times smaller than the [E, MP, E]
    # grid a naive formulation would hold live across the loop.
    valid_ptr = (
        jnp.arange(MP, dtype=jnp.int32)[None, :] < slab.npreds[:, None]
    ) & (slab.pstage >= 0)  # [E, MP]
    adj = jnp.any(
        (slab.pstage[:, :, None] == slab.stage[None, None, :])
        & (slab.poff[:, :, None] == slab.off[None, None, :])
        & valid_ptr[:, :, None],
        axis=1,
    )  # [E, E']

    def body(_, m):
        reach = jnp.any(adj & m[:, None], axis=0)  # [E']
        return m | (reach & (slab.stage >= 0))

    marked = jax.lax.fori_loop(0, depth, body, marked)

    free = ~marked
    return slab._replace(
        stage=jnp.where(free, -1, slab.stage),
        off=jnp.where(free, -1, slab.off),
        refs=jnp.where(free, 0, slab.refs),
        npreds=jnp.where(free, 0, slab.npreds),
    )


def walks_batched(
    slab: SlabState,
    en,
    stage,
    off,
    ver,
    vlen,
    is_remove,
    want_out,
    max_walk: int,
    collect: bool = True,
    hot_entries: int = 0,
    drain: bool = False,
):
    """ALL of one step's buffer walks — branch refcount walks, dead-run
    removals, and final-match extractions — in a single lockstep pass.

    ``is_remove[p]`` selects decrement/prune/delete semantics (dead/final
    walkers) vs. increment semantics (branch walkers); ``want_out[p]``
    walkers additionally emit their hops.  Merging the three phases is
    sound by the same refcount invariant as :func:`peek_batched`:
    per-entry refcount deltas commute (summed per hop), and only the last
    remaining traverser of a node can observe ``refs == 0``, so
    delete/prune attribution to the last same-hop remove-walker
    reproduces the sequential outcome regardless of phase interleaving.
    The engine-level A/B test (``sequential_slab``) and the oracle fuzz
    suite validate the merged order end to end.

    Returns ``(slab, out_stage [P, W], out_off [P, W], count [P])`` —
    rows meaningful only where ``want_out``.
    """
    E, MP = slab.pstage.shape
    D = slab.pver.shape[-1]
    P = jnp.asarray(stage).shape[0]
    W = max_walk
    i32 = jnp.int32
    f32 = jnp.float32
    mp_idx = jnp.arange(MP, dtype=i32)
    pidx = jnp.arange(P, dtype=i32)
    later = pidx[None, :] > pidx[:, None]

    # Safety of merging increments with removals: the only way a removal
    # could collect a node an in-flight branch walk still needs is a
    # refs==1 path shared by a branch walker and a dead walker of the SAME
    # run (cross-run shared nodes always carry one ref per lineage, i.e.
    # >= 2).  That cannot happen: a run that branches has a successor by
    # definition (matcher.py: has_succ = survivor | any branch), so it is
    # never dead in the same step.
    is_remove = jnp.asarray(is_remove)
    want_out = jnp.asarray(want_out)
    ptrs = _pack_ptrs(slab)  # read-only: prunes are tombstoned, not shifted
    valid0 = mp_idx[None, :] < slab.npreds[:, None]  # [E, MP] at phase start

    def cond(carry):
        active = carry[6]
        hops = carry[11]
        return jnp.any(active) & (hops < W)

    def body(carry):
        (slab, dead, stage, off, qver, qlen, active, out_stage, out_off,
         count, trunc, hops) = carry
        hit = (slab.stage[None, :] == stage[:, None]) & (
            slab.off[None, :] == off[:, None]
        )
        found = jnp.any(hit, axis=1)
        if hot_entries:
            slab = _tier_counts(
                slab, active, jnp.any(hit[:, :hot_entries], axis=1), found
            )
        slab = _hop_counts(
            slab, active, want_out, kind="drain" if drain else "extract",
            stage=stage,
        )
        slab = slab._replace(
            missing=slab.missing + jnp.sum((active & ~found).astype(i32))
        )
        active = active & found
        ham = hit & active[:, None]  # [P, E]

        m1 = jnp.any(ham, axis=0)
        inc = jnp.sum((ham & ~is_remove[:, None]).astype(i32), axis=0)
        dec = jnp.sum((ham & is_remove[:, None]).astype(i32), axis=0)
        refs_after_e = jnp.maximum(slab.refs + inc - dec, 0)
        refs_after = jnp.sum(jnp.where(ham, refs_after_e[None, :], 0), axis=1)
        slab = slab._replace(refs=jnp.where(m1, refs_after_e, slab.refs))

        # Queue-last remove-walker at each entry — the only one that may
        # collect (prune/delete) when refs reaches zero.
        arm = active & is_remove
        e = jnp.argmax(hit, axis=1)
        last = arm & ~jnp.any(
            (e[None, :] == e[:, None]) & later & arm[None, :], axis=1
        )
        # Two remove-walkers at one entry in one hop is the exact condition
        # under which last-walker attribution can deviate from sequential
        # order — count every extra walker so the deviation is observable
        # (EngineConfig.walker_budget; 0 by construction at budget=1).
        n_rm = jnp.sum((ham & is_remove[:, None]).astype(i32), axis=0)
        slab = slab._replace(
            collisions=slab.collisions + jnp.sum(jnp.maximum(n_rm - 1, 0))
        )

        # Row extraction stays a one-hot matmul over the full packed slab:
        # a batched gather (``jnp.take(ptrs, e, axis=0)``) was measured 4x
        # SLOWER end-to-end (41s vs 9.5s headline scan) — TPU dynamic
        # gathers in a while-loop body neither fuse nor vectorize.  The
        # einsum's full-slab re-read per hop is the remaining HBM cost the
        # Pallas walk kernel eliminates (state resident in VMEM).
        rows = _rows(ptrs, ham)
        pv, ps, po, pl = (
            rows[..., :D],
            rows[..., D],
            rows[..., D + 1],
            rows[..., D + 2],
        )
        live = valid0 & ~dead  # [E, MP]
        live_p = jnp.einsum(
            "pe,em->pm", ham.astype(f32), live.astype(f32),
            preferred_element_type=f32,
        ) > 0.5
        np_live = jnp.sum(live_p.astype(i32), axis=1)
        delete = last & collect & (refs_after == 0) & (np_live <= 1)
        md = jnp.any(hit & delete[:, None], axis=0)
        slab = slab._replace(
            stage=jnp.where(md, -1, slab.stage),
            off=jnp.where(md, -1, slab.off),
        )

        # Emit the hop for extraction walkers.
        emit = active & want_out
        mw = (jnp.arange(W, dtype=i32)[None, :] == count[:, None]) & emit[:, None]
        out_stage = jnp.where(mw, stage[:, None], out_stage)
        out_off = jnp.where(mw, off[:, None], out_off)
        count = count + jnp.where(emit, 1, 0)

        ok = _compat_rows(qver, qlen, pv, pl) & live_p
        j = jnp.argmax(ok, axis=1)
        sel = jnp.any(ok, axis=1) & active
        prune = sel & last & collect & (refs_after == 0)

        ohj = mp_idx[None, :] == j[:, None]
        tomb = jnp.einsum(
            "pe,pm->em", (hit & prune[:, None]).astype(f32), ohj.astype(f32),
            preferred_element_type=f32,
        ) > 0.5
        dead = dead | tomb
        slab = slab._replace(
            npreds=slab.npreds - jnp.sum(tomb.astype(i32), axis=1)
        )

        # Selected pointer row, all channels in one masked reduction.
        sel_row = jnp.sum(jnp.where(ohj[:, :, None], rows, 0), axis=1)  # [P, C]
        ns = sel_row[:, D]
        nactive = sel & (ns >= 0)
        stage = jnp.where(nactive, ns.astype(i32), stage)
        off = jnp.where(nactive, sel_row[:, D + 1].astype(i32), off)
        qver = jnp.where(nactive[:, None], sel_row[:, :D], qver)
        qlen = jnp.where(nactive, sel_row[:, D + 2].astype(i32), qlen)

        # Extraction walkers get W emitting hops; others walk at most W
        # hops total (the while bound) — both truncations are counted.
        budget_out = emit & (count >= W)
        trunc = trunc + jnp.sum((budget_out & nactive).astype(i32))
        active = nactive & ~budget_out
        return (slab, dead, stage, off, qver, qlen, active, out_stage,
                out_off, count, trunc, hops + 1)

    init = (
        slab,
        jnp.zeros((E, MP), bool),
        jnp.asarray(stage, i32),
        jnp.asarray(off, i32),
        jnp.asarray(ver, f32),
        jnp.asarray(vlen, i32),
        jnp.asarray(en),
        jnp.full((P, W), -1, i32),
        jnp.full((P, W), -1, i32),
        jnp.zeros((P,), i32),
        jnp.zeros((), i32),
        jnp.zeros((), i32),
    )
    (slab, dead, _, _, _, _, active, out_stage, out_off, count, trunc, _) = (
        jax.lax.while_loop(cond, body, init)
    )

    # Apply tombstones: stable-compact surviving pointers to the front.
    any_dead = jnp.any(dead, axis=1)
    live = valid0 & ~dead
    tgt = jnp.cumsum(live.astype(i32), axis=1) - 1
    perm = live[:, :, None] & (mp_idx[None, None, :] == tgt[:, :, None])

    def comp2(field):
        v = jnp.sum(jnp.where(perm, field[:, :, None], 0), axis=1)
        return jnp.where(any_dead[:, None], v.astype(field.dtype), field)

    def comp3(field):
        v = jnp.sum(jnp.where(perm[..., None], field[:, :, None, :], 0), axis=1)
        return jnp.where(any_dead[:, None, None], v.astype(field.dtype), field)

    slab = slab._replace(
        pstage=comp2(slab.pstage),
        poff=comp2(slab.poff),
        pvlen=comp2(slab.pvlen),
        pver=comp3(slab.pver),
        trunc=slab.trunc + trunc + jnp.sum(active.astype(i32)),
    )
    return slab, out_stage, out_off, count


# ---------------------------------------------------------------------------
# Batched per-step kernels
#
# The sequential entry points above apply ONE op per call; chained under the
# engine's per-run loop that costs a full pass over the pointer arrays per op
# (HBM-bound) or a serial kernel chain (launch-bound).  The batched kernels
# apply ALL of one event-step's ops in a constant number of wide passes:
#
# * ``puts_batched``   — the step's consuming puts, in queue/frame order,
#   grouped by target entry (every consuming put of one step targets the
#   *current* event, so groups are keyed by stage);
# * ``branch_batched`` — all branch refcount walks in lockstep.  Increments
#   commute and pointer selection never reads refcounts, so lockstep is
#   *exactly* sequential order;
# * ``peek_batched``   — all removal walks in lockstep with a same-entry
#   stall protocol: when two walkers meet at one entry in the same hop, the
#   later (higher run-slot) walker waits, so per-entry mutation order equals
#   the reference's queue order.  Walks are backward over strictly older
#   events, so no walker revisits an entry and stalls always clear.
#
# Walk-phase row extraction runs as one f32 matmul per hop on the packed
# pointer tensor (ver ∘ pstage ∘ poff ∘ pvlen) — MXU work; all packed values
# are small ints (< 2^24), exact in f32.
# ---------------------------------------------------------------------------


class PutOps(NamedTuple):
    """One step's consuming puts, flattened in reference order (queue order,
    then frame order within a run)."""

    en: jnp.ndarray  # [P] bool
    first: jnp.ndarray  # [P] bool — put_first (null-predecessor origin)
    cur_stage: jnp.ndarray  # [P] int32 — target stage (identity position)
    prev_stage: jnp.ndarray  # [P] int32 — -1 for first puts
    prev_off: jnp.ndarray  # [P] int32
    ver: jnp.ndarray  # [P, D] int32
    vlen: jnp.ndarray  # [P] int32


def puts_batched(
    slab: SlabState, ops: PutOps, off, hot_entries: int = 0
) -> SlabState:
    """Apply all of one step's consuming puts in one pass.

    Replicates the sequential semantics op by op: chained puts require an
    existing predecessor (else counted ``missing``); the *last* ``put_first``
    of a target group resets the entry and erases the group's earlier
    appends (``KVSharedVersionedBuffer.java:117-128`` overwrite quirk);
    surviving appends take consecutive pointer slots in op order.  All
    targets share the current event offset ``off``, so groups are keyed by
    ``cur_stage`` alone; predecessors always reference older events, so no
    op's predecessor lookup can observe another op of the same step.

    Two-tier slabs (``hot_entries > 0``) take the ranked sequential loop
    instead: the closed-form creator-to-free-slot ranking above assumes any
    free slot is usable, while two-tier allocation interleaves demotions
    between creations.  The jnp two-tier path exists for differential
    parity, not throughput (the Pallas kernels are the perf path), so the
    loop's extra passes are acceptable.
    """
    if hot_entries:
        return _puts_sequential(slab, ops, off, hot_entries)
    i32 = jnp.int32
    E, MP = slab.pstage.shape
    P = ops.en.shape[0]
    pidx = jnp.arange(P, dtype=i32)
    earlier = pidx[None, :] < pidx[:, None]  # [p, p']: p' before p
    later = pidx[None, :] > pidx[:, None]

    # Chained puts need an existing predecessor entry.
    prev_hit = (slab.stage[None, :] == ops.prev_stage[:, None]) & (
        slab.off[None, :] == ops.prev_off[:, None]
    )
    prev_found = jnp.any(prev_hit, axis=1)
    miss = ops.en & ~ops.first & ~prev_found
    en = ops.en & (ops.first | prev_found)

    # Target grouping by stage (same group == same target entry).
    same = ops.cur_stage[None, :] == ops.cur_stage[:, None]  # [P, P]
    cur_hit = (slab.stage[None, :] == ops.cur_stage[:, None]) & (
        slab.off[None, :] == off
    )
    exist0 = jnp.any(cur_hit, axis=1)
    e0 = jnp.argmax(cur_hit, axis=1)

    # Entry allocation: the first enabled op of a group whose entry does not
    # exist claims the next free slot (creators ranked in op order).
    first_of_group = en & ~jnp.any(same & earlier & en[None, :], axis=1)
    creator = first_of_group & ~exist0
    crank = jnp.cumsum(creator.astype(i32)) - 1
    free = slab.stage < 0
    nfree = jnp.sum(free.astype(i32))
    free_rank = jnp.cumsum(free.astype(i32)) - 1  # [E]
    alloc_hit = (
        free[None, :] & (free_rank[None, :] == crank[:, None]) & creator[:, None]
    )
    has_free = creator & (crank < nfree)
    grp_creator = same & creator[None, :]  # [P, P]
    alloc_e_all = jnp.argmax(alloc_hit, axis=1)
    e_created = jnp.sum(jnp.where(grp_creator, alloc_e_all[None, :], 0), axis=1)
    grp_has_free = jnp.any(grp_creator & has_free[None, :], axis=1)
    e = jnp.where(exist0, e0, e_created).astype(i32)
    entry_ok = en & (exist0 | grp_has_free)
    # Sequential parity: every op that finds neither an existing entry nor a
    # free slot counts one full drop.
    full = en & ~exist0 & ~grp_has_free

    # put_first reset: a first-put that lands (entry_ok) resets its entry's
    # pointer list; the group's ops therefore run in *segments* delimited by
    # resets.  Every segment's appends really happened sequentially (and can
    # drop on overflow — counted), but only the final segment's writes
    # survive the last reset.
    isfirst_ok = entry_ok & ops.first
    reset_at_or_before = same & ~later & isfirst_ok[None, :]
    has_reset = jnp.any(reset_at_or_before, axis=1)
    seg_head = jnp.max(jnp.where(reset_at_or_before, pidx[None, :], -1), axis=1)
    seg_eq = same & (seg_head[None, :] == seg_head[:, None])

    npreds0_e = jnp.sum(jnp.where(cur_hit, slab.npreds[None, :], 0), axis=1)
    base0 = jnp.where(exist0, npreds0_e, 0)
    base = jnp.where(has_reset, 0, base0)

    # npreds as each op saw it: base of its segment plus earlier successful
    # appends in the segment (appends saturate at MP — a dropped append
    # leaves npreds unchanged for its successors).
    prior = jnp.sum((seg_eq & earlier & entry_ok[None, :]).astype(i32), axis=1)
    slot = jnp.minimum(base + prior, MP)
    pred_drop = entry_ok & (slot >= MP)

    # Only final-segment ops persist (no reset after them in the group).
    last_seg = ~jnp.any(same & later & isfirst_ok[None, :], axis=1)
    surv = entry_ok & last_seg
    fit = surv & (slot < MP)
    grp_has_first = jnp.any(same & isfirst_ok[None, :], axis=1)
    base_n = jnp.where(grp_has_first | ~exist0, 0, npreds0_e)

    entry_oh = (jnp.arange(E, dtype=i32)[None, :] == e[:, None]) & fit[:, None]
    slot_oh = jnp.arange(MP, dtype=i32)[None, :] == slot[:, None]
    m3 = entry_oh[:, :, None] & slot_oh[:, None, :]  # [P, E, MP]
    hit3 = jnp.any(m3, axis=0)

    pstage_val = jnp.where(ops.first, -1, ops.prev_stage)
    poff_val = jnp.where(ops.first, -1, ops.prev_off)

    def write(field, val):
        upd = jnp.sum(jnp.where(m3, val[:, None, None], 0), axis=0)
        return jnp.where(hit3, upd.astype(field.dtype), field)

    new_pstage = write(slab.pstage, pstage_val)
    new_poff = write(slab.poff, poff_val)
    new_pvlen = write(slab.pvlen, ops.vlen)
    upd_v = jnp.sum(
        jnp.where(m3[..., None], ops.ver[:, None, None, :], 0), axis=0
    )
    new_pver = jnp.where(hit3[..., None], upd_v.astype(slab.pver.dtype), slab.pver)

    # Entry metadata, group-consistent (cnt is the group's fit count).
    cnt = jnp.sum((same & fit[None, :]).astype(i32), axis=1)
    npreds_val = jnp.minimum(base_n + cnt, MP)
    reset_refs = grp_has_first | ~exist0
    ge = (jnp.arange(E, dtype=i32)[None, :] == e[:, None]) & entry_ok[:, None]
    anyop = jnp.any(ge, axis=0)
    npreds_e = jnp.max(jnp.where(ge, npreds_val[:, None], 0), axis=0)
    setref_e = jnp.any(ge & reset_refs[:, None], axis=0)
    stage_e = jnp.max(jnp.where(ge, ops.cur_stage[:, None], -1), axis=0)

    return slab._replace(
        stage=jnp.where(anyop, stage_e.astype(i32), slab.stage),
        off=jnp.where(anyop, off, slab.off),
        refs=jnp.where(anyop & setref_e, 1, slab.refs),
        npreds=jnp.where(anyop, npreds_e.astype(i32), slab.npreds),
        pstage=new_pstage,
        poff=new_poff,
        pvlen=new_pvlen,
        pver=new_pver,
        missing=slab.missing + jnp.sum(miss.astype(i32)),
        full_drops=slab.full_drops + jnp.sum(full.astype(i32)),
        pred_drops=slab.pred_drops + jnp.sum(pred_drop.astype(i32)),
    )


def _puts_sequential(
    slab: SlabState, ops: PutOps, off, hot_entries: int
) -> SlabState:
    """One step's consuming puts applied one op at a time in queue order —
    the two-tier variant of :func:`puts_batched` (see its docstring)."""
    from kafkastreams_cep_tpu.ops.onehot import get_at

    P = int(ops.en.shape[0])

    def body(p, slab):
        en = get_at(ops.en, p)
        first = get_at(ops.first, p)
        cur = get_at(ops.cur_stage, p)
        slab = put_first(
            slab, cur, off, get_at(ops.ver, p), get_at(ops.vlen, p),
            enable=en & first, hot_entries=hot_entries,
        )
        return put(
            slab, cur, off, get_at(ops.prev_stage, p),
            get_at(ops.prev_off, p), get_at(ops.ver, p), get_at(ops.vlen, p),
            enable=en & ~first, hot_entries=hot_entries,
        )

    return jax.lax.fori_loop(0, P, body, slab)


def _pack_ptrs(slab: SlabState) -> jnp.ndarray:
    """Pointer arrays packed as one f32 tensor ``[E, MP, D+3]`` so walk-hop
    row extraction is a single MXU matmul.  Layout: ver, pstage, poff, pvlen.
    All values are small ints — exact in f32 (offsets are bounded by the
    engine's documented 2^24-events-per-lane limit)."""
    return jnp.concatenate(
        [
            slab.pver.astype(jnp.float32),
            slab.pstage[..., None].astype(jnp.float32),
            slab.poff[..., None].astype(jnp.float32),
            slab.pvlen[..., None].astype(jnp.float32),
        ],
        axis=-1,
    )


def _rows(ptrs: jnp.ndarray, hit: jnp.ndarray):
    """Extract each walker's entry row from the packed pointer tensor:
    ``[P, E] one-hot x [E, MP*(D+3)] -> [P, MP, D+3]`` — one f32 matmul."""
    E, MP, C = ptrs.shape
    rows = jnp.einsum(
        "pe,ec->pc",
        hit.astype(jnp.float32),
        ptrs.reshape(E, MP * C),
        preferred_element_type=jnp.float32,
    )
    return rows.reshape(-1, MP, C)


def _compat_rows(qver, qlen, pv, pl):
    """``dewey_ops.is_compatible`` vectorized over walkers x pointers:
    ``qver [P, D]`` (f32), ``qlen [P]``, ``pv [P, MP, D]`` (f32),
    ``pl [P, MP]``.  One source of truth for the compatibility rule — the
    masked elementwise math works identically on f32-encoded components."""
    per_walker = jax.vmap(dewey_ops.is_compatible, in_axes=(None, None, 0, 0))
    return jax.vmap(per_walker)(qver, qlen, pv, pl)


def branch_batched(
    slab: SlabState, en, stage, off, ver, vlen, max_walk: int,
    hot_entries: int = 0,
) -> SlabState:
    """All branch refcount walks of one step, in lockstep
    (``KVSharedVersionedBuffer.java:99-110``).

    Per-hop refcount increments are summed across walkers — increments
    commute and pointer selection never reads refcounts, so the result is
    identical to any sequential interleaving.  The hop loop is a
    ``while_loop`` that exits as soon as no walker is active — the common
    case (no branching this event) costs one condition check.
    """
    E, MP = slab.pstage.shape
    D = slab.pver.shape[-1]
    i32 = jnp.int32
    mp_idx = jnp.arange(MP, dtype=i32)
    ptrs = _pack_ptrs(slab)  # read-only in this phase

    def cond(carry):
        slab, stage, off, qver, qlen, active, hops = carry
        return jnp.any(active) & (hops < max_walk)

    def body(carry):
        slab, stage, off, qver, qlen, active, hops = carry
        hit = (slab.stage[None, :] == stage[:, None]) & (
            slab.off[None, :] == off[:, None]
        )
        found = jnp.any(hit, axis=1)
        if hot_entries:
            slab = _tier_counts(
                slab, active, jnp.any(hit[:, :hot_entries], axis=1), found
            )
        slab = _hop_counts(slab, active, stage=stage)
        slab = slab._replace(
            missing=slab.missing + jnp.sum((active & ~found).astype(i32))
        )
        active = active & found
        inc = jnp.sum((hit & active[:, None]).astype(i32), axis=0)
        slab = slab._replace(refs=slab.refs + inc)

        rows = _rows(ptrs, hit & active[:, None])  # [P, MP, D+3]
        pv, ps, po, pl = (
            rows[..., :D],
            rows[..., D],
            rows[..., D + 1],
            rows[..., D + 2],
        )
        np_ = jnp.sum(jnp.where(hit, slab.npreds[None, :], 0), axis=1)
        ok = _compat_rows(qver, qlen, pv, pl) & (mp_idx[None, :] < np_[:, None])
        j = jnp.argmax(ok, axis=1)
        sel = jnp.any(ok, axis=1)
        ohj = mp_idx[None, :] == j[:, None]
        ns = jnp.sum(jnp.where(ohj, ps, 0), axis=1)
        active = active & sel & (ns >= 0)
        stage = jnp.where(active, ns.astype(i32), stage)
        off = jnp.where(
            active, jnp.sum(jnp.where(ohj, po, 0), axis=1).astype(i32), off
        )
        qver = jnp.where(
            active[:, None], jnp.sum(jnp.where(ohj[..., None], pv, 0), axis=1), qver
        )
        qlen = jnp.where(
            active, jnp.sum(jnp.where(ohj, pl, 0), axis=1).astype(i32), qlen
        )
        return (slab, stage, off, qver, qlen, active, hops + 1)

    init = (
        slab,
        jnp.asarray(stage, i32),
        jnp.asarray(off, i32),
        jnp.asarray(ver, jnp.float32),
        jnp.asarray(vlen, i32),
        jnp.asarray(en),
        jnp.zeros((), i32),
    )
    slab, _, _, _, _, active, _ = jax.lax.while_loop(cond, body, init)
    return slab._replace(
        trunc=slab.trunc + jnp.sum(active.astype(i32))
    )


def walks_compacted(
    slab: SlabState,
    en,
    stage,
    off,
    ver,
    vlen,
    is_remove,
    want_out,
    max_walk: int,
    budget: int,
    out_base: int,
    out_rows: int,
    hot_entries: int = 0,
    drain: bool = False,
):
    """The step's walk pass over a *small* compacted walker pool.

    The engine presents P candidate walkers per step (every branch frame,
    every dead run, every potential final extraction) but typically only a
    handful are enabled.  Carrying all P slots through every walk hop made
    the walk pass ~90% of the headline step (PROFILE_r04.md): per-hop HBM
    traffic is proportional to the pool width.  This wrapper compacts the
    *enabled* walkers, in queue-order rank, into ``budget`` slots and runs
    :func:`walks_batched` over batches of that width until all are served.

    Ordering: batches are processed in ascending rank order; each batch's
    deletes/prunes and pointer compaction complete before the next batch
    starts.  With ``budget=1`` (the engine default) every walker runs alone
    — exactly the reference's sequential per-walker order.  With wider
    budgets, walkers *within* a batch run under :func:`walks_batched`'s
    lockstep protocol, which deviates from sequential when two removal
    walkers meet at one entry in the same hop (prune/delete attribution
    goes to the queue-last walker only; a refs==0 entry can survive with a
    stale pointer) — see ``EngineConfig.walker_budget``.

    Only rows ``[out_base, out_base + out_rows)`` of the candidate list can
    request output (the engine's final-extraction segment); their hops are
    scattered back to ``out_rows``-indexed rows so the engine never
    materializes a [P, W] output.

    Returns ``(slab, out_stage [out_rows, W], out_off [out_rows, W],
    count [out_rows])``.
    """
    i32 = jnp.int32
    W = max_walk
    P = jnp.asarray(stage).shape[0]
    B = budget
    en = jnp.asarray(en)
    stage = jnp.asarray(stage, i32)
    off = jnp.asarray(off, i32)
    ver = jnp.asarray(ver, i32)
    vlen = jnp.asarray(vlen, i32)
    is_remove = jnp.asarray(is_remove)
    want_out = jnp.asarray(want_out)

    rank = jnp.cumsum(en.astype(i32)) - 1  # queue-order rank of enabled
    n = jnp.sum(en.astype(i32))
    bidx = jnp.arange(B, dtype=i32)

    def cond(carry):
        return carry[1] < n

    def body(carry):
        slab, start, out_stage, out_off, count = carry
        ohc = (en & (rank >= start) & (rank < start + B))[:, None] & (
            (rank - start)[:, None] == bidx[None, :]
        )  # [P, B] — at most one True per row and per column

        def gather(field, fill=0):
            m = ohc.reshape((P, B) + (1,) * (field.ndim - 1))
            v = jnp.sum(jnp.where(m, field[:, None], 0), axis=0)
            if field.dtype == jnp.bool_:
                return jnp.any(m & field.reshape((P, 1) + field.shape[1:]), axis=0)
            got = jnp.any(ohc, axis=0).reshape((B,) + (1,) * (field.ndim - 1))
            return jnp.where(got, v.astype(field.dtype), fill)

        b_en = jnp.any(ohc, axis=0)
        slab, b_out_stage, b_out_off, b_count = walks_batched(
            slab,
            b_en,
            gather(stage),
            gather(off),
            gather(ver),
            gather(vlen),
            gather(is_remove),
            gather(want_out),
            W,
            hot_entries=hot_entries,
            drain=drain,
        )
        # Scatter served output walkers back to their final-segment rows.
        oho = ohc[out_base:out_base + out_rows]  # [out_rows, B]
        got = jnp.any(oho, axis=1)
        upd_st = jnp.sum(jnp.where(oho[:, :, None], b_out_stage[None], 0), axis=1)
        upd_of = jnp.sum(jnp.where(oho[:, :, None], b_out_off[None], 0), axis=1)
        upd_ct = jnp.sum(jnp.where(oho, b_count[None], 0), axis=1)
        out_stage = jnp.where(got[:, None], upd_st.astype(i32), out_stage)
        out_off = jnp.where(got[:, None], upd_of.astype(i32), out_off)
        count = jnp.where(got, upd_ct.astype(i32), count)
        return slab, start + B, out_stage, out_off, count

    init = (
        slab,
        jnp.zeros((), i32),
        jnp.full((out_rows, W), -1, i32),
        jnp.full((out_rows, W), -1, i32),
        jnp.zeros((out_rows,), i32),
    )
    slab, _, out_stage, out_off, count = jax.lax.while_loop(cond, body, init)
    return slab, out_stage, out_off, count


def peek_batched(
    slab: SlabState,
    en,
    stage,
    off,
    ver,
    vlen,
    max_walk: int,
    remove: bool,
    hot_entries: int = 0,
    drain: bool = False,
):
    """Lockstep removal walks — a thin wrapper over :func:`walks_batched`
    with every walker removing and emitting (``remove=False`` keeps the
    reference's get-still-decrements quirk but skips delete/prune).

    Returns ``(slab, out_stage [P, W], out_off [P, W], count [P])``.
    """
    P = jnp.asarray(stage).shape[0]
    ones = jnp.ones((P,), bool)
    return walks_batched(
        slab, en, stage, off, ver, vlen,
        is_remove=ones, want_out=ones, max_walk=max_walk, collect=remove,
        hot_entries=hot_entries, drain=drain,
    )


# Eager per-op dispatch is orders of magnitude slower than compiled code on
# this host; the public sequential entry points are jitted (the engine's
# sequential mode additionally inlines them under its own jit, where these
# wrappers are free).  The batched kernels are always called under the
# engine's jit and need no wrappers.
put_first = jax.jit(put_first, static_argnames=("hot_entries",))
put = jax.jit(put, static_argnames=("hot_entries",))
branch = jax.jit(branch, static_argnames=("max_walk", "hot_entries"))
peek = jax.jit(
    peek, static_argnames=("max_walk", "remove", "hot_entries", "hop_kind")
)
