"""Fused Pallas walk-pass kernel — the step's buffer walks in VMEM.

The walk pass (branch refcount walks ``KVSharedVersionedBuffer.java:99-110``,
dead-run removals ``:147-171``, final-match extraction ``NFA.java:111-115``)
is ~90% of the headline step in the jnp engine (PROFILE_r04.md): every hop of
its while-loop re-reads the packed pointer slab from HBM.  This kernel keeps
each lane-block's slab resident in VMEM across *all* hops of *all* walkers of
the step, reducing per-step slab HBM traffic to one read + one write.

Execution model
---------------
One grid program owns ``L`` lanes (lane axis last, width 128).  Walker
candidates arrive as a ``[PW]``-row queue per lane with a precomputed
queue-order ``rank``; the kernel loops ``b = 0..max(n_enabled)`` batches, and
in each batch every lane serves its rank-``b`` walker — **one walker per lane
at a time**, so per-lane buffer mutation order is *exactly* the reference's
sequential queue order (no lockstep merge argument needed), while the vector
unit parallelizes across the 128 lanes of the block.

Pointer prunes are physical (`TimedKeyValue.removePredecessor` shift-left),
applied immediately — again exactly the sequential semantics, affordable
because the arrays live in VMEM.

Semantics are differentially tested against the jnp pass
(``ops/slab.py: walks_compacted``) and, through it, against the sequential
per-op path and the host oracle (``tests/test_walk_kernel.py``,
``tests/test_engine_fuzz.py``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kafkastreams_cep_tpu.ops.slab import SlabState

LANE_BLOCK = 128

def _cumsum0(x):
    """Inclusive prefix sum along axis 0 via log-shift adds — Mosaic has
    no cumsum lowering; log2(N) shifted adds of the [N, L] plane do."""
    n = x.shape[0]
    k = 1
    while k < n:
        pad = jnp.zeros((k,) + x.shape[1:], x.dtype)
        x = x + jnp.concatenate([pad, x[:-k]], axis=0)
        k *= 2
    return x


def _coalesced_demote(
    refs, p_en, p_first, p_cur, p_pst, p_pof, off_l,
    EHk: int, EO: int, MP: int,
):
    """One pass serving ALL of a step's hot→overflow demotions, plus the
    per-creation hot-slot claim map the put loop allocates from —
    replacing the per-put ``pl.when`` demotion (PROFILE_r06 "next
    leverage" item 2: hot-tier thrash at E_hot ≪ live entries paid one
    masked move pass per put).

    Sequential-equivalence argument: within one step every put targets
    the current event, so (a) predecessor lookups (strictly older events)
    and target-existence groups are fixed at step start, (b) each target
    group's FIRST enabled op is the only creator, (c) creations consume
    free hot slots in ascending index order (the sequential allocator's
    lowest-index-free rule) and then demote victims in ascending
    (event offset, index) order (its min-off rule — entries created this
    step carry the current, maximal offset, so victims always come from
    the step-start occupancy while E_hot ≥ the pattern's consuming-stage
    count, which the E_hot ≥ 8 floor guarantees for every compiled
    pattern here), with victim ``d`` landing in the ``d``-th free
    overflow slot.  All of that is computable up front, so the moves
    coalesce into one pass and the loop's allocation becomes a rank
    lookup.  Bit-exact parity with the per-op jnp path is pinned by
    ``tests/test_two_tier.py``.

    ``refs`` is ``(stage, off, refs, npreds, pstage, poff, pvlen, pver,
    dm)`` output refs (pver laid out ``[E, MP, D, L]``); ``p_*`` are the
    step's put-op planes ``[PP, L]`` (values, lane-last).  Returns
    ``(creator [PP, L] bool, crank [PP, L], claim [EHk, L], k_cap
    [1, L])``: creation-rank ``c`` allocates the slot with ``claim == c``
    and drops iff ``c >= k_cap``.
    """
    (o_stage, o_off, o_refs, o_npreds, o_pstage, o_poff, o_pvlen, o_pver,
     o_dm) = refs
    i32 = jnp.int32
    PP, L = p_cur.shape
    E = EHk + EO
    st0 = o_stage[:]
    of0 = o_off[:]

    # Per-op enablement and target existence, fixed at step start (puts
    # never delete; predecessors and targets cannot collide in-step).
    prev_found = jnp.any(
        (st0[None] == p_pst[:, None, :]) & (of0[None] == p_pof[:, None, :]),
        axis=1,
    )  # [PP, L]
    en_ok = p_en & (p_first | prev_found)
    exist0 = jnp.any(
        (st0[None] == p_cur[:, None, :]) & (of0[None] == off_l[None]),
        axis=1,
    )

    # Group (same target stage) first-enabled op = the creator.
    iota_p0 = jax.lax.broadcasted_iota(i32, (PP, PP, L), 0)
    iota_p1 = jax.lax.broadcasted_iota(i32, (PP, PP, L), 1)
    same = p_cur[None, :, :] == p_cur[:, None, :]
    earlier_en = same & (iota_p1 < iota_p0) & en_ok[None, :, :]
    creator = en_ok & ~jnp.any(earlier_en, axis=1) & ~exist0
    creator_i = jnp.where(creator, 1, 0)
    crank = _cumsum0(creator_i) - creator_i  # exclusive: creation rank
    n_create = jnp.sum(creator_i, axis=0, keepdims=True)  # [1, L]

    # Free-slot ranks and demotion victims.
    iota_eh = jax.lax.broadcasted_iota(i32, (EHk, L), 0)
    free_h = st0[0:EHk] < 0
    free_h_i = jnp.where(free_h, 1, 0)
    frank = _cumsum0(free_h_i) - free_h_i
    n_free_hot = jnp.sum(free_h_i, axis=0, keepdims=True)
    occ_h = ~free_h
    n_occ = jnp.sum(jnp.where(occ_h, 1, 0), axis=0, keepdims=True)
    iota_eo = jax.lax.broadcasted_iota(i32, (EO, L), 0)
    free_o = st0[EHk:] < 0
    free_o_i = jnp.where(free_o, 1, 0)
    orank = _cumsum0(free_o_i) - free_o_i
    n_free_ov = jnp.sum(free_o_i, axis=0, keepdims=True)
    k_cap = n_free_hot + n_free_ov

    # Victim rank: ascending (offset, index) among step-start occupied.
    of_h = of0[0:EHk]
    iota_a = jax.lax.broadcasted_iota(i32, (EHk, EHk, L), 0)
    iota_b = jax.lax.broadcasted_iota(i32, (EHk, EHk, L), 1)
    less = (of_h[None, :, :] < of_h[:, None, :]) | (
        (of_h[None, :, :] == of_h[:, None, :]) & (iota_b < iota_a)
    )
    vrank = jnp.sum(
        jnp.where(less & occ_h[None, :, :], 1, 0), axis=1
    )  # [EHk, L]

    n_demote = jnp.clip(
        n_create - n_free_hot, 0, jnp.minimum(n_free_ov, n_occ)
    )
    o_dm[:] = o_dm[:] + n_demote
    is_victim = occ_h & (vrank < n_demote)

    @pl.when(jnp.any(is_victim))
    def _():
        # Victim d -> d-th free overflow slot, ALL moves in one pass.
        mv = (
            is_victim[:, None, :]
            & free_o[None, :, :]
            & (vrank[:, None, :] == orank[None, :, :])
        )  # [EHk, EO, L]
        anym = jnp.any(mv, axis=0)  # [EO, L]

        def mv2(ref):
            v = jnp.sum(jnp.where(mv, ref[0:EHk][:, None, :], 0), axis=0)
            ref[EHk:] = jnp.where(anym, v, ref[EHk:])

        mv2(o_refs)
        mv2(o_npreds)

        def mv3(ref):
            v = jnp.sum(
                jnp.where(mv[:, :, None, :], ref[0:EHk][:, None], 0),
                axis=0,
            )  # [EO, MP, L]
            ref[EHk:] = jnp.where(anym[:, None, :], v, ref[EHk:])

        mv3(o_pstage)
        mv3(o_poff)
        mv3(o_pvlen)
        for m in range(MP):
            v = jnp.sum(
                jnp.where(mv[:, :, None, :], o_pver[0:EHk, m][:, None], 0),
                axis=0,
            )  # [EO, D, L]
            o_pver[EHk:, m] = jnp.where(anym[:, None, :], v, o_pver[EHk:, m])
        vst = jnp.sum(jnp.where(mv, o_stage[0:EHk][:, None, :], 0), axis=0)
        vof = jnp.sum(jnp.where(mv, o_off[0:EHk][:, None, :], 0), axis=0)
        o_stage[EHk:] = jnp.where(anym, vst, o_stage[EHk:])
        o_off[EHk:] = jnp.where(anym, vof, o_off[EHk:])
        o_stage[0:EHk] = jnp.where(is_victim, -1, o_stage[0:EHk])
        o_off[0:EHk] = jnp.where(is_victim, -1, o_off[0:EHk])

    # Claim map: creation rank c takes the c-th free hot slot (ascending
    # index), then victims in vrank order.
    BIG = jnp.int32(PP + E + 1)
    claim = jnp.where(free_h, frank, BIG)
    claim = jnp.where(is_victim, n_free_hot + vrank, claim)
    return creator, crank, claim, k_cap


def _kernel(
    # inputs (lane-last blocks)
    stage, off, refs, npreds, pstage, poff, pvlen, pver, missing, trunc,
    fulld, predd, hh, hm, ow, dm, wh, eh, dh,
    p_first, p_cur, p_pstage, p_poff, p_vlen, p_ver, p_rank, p_nen, ev_off,
    en, wstage, woff, wvlen, wver, wrem, wout, rank, nen,
    # the tail holds, in order: [shp] (stage-hop input, SA > 0 only), the
    # 22 outputs, [o_shp] (SA > 0 only), the two staging scratch buffers,
    # and the tier scratch (EH > 0 only) — unpacked by index below so the
    # attribution plumbing vanishes entirely when SA == 0.
    *rest,
    W: int, out_base: int, out_rows: int, with_puts: bool, EH: int,
    SA: int, drain: bool,
):
    i = 0
    if SA:
        shp = rest[i]
        i += 1
    (o_stage, o_off, o_refs, o_npreds, o_pstage, o_poff, o_pvlen, o_pver,
     o_missing, o_trunc, o_fulld, o_predd, o_hh, o_hm, o_ow, o_dm,
     o_wh, o_eh, o_dh,
     o_ostage, o_ooff, o_count) = rest[i:i + 22]
    i += 22
    if SA:
        o_shp = rest[i]
        i += 1
    st_stage, st_off = rest[i], rest[i + 1]
    tier_scratch = rest[i + 2:]
    E, MP, L = pstage.shape
    # pver blocks arrive [E, MP, D, L], the order in which the device keeps
    # the state's [K, E, MP, D] (lanes minor): the slab then reaches the
    # kernel, and returns from it, with no relayout copy of the state's
    # largest array.  The tiled trailing dims are (D, L).
    D = pver.shape[2]
    PW = en.shape[0]
    OR = out_rows
    i32 = jnp.int32
    # Two-tier layout (ops/slab.py "Two-tier layout" note): rows [0, EHk)
    # are the hot tier, [EHk, E) the overflow tier.  EH == 0 instantiates
    # the legacy single tier as EHk = E / EO = 0 — every overflow-side
    # block below is then skipped at trace time and the hot-side code IS
    # the original full-slab code.
    EHk = EH if EH else E
    EO = E - EHk
    if EO:
        (sc_found, sc_refs, sc_np, sc_ps, sc_po, sc_pl, sc_pv) = tier_scratch

    # Working state lives in the output refs (VMEM) for the whole pass.
    o_stage[:] = stage[:]
    o_off[:] = off[:]
    o_refs[:] = refs[:]
    o_npreds[:] = npreds[:]
    o_pstage[:] = pstage[:]
    o_poff[:] = poff[:]
    o_pvlen[:] = pvlen[:]
    o_pver[:] = pver[:]
    o_missing[:] = missing[:]
    o_trunc[:] = trunc[:]
    o_fulld[:] = fulld[:]
    o_predd[:] = predd[:]
    o_hh[:] = hh[:]
    o_hm[:] = hm[:]
    o_ow[:] = ow[:]
    o_dm[:] = dm[:]
    o_wh[:] = wh[:]
    o_eh[:] = eh[:]
    o_dh[:] = dh[:]
    if SA:
        o_shp[:] = shp[:]
        iota_sa = jax.lax.broadcasted_iota(i32, (SA, L), 0)
    o_ostage[:] = jnp.full((OR, W, L), -1, i32)
    o_ooff[:] = jnp.full((OR, W, L), -1, i32)
    o_count[:] = jnp.zeros((OR, L), i32)

    iota_pw = jax.lax.broadcasted_iota(i32, (PW, L), 0)
    iota_mp = jax.lax.broadcasted_iota(i32, (MP, L), 0)
    iota_mp3 = jax.lax.broadcasted_iota(i32, (E, MP, L), 1)
    iota_mp3h = jax.lax.broadcasted_iota(i32, (EHk, MP, L), 1)
    iota_d3 = jax.lax.broadcasted_iota(i32, (MP, D, L), 1)
    iota_or3 = jax.lax.broadcasted_iota(i32, (OR, W, L), 0)
    iota_w2 = jax.lax.broadcasted_iota(i32, (W, L), 0)
    iota_or2 = jax.lax.broadcasted_iota(i32, (OR, L), 0)
    iota_eh = jax.lax.broadcasted_iota(i32, (EHk, L), 0)
    if EO:
        iota_mp3o = jax.lax.broadcasted_iota(i32, (EO, MP, L), 1)

    # ---- consuming-put phase (reference order precedes all walks; one
    # put per lane per batch in queue-order rank = the sequential
    # semantics of slab.put / slab.put_first exactly) ----
    if with_puts:
        iota_e = jax.lax.broadcasted_iota(i32, (E, L), 0)
        max_pn = jnp.max(p_nen[0, :])
        if EO:
            # Coalesced demotion pre-pass: ALL of the step's hot→overflow
            # demotions in one move pass (not one pl.when per put), plus
            # the claim map the loop's allocation reads.
            creator_c, crank_c, claim_c, kcap_c = _coalesced_demote(
                (o_stage, o_off, o_refs, o_npreds, o_pstage, o_poff,
                 o_pvlen, o_pver, o_dm),
                p_rank[:] >= 0, p_first[:] != 0, p_cur[:],
                p_pstage[:], p_poff[:], ev_off[:],
                EHk=EHk, EO=EO, MP=MP,
            )

        def put_body(b):
            pselm = p_rank[:] == b  # [PP, L] — at most one True per lane
            en0 = jnp.any(pselm, axis=0, keepdims=True)  # [1, L]

            def ppick(f):
                return jnp.sum(jnp.where(pselm, f, 0), axis=0, keepdims=True)

            first = jnp.any(
                pselm & (p_first[:] != 0), axis=0, keepdims=True
            )
            cur = ppick(p_cur[:])
            pst = ppick(p_pstage[:])
            pof = ppick(p_poff[:])
            pvl = ppick(p_vlen[:])
            pvr = jnp.sum(
                jnp.where(pselm[None], p_ver[:], 0), axis=1
            )  # [D, L]
            off_l = ev_off[:]  # [1, L]

            # Chained puts need an existing predecessor entry
            # (KVSharedVersionedBuffer.java:86-89; counted miss here).
            prev_hit = (o_stage[:] == pst) & (o_off[:] == pof)
            prev_found = jnp.any(prev_hit, axis=0, keepdims=True)
            o_missing[:] = o_missing[:] + jnp.where(
                en0 & ~first & ~prev_found, 1, 0
            )
            en_ok = en0 & (first | prev_found)

            cur_hit = (o_stage[:] == cur) & (o_off[:] == off_l)  # [E, L]
            exist = jnp.any(cur_hit, axis=0, keepdims=True)
            # Two-tier allocation: demotions already ran in the coalesced
            # pre-pass, so allocation is a rank lookup into the claim map
            # (creation rank c -> the slot claiming c; c >= k_cap drops —
            # exactly the whole-slab-full condition).  EO == 0 keeps the
            # legacy first-free-slot scan verbatim.
            if EO:
                is_cr = jnp.any(
                    pselm & creator_c, axis=0, keepdims=True
                )  # [1, L] — this batch's op is its group's creator
                crk = ppick(crank_c)
                alloc_h = (claim_c == crk) & is_cr  # [EHk, L], <=1 True
                alloc = jnp.min(
                    jnp.where(alloc_h, iota_eh, E), axis=0, keepdims=True
                )
                # alloc < E guard: a creation past the start-occupied
                # victim pool would claim nothing (unreachable while
                # E_hot >= the pattern's consuming-stage count — the
                # E_hot >= 8 floor); the guard turns it into a counted
                # drop instead of a silent no-op write.
                has_free = is_cr & (crk < kcap_c) & (alloc < E)
            else:
                free_h = o_stage[:] < 0
                ffs_h = jnp.min(
                    jnp.where(free_h, iota_eh, EHk), axis=0, keepdims=True
                )
                alloc = ffs_h
                has_free = ffs_h < EHk
            # Boolean algebra, not where(): Mosaic can't select i1 vectors.
            tgt = (exist & cur_hit) | (~exist & (iota_e == alloc))  # [E, L]
            ok = en_ok & (exist | has_free)
            o_fulld[:] = o_fulld[:] + jnp.where(
                en_ok & ~exist & ~has_free, 1, 0
            )
            m1 = tgt & ok
            # put_first resets the entry (:117-128); creation initializes.
            reset = ok & (first | ~exist)
            o_stage[:] = jnp.where(m1, cur, o_stage[:])
            o_off[:] = jnp.where(m1, off_l, o_off[:])
            o_refs[:] = jnp.where(m1 & reset, 1, o_refs[:])
            np_e = jnp.sum(
                jnp.where(m1, o_npreds[:], 0), axis=0, keepdims=True
            )
            n_eff = jnp.where(reset, 0, np_e)  # [1, L]
            pfull = ok & (n_eff >= MP)
            o_predd[:] = o_predd[:] + jnp.where(pfull, 1, 0)
            do = ok & ~pfull
            slot = jnp.minimum(n_eff, MP - 1)
            m2 = (
                m1[:, None, :]
                & (iota_mp3 == slot[:, None, :])
                & do[:, None, :]
            )  # [E, MP, L]
            o_pstage[:] = jnp.where(
                m2, jnp.where(first, -1, pst)[:, None, :], o_pstage[:]
            )
            o_poff[:] = jnp.where(
                m2, jnp.where(first, -1, pof)[:, None, :], o_poff[:]
            )
            o_pvlen[:] = jnp.where(m2, pvl[:, None, :], o_pvlen[:])
            o_pver[:] = jnp.where(
                m2[:, :, None, :], pvr[None, None], o_pver[:]
            )
            o_npreds[:] = jnp.where(
                m1, n_eff + jnp.where(do, 1, 0), o_npreds[:]
            )
            return b + 1

        jax.lax.while_loop(
            lambda b: b < max_pn, put_body, jnp.zeros((), i32)
        )

    max_n = jnp.max(nen[0, :])

    def batch_body(b):
        selm = rank[:] == b  # [PW, L] — at most one True per lane
        act0 = jnp.any(selm, axis=0, keepdims=True)  # [1, L]

        def pick(f):  # [PW, L] -> [1, L]
            return jnp.sum(jnp.where(selm, f, 0), axis=0, keepdims=True)

        st_stage[:] = jnp.full((W, L), -1, i32)
        st_off[:] = jnp.full((W, L), -1, i32)
        ws = pick(wstage[:])
        wo = pick(woff[:])
        wvl = pick(wvlen[:])
        wrm = jnp.any(selm & (wrem[:] != 0), axis=0, keepdims=True)
        wot = jnp.any(selm & (wout[:] != 0), axis=0, keepdims=True)
        srow = pick(iota_pw - out_base)
        # wver arrives [D, PW, L] (tile-exact trailing dims).
        qv0 = jnp.sum(
            jnp.where(selm[None, :, :], wver[:], 0), axis=1
        )  # [D, L]

        def hop_cond(c):
            h, active = c[0], c[1]
            return (h < W) & jnp.any(active != 0)

        def hop_body(c):
            h, active_i, cs, co, qv, ql, cnt = c
            active = active_i != 0
            # Walk-cost accounting (ops/slab.py _hop_counts): every active
            # walker's hop classified once, by walker class; the emit
            # class is static (drain pass vs eager extraction).
            emit_hop = jnp.where(active & wot, 1, 0)
            o_wh[:] = o_wh[:] + jnp.where(active & ~wot, 1, 0)
            if drain:
                o_dh[:] = o_dh[:] + emit_hop
            else:
                o_eh[:] = o_eh[:] + emit_hop
            if SA:
                # Per-stage hop attribution (ops/slab.py _hop_counts):
                # every active hop tallies at the walker's current stage.
                o_shp[:] = o_shp[:] + jnp.where(
                    (iota_sa == cs) & active, 1, 0
                )
            # Hot-tier lookup first: [EHk, L] compares instead of [E, L].
            # The overflow rows are consulted only when some lane of the
            # block missed hot — the common all-hot hop never touches them
            # (the E-linear -> E_hot-linear win of the two-tier layout).
            hit_h = (o_stage[0:EHk] == cs) & (o_off[0:EHk] == co)
            found_h = jnp.any(hit_h, axis=0, keepdims=True)  # [1, L]
            if EO:
                miss = active & ~found_h
                sc_found[:] = jnp.zeros((1, L), i32)
                sc_refs[:] = jnp.zeros((1, L), i32)
                sc_np[:] = jnp.zeros((1, L), i32)
                sc_ps[:] = jnp.zeros((MP, L), i32)
                sc_po[:] = jnp.zeros((MP, L), i32)
                sc_pl[:] = jnp.zeros((MP, L), i32)
                sc_pv[:] = jnp.zeros((MP, D, L), i32)

                @pl.when(jnp.any(miss))
                def _():
                    hit_o = (o_stage[EHk:] == cs) & (o_off[EHk:] == co)
                    hamo = hit_o & miss  # [EO, L]
                    sc_found[:] = jnp.where(
                        jnp.any(hamo, axis=0, keepdims=True), 1, 0
                    )
                    sc_refs[:] = jnp.sum(
                        jnp.where(hamo, o_refs[EHk:], 0),
                        axis=0, keepdims=True,
                    )
                    sc_np[:] = jnp.sum(
                        jnp.where(hamo, o_npreds[EHk:], 0),
                        axis=0, keepdims=True,
                    )
                    hamo3 = hamo[:, None, :]
                    sc_ps[:] = jnp.sum(
                        jnp.where(hamo3, o_pstage[EHk:], 0), axis=0
                    )
                    sc_po[:] = jnp.sum(
                        jnp.where(hamo3, o_poff[EHk:], 0), axis=0
                    )
                    sc_pl[:] = jnp.sum(
                        jnp.where(hamo3, o_pvlen[EHk:], 0), axis=0
                    )
                    sc_pv[:] = jnp.sum(
                        jnp.where(
                            hamo[:, None, None, :], o_pver[EHk:], 0
                        ),
                        axis=0,
                    )

                act_o = sc_found[:] != 0  # active walkers resolved overflow
                found = found_h | act_o
                o_hh[:] = o_hh[:] + jnp.where(active & found_h, 1, 0)
                o_hm[:] = o_hm[:] + jnp.where(miss, 1, 0)
                o_ow[:] = o_ow[:] + jnp.where(act_o, 1, 0)
            else:
                act_o = jnp.zeros((1, L), jnp.bool_)
                found = found_h
            o_missing[:] = o_missing[:] + jnp.where(active & ~found, 1, 0)
            active = active & found
            ham_h = hit_h & active  # [EHk, L] — <=1 True/lane (unique keys)

            refs_e = jnp.sum(
                jnp.where(ham_h, o_refs[0:EHk], 0), axis=0, keepdims=True
            )
            np_e = jnp.sum(
                jnp.where(ham_h, o_npreds[0:EHk], 0), axis=0, keepdims=True
            )
            if EO:
                # Per-lane sums pick the single hit entry, so the hot and
                # staged-overflow contributions are disjoint: add them.
                refs_e = refs_e + sc_refs[:]
                np_e = np_e + sc_np[:]
            # Remove-walkers decrement (floored at zero,
            # TimedKeyValue.java:59-61); branch walkers increment.
            newref = jnp.where(wrm, jnp.maximum(refs_e - 1, 0), refs_e + 1)
            o_refs[0:EHk] = jnp.where(ham_h, newref, o_refs[0:EHk])
            dele = active & wrm & (newref == 0) & (np_e <= 1)
            dmask = ham_h & dele
            o_stage[0:EHk] = jnp.where(dmask, -1, o_stage[0:EHk])
            o_off[0:EHk] = jnp.where(dmask, -1, o_off[0:EHk])

            # Emit the hop for extraction walkers into the per-batch [W, L]
            # staging buffer (scattering straight into the [OR, W, L] output
            # every hop costs OR/1 times the traffic).
            emit = active & wot
            mw = (iota_w2 == cnt) & emit
            st_stage[:] = jnp.where(mw, cs, st_stage[:])
            st_off[:] = jnp.where(mw, co, st_off[:])
            cnt = cnt + jnp.where(emit, 1, 0)

            # The hit entry's pointer rows (masked reduce over the hot rows
            # — the slab stays in VMEM, so this is pure vector work; the
            # overflow contribution was staged under the miss branch).
            ham3 = ham_h[:, None, :]
            ps_ = jnp.sum(jnp.where(ham3, o_pstage[0:EHk], 0), axis=0)
            po_ = jnp.sum(jnp.where(ham3, o_poff[0:EHk], 0), axis=0)
            pl_ = jnp.sum(jnp.where(ham3, o_pvlen[0:EHk], 0), axis=0)
            pv_ = jnp.sum(
                jnp.where(ham_h[:, None, None, :], o_pver[0:EHk], 0),
                axis=0,
            )  # [MP, D, L]
            if EO:
                ps_ = ps_ + sc_ps[:]
                po_ = po_ + sc_po[:]
                pl_ = pl_ + sc_pl[:]
                pv_ = pv_ + sc_pv[:]
            live = iota_mp < np_e  # [MP, L]

            # dewey_ops.is_compatible vectorized over the MP pointers
            # (DeweyVersion.java:62-82).  Prefix checks count violations in
            # i32 — Mosaic cannot select on i1 vectors.
            neq = (qv[None] != pv_).astype(jnp.int32)  # [MP, D, L]
            plm = pl_[:, None, :]
            prefix_full = (
                jnp.sum(neq * (iota_d3 < plm).astype(jnp.int32), axis=1) == 0
            )
            prefix_butl = (
                jnp.sum(neq * (iota_d3 < plm - 1).astype(jnp.int32), axis=1)
                == 0
            )
            last_q = jnp.sum(
                jnp.where(iota_d3 == plm - 1, qv[None], 0), axis=1
            )
            last_p = jnp.sum(jnp.where(iota_d3 == plm - 1, pv_, 0), axis=1)
            ok = ((ql > pl_) & prefix_full) | (
                (ql == pl_) & prefix_butl & (last_q >= last_p)
            )
            ok = ok & live  # [MP, L]
            # First compatible pointer = masked min over slot index (Mosaic
            # argmax supports only f32; this is the spike-validated idiom).
            j = jnp.min(jnp.where(ok, iota_mp, MP), axis=0, keepdims=True)
            selany = j < MP  # [1, L]
            ohj = iota_mp == j  # [MP, L]

            # Physical prune of the traversed pointer when refs hit zero
            # (KVSharedVersionedBuffer.java:164-168): shift-left at
            # (entry, slots >= j), last slot keeping its own value
            # (TimedKeyValue.removePredecessor).
            prune = selany & active & wrm & (newref == 0)
            prune_h = prune & found_h

            @pl.when(jnp.any(prune_h))
            def _():
                pm = ham3 & (iota_mp3h >= j[None]) & prune_h[None]

                def shift(get, put, m, axis=1):
                    f = get()
                    nxt = jnp.concatenate(
                        [
                            jax.lax.slice_in_dim(f, 1, None, axis=axis),
                            jax.lax.slice_in_dim(f, -1, None, axis=axis),
                        ],
                        axis=axis,
                    )
                    put(jnp.where(m, nxt, f))

                def set_h(ref):
                    def put(v):
                        ref[0:EHk] = v
                    return put

                shift(lambda: o_pstage[0:EHk], set_h(o_pstage), pm)
                shift(lambda: o_poff[0:EHk], set_h(o_poff), pm)
                shift(lambda: o_pvlen[0:EHk], set_h(o_pvlen), pm)

                def put_pver(v):
                    o_pver[0:EHk] = v

                shift(lambda: o_pver[0:EHk], put_pver, pm[:, :, None, :])
                o_npreds[0:EHk] = o_npreds[0:EHk] - jnp.where(
                    ham_h & prune_h, 1, 0
                )

            if EO:
                # One overflow-side mutation pass serves refs decrement,
                # delete, and prune for walkers resolved in the overflow
                # tier; recomputing the [EO, L] hit is cheaper than staging
                # [EO, ...] masks, and the pass is skipped whenever every
                # lane of the block resolved hot.
                @pl.when(jnp.any(act_o))
                def _():
                    hit_o = (o_stage[EHk:] == cs) & (o_off[EHk:] == co)
                    hamo = hit_o & act_o  # [EO, L]
                    o_refs[EHk:] = jnp.where(hamo, newref, o_refs[EHk:])
                    dmo = hamo & dele
                    o_stage[EHk:] = jnp.where(dmo, -1, o_stage[EHk:])
                    o_off[EHk:] = jnp.where(dmo, -1, o_off[EHk:])
                    prune_o = prune & act_o
                    pmo = (
                        hamo[:, None, :]
                        & (iota_mp3o >= j[None])
                        & prune_o[None]
                    )

                    def shift_o(get, put, m, axis=1):
                        f = get()
                        nxt = jnp.concatenate(
                            [
                                jax.lax.slice_in_dim(f, 1, None, axis=axis),
                                jax.lax.slice_in_dim(f, -1, None, axis=axis),
                            ],
                            axis=axis,
                        )
                        put(jnp.where(m, nxt, f))

                    def set_o(ref):
                        def put(v):
                            ref[EHk:] = v
                        return put

                    shift_o(lambda: o_pstage[EHk:], set_o(o_pstage), pmo)
                    shift_o(lambda: o_poff[EHk:], set_o(o_poff), pmo)
                    shift_o(lambda: o_pvlen[EHk:], set_o(o_pvlen), pmo)

                    def put_pver_o(v):
                        o_pver[EHk:] = v

                    shift_o(
                        lambda: o_pver[EHk:], put_pver_o,
                        pmo[:, :, None, :],
                    )
                    o_npreds[EHk:] = o_npreds[EHk:] - jnp.where(
                        hamo & prune_o, 1, 0
                    )

            nxt_s = jnp.sum(jnp.where(ohj, ps_, 0), axis=0, keepdims=True)
            nxt_o = jnp.sum(jnp.where(ohj, po_, 0), axis=0, keepdims=True)
            nxt_l = jnp.sum(jnp.where(ohj, pl_, 0), axis=0, keepdims=True)
            nxt_v = jnp.sum(jnp.where(ohj[:, None, :], pv_, 0), axis=0)  # [D, L]

            nactive = active & selany & (nxt_s >= 0)
            # Extraction walkers get W emitting hops; cut beyond that is a
            # counted truncation (matches ops/slab.py walks_batched).
            budget_out = emit & (cnt >= W)
            o_trunc[:] = o_trunc[:] + jnp.where(budget_out & nactive, 1, 0)
            active = nactive & ~budget_out
            cs = jnp.where(active, nxt_s, cs)
            co = jnp.where(active, nxt_o, co)
            ql = jnp.where(active, nxt_l, ql)
            qv = jnp.where(active, nxt_v, qv)
            return h + 1, active.astype(jnp.int32), cs, co, qv, ql, cnt

        zero_l = jnp.zeros((1, L), i32)
        # Early exit matters: the average walk ends well before the W-hop
        # bound (a fixed-trip fori_loop measured ~2x slower end-to-end).
        h, active_i, cs, co, qv, ql, cnt = jax.lax.while_loop(
            hop_cond, hop_body,
            (jnp.zeros((), i32), act0.astype(i32), ws, wo, qv0, wvl, zero_l),
        )
        # Walkers still active at the hop bound were truncated.
        o_trunc[:] = o_trunc[:] + active_i
        # Served extraction walkers scatter their staged hops + hop count.
        mo = (iota_or3 == srow[None]) & wot[None]
        o_ostage[:] = jnp.where(mo, st_stage[:][None], o_ostage[:])
        o_ooff[:] = jnp.where(mo, st_off[:][None], o_ooff[:])
        cm = (iota_or2 == srow) & wot
        o_count[:] = jnp.where(cm, cnt, o_count[:])
        return b + 1

    jax.lax.while_loop(
        lambda b: b < max_n, batch_body, jnp.zeros((), i32)
    )


def _to_lane_last(x):
    """[K, ...] -> [..., K]."""
    return jnp.moveaxis(x, 0, -1)


def _from_lane_last(x):
    return jnp.moveaxis(x, -1, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_walk", "out_base", "out_rows", "interpret", "hot_entries",
        "drain",
    ),
)
def walk_pass_kernel(
    slab: SlabState,
    en,
    stage,
    off,
    ver,
    vlen,
    is_remove,
    want_out,
    max_walk: int,
    out_base: int,
    out_rows: int,
    interpret: bool = False,
    put_ops=None,
    ev_off=None,
    hot_entries: int = 0,
    drain: bool = False,
) -> Tuple[SlabState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The step's walk pass for a ``[K]``-batched slab via the fused kernel.

    Same contract as ``jax.vmap`` of ``ops/slab.py: walks_compacted`` —
    ``K`` must be a multiple of 128.  Returns
    ``(slab, out_stage [K, out_rows, W], out_off, count [K, out_rows])``.

    With ``put_ops`` (a ``[K]``-batched :class:`ops.slab.PutOps`) and
    ``ev_off`` (``[K]`` current-event offsets), the step's consuming puts
    apply in-kernel BEFORE the walks — same contract as ``jax.vmap`` of
    ``puts_batched`` — so the slab crosses HBM once per step instead of
    twice.

    ``hot_entries`` > 0 enables the two-tier layout (ops/slab.py
    "Two-tier layout"): allocation prefers the hot rows (demoting the
    min-off hot entry when full), each hop's lookup/reduce runs over the
    hot rows, and the overflow rows are touched only under a block-level
    ``pl.when`` that skips when every lane of the block resolved hot —
    the common hop pays an E_hot-sized reduce instead of an E-sized one.
    Bit-exact (including the residency counters) with ``jax.vmap`` of the
    jnp path at the same ``hot_entries``.
    """
    i32 = jnp.int32
    K, E = slab.stage.shape
    MP = slab.pstage.shape[2]
    D = slab.pver.shape[3]
    PW = en.shape[1]
    W = max_walk
    OR = out_rows
    if K % LANE_BLOCK:
        raise ValueError(f"K={K} not a multiple of {LANE_BLOCK}")
    if hot_entries and (hot_entries % 8 or not 0 < hot_entries < E):
        raise ValueError(
            f"hot_entries={hot_entries} must be a multiple of 8 strictly "
            f"below slab_entries={E}"
        )

    en_i = en.astype(i32)
    rank = jnp.where(en, jnp.cumsum(en_i, axis=1) - 1, -1)

    tin = _to_lane_last
    tout = _from_lane_last
    row = lambda x: x[None, :]
    unrow = lambda x: x[0]

    nen = jnp.sum(en_i, axis=1)  # [K]

    with_puts = put_ops is not None
    if with_puts:
        p_en_i = jnp.asarray(put_ops.en).astype(i32)
        p_rank = jnp.where(put_ops.en, jnp.cumsum(p_en_i, axis=1) - 1, -1)
        put_ins = [
            tin(jnp.asarray(put_ops.first).astype(i32)),
            tin(jnp.asarray(put_ops.cur_stage, i32)),
            tin(jnp.asarray(put_ops.prev_stage, i32)),
            tin(jnp.asarray(put_ops.prev_off, i32)),
            tin(jnp.asarray(put_ops.vlen, i32)),
            jnp.transpose(jnp.asarray(put_ops.ver, i32), (2, 1, 0)),
            tin(p_rank),
            row(jnp.sum(p_en_i, axis=1)),
            row(jnp.asarray(ev_off, i32)),
        ]
    else:
        zc = jnp.zeros((1, K), i32)
        put_ins = [zc, zc, zc, zc, zc,
                   jnp.zeros((1, 1, K), i32), zc, zc, zc]

    ins = [
        tin(slab.stage),
        tin(slab.off),
        tin(slab.refs),
        tin(slab.npreds),
        tin(slab.pstage),
        tin(slab.poff),
        tin(slab.pvlen),
        tin(slab.pver),  # [E, MP, D, K]
        # Per-lane scalar counters arrive as [K]; kernel blocks want [1, L].
        row(slab.missing),
        row(slab.trunc),
        row(slab.full_drops),
        row(slab.pred_drops),
        row(slab.hot_hits),
        row(slab.hot_misses),
        row(slab.overflow_walks),
        row(slab.demotions),
        row(slab.walk_hops),
        row(slab.extract_hops),
        row(slab.drain_hops),
        *put_ins,
        tin(en_i),
        tin(jnp.asarray(stage, i32)),
        tin(jnp.asarray(off, i32)),
        tin(jnp.asarray(vlen, i32)),
        # [K, PW, D] -> [D, PW, K] (tile-exact trailing dims).
        jnp.transpose(jnp.asarray(ver, i32), (2, 1, 0)),
        tin(jnp.asarray(is_remove).astype(i32)),
        tin(jnp.asarray(want_out).astype(i32)),
        tin(rank),
        row(nen),
    ]
    # Per-stage hop attribution rides only when enabled — SA == 0 adds no
    # input, no output, and no kernel ops (zero new device work).
    SA = int(slab.stage_hops.shape[-1])
    if SA:
        ins.append(tin(slab.stage_hops))  # [S, K]

    L = LANE_BLOCK
    grid = (K // L,)

    def bspec(shape):
        nd = len(shape)
        return pl.BlockSpec(
            shape[:-1] + (L,),
            (lambda i, nd=nd: (0,) * (nd - 1) + (i,)),
            memory_space=pltpu.VMEM,
        )

    in_specs = [bspec(tuple(x.shape[:-1]) + (L,)) for x in ins]
    out_shapes = [
        jax.ShapeDtypeStruct((E, K), i32),  # stage
        jax.ShapeDtypeStruct((E, K), i32),  # off
        jax.ShapeDtypeStruct((E, K), i32),  # refs
        jax.ShapeDtypeStruct((E, K), i32),  # npreds
        jax.ShapeDtypeStruct((E, MP, K), i32),  # pstage
        jax.ShapeDtypeStruct((E, MP, K), i32),  # poff
        jax.ShapeDtypeStruct((E, MP, K), i32),  # pvlen
        jax.ShapeDtypeStruct((E, MP, D, K), i32),  # pver
        jax.ShapeDtypeStruct((1, K), i32),  # missing
        jax.ShapeDtypeStruct((1, K), i32),  # trunc
        jax.ShapeDtypeStruct((1, K), i32),  # full_drops
        jax.ShapeDtypeStruct((1, K), i32),  # pred_drops
        jax.ShapeDtypeStruct((1, K), i32),  # hot_hits
        jax.ShapeDtypeStruct((1, K), i32),  # hot_misses
        jax.ShapeDtypeStruct((1, K), i32),  # overflow_walks
        jax.ShapeDtypeStruct((1, K), i32),  # demotions
        jax.ShapeDtypeStruct((1, K), i32),  # walk_hops
        jax.ShapeDtypeStruct((1, K), i32),  # extract_hops
        jax.ShapeDtypeStruct((1, K), i32),  # drain_hops
        jax.ShapeDtypeStruct((OR, W, K), i32),  # out_stage
        jax.ShapeDtypeStruct((OR, W, K), i32),  # out_off
        jax.ShapeDtypeStruct((OR, K), i32),  # count
    ]
    if SA:
        out_shapes.append(jax.ShapeDtypeStruct((SA, K), i32))  # stage_hops
    out_specs = [bspec(tuple(s.shape[:-1]) + (L,)) for s in out_shapes]

    scratch_shapes = [
        pltpu.VMEM((W, LANE_BLOCK), jnp.int32),
        pltpu.VMEM((W, LANE_BLOCK), jnp.int32),
    ]
    if hot_entries:
        # Per-hop staging of the overflow tier's contribution (written only
        # under the miss branch, read unconditionally in the combine).
        scratch_shapes += [
            pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_found
            pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_refs
            pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_np
            pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_ps
            pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_po
            pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_pl
            pltpu.VMEM((MP, D, LANE_BLOCK), jnp.int32),  # sc_pv
        ]

    # The slab is updated in place: each of its 19 leaves (and the stage
    # hops) is written over its own input.  A lane block is read whole
    # before its block is written, so no block reads a neighbour's result,
    # and the step holds one copy of the slab where it held two.
    aliases = {i: i for i in range(19)}
    if SA:
        aliases[len(ins) - 1] = 22

    outs = pl.pallas_call(
        functools.partial(
            _kernel, W=W, out_base=out_base, out_rows=out_rows,
            with_puts=with_puts, EH=hot_entries, SA=SA, drain=drain,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*ins)

    (n_stage, n_off, n_refs, n_npreds, n_pstage, n_poff, n_pvlen, n_pver,
     n_missing, n_trunc, n_fulld, n_predd, n_hh, n_hm, n_ow, n_dm,
     n_wh, n_eh, n_dh,
     o_stage, o_off, o_count) = outs[:22]
    new_stage_hops = tout(outs[22]) if SA else slab.stage_hops
    new_slab = slab._replace(
        stage=tout(n_stage),
        off=tout(n_off),
        refs=tout(n_refs),
        npreds=tout(n_npreds),
        pstage=tout(n_pstage),
        poff=tout(n_poff),
        pvlen=tout(n_pvlen),
        pver=tout(n_pver),
        missing=unrow(n_missing),
        trunc=unrow(n_trunc),
        full_drops=unrow(n_fulld),
        pred_drops=unrow(n_predd),
        hot_hits=unrow(n_hh),
        hot_misses=unrow(n_hm),
        overflow_walks=unrow(n_ow),
        demotions=unrow(n_dm),
        walk_hops=unrow(n_wh),
        extract_hops=unrow(n_eh),
        drain_hops=unrow(n_dh),
        stage_hops=new_stage_hops,
    )
    return (
        new_slab,
        tout(o_stage),
        tout(o_off),
        tout(o_count),
    )
