"""Whole-scan fused Pallas kernel — the entire event loop in VMEM.

Round 4's walk kernel (``ops/walk_kernel.py``) fused the buffer phases of
ONE step; the remaining ~2 ms/step of jnp (predicates, the unrolled
evaluation chain, op building, queue compaction) plus the per-step kernel
launch and per-step slab HBM round-trip set the round-4 ceiling at ~630K
ev/s (PROFILE_r04.md postscript item 5).  This kernel fuses the WHOLE
scan: grid ``(K/128, T)`` with the time axis as the sequential minor
dimension, so each 128-lane block's run state and slab live in VMEM
output blocks revisited across all ``T`` steps (the standard TPU
reduction/accumulator pattern) — state and slab cross HBM once per scan,
not once per step — while each step's events stream in and each step's
match emissions stream out through ``t``-indexed blocks.

Inside one grid step the phases are the engine's, in the engine's order
(``engine/matcher.py _build_step``): predicate evaluation over the run
axis, the unrolled ``NFA.evaluate`` chain (``NFA.java:94-289``) including
typed fold application, consuming puts and the merged walk pass (ported
from ``ops/walk_kernel.py`` — one walker per lane per batch in queue-order
rank, sequential-exact by construction), and scatter-free queue
compaction.  User predicates and fold functions are traced INTO the
kernel as ``[R, L]`` vector programs — they are already required to be
pure elementwise array code, so the same lambdas lower to Mosaic; a
pattern whose predicates do not lower falls back to the per-step path
(``build_scan`` raises at trace time, callers catch).

Single-query only (``Q == 1``); stacked banks keep the per-step kernel.
Differentially tested against the jnp engine in
``tests/test_scan_kernel.py`` (interpret mode on CPU) and through the
engine A/B fuzz suites.
"""

from __future__ import annotations

import functools
from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kafkastreams_cep_tpu.compiler.tables import OP_BEGIN, OP_TAKE, TYPE_BEGIN
from kafkastreams_cep_tpu.engine.matcher import (
    ArrayStates,
    EngineConfig,
    EngineState,
    EventBatch,
    StepOutput,
)
from kafkastreams_cep_tpu.ops.slab import SlabState
from kafkastreams_cep_tpu.ops.walk_kernel import _coalesced_demote
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("ops.scan_kernel")

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


def _sel_table(table: np.ndarray, idx):
    """``table[idx]`` for a tiny static table and a traced [..] index —
    compile-time-unrolled one-hot (S is the stage count, single digits)."""
    out = jnp.zeros_like(idx)
    for s, v in enumerate(np.asarray(table).tolist()):
        out = jnp.where(idx == s, jnp.int32(v), out)
    return out


def build_scan(tables, config: EngineConfig, promotion=None):
    """A jitted ``scan(state, events) -> (state, outs)`` over the fused
    whole-scan kernel, or raise if the pattern cannot lower.

    Contract matches ``BatchMatcher.scan``: ``state`` is a ``[K]``-batched
    :class:`EngineState`, ``events`` a ``[K, T]`` :class:`EventBatch`,
    outputs ``[K, T, R, W]``.  ``K`` must be a multiple of 128.

    ``promotion`` (the tiering plan's prefix length ``p``) compiles the
    *tiered* variant: ``scan(state, events, promo) -> (state, outs,
    promoted)`` where ``promo`` is the stencil tier's
    :class:`~kafkastreams_cep_tpu.engine.stencil.PromoOutput` feed.  The
    promotion step (``engine/tiered.py: build_promote`` — the prefix
    chain's slab writes plus the suffix run-queue append) runs as a fused
    phase after the engine phases of each step, and the whole engine step
    is gated per step on device: a step with no live suffix run and no
    prefix completion touches nothing but the step counter — the
    in-kernel analog of the chunked path's ``lax.cond`` skip
    (``parallel/tiered.py``).
    """
    cfg = config
    PROMO = int(promotion) if promotion else 0
    R, E, MP, D, W = (
        cfg.max_runs, cfg.slab_entries, cfg.slab_preds, cfg.dewey_depth,
        cfg.max_walk,
    )
    # Two-tier slab layout (ops/slab.py "Two-tier layout" note): rows
    # [0, EHk) hot, [EHk, E) overflow.  slab_hot_entries == 0 instantiates
    # the legacy single tier as EHk = E / EO = 0 — the overflow-side blocks
    # below then vanish at trace time and the hot-side code is the original
    # full-slab code.
    EH = cfg.slab_hot_entries
    EHk = EH if EH else E
    EO = E - EHk
    # Lazy extraction (EngineConfig.lazy_extraction): completed matches
    # append to the in-state handle ring (phase 6) instead of running
    # extraction walkers in phase 4; the drain pass runs OUTSIDE this
    # kernel (engine/matcher.py build_drain) at scan cadence.
    LAZY = cfg.lazy_extraction
    HB = cfg.handle_ring
    # Per-stage attribution width (EngineConfig.stage_attribution): when
    # 0 the two attribution arrays are absent from the kernel I/O and all
    # tally code vanishes at trace time — zero new device work.
    SA = tables.num_stages if cfg.stage_attribution else 0
    # kernel output refs (run state + slab + counters + ring + emits
    # [+ the two stage-attribution arrays when SA > 0][+ the promotion
    # count accumulator when PROMO])
    N_OUT = 43 + (2 if SA else 0) + (1 if PROMO else 0)
    H = tables.max_hops
    NS = max(tables.num_states, 1)
    S_CAND = 1 + H + 1
    RS = R * S_CAND
    RH = R * H
    PW = RH + 2 * R  # walker queue: branches, dead removals, finals
    S = tables.num_stages
    L = LANE_BLOCK
    i32 = jnp.int32

    ident = np.asarray(tables.ident)
    types = np.asarray(tables.types)
    consume_op = np.asarray(tables.consume_op)
    consume_pred = np.asarray(tables.consume_pred)
    consume_target = np.asarray(tables.consume_target)
    ignore_pred = np.asarray(tables.ignore_pred)
    proceed_pred = np.asarray(tables.proceed_pred)
    proceed_target = np.asarray(tables.proceed_target)
    window_ms = np.asarray(tables.window_ms.astype(np.int64))
    final_pos = int(tables.final_pos)
    begin_pos = int(tables.begin_pos)
    # Same predicate-dedup pass as the jnp path (_build_step): distinct
    # predicates evaluate once per event, shared across every edge that
    # references them; provably state-independent ones get an empty
    # states env so their kernel code carries no agg dependence.
    from kafkastreams_cep_tpu.compiler.multitenant import (
        plan_step_predicates,
    )

    pred_plan = plan_step_predicates([tables])
    pred_entries = list(pred_plan.event_entries) + list(
        pred_plan.run_entries
    )
    _remap = pred_plan.remaps[0]
    if len(_remap):
        def _remap_ids(a):
            return np.where(a >= 0, _remap[np.maximum(a, 0)], a)

        consume_pred = _remap_ids(consume_pred)
        ignore_pred = _remap_ids(ignore_pred)
        proceed_pred = _remap_ids(proceed_pred)
    is_float = [d == "float32" for d in tables.state_dtypes] + [False] * (
        NS - tables.num_states
    )
    inits_np = np.asarray(
        [
            int(np.float32(x).view(np.int32)) if f else int(np.int32(x))
            for x, f in zip(
                list(tables.state_inits) + [0] * (NS - tables.num_states),
                is_float,
            )
        ]
        or [0],
        dtype=np.int32,
    )

    if PROMO:
        # Promotion statics (engine/tiered.py build_promote): the prefix
        # stage identities, the appended run's eval position, and the
        # chain's per-put predecessor links are all trace-time constants.
        if not 0 < PROMO <= D:
            raise ValueError(
                f"promotion={PROMO} must be in 1..dewey_depth={D}"
            )
        promo_idents = [int(ident[j]) for j in range(PROMO)]
        promo_eval = int(consume_target[PROMO - 1])

    def dec(v, flt):
        return jax.lax.bitcast_convert_type(v, jnp.float32) if flt else v

    def enc(v, flt):
        if flt:
            return jax.lax.bitcast_convert_type(
                jnp.asarray(v, jnp.float32), jnp.int32
            )
        return jnp.asarray(v, i32)

    # Aggregator slots: (stage position, state slot, fn).
    agg_slots = [(a.stage, a.state, a.fn) for a in tables.aggs]

    def kernel(
        # inputs: run state (lane-last)
        alive, id_pos, eval_pos, vlen, event_off, start_ts, branching, agg,
        ver,
        # slab
        sstage, soff, srefs, snpreds, spstage, spoff, spvlen, spver,
        # counters
        run_drops, ver_ovf, fulld, predd, missing, trunc, hh, hm, ow, dm,
        wh, eh, dh,
        # lazy-extraction handle ring + step counter
        hr_stage, hr_off, hr_vlen_i, hr_ts, hr_seq, hr_row, hr_ver,
        hr_count, seq0, hovf,
        # tail: [stc_in, shp_in when SA] then per-t event slices, outputs,
        # scratch — unpacked by index so SA == 0 adds nothing.
        *rest,
    ):
        ri = 0
        if SA:
            stc_in, shp_in = rest[0], rest[1]
            ri = 2
        ev_key, ev_ts, ev_off, ev_valid = rest[ri:ri + 4]
        ri += 4
        n_leaves = len(value_dtypes)
        ev_leaves = rest[ri:ri + n_leaves]
        ri += n_leaves
        if PROMO:
            # Per-step promotion feed (stencil tier): fire flag, the p
            # prefix-event offsets, the window anchor, the seed version.
            pr_fire, pr_offs, pr_anchor, pr_sver = rest[ri:ri + 4]
            ri += 4
        outs_flat = rest[ri:ri + N_OUT]
        (o_alive, o_id, o_eval, o_vlen, o_event, o_start, o_branch, o_agg,
         o_ver, o_sstage, o_soff, o_srefs, o_snpreds, o_spstage, o_spoff,
         o_spvlen, o_spver, o_rd, o_vo, o_fd, o_pd, o_ms, o_tr,
         o_hh, o_hm, o_ow, o_dm, o_wh, o_eh, o_dh,
         o_hrstage, o_hroff, o_hrvlen, o_hrts, o_hrseq, o_hrrow, o_hrver,
         o_hrcount, o_seq, o_hovf) = outs_flat[:40]
        oi = 40
        if SA:
            o_stc, o_shp = outs_flat[40], outs_flat[41]
            oi = 42
        if PROMO:
            o_promoted = outs_flat[oi]
            oi += 1
        o_ostage, o_ooff, o_ocount = outs_flat[oi:oi + 3]
        if EO:
            (sc_found, sc_refs, sc_np, sc_ps, sc_po, sc_pl, sc_pv) = rest[
                ri + N_OUT:
            ]

        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            o_alive[:] = alive[:]
            o_id[:] = id_pos[:]
            o_eval[:] = eval_pos[:]
            o_vlen[:] = vlen[:]
            o_event[:] = event_off[:]
            o_start[:] = start_ts[:]
            o_branch[:] = branching[:]
            o_agg[:] = agg[:]
            o_ver[:] = ver[:]
            o_sstage[:] = sstage[:]
            o_soff[:] = soff[:]
            o_srefs[:] = srefs[:]
            o_snpreds[:] = snpreds[:]
            o_spstage[:] = spstage[:]
            o_spoff[:] = spoff[:]
            o_spvlen[:] = spvlen[:]
            o_spver[:] = spver[:]
            o_rd[:] = run_drops[:]
            o_vo[:] = ver_ovf[:]
            o_fd[:] = fulld[:]
            o_pd[:] = predd[:]
            o_ms[:] = missing[:]
            o_tr[:] = trunc[:]
            o_hh[:] = hh[:]
            o_hm[:] = hm[:]
            o_ow[:] = ow[:]
            o_dm[:] = dm[:]
            o_wh[:] = wh[:]
            o_eh[:] = eh[:]
            o_dh[:] = dh[:]
            o_hrstage[:] = hr_stage[:]
            o_hroff[:] = hr_off[:]
            o_hrvlen[:] = hr_vlen_i[:]
            o_hrts[:] = hr_ts[:]
            o_hrseq[:] = hr_seq[:]
            o_hrrow[:] = hr_row[:]
            o_hrver[:] = hr_ver[:]
            o_hrcount[:] = hr_count[:]
            o_hovf[:] = hovf[:]
            if SA:
                o_stc[:] = stc_in[:]
                o_shp[:] = shp_in[:]
            if PROMO:
                o_promoted[:] = jnp.zeros((1, L), i32)

        # The per-lane step counter ticks every step (padding included) —
        # it is the emission t-index, not match state.  seq_now is this
        # step's stamp; the output carries the post-scan value.
        seq_now = seq0[:] + t
        o_seq[:] = seq_now + 1

        # Event blocks arrive [1, 1, L] ([T, 1, K] arrays — the middle 1
        # keeps the trailing dims tileable); squeeze the t axis.
        valid = ev_valid[:][0] != 0  # [1, L]
        key = ev_key[:][0]
        ts = ev_ts[:][0]
        off = ev_off[:][0]

        # Emission blocks are fresh garbage at every t: write the
        # no-emission frame up front so a gated-off step still outputs a
        # well-formed (empty) slice.  Steps that do run overwrite these
        # in phase 4 and re-mask them in phase 5.
        o_ostage[:] = jnp.full((1, R, W, L), -1, i32)
        o_ooff[:] = jnp.full((1, R, W, L), -1, i32)
        o_ocount[:] = jnp.zeros((1, R, L), i32)
        if PROMO:
            fire_row = pr_fire[:][0] != 0  # [1, L]

        # The engine step proper.  Under PROMO the whole step runs
        # under a per-step device gate — the in-kernel analog of the
        # chunked path's lax.cond skip (parallel/tiered.py): with no
        # live suffix run and no prefix completion, every phase
        # effect below is masked to zero and the state/emission
        # writes are no-ops (the empty emission frame was already
        # written above), so skipping the step is exact.
        def _engine_step():
            # ---- phase 1: predicates over the run axis ([R, L] operands) ----
            st_alive = o_alive[:] != 0  # [R, L]
            st_branch = o_branch[:] != 0
            agg_now = o_agg[:]  # [NS, R, L]
            states = ArrayStates(
                {
                    n: dec(agg_now[i], is_float[i])
                    for i, n in enumerate(tables.state_names)
                }
            )
            value = jax.tree_util.tree_unflatten(
                value_treedef, [l[:][0] for l in ev_leaves]
            )
            empty_states = ArrayStates({})
            preds = [
                jnp.broadcast_to(
                    jnp.asarray(
                        e.pred(
                            key, value, ts,
                            states if e.stateful else empty_states,
                        ),
                        jnp.bool_,
                    ),
                    (R, L),
                )
                for e in pred_entries
            ]

            def pv(pid):
                """Predicate value by (traced) id; -1 = absent edge = False.
                Boolean algebra, not where() — Mosaic cannot select i1
                vectors (same note as ops/walk_kernel.py)."""
                out = jnp.zeros((R, L), jnp.bool_)
                for p, v in enumerate(preds):
                    out = out | ((pid == p) & v)
                return out

            # ---- phase 2: the unrolled evaluation chain (NFA.java:94-289),
            # the direct vector port of matcher.chain_one with [R, L] frames --
            iota_d = jax.lax.broadcasted_iota(i32, (D, R, L), 0)

            def add_run(vv, vl):
                return vv + jnp.where(iota_d == vl[None] - 1, 1, 0)

            seed = o_id[:] < 0
            idc = jnp.maximum(o_id[:], 0)
            id_type_begin = seed | (_sel_table(types, idc) == TYPE_BEGIN)
            start = jnp.where(id_type_begin, ts, o_start[:])

            if cfg.enforce_windows:
                w = _sel_table(window_ms.astype(np.int32), o_eval[:])
                out_w = (
                    (~id_type_begin) & (w != -1) & (ts - o_start[:] > w)
                )
            else:
                out_w = jnp.zeros((R, L), jnp.bool_)
            active = st_alive & ~out_w & valid

            cross0 = _sel_table(ident, o_eval[:]) != idc
            do_add0 = active & ~seed & cross0 & ~st_branch
            ovf0 = o_vlen[:] >= D
            vl = jnp.where(do_add0 & ~ovf0, o_vlen[:] + 1, o_vlen[:])
            vv = o_ver[:]
            ovf_ct = jnp.sum(
                jnp.where(do_add0 & ovf0, 1, 0), axis=0, keepdims=True
            )

            cur = o_eval[:]
            prev = jnp.where(seed, i32(-1), o_id[:])

            surv_alive = jnp.zeros((R, L), jnp.bool_)
            surv_final = jnp.zeros((R, L), jnp.bool_)
            surv_id = jnp.zeros((R, L), i32)
            surv_eval = jnp.zeros((R, L), i32)
            surv_ver = jnp.zeros((D, R, L), i32)
            surv_vlen = jnp.zeros((R, L), i32)
            surv_event = jnp.zeros((R, L), i32)
            surv_start = jnp.zeros((R, L), i32)
            surv_branching = jnp.zeros((R, L), jnp.bool_)

            put_en, put_cur, put_prev, put_ver, put_vlen = [], [], [], [], []
            br_en, br_prev, br_ver, br_vlen = [], [], [], []
            br_run_ver, br_id, br_eval, br_event, br_start = [], [], [], [], []
            consumed_h, frame_pos = [], []
            if SA:
                iota_sar = jax.lax.broadcasted_iota(i32, (SA, R, L), 0)
                tly = [jnp.zeros((SA, L), i32) for _ in range(4)]

            for _h in range(H):
                cs = jnp.maximum(cur, 0)
                cop = _sel_table(consume_op, cs)
                cp = pv(_sel_table(consume_pred, cs))
                take_m = active & (cop == OP_TAKE) & cp
                begin_m = active & (cop == OP_BEGIN) & cp
                ig_m = active & pv(_sel_table(ignore_pred, cs))
                pr_m = active & pv(_sel_table(proceed_pred, cs))
                branch_m = (
                    (pr_m & take_m) | (ig_m & take_m) | (ig_m & begin_m)
                    | (ig_m & pr_m)
                ) & (prev >= 0)
                consumed = take_m | begin_m
                if SA:
                    # Per-stage selectivity tallies (matcher.chain_one):
                    # evaluated / accepted / ignored / rejected frames by
                    # stage, reduced over the run axis.
                    rejected = active & ~consumed & ~ig_m & ~pr_m
                    hit_s = iota_sar == cs[None]
                    for c, m in enumerate((active, consumed, ig_m, rejected)):
                        tly[c] = tly[c] + jnp.sum(
                            jnp.where(hit_s & m[None], 1, 0), axis=1
                        )

                st = take_m & ~branch_m
                sb = begin_m
                si = ig_m & ~branch_m
                fire = st | sb | si
                tgt = _sel_table(consume_target, cs)
                surv_id = jnp.where(
                    fire, jnp.where(si, o_id[:], _sel_table(ident, cs)), surv_id
                )
                surv_eval = jnp.where(
                    fire, jnp.where(st, cs, jnp.where(sb, tgt, o_eval[:])),
                    surv_eval,
                )
                surv_ver = jnp.where(fire[None], vv, surv_ver)
                surv_vlen = jnp.where(fire, vl, surv_vlen)
                surv_event = jnp.where(
                    fire, jnp.where(si, o_event[:], off), surv_event
                )
                surv_start = jnp.where(
                    fire, jnp.where(si, o_start[:], start), surv_start
                )
                # Boolean algebra (no i1 selects in Mosaic).
                surv_branching = (fire & si & st_branch) | (
                    ~fire & surv_branching
                )
                surv_final = (fire & sb & (tgt == final_pos)) | (
                    ~fire & surv_final
                )
                surv_alive = surv_alive | fire

                put_en.append(consumed)
                put_cur.append(_sel_table(ident, cs))
                put_prev.append(
                    jnp.where(
                        prev >= 0, _sel_table(ident, jnp.maximum(prev, 0)),
                        i32(-1),
                    )
                )
                put_ver.append(
                    jnp.where((take_m & branch_m)[None], add_run(vv, vl), vv)
                )
                put_vlen.append(vl)

                br_en.append(branch_m)
                br_prev.append(_sel_table(ident, jnp.maximum(prev, 0)))
                br_ver.append(vv)
                br_vlen.append(vl)
                br_run_ver.append(add_run(vv, vl))
                br_id.append(_sel_table(ident, jnp.maximum(prev, 0)))
                br_eval.append(cs)
                br_event.append(jnp.where(ig_m, o_event[:], off))
                br_start.append(start)
                consumed_h.append(consumed)
                frame_pos.append(cs)

                ptgt = _sel_table(proceed_target, cs)
                ptc = jnp.maximum(ptgt, 0)
                do_add = (
                    pr_m
                    & (_sel_table(ident, ptc) != _sel_table(ident, cs))
                    & ~st_branch
                )
                ovf_b = vl >= D
                ovf_ct = ovf_ct + jnp.sum(
                    jnp.where(do_add & ovf_b, 1, 0), axis=0, keepdims=True
                )
                vl = jnp.where(do_add & ~ovf_b, vl + 1, vl)
                prev = jnp.where(pr_m, cs, prev)
                cur = jnp.where(pr_m, ptc, cur)
                active = pr_m

            # Folds (deepest frame last to first, NFA.java:243 before :248),
            # with branch copies restricted to the branching stage's states.
            # (Init values build from scalar literals — Pallas kernels cannot
            # capture array constants.)
            # The agg planes stay a Python list of [R, L] arrays — indexed
            # updates on a stacked array would lower to scatter, which Mosaic
            # has no rule for.
            s_list = [agg_now[ns] for ns in range(NS)]
            init_list = [
                jnp.full((R, L), int(v), i32) for v in inits_np.tolist()
            ]
            br_agg: List[Any] = [None] * H
            for h in range(H - 1, -1, -1):
                copy_rows = []
                for ns in range(NS):
                    m = jnp.zeros((R, L), jnp.bool_)
                    for stage_pos, state_slot, _fn in agg_slots:
                        if state_slot == ns:
                            m = m | (frame_pos[h] == stage_pos)
                    copy_rows.append(m)
                br_agg[h] = jnp.stack(
                    [
                        jnp.where(copy_rows[ns], s_list[ns], init_list[ns])
                        for ns in range(NS)
                    ]
                )
                for stage_pos, state_slot, fn in agg_slots:
                    cond = consumed_h[h] & (frame_pos[h] == stage_pos)
                    flt = is_float[state_slot]
                    val = enc(fn(key, value, dec(s_list[state_slot], flt)), flt)
                    s_list[state_slot] = jnp.where(
                        cond, val, s_list[state_slot]
                    )
            final_agg = jnp.stack(s_list)
            inits_rl = jnp.stack(init_list)

            any_br = (
                functools.reduce(jnp.logical_or, br_en)
                if H else jnp.zeros((R, L), jnp.bool_)
            )
            has_succ = surv_alive | any_br
            dead = st_alive & ~seed & ~has_succ & valid
            final_en = surv_alive & surv_final & valid
            if SA:
                o_stc[:] = o_stc[:] + jnp.stack(tly)

            # ---- phase 3: consuming puts, in queue order (one per lane per
            # batch — the sequential semantics; port of walk_kernel put phase
            # against the resident slab refs) ----
            def stack_rh(frames):  # H x [R, L] -> [RH, L], run-major
                return jnp.stack(frames, axis=1).reshape(RH, L)

            def stack_rh_d(frames):  # H x [D, R, L] -> [D, RH, L]
                return jnp.stack(frames, axis=2).reshape(D, RH, L)

            # Masks stack/reshape in i32 — Mosaic cannot relayout i1
            # vectors through stack/reshape (bitcast_vreg failure).
            p_en_i = stack_rh([jnp.where(m, 1, 0) for m in put_en])
            p_en = p_en_i != 0
            p_cur = stack_rh(put_cur)
            p_prev = stack_rh(put_prev)
            p_pver = stack_rh_d(put_ver)
            p_pvlen = stack_rh(put_vlen)
            p_first_i = jnp.where(p_en & (p_prev < 0), 1, 0)
            prev_off_rep = jnp.broadcast_to(
                o_event[:][:, None, :], (R, H, L)
            ).reshape(RH, L)

            p_rank = jnp.where(p_en, _cumsum0(p_en_i) - 1, -1)
            max_pn = jnp.max(jnp.sum(p_en_i, axis=0))
            if EO:
                # Coalesced demotion pre-pass (ops/walk_kernel.py): one move
                # pass per step instead of one pl.when per put.
                creator_c, crank_c, claim_c, kcap_c = _coalesced_demote(
                    (o_sstage, o_soff, o_srefs, o_snpreds, o_spstage, o_spoff,
                     o_spvlen, o_spver, o_dm),
                    p_en, p_first_i != 0, p_cur, p_prev, prev_off_rep, off,
                    EHk=EHk, EO=EO, MP=MP, D=D,
                )

            iota_e = jax.lax.broadcasted_iota(i32, (E, L), 0)
            iota_mp = jax.lax.broadcasted_iota(i32, (MP, L), 0)
            iota_mp3 = jax.lax.broadcasted_iota(i32, (E, MP, L), 1)
            iota_d3 = jax.lax.broadcasted_iota(i32, (D, MP, L), 0)
            iota_eh = jax.lax.broadcasted_iota(i32, (EHk, L), 0)
            iota_mp3h = jax.lax.broadcasted_iota(i32, (EHk, MP, L), 1)
            if EO:
                iota_mp3o = jax.lax.broadcasted_iota(i32, (EO, MP, L), 1)

            def put_body(b):
                pselm = p_rank == b  # [RH, L]
                en0 = jnp.any(pselm, axis=0, keepdims=True)

                def ppick(f):
                    return jnp.sum(jnp.where(pselm, f, 0), axis=0, keepdims=True)

                first = jnp.any(
                    pselm & (p_first_i != 0), axis=0, keepdims=True
                )
                cur_s = ppick(p_cur)
                pst = ppick(p_prev)
                pof = ppick(prev_off_rep)
                pvl = ppick(p_pvlen)
                pvr = jnp.sum(jnp.where(pselm[None], p_pver, 0), axis=1)  # [D, L]
                off_l = off  # [1, L]

                prev_hit = (o_sstage[:] == pst) & (o_soff[:] == pof)
                prev_found = jnp.any(prev_hit, axis=0, keepdims=True)
                o_ms[:] = o_ms[:] + jnp.where(en0 & ~first & ~prev_found, 1, 0)
                en_ok = en0 & (first | prev_found)

                cur_hit = (o_sstage[:] == cur_s) & (o_soff[:] == off_l)
                exist = jnp.any(cur_hit, axis=0, keepdims=True)
                # Two-tier allocation: demotions already ran in the coalesced
                # pre-pass (ops/walk_kernel.py _coalesced_demote); allocation
                # is a rank lookup into the claim map.  EO == 0 keeps the
                # legacy first-free-slot scan verbatim.
                if EO:
                    is_cr = jnp.any(
                        pselm & creator_c, axis=0, keepdims=True
                    )
                    crk = ppick(crank_c)
                    alloc_h = (claim_c == crk) & is_cr
                    alloc = jnp.min(
                        jnp.where(alloc_h, iota_eh, E), axis=0, keepdims=True
                    )
                    has_free = is_cr & (crk < kcap_c) & (alloc < E)
                else:
                    free_h = o_sstage[:] < 0
                    ffs_h = jnp.min(
                        jnp.where(free_h, iota_eh, EHk), axis=0, keepdims=True
                    )
                    alloc = ffs_h
                    has_free = ffs_h < EHk
                tgt = (exist & cur_hit) | (~exist & (iota_e == alloc))
                ok = en_ok & (exist | has_free)
                o_fd[:] = o_fd[:] + jnp.where(en_ok & ~exist & ~has_free, 1, 0)
                m1 = tgt & ok
                reset = ok & (first | ~exist)
                o_sstage[:] = jnp.where(m1, cur_s, o_sstage[:])
                o_soff[:] = jnp.where(m1, off_l, o_soff[:])
                o_srefs[:] = jnp.where(m1 & reset, 1, o_srefs[:])
                np_e = jnp.sum(
                    jnp.where(m1, o_snpreds[:], 0), axis=0, keepdims=True
                )
                n_eff = jnp.where(reset, 0, np_e)
                pfull = ok & (n_eff >= MP)
                o_pd[:] = o_pd[:] + jnp.where(pfull, 1, 0)
                do = ok & ~pfull
                slot = jnp.minimum(n_eff, MP - 1)
                m2 = (
                    m1[:, None, :]
                    & (iota_mp3 == slot[:, None, :])
                    & do[:, None, :]
                )
                o_spstage[:] = jnp.where(
                    m2, jnp.where(first, -1, pst)[:, None, :], o_spstage[:]
                )
                o_spoff[:] = jnp.where(
                    m2, jnp.where(first, -1, pof)[:, None, :], o_spoff[:]
                )
                o_spvlen[:] = jnp.where(m2, pvl[:, None, :], o_spvlen[:])
                o_spver[:] = jnp.where(
                    m2[None], pvr[:, None, None, :], o_spver[:]
                )
                o_snpreds[:] = jnp.where(
                    m1, n_eff + jnp.where(do, 1, 0), o_snpreds[:]
                )
                return b + 1

            jax.lax.while_loop(lambda b: b < max_pn, put_body, jnp.zeros((), i32))

            # ---- phase 4: the merged walk pass (branch refcount walks
            # deepest-first, dead-run removals, final extractions) — port of
            # walk_kernel batch loop against the resident refs ----
            def rev_rh(frames):  # deepest-first: reverse the frame axis
                return jnp.stack(frames[::-1], axis=1).reshape(RH, L)

            def rev_rh_d(frames):
                return jnp.stack(frames[::-1], axis=2).reshape(D, RH, L)

            dead_en = dead & (o_event[:] >= 0)
            # Lazy extraction: the final segment keeps its rows (static
            # layout) but never enables — matches become ring handles in
            # phase 6 instead of W-hop extraction walkers here.
            final_w = (
                jnp.zeros((R, L), i32) if LAZY else jnp.where(final_en, 1, 0)
            )
            w_en_i = jnp.concatenate([
                rev_rh([jnp.where(m, 1, 0) for m in br_en]),
                jnp.where(dead_en, 1, 0),
                final_w,
            ])
            w_en = w_en_i != 0
            w_rem_i = jnp.concatenate(
                [jnp.zeros((RH, L), i32), jnp.ones((2 * R, L), i32)]
            )
            w_out_i = jnp.concatenate(
                [jnp.zeros((RH + R, L), i32), jnp.ones((R, L), i32)]
            )
            w_stage = jnp.concatenate(
                [rev_rh(br_prev), jnp.maximum(o_id[:], 0), surv_id]
            )
            w_off = jnp.concatenate(
                [prev_off_rep, o_event[:], jnp.broadcast_to(off, (R, L))]
            )
            w_ver = jnp.concatenate([rev_rh_d(br_ver), o_ver[:], surv_ver], axis=1)
            w_vlen = jnp.concatenate([rev_rh(br_vlen), o_vlen[:], surv_vlen])
            w_rank = jnp.where(w_en, _cumsum0(w_en_i) - 1, -1)
            max_n = jnp.max(jnp.sum(w_en_i, axis=0))
            iota_pw = jax.lax.broadcasted_iota(i32, (PW, L), 0)
            if SA:
                iota_sa2 = jax.lax.broadcasted_iota(i32, (SA, L), 0)
            # Emission blocks carry the t axis as a leading 1 (out_t_spec).
            iota_or3 = jax.lax.broadcasted_iota(i32, (1, R, W, L), 1)
            iota_w2 = jax.lax.broadcasted_iota(i32, (W, L), 0)
            iota_or2 = jax.lax.broadcasted_iota(i32, (1, R, L), 1)

            def batch_body(carry):
                b = carry
                selm = w_rank == b
                act0 = jnp.any(selm, axis=0, keepdims=True)

                def pick(f):
                    return jnp.sum(jnp.where(selm, f, 0), axis=0, keepdims=True)

                ws = pick(w_stage)
                wo = pick(w_off)
                wvl = pick(w_vlen)
                wrm_i = jnp.where(
                    jnp.any(selm & (w_rem_i != 0), axis=0, keepdims=True), 1, 0
                )
                wot_i = jnp.where(
                    jnp.any(selm & (w_out_i != 0), axis=0, keepdims=True), 1, 0
                )
                srow = pick(iota_pw - (RH + R))
                qv0 = jnp.sum(jnp.where(selm[None], w_ver, 0), axis=1)  # [D, L]

                st_stage = jnp.full((W, L), -1, i32)
                st_off = jnp.full((W, L), -1, i32)

                def hop_cond(c):
                    h, active_i = c[0], c[1]
                    return (h < W) & jnp.any(active_i != 0)

                def hop_body(c):
                    h, active_i, cs, co, qv, ql, cnt, st_stage, st_off = c
                    hactive = active_i != 0
                    # Walk-cost accounting (ops/slab.py _hop_counts); the
                    # drain pass never runs in-kernel, so the emit class is
                    # always the eager extraction counter.
                    o_wh[:] = o_wh[:] + jnp.where(
                        hactive & (wot_i == 0), 1, 0
                    )
                    o_eh[:] = o_eh[:] + jnp.where(
                        hactive & (wot_i != 0), 1, 0
                    )
                    if SA:
                        # Per-stage hop attribution at the walker's current
                        # stage (ops/slab.py _hop_counts; walk_kernel parity).
                        o_shp[:] = o_shp[:] + jnp.where(
                            (iota_sa2 == cs) & hactive, 1, 0
                        )
                    # Hot-tier lookup first (ops/walk_kernel.py hop): the
                    # overflow rows are touched only when some lane of the
                    # block missed hot.
                    hit_h = (o_sstage[0:EHk] == cs) & (o_soff[0:EHk] == co)
                    found_h = jnp.any(hit_h, axis=0, keepdims=True)
                    if EO:
                        miss = hactive & ~found_h
                        sc_found[:] = jnp.zeros((1, L), i32)
                        sc_refs[:] = jnp.zeros((1, L), i32)
                        sc_np[:] = jnp.zeros((1, L), i32)
                        sc_ps[:] = jnp.zeros((MP, L), i32)
                        sc_po[:] = jnp.zeros((MP, L), i32)
                        sc_pl[:] = jnp.zeros((MP, L), i32)
                        sc_pv[:] = jnp.zeros((D, MP, L), i32)

                        @pl.when(jnp.any(miss))
                        def _():
                            hit_o = (o_sstage[EHk:] == cs) & (
                                o_soff[EHk:] == co
                            )
                            hamo = hit_o & miss  # [EO, L]
                            sc_found[:] = jnp.where(
                                jnp.any(hamo, axis=0, keepdims=True), 1, 0
                            )
                            sc_refs[:] = jnp.sum(
                                jnp.where(hamo, o_srefs[EHk:], 0),
                                axis=0, keepdims=True,
                            )
                            sc_np[:] = jnp.sum(
                                jnp.where(hamo, o_snpreds[EHk:], 0),
                                axis=0, keepdims=True,
                            )
                            hamo3 = hamo[:, None, :]
                            sc_ps[:] = jnp.sum(
                                jnp.where(hamo3, o_spstage[EHk:], 0), axis=0
                            )
                            sc_po[:] = jnp.sum(
                                jnp.where(hamo3, o_spoff[EHk:], 0), axis=0
                            )
                            sc_pl[:] = jnp.sum(
                                jnp.where(hamo3, o_spvlen[EHk:], 0), axis=0
                            )
                            sc_pv[:] = jnp.sum(
                                jnp.where(
                                    hamo[None, :, None, :], o_spver[:, EHk:], 0
                                ),
                                axis=1,
                            )

                        act_o = sc_found[:] != 0
                        found = found_h | act_o
                        o_hh[:] = o_hh[:] + jnp.where(hactive & found_h, 1, 0)
                        o_hm[:] = o_hm[:] + jnp.where(miss, 1, 0)
                        o_ow[:] = o_ow[:] + jnp.where(act_o, 1, 0)
                    else:
                        act_o = jnp.zeros((1, L), jnp.bool_)
                        found = found_h
                    o_ms[:] = o_ms[:] + jnp.where(hactive & ~found, 1, 0)
                    hactive = hactive & found
                    ham_h = hit_h & hactive

                    refs_e = jnp.sum(
                        jnp.where(ham_h, o_srefs[0:EHk], 0),
                        axis=0, keepdims=True,
                    )
                    np_e = jnp.sum(
                        jnp.where(ham_h, o_snpreds[0:EHk], 0),
                        axis=0, keepdims=True,
                    )
                    if EO:
                        refs_e = refs_e + sc_refs[:]
                        np_e = np_e + sc_np[:]
                    newref = jnp.where(
                        wrm_i != 0, jnp.maximum(refs_e - 1, 0), refs_e + 1
                    )
                    o_srefs[0:EHk] = jnp.where(ham_h, newref, o_srefs[0:EHk])
                    dele = hactive & (wrm_i != 0) & (newref == 0) & (np_e <= 1)
                    dmask = ham_h & dele
                    o_sstage[0:EHk] = jnp.where(dmask, -1, o_sstage[0:EHk])
                    o_soff[0:EHk] = jnp.where(dmask, -1, o_soff[0:EHk])

                    emit = hactive & (wot_i != 0)
                    mw = (iota_w2 == cnt) & emit
                    st_stage = jnp.where(mw, cs, st_stage)
                    st_off = jnp.where(mw, co, st_off)
                    cnt = cnt + jnp.where(emit, 1, 0)

                    ham3 = ham_h[:, None, :]
                    ps_ = jnp.sum(jnp.where(ham3, o_spstage[0:EHk], 0), axis=0)
                    po_ = jnp.sum(jnp.where(ham3, o_spoff[0:EHk], 0), axis=0)
                    pl_ = jnp.sum(jnp.where(ham3, o_spvlen[0:EHk], 0), axis=0)
                    pv_ = jnp.sum(
                        jnp.where(ham_h[None, :, None, :], o_spver[:, 0:EHk], 0),
                        axis=1,
                    )  # [D, MP, L]
                    if EO:
                        ps_ = ps_ + sc_ps[:]
                        po_ = po_ + sc_po[:]
                        pl_ = pl_ + sc_pl[:]
                        pv_ = pv_ + sc_pv[:]
                    live = iota_mp < np_e

                    neq = (qv[:, None, :] != pv_).astype(i32)
                    plm = pl_[None, :, :]
                    prefix_full = (
                        jnp.sum(neq * (iota_d3 < plm).astype(i32), axis=0) == 0
                    )
                    prefix_butl = (
                        jnp.sum(neq * (iota_d3 < plm - 1).astype(i32), axis=0)
                        == 0
                    )
                    last_q = jnp.sum(
                        jnp.where(iota_d3 == plm - 1, qv[:, None, :], 0), axis=0
                    )
                    last_p = jnp.sum(
                        jnp.where(iota_d3 == plm - 1, pv_, 0), axis=0
                    )
                    ok = ((ql > pl_) & prefix_full) | (
                        (ql == pl_) & prefix_butl & (last_q >= last_p)
                    )
                    ok = ok & live
                    j = jnp.min(
                        jnp.where(ok, iota_mp, MP), axis=0, keepdims=True
                    )
                    selany = j < MP
                    ohj = iota_mp == j

                    prune = selany & hactive & (wrm_i != 0) & (newref == 0)
                    prune_h = prune & found_h

                    def _shifted(f, m, axis):
                        nxt = jnp.concatenate(
                            [
                                jax.lax.slice_in_dim(f, 1, None, axis=axis),
                                jax.lax.slice_in_dim(f, -1, None, axis=axis),
                            ],
                            axis=axis,
                        )
                        return jnp.where(m, nxt, f)

                    @pl.when(jnp.any(prune_h))
                    def _():
                        pm = ham3 & (iota_mp3h >= j[None]) & prune_h[None]
                        o_spstage[0:EHk] = _shifted(o_spstage[0:EHk], pm, 1)
                        o_spoff[0:EHk] = _shifted(o_spoff[0:EHk], pm, 1)
                        o_spvlen[0:EHk] = _shifted(o_spvlen[0:EHk], pm, 1)
                        o_spver[:, 0:EHk] = _shifted(
                            o_spver[:, 0:EHk], pm[None], 2
                        )
                        o_snpreds[0:EHk] = o_snpreds[0:EHk] - jnp.where(
                            ham_h & prune_h, 1, 0
                        )

                    if EO:
                        # One overflow-side mutation pass: refs decrement,
                        # delete, and prune for walkers resolved overflow —
                        # skipped whenever every lane resolved hot.
                        @pl.when(jnp.any(act_o))
                        def _():
                            hit_o = (o_sstage[EHk:] == cs) & (
                                o_soff[EHk:] == co
                            )
                            hamo = hit_o & act_o
                            o_srefs[EHk:] = jnp.where(
                                hamo, newref, o_srefs[EHk:]
                            )
                            dmo = hamo & dele
                            o_sstage[EHk:] = jnp.where(dmo, -1, o_sstage[EHk:])
                            o_soff[EHk:] = jnp.where(dmo, -1, o_soff[EHk:])
                            prune_o = prune & act_o
                            pmo = (
                                hamo[:, None, :]
                                & (iota_mp3o >= j[None])
                                & prune_o[None]
                            )
                            o_spstage[EHk:] = _shifted(o_spstage[EHk:], pmo, 1)
                            o_spoff[EHk:] = _shifted(o_spoff[EHk:], pmo, 1)
                            o_spvlen[EHk:] = _shifted(o_spvlen[EHk:], pmo, 1)
                            o_spver[:, EHk:] = _shifted(
                                o_spver[:, EHk:], pmo[None], 2
                            )
                            o_snpreds[EHk:] = o_snpreds[EHk:] - jnp.where(
                                hamo & prune_o, 1, 0
                            )

                    nxt_s = jnp.sum(jnp.where(ohj, ps_, 0), axis=0, keepdims=True)
                    nxt_o = jnp.sum(jnp.where(ohj, po_, 0), axis=0, keepdims=True)
                    nxt_l = jnp.sum(jnp.where(ohj, pl_, 0), axis=0, keepdims=True)
                    nxt_v = jnp.sum(jnp.where(ohj[None], pv_, 0), axis=1)

                    nactive = hactive & selany & (nxt_s >= 0)
                    budget_out = emit & (cnt >= W)
                    o_tr[:] = o_tr[:] + jnp.where(budget_out & nactive, 1, 0)
                    hactive = nactive & ~budget_out
                    cs = jnp.where(hactive, nxt_s, cs)
                    co = jnp.where(hactive, nxt_o, co)
                    ql = jnp.where(hactive, nxt_l, ql)
                    qv = jnp.where(hactive, nxt_v, qv)
                    return (h + 1, jnp.where(hactive, 1, 0), cs, co, qv, ql, cnt,
                            st_stage, st_off)

                zero_l = jnp.zeros((1, L), i32)
                (h, active_i, cs, co, qv, ql, cnt, st_stage, st_off) = (
                    jax.lax.while_loop(
                        hop_cond, hop_body,
                        (jnp.zeros((), i32), jnp.where(act0, 1, 0), ws, wo, qv0, wvl,
                         zero_l, st_stage, st_off),
                    )
                )
                o_tr[:] = o_tr[:] + active_i
                mo = (iota_or3 == srow[None, :, None, :]) & (
                    wot_i[None, :, None, :] != 0
                )
                o_ostage[:] = jnp.where(mo, st_stage[None, None], o_ostage[:])
                o_ooff[:] = jnp.where(mo, st_off[None, None], o_ooff[:])
                cm = (iota_or2 == srow[None]) & (wot_i[None] != 0)
                o_ocount[:] = jnp.where(cm, cnt[None], o_ocount[:])
                return b + 1

            jax.lax.while_loop(
                lambda b: b < max_n, batch_body, jnp.zeros((), i32)
            )

            # ---- phase 5: queue compaction (matcher.finish port) ----
            # Candidates stay as separate per-slot [R, L] planes — any
            # [R, S_CAND, L] -> [RS, L] interleave reshape leaves Mosaic
            # relayouting every downstream op (measured ~1.5 s of the scan);
            # pure masked reductions over unrolled slots cost ~a tenth.
            reseed_ver = jnp.where(
                has_succ[None], add_run(o_ver[:], o_vlen[:]), o_ver[:]
            )
            seed_mask = st_alive & seed

            ones_rl = jnp.ones((R, L), i32)
            zeros_rl = jnp.zeros((R, L), i32)
            neg1_rl = jnp.full((R, L), -1, i32)
            # Queue order: per run [survivor, branches deepest-first, re-seed].
            alive_c = (
                [surv_alive & ~surv_final]
                + [br_en[H - 1 - j] for j in range(H)]
                + [seed_mask]
            )
            planes_c = {
                "id": [surv_id] + [br_id[H - 1 - j] for j in range(H)] + [neg1_rl],
                "eval": [surv_eval] + [br_eval[H - 1 - j] for j in range(H)]
                + [jnp.full((R, L), begin_pos, i32)],
                "vlen": [surv_vlen] + [br_vlen[H - 1 - j] for j in range(H)]
                + [o_vlen[:]],
                "event": [surv_event] + [br_event[H - 1 - j] for j in range(H)]
                + [neg1_rl],
                "start": [surv_start] + [br_start[H - 1 - j] for j in range(H)]
                + [neg1_rl],
                "branch": [jnp.where(surv_branching, 1, 0)]
                + [ones_rl] * H + [zeros_rl],
                "got": [ones_rl] * (H + 2),
            }
            for k in range(D):
                planes_c[f"ver{k}"] = (
                    [surv_ver[k]]
                    + [br_run_ver[H - 1 - j][k] for j in range(H)]
                    + [reseed_ver[k]]
                )
            for ns in range(NS):
                planes_c[f"agg{ns}"] = (
                    [final_agg[ns]]
                    + [br_agg[H - 1 - j][ns] for j in range(H)]
                    + [init_list[ns]]
                )

            # Queue-order rank of each candidate: exclusive prefix of per-run
            # totals over the run axis, plus the within-run prefix.
            run_tot = zeros_rl
            for m in alive_c:
                run_tot = run_tot + jnp.where(m, 1, 0)
            run_pre = run_tot
            b = 1
            while b < R:
                run_pre = run_pre + jnp.concatenate(
                    [jnp.zeros((b, L), i32), run_pre[:-b]], axis=0
                )
                b *= 2
            run_pre = run_pre - run_tot  # exclusive
            idx_c, kept_c = [], []
            within = zeros_rl
            for m in alive_c:
                idx = run_pre + within
                idx_c.append(idx)
                kept_c.append(m & (idx < R))
                within = within + jnp.where(m, 1, 0)

            dropped = jnp.zeros((1, L), i32)
            for m, idx in zip(alive_c, idx_c):
                dropped = dropped + jnp.sum(
                    jnp.where(m & (idx >= R), 1, 0), axis=0, keepdims=True
                )
            o_rd[:] = o_rd[:] + jnp.where(valid, dropped, 0)
            o_vo[:] = o_vo[:] + jnp.where(valid, ovf_ct, 0)

            # Destination assembly: for each queue slot j, a masked reduce
            # over all candidates picks the (unique) one with rank j.
            names = list(planes_c)
            rows = {name: [] for name in names}
            for j in range(R):
                sel = [k & (idx == j) for k, idx in zip(kept_c, idx_c)]
                for name in names:
                    v = jnp.zeros((1, L), i32)
                    for s, p in zip(sel, planes_c[name]):
                        v = v + jnp.sum(
                            jnp.where(s, p, 0), axis=0, keepdims=True
                        )
                    rows[name].append(v)

            def assemble(name):
                return jnp.concatenate(rows[name], axis=0)  # [R, L]

            got = assemble("got") != 0
            new_alive = got

            def head(name, fill):
                return jnp.where(got, assemble(name), i32(fill))

            n_id = head("id", -1)
            n_eval = head("eval", 0)
            n_vlen = head("vlen", 0)
            n_event = head("event", -1)
            n_start = head("start", -1)
            n_branch = head("branch", 0)
            n_ver = jnp.stack([head(f"ver{k}", 0) for k in range(D)])
            n_agg = jnp.stack([head(f"agg{ns}", 0) for ns in range(NS)])

            # Padding steps freeze the state (matcher.finish contract).
            o_alive[:] = jnp.where(valid & new_alive, 1,
                                   jnp.where(valid, 0, o_alive[:]))
            o_id[:] = jnp.where(valid, n_id, o_id[:])
            o_eval[:] = jnp.where(valid, n_eval, o_eval[:])
            o_vlen[:] = jnp.where(valid, n_vlen, o_vlen[:])
            o_event[:] = jnp.where(valid, n_event, o_event[:])
            o_start[:] = jnp.where(valid, n_start, o_start[:])
            o_branch[:] = jnp.where(valid, n_branch, o_branch[:])
            o_ver[:] = jnp.where(valid[None], n_ver, o_ver[:])
            o_agg[:] = jnp.where(valid[None], n_agg, o_agg[:])
            # Emission masking for padding steps.
            o_ostage[:] = jnp.where(valid[None, :, None, :], o_ostage[:], -1)
            o_ooff[:] = jnp.where(valid[None, :, None, :], o_ooff[:], -1)
            o_ocount[:] = jnp.where(valid[None], o_ocount[:], 0)

            # ---- phase 6 (lazy only): handle-ring append + root pin — the
            # in-kernel port of matcher.finish's lazy branch.  Completed
            # matches take consecutive ring slots in run-queue order; each
            # appended handle pins its root entry (refs +1) so no later
            # removal walk can delete the chain root before the out-of-kernel
            # drain pass unpins it.  Ring-full matches are dropped and
            # counted (handle_overflows — the loss-free contract's counter).
            if LAZY:
                fin_i = jnp.where(final_en, 1, 0)  # [R, L]
                frank = _cumsum0(fin_i) - 1
                dst = o_hrcount[:] + frank  # [R, L]
                fit = final_en & (dst < HB)
                iota_hb3 = jax.lax.broadcasted_iota(i32, (R, HB, L), 1)
                m3h = fit[:, None, :] & (iota_hb3 == dst[:, None, :])
                got = jnp.any(m3h, axis=0)  # [HB, L]

                def ring2(val_rl):  # [R, L] -> [HB, L] (masked pick)
                    return jnp.sum(jnp.where(m3h, val_rl[:, None, :], 0), axis=0)

                o_hrstage[:] = jnp.where(got, ring2(surv_id), o_hrstage[:])
                o_hroff[:] = jnp.where(got, off, o_hroff[:])
                o_hrvlen[:] = jnp.where(got, ring2(surv_vlen), o_hrvlen[:])
                o_hrts[:] = jnp.where(got, ts, o_hrts[:])
                o_hrseq[:] = jnp.where(got, seq_now, o_hrseq[:])
                iota_r = jax.lax.broadcasted_iota(i32, (R, L), 0)
                o_hrrow[:] = jnp.where(got, ring2(iota_r), o_hrrow[:])
                for k in range(D):
                    o_hrver[k] = jnp.where(
                        got, ring2(surv_ver[k]), o_hrver[k]
                    )
                o_hrcount[:] = o_hrcount[:] + jnp.sum(
                    jnp.where(fit, 1, 0), axis=0, keepdims=True
                )
                o_hovf[:] = o_hovf[:] + jnp.sum(
                    jnp.where(final_en & ~fit, 1, 0), axis=0, keepdims=True
                )
                pin = jnp.sum(
                    jnp.where(
                        (o_sstage[:][None, :, :] == surv_id[:, None, :])
                        & (o_soff[:][None, :, :] == off[None])
                        & fit[:, None, :],
                        1, 0,
                    ),
                    axis=0,
                )  # [E, L]
                o_srefs[:] = o_srefs[:] + pin

            # ---- promotion phase (tiered hybrid only): replay the prefix
            # chain's slab writes and append the suffix run — the in-kernel
            # port of engine/tiered.py build_promote, fused AFTER the engine
            # phases so a prefix completing at t first evaluates at t+1
            # (exactly the untiered run's schedule). ----
            if PROMO:
                p_offs = pr_offs[:][0]  # [PROMO, L]
                anchor = pr_anchor[:][0]  # [1, L]
                sver = pr_sver[:][0]  # [1, L]
                # Live runs are a contiguous prefix (phase 5 compaction just
                # ran), so the append row is the live count.
                pcnt = jnp.sum(
                    jnp.where(o_alive[:] != 0, 1, 0), axis=0, keepdims=True
                )  # [1, L]
                fit = fire_row & (pcnt < R)
                # Promoted Dewey version [v, 0, ..., 0] as [D, L] planes.
                pvr = jnp.concatenate(
                    [sver, jnp.zeros((D - 1, L), i32)], axis=0
                )
                if EO:
                    iota_eo2 = jax.lax.broadcasted_iota(i32, (EO, L), 0)

                # One put per prefix stage, at most one per lane per step —
                # each is the scalar slab op (ops/slab.py put_first / put)
                # as full-plane masked vector code, the same shapes as
                # phase 3's put_body but with a statically known chain.
                for j in range(PROMO):
                    first = j == 0
                    cur_s = i32(promo_idents[j])
                    off_j = p_offs[j:j + 1]  # [1, L]
                    if first:
                        en_ok = fit
                    else:
                        pst = i32(promo_idents[j - 1])
                        pof = p_offs[j - 1:j]
                        prev_hit = (o_sstage[:] == pst) & (o_soff[:] == pof)
                        prev_found = jnp.any(prev_hit, axis=0, keepdims=True)
                        o_ms[:] = o_ms[:] + jnp.where(
                            fit & ~prev_found, 1, 0
                        )
                        en_ok = fit & prev_found

                    cur_hit = (o_sstage[:] == cur_s) & (o_soff[:] == off_j)
                    exist = jnp.any(cur_hit, axis=0, keepdims=True)
                    want = en_ok & ~exist
                    free_h = o_sstage[0:EHk] < 0
                    any_fh = jnp.any(free_h, axis=0, keepdims=True)
                    ffs_h = jnp.min(
                        jnp.where(free_h, iota_eh, EHk), axis=0, keepdims=True
                    )
                    if EO:
                        # Inline two-tier allocation (ops/slab.py
                        # _alloc_slot): free hot slot first, else demote the
                        # min-offset (lowest index on ties) hot entry into
                        # the first free overflow slot and reuse its slot.
                        free_o = o_sstage[EHk:] < 0
                        any_fo = jnp.any(free_o, axis=0, keepdims=True)
                        ffs_o = jnp.min(
                            jnp.where(free_o, iota_eo2, EO), axis=0,
                            keepdims=True,
                        )
                        occ_h = o_sstage[0:EHk] >= 0
                        okey = jnp.where(
                            occ_h, o_soff[0:EHk], i32(1 << 30)
                        )
                        vkey = jnp.min(okey, axis=0, keepdims=True)
                        victim = jnp.min(
                            jnp.where(okey == vkey, iota_eh, EHk), axis=0,
                            keepdims=True,
                        )
                        demote = want & ~any_fh & any_fo
                        o_dm[:] = o_dm[:] + jnp.where(demote, 1, 0)
                        vm = (iota_eh == victim) & demote  # [EHk, L]
                        om = (iota_eo2 == ffs_o) & demote  # [EO, L]
                        vstage = jnp.sum(
                            jnp.where(vm, o_sstage[0:EHk], 0), axis=0,
                            keepdims=True,
                        )
                        voff = jnp.sum(
                            jnp.where(vm, o_soff[0:EHk], 0), axis=0,
                            keepdims=True,
                        )
                        vrefs = jnp.sum(
                            jnp.where(vm, o_srefs[0:EHk], 0), axis=0,
                            keepdims=True,
                        )
                        vnp = jnp.sum(
                            jnp.where(vm, o_snpreds[0:EHk], 0), axis=0,
                            keepdims=True,
                        )
                        vm3 = vm[:, None, :]
                        vps = jnp.sum(
                            jnp.where(vm3, o_spstage[0:EHk], 0), axis=0
                        )  # [MP, L]
                        vpo = jnp.sum(
                            jnp.where(vm3, o_spoff[0:EHk], 0), axis=0
                        )
                        vpl = jnp.sum(
                            jnp.where(vm3, o_spvlen[0:EHk], 0), axis=0
                        )
                        vpv = jnp.sum(
                            jnp.where(
                                vm[None, :, None, :], o_spver[:, 0:EHk], 0
                            ),
                            axis=1,
                        )  # [D, MP, L]
                        om3 = om[:, None, :]
                        o_sstage[EHk:] = jnp.where(om, vstage, o_sstage[EHk:])
                        o_soff[EHk:] = jnp.where(om, voff, o_soff[EHk:])
                        o_srefs[EHk:] = jnp.where(om, vrefs, o_srefs[EHk:])
                        o_snpreds[EHk:] = jnp.where(
                            om, vnp, o_snpreds[EHk:]
                        )
                        o_spstage[EHk:] = jnp.where(
                            om3, vps[None], o_spstage[EHk:]
                        )
                        o_spoff[EHk:] = jnp.where(
                            om3, vpo[None], o_spoff[EHk:]
                        )
                        o_spvlen[EHk:] = jnp.where(
                            om3, vpl[None], o_spvlen[EHk:]
                        )
                        o_spver[:, EHk:] = jnp.where(
                            om[None, :, None, :], vpv[:, None],
                            o_spver[:, EHk:],
                        )
                        o_sstage[0:EHk] = jnp.where(vm, -1, o_sstage[0:EHk])
                        o_soff[0:EHk] = jnp.where(vm, -1, o_soff[0:EHk])
                        alloc = jnp.where(any_fh, ffs_h, victim)
                        has_free = any_fh | any_fo
                    else:
                        alloc = ffs_h
                        has_free = ffs_h < EHk

                    tgt = (exist & cur_hit) | (~exist & (iota_e == alloc))
                    ok = en_ok & (exist | has_free)
                    o_fd[:] = o_fd[:] + jnp.where(
                        en_ok & ~exist & ~has_free, 1, 0
                    )
                    m1 = tgt & ok
                    # put_first overwrites (resets refs/npreds) even on an
                    # existing entry; put resets only on create.
                    reset = ok if first else ok & ~exist
                    np_e = jnp.sum(
                        jnp.where(m1, o_snpreds[:], 0), axis=0, keepdims=True
                    )
                    n_eff = jnp.where(reset, 0, np_e)
                    o_sstage[:] = jnp.where(m1, cur_s, o_sstage[:])
                    o_soff[:] = jnp.where(m1, off_j, o_soff[:])
                    o_srefs[:] = jnp.where(m1 & reset, 1, o_srefs[:])
                    pfull = ok & (n_eff >= MP)
                    o_pd[:] = o_pd[:] + jnp.where(pfull, 1, 0)
                    do = ok & ~pfull
                    slot = jnp.minimum(n_eff, MP - 1)
                    m2 = (
                        m1[:, None, :]
                        & (iota_mp3 == slot[:, None, :])
                        & do[:, None, :]
                    )
                    if first:
                        o_spstage[:] = jnp.where(m2, i32(-1), o_spstage[:])
                        o_spoff[:] = jnp.where(m2, i32(-1), o_spoff[:])
                    else:
                        o_spstage[:] = jnp.where(m2, pst, o_spstage[:])
                        o_spoff[:] = jnp.where(
                            m2, pof[:, None, :], o_spoff[:]
                        )
                    o_spvlen[:] = jnp.where(m2, i32(j + 1), o_spvlen[:])
                    o_spver[:] = jnp.where(
                        m2[None], pvr[:, None, None, :], o_spver[:]
                    )
                    o_snpreds[:] = jnp.where(
                        m1, n_eff + jnp.where(do, 1, 0), o_snpreds[:]
                    )

                # Suffix run append at the first free queue row.
                iota_r2 = jax.lax.broadcasted_iota(i32, (R, L), 0)
                row_m = (iota_r2 == pcnt) & fit  # [R, L]
                o_alive[:] = jnp.where(row_m, 1, o_alive[:])
                o_id[:] = jnp.where(
                    row_m, i32(promo_idents[PROMO - 1]), o_id[:]
                )
                o_eval[:] = jnp.where(row_m, i32(promo_eval), o_eval[:])
                o_vlen[:] = jnp.where(row_m, i32(PROMO), o_vlen[:])
                o_event[:] = jnp.where(
                    row_m, p_offs[PROMO - 1:PROMO], o_event[:]
                )
                o_start[:] = jnp.where(row_m, anchor, o_start[:])
                o_branch[:] = jnp.where(row_m, 0, o_branch[:])
                o_ver[:] = jnp.where(row_m[None], pvr[:, None, :], o_ver[:])
                o_agg[:] = jnp.where(row_m[None], inits_rl, o_agg[:])
                # Queue-full promotion = the run the untiered narrow queue
                # could not hold (engine/tiered.py run_drops semantics).
                o_rd[:] = o_rd[:] + jnp.where(fire_row & ~fit, 1, 0)
                o_promoted[:] = o_promoted[:] + jnp.where(fit, 1, 0)
        if PROMO:

            @pl.when(jnp.any(o_alive[:] != 0) | jnp.any(fire_row))
            def _():
                _engine_step()

        else:
            _engine_step()

    # ------------------------------------------------------------------
    # Host-side wrapper: layouts, specs, and the jitted entry point.
    # ------------------------------------------------------------------
    value_dtypes = None
    value_treedef = None

    def scan(state: EngineState, events: EventBatch, promo=None):
        nonlocal value_dtypes, value_treedef
        K = int(state.alive.shape[0])
        T = int(events.ts.shape[1])
        if K % LANE_BLOCK:
            raise ValueError(f"K={K} not a multiple of {LANE_BLOCK}")

        leaves, treedef = jax.tree_util.tree_flatten(events.value)
        value_treedef = treedef
        value_dtypes = [l.dtype for l in leaves]

        tin = lambda x: jnp.moveaxis(x, 0, -1)  # [K, ...] -> [..., K]
        tout = lambda x: jnp.moveaxis(x, -1, 0)
        row = lambda x: x[None, :]
        # [K, T] -> [T, 1, K]: the middle singleton keeps event blocks'
        # trailing dims (1, L) legal under the TPU (8, 128) tiling rule.
        tev = lambda x: jnp.swapaxes(x, 0, 1)[:, None, :]

        ins = [
            tin(state.alive.astype(jnp.int32)),
            tin(state.id_pos),
            tin(state.eval_pos),
            tin(state.vlen),
            tin(state.event_off),
            tin(state.start_ts),
            tin(state.branching.astype(jnp.int32)),
            jnp.transpose(state.agg, (2, 1, 0)),  # [K, R, NS] -> [NS, R, K]
            jnp.transpose(state.ver, (2, 1, 0)),  # [K, R, D] -> [D, R, K]
            tin(state.slab.stage),
            tin(state.slab.off),
            tin(state.slab.refs),
            tin(state.slab.npreds),
            tin(state.slab.pstage),
            tin(state.slab.poff),
            tin(state.slab.pvlen),
            jnp.transpose(state.slab.pver, (3, 1, 2, 0)),  # [D, E, MP, K]
            row(state.run_drops),
            row(state.ver_overflows),
            row(state.slab.full_drops),
            row(state.slab.pred_drops),
            row(state.slab.missing),
            row(state.slab.trunc),
            row(state.slab.hot_hits),
            row(state.slab.hot_misses),
            row(state.slab.overflow_walks),
            row(state.slab.demotions),
            row(state.slab.walk_hops),
            row(state.slab.extract_hops),
            row(state.slab.drain_hops),
            tin(state.hr_stage),
            tin(state.hr_off),
            tin(state.hr_vlen),
            tin(state.hr_ts),
            tin(state.hr_seq),
            tin(state.hr_row),
            jnp.transpose(state.hr_ver, (2, 1, 0)),  # [D, HB, K]
            row(state.hr_count),
            row(state.step_seq),
            row(state.handle_overflows),
        ]
        if SA:
            ins += [
                # [K, 4, S] -> [4, S, K] and [K, S] -> [S, K].
                jnp.transpose(state.stage_counts, (1, 2, 0)),
                tin(state.slab.stage_hops),
            ]
        ins += [
            tev(jnp.asarray(events.key, jnp.int32)),
            tev(jnp.asarray(events.ts, jnp.int32)),
            tev(jnp.asarray(events.off, jnp.int32)),
            tev(jnp.asarray(events.valid).astype(jnp.int32)),
            *[tev(jnp.asarray(l)) for l in leaves],
        ]
        if PROMO:
            # The stencil tier's promotion feed joins the event stream:
            # per-t blocks like the event slices, with the offs matrix
            # carrying its [p] axis as the block's middle dims.
            ins += [
                tev(jnp.asarray(promo.fire).astype(jnp.int32)),
                jnp.transpose(
                    jnp.asarray(promo.offs, jnp.int32), (1, 2, 0)
                ),  # [K, T, p] -> [T, p, K]
                tev(jnp.asarray(promo.anchor_ts, jnp.int32)),
                tev(jnp.asarray(promo.sver, jnp.int32)),
            ]

        grid = (K // LANE_BLOCK, T)

        def state_spec(shape):
            nd = len(shape)
            return pl.BlockSpec(
                shape[:-1] + (LANE_BLOCK,),
                (lambda i, t, nd=nd: (0,) * (nd - 1) + (i,)),
                memory_space=pltpu.VMEM,
            )

        def ev_spec(shape):
            # [T, ..., K]: block (1, ..., L) stepping the t axis — event
            # slices are [T, 1, K]; the promotion offs feed is [T, p, K].
            nd = len(shape)
            return pl.BlockSpec(
                (1,) + shape[1:-1] + (LANE_BLOCK,),
                (lambda i, t, nd=nd: (t,) + (0,) * (nd - 2) + (i,)),
                memory_space=pltpu.VMEM,
            )

        def out_t_spec(shape):
            nd = len(shape)
            return pl.BlockSpec(
                (1,) + shape[1:-1] + (LANE_BLOCK,),
                (lambda i, t, nd=nd: (t,) + (0,) * (nd - 2) + (i,)),
                memory_space=pltpu.VMEM,
            )

        # Inputs have n_sin state arrays; outputs additionally carry the
        # promotion-count accumulator (state-spec, no input analog).
        n_sin = 40 + (2 if SA else 0)
        n_state = n_sin + (1 if PROMO else 0)
        in_specs = (
            [state_spec(tuple(x.shape)) for x in ins[:n_sin]]
            + [ev_spec(tuple(x.shape)) for x in ins[n_sin:]]
        )

        f32_leaves = [
            np.dtype(d).kind == "f" for d in value_dtypes
        ]
        i32 = jnp.int32
        out_shapes = [
            jax.ShapeDtypeStruct((R, K), i32),  # alive
            jax.ShapeDtypeStruct((R, K), i32),  # id_pos
            jax.ShapeDtypeStruct((R, K), i32),  # eval_pos
            jax.ShapeDtypeStruct((R, K), i32),  # vlen
            jax.ShapeDtypeStruct((R, K), i32),  # event_off
            jax.ShapeDtypeStruct((R, K), i32),  # start_ts
            jax.ShapeDtypeStruct((R, K), i32),  # branching
            jax.ShapeDtypeStruct((NS, R, K), i32),  # agg
            jax.ShapeDtypeStruct((D, R, K), i32),  # ver
            jax.ShapeDtypeStruct((E, K), i32),  # slab stage
            jax.ShapeDtypeStruct((E, K), i32),  # slab off
            jax.ShapeDtypeStruct((E, K), i32),  # refs
            jax.ShapeDtypeStruct((E, K), i32),  # npreds
            jax.ShapeDtypeStruct((E, MP, K), i32),  # pstage
            jax.ShapeDtypeStruct((E, MP, K), i32),  # poff
            jax.ShapeDtypeStruct((E, MP, K), i32),  # pvlen
            jax.ShapeDtypeStruct((D, E, MP, K), i32),  # pver
            jax.ShapeDtypeStruct((1, K), i32),  # run_drops
            jax.ShapeDtypeStruct((1, K), i32),  # ver_overflows
            jax.ShapeDtypeStruct((1, K), i32),  # full_drops
            jax.ShapeDtypeStruct((1, K), i32),  # pred_drops
            jax.ShapeDtypeStruct((1, K), i32),  # missing
            jax.ShapeDtypeStruct((1, K), i32),  # trunc
            jax.ShapeDtypeStruct((1, K), i32),  # hot_hits
            jax.ShapeDtypeStruct((1, K), i32),  # hot_misses
            jax.ShapeDtypeStruct((1, K), i32),  # overflow_walks
            jax.ShapeDtypeStruct((1, K), i32),  # demotions
            jax.ShapeDtypeStruct((1, K), i32),  # walk_hops
            jax.ShapeDtypeStruct((1, K), i32),  # extract_hops
            jax.ShapeDtypeStruct((1, K), i32),  # drain_hops
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_stage
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_off
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_vlen
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_ts
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_seq
            jax.ShapeDtypeStruct((HB, K), i32),  # hr_row
            jax.ShapeDtypeStruct((D, HB, K), i32),  # hr_ver
            jax.ShapeDtypeStruct((1, K), i32),  # hr_count
            jax.ShapeDtypeStruct((1, K), i32),  # step_seq
            jax.ShapeDtypeStruct((1, K), i32),  # handle_overflows
        ]
        if SA:
            out_shapes += [
                jax.ShapeDtypeStruct((4, SA, K), i32),  # stage_counts
                jax.ShapeDtypeStruct((SA, K), i32),  # stage_hops
            ]
        if PROMO:
            out_shapes += [
                jax.ShapeDtypeStruct((1, K), i32),  # promoted count
            ]
        out_shapes += [
            jax.ShapeDtypeStruct((T, R, W, K), i32),  # out stage
            jax.ShapeDtypeStruct((T, R, W, K), i32),  # out off
            jax.ShapeDtypeStruct((T, R, K), i32),  # out count
        ]
        out_specs = (
            [state_spec(tuple(s.shape)) for s in out_shapes[:n_state]]
            + [out_t_spec(tuple(s.shape)) for s in out_shapes[n_state:]]
        )
        scratch_shapes = []
        if EO:
            # Per-hop staging of the overflow tier's contribution (written
            # only under the miss branch, read in the combine).
            scratch_shapes = [
                pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_found
                pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_refs
                pltpu.VMEM((1, LANE_BLOCK), jnp.int32),  # sc_np
                pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_ps
                pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_po
                pltpu.VMEM((MP, LANE_BLOCK), jnp.int32),  # sc_pl
                pltpu.VMEM((D, MP, LANE_BLOCK), jnp.int32),  # sc_pv
            ]

        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=110 * 1024 * 1024,
                dimension_semantics=("parallel", "arbitrary"),
            ),
            scratch_shapes=scratch_shapes,
            interpret=scan.interpret,
        )(*ins)

        (n_alive, n_id, n_eval, n_vlen, n_event, n_start, n_branch, n_agg,
         n_ver, n_sstage, n_soff, n_srefs, n_snpreds, n_spstage, n_spoff,
         n_spvlen, n_spver, n_rd, n_vo, n_fd, n_pd, n_ms, n_tr,
         n_hh, n_hm, n_ow, n_dm, n_wh, n_eh, n_dh,
         n_hrstage, n_hroff, n_hrvlen, n_hrts, n_hrseq, n_hrrow, n_hrver,
         n_hrcount, n_seq, n_hovf) = outs[:40]
        if SA:
            n_stc = jnp.transpose(outs[40], (2, 0, 1))  # [K, 4, S]
            n_shp = jnp.moveaxis(outs[41], -1, 0)  # [K, S]
        else:
            n_stc = state.stage_counts
            n_shp = state.slab.stage_hops
        o_stage, o_off, o_count = outs[n_state:]

        unrow = lambda x: x[0]
        new_state = EngineState(
            alive=tout(n_alive).astype(bool),
            id_pos=tout(n_id),
            eval_pos=tout(n_eval),
            ver=jnp.transpose(n_ver, (2, 1, 0)),
            vlen=tout(n_vlen),
            event_off=tout(n_event),
            start_ts=tout(n_start),
            branching=tout(n_branch).astype(bool),
            agg=jnp.transpose(n_agg, (2, 1, 0)),
            slab=SlabState(
                stage=tout(n_sstage),
                off=tout(n_soff),
                refs=tout(n_srefs),
                npreds=tout(n_snpreds),
                pstage=tout(n_spstage),
                poff=tout(n_spoff),
                pvlen=tout(n_spvlen),
                pver=jnp.transpose(n_spver, (3, 1, 2, 0)),
                full_drops=unrow(n_fd),
                pred_drops=unrow(n_pd),
                missing=unrow(n_ms),
                trunc=unrow(n_tr),
                collisions=state.slab.collisions,  # sequential: none
                hot_hits=unrow(n_hh),
                hot_misses=unrow(n_hm),
                overflow_walks=unrow(n_ow),
                demotions=unrow(n_dm),
                walk_hops=unrow(n_wh),
                extract_hops=unrow(n_eh),
                drain_hops=unrow(n_dh),
                stage_hops=n_shp,
            ),
            run_drops=unrow(n_rd),
            ver_overflows=unrow(n_vo),
            hr_stage=tout(n_hrstage),
            hr_off=tout(n_hroff),
            hr_ver=jnp.transpose(n_hrver, (2, 1, 0)),
            hr_vlen=tout(n_hrvlen),
            hr_ts=tout(n_hrts),
            hr_seq=tout(n_hrseq),
            hr_row=tout(n_hrrow),
            hr_count=unrow(n_hrcount),
            step_seq=unrow(n_seq),
            handle_overflows=unrow(n_hovf),
            stage_counts=n_stc,
        )
        out = StepOutput(
            stage=jnp.transpose(o_stage, (3, 0, 1, 2)),  # [K, T, R, W]
            off=jnp.transpose(o_off, (3, 0, 1, 2)),
            count=jnp.transpose(o_count, (2, 0, 1)),
        )
        if PROMO:
            return new_state, out, unrow(outs[n_sin])  # promoted [K]
        return new_state, out

    scan.interpret = False
    return scan
