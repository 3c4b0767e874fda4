"""Compiler tiering (ISSUE 7) — differential corpus and satellites.

The contract (compiler/tiering.py, engine/tiered.py, parallel/tiered.py):

1. *Bit-identical execution*: for strict-prefix lengths 0, 1, n-1, and n
   (pure stencil), the tiered matcher's matches, emission order, and
   loss counters equal the untiered engine's on loss-free traces —
   across the jnp path and the fused walk-kernel path, including
   multi-batch boundaries with ragged valid prefixes.
2. *Durable carry*: checkpoint/restore and ``widen_state`` preserve a
   live stencil carry — a prefix straddling the snapshot still promotes
   and matches after resume/migration.
3. *Lazy-chain ordering*: reordering a stage's commuting conjuncts never
   changes matches or the accept/ignore/reject attribution tallies.
4. *No-prune assertion*: ``enforce_windows`` + ``within()`` refuses the
   stencil route at compile time instead of silently mis-pruning.
"""

import dataclasses
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.compiler.tables import lower
from kafkastreams_cep_tpu.compiler.tiering import (
    TIER_HYBRID,
    TIER_NFA,
    TIER_STENCIL,
    apply_lazy_order,
    check_no_prune,
    plan_tiering,
    strict_prefix_len,
)
from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch
from kafkastreams_cep_tpu.engine.matcher import TIER_COUNTER_NAMES
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.parallel.tiered import TieredBatchMatcher
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record

A, B, C, D, X = 0, 1, 2, 3, 4

# Loss-free on every trace below (asserted): the corpus certifies the
# bit-identical contract in the regime both engines guarantee it.
# dewey_depth carries headroom over the per-batch digit growth of
# waiting skip-till runs (one digit per waited event between renorm
# sweeps): AT Dewey exhaustion the engines may count ver_overflows
# differently — the untiered queue's partial-prefix runs change what
# the renorm can delete — but that regime is already lossy by the
# counter's own definition.
CFG = EngineConfig(
    max_runs=32, slab_entries=96, slab_preds=12, dewey_depth=20,
    max_walk=12,
)
TCFG = dataclasses.replace(CFG, tiering=True)
# Capacity-shedding counters: zero certifies no state was dropped.
DROP_COUNTERS = (
    "run_drops", "slab_full_drops", "slab_pred_drops", "slab_trunc",
    "walk_collisions", "handle_overflows",
)
# Tiny shapes for the (slow) interpret-mode kernel parity runs.
KCFG = EngineConfig(
    max_runs=16, slab_entries=32, slab_preds=8, dewey_depth=8, max_walk=8,
)


def prefix0():
    """Strict-prefix length 0: a fold on the first stage blocks it."""
    return (
        Query()
        .select("a").where(sc.value_is(A))
        .fold("cnt", lambda k, v, c: c + 1)
        .then()
        .select("b").skip_till_next_match().where(sc.value_is(B))
        .build()
    )


def prefix_n_minus_1():
    """Strict A, B, C then skip-till-next D: prefix 3 of n=4."""
    return (
        Query()
        .select("pa").where(sc.value_is(A))
        .then()
        .select("pb").where(sc.value_is(B))
        .then()
        .select("pc").where(sc.value_is(C))
        .then()
        .select("sd").skip_till_next_match().where(sc.value_is(D))
        .build()
    )


# (name, pattern factory, expected tier, expected prefix length)
CORPUS = [
    ("p0_fold", prefix0, TIER_NFA, 0),
    ("p1_skip_next", sc.skip_till_next, TIER_HYBRID, 1),
    ("p2_skip_any", sc.skip_till_any, TIER_HYBRID, 2),
    ("p3_kleene", sc.kleene_one_or_more, TIER_HYBRID, 3),
    ("pn1_strict3_skip", prefix_n_minus_1, TIER_HYBRID, 3),
    ("pn_strict3", sc.strict3, TIER_STENCIL, 3),
]


def batch_of(codes, offs, valid, ts0=1000):
    codes = jnp.asarray(codes, jnp.int32)
    K, T = codes.shape
    return EventBatch(
        key=jnp.zeros((K, T), jnp.int32),
        value=codes,
        ts=jnp.asarray(ts0 + np.asarray(offs), jnp.int32),
        off=jnp.asarray(offs, jnp.int32),
        valid=jnp.asarray(valid, bool),
    )


def grid(out):
    """StepOutput -> {(k, t): [(stages, offs), ...]} in run-row order.

    Row *indices* may differ between the engines (the untiered queue also
    holds partial-prefix runs), but relative row order — the emission
    tie-break within one (k, t) — must not."""
    st, of, ct = (np.asarray(x) for x in (out.stage, out.off, out.count))
    res = {}
    for k, t, r in zip(*np.nonzero(ct)):
        n = int(ct[k, t, r])
        res.setdefault((int(k), int(t)), []).append(
            (tuple(st[k, t, r, :n]), tuple(of[k, t, r, :n]))
        )
    return res


def random_codes(K, total, seed):
    rng = np.random.default_rng(seed)
    return rng.choice(5, size=(K, total), p=[0.3, 0.25, 0.2, 0.2, 0.05]), rng


def ragged_batches(codes, rng, chunk):
    """Split [K, total] codes into ragged valid-prefix batches."""
    K, total = codes.shape
    consumed = np.zeros(K, dtype=int)
    batches = []
    while consumed.min() < total:
        counts = rng.integers(chunk // 2, chunk + 1, size=K)
        vals = np.zeros((K, chunk), np.int64)
        offs = np.zeros((K, chunk), np.int64)
        valid = np.zeros((K, chunk), bool)
        for k in range(K):
            c = min(int(counts[k]), total - consumed[k])
            vals[k, :c] = codes[k, consumed[k]:consumed[k] + c]
            offs[k, :c] = np.arange(consumed[k], consumed[k] + c)
            valid[k, :c] = True
            consumed[k] += c
        batches.append(batch_of(vals, offs, valid))
    return batches


def test_plans_cover_the_prefix_spectrum():
    for name, factory, tier, p in CORPUS:
        tables = lower(factory())
        assert strict_prefix_len(tables) == p, name
        plan = plan_tiering(tables, CFG)
        assert (plan.tier, plan.prefix_len) == (tier, p), (name, plan)


@pytest.mark.parametrize("name,factory,tier,p", CORPUS,
                         ids=[c[0] for c in CORPUS])
def test_tiered_bit_identical_jnp(name, factory, tier, p):
    """Matches, emission order, and counters equal the untiered engine
    over multi-batch ragged scans (jnp path)."""
    K = 6
    # skip-till-any branches exponentially in consumed events; a shorter
    # trace keeps the shared config drop-free for it too.
    total = 24 if name == "p2_skip_any" else 36
    # crc32, not hash(): str hash is randomized per process, and an
    # unlucky PYTHONHASHSEED draws a corpus that sheds capacity (the
    # drop-free assertion below then flakes run-to-run).
    codes, rng = random_codes(K, total, seed=zlib.crc32(name.encode()))
    pat = factory()
    b = BatchMatcher(pat, K, CFG)
    tm = TieredBatchMatcher(pat, K, CFG)
    assert tm.plan.tier == tier
    sb, st = b.init_state(), tm.init_state()
    n_matches = 0
    for ev in ragged_batches(codes, rng, 12):
        sb, ob = b.scan(sb, ev)
        st, ot = tm.scan(st, ev)
        gb, gt = grid(ob), grid(ot)
        assert gb == gt
        n_matches += sum(len(v) for v in gb.values())
        # Maintenance sweep between batches (the processor's cadence):
        # renorm keeps the fixed Dewey width sufficient on straddling
        # runs, and must preserve parity with a live stencil carry.
        sb = b.sweep(sb)
        st = tm.sweep(st)
    cb, ct = b.counters(sb), tm.counters(st)
    assert cb == ct  # bit-identical loss counters, ver_overflows included
    # Drop-free corpus: no capacity shedding on either side.  (A waiting
    # skip-till run appends one Dewey digit per event; ver_overflows may
    # tick — identically, asserted above — when a run waits longer than
    # the renorm cadence can compact.)
    assert all(cb[n] == 0 for n in DROP_COUNTERS), (name, cb)
    tc = tm.tier_counters(st)
    if tier == TIER_NFA:
        assert tc == {n_: 0 for n_ in TIER_COUNTER_NAMES}
    elif tier == TIER_STENCIL:
        # Pure stencil: completions ARE matches; nothing ever promotes.
        assert tc["prefix_fires"] == n_matches > 0
        assert tc["tier_promotions"] == 0
    else:
        assert tc["prefix_events_screened"] > 0
        assert tc["prefix_fires"] == tc["tier_promotions"]  # no drops
    if name in ("p1_skip_next", "p2_skip_any", "pn_strict3"):
        assert n_matches > 0  # the distribution produces real matches


def test_processor_emission_order_parity():
    """End-to-end: the tiered processor forwards (key, Sequence) pairs in
    exactly the untiered order, including same-event multi-match
    tie-breaks (skip-till-any branching)."""
    K = 4
    codes, _ = random_codes(K, 36, seed=77)

    def feed(proc):
        out = []
        for lo in range(0, 36, 12):
            recs = [
                Record(key=k, value=int(codes[k, t]), timestamp=1000 + t)
                for t in range(lo, lo + 12)
                for k in range(K)
            ]
            out.extend(proc.process(recs))
        return [
            (k, [(stg, [e.offset for e in evs])
                 for stg, evs in s.as_map().items()])
            for k, s in out
        ]

    pu = CEPProcessor(sc.skip_till_any(), K, CFG)
    pt = CEPProcessor(sc.skip_till_any(), K, TCFG)
    mu, mt = feed(pu), feed(pt)
    assert len(mu) > 1
    assert mu == mt
    assert pu.counters() == pt.counters()
    assert all(pu.counters()[n] == 0 for n in DROP_COUNTERS)
    snap = pt.metrics_snapshot()
    assert snap["prefix_fires"] > 0
    assert snap["tier_plan"]["tier"] == TIER_HYBRID
    # Labeled Prometheus series: the tier counters render per pattern.
    from kafkastreams_cep_tpu.utils.telemetry import render_prometheus

    text = render_prometheus(snap)
    assert 'cep_prefix_fires{pattern="stream"}' in text
    assert "cep_tier_promotions" in text


def _planted_codes(K, total):
    """Mostly noise, with full prefix+suffix occurrences planted so a
    prefix straddles the batch/checkpoint boundary at t=29/30."""
    codes = np.full((K, total), X, dtype=np.int64)
    for k in range(K):
        codes[k, 5], codes[k, 6], codes[k, 7], codes[k, 11] = A, B, C, D
        # Prefix A@28 B@29 | C@30 (boundary at 30), suffix D@34.
        codes[k, 28], codes[k, 29], codes[k, 30], codes[k, 34] = A, B, C, D
    return codes


def _feed(proc, codes, lo, hi, chunk=10):
    out = []
    for start in range(lo, hi, chunk):
        recs = [
            Record(key=k, value=int(codes[k, t]), timestamp=1000 + t)
            for t in range(start, min(start + chunk, hi))
            for k in range(codes.shape[0])
        ]
        out.extend(proc.process(recs))
    return [
        (k, [(stg, [e.offset for e in evs])
             for stg, evs in s.as_map().items()])
        for k, s in out
    ]


def test_checkpoint_restore_with_live_stencil_carry(tmp_path):
    """A prefix that straddles the snapshot still promotes after restore:
    the carry (trailing window, seed-version count, tier counters) is
    durable state."""
    from kafkastreams_cep_tpu.runtime.checkpoint import (
        restore_processor,
        save_checkpoint,
    )

    K = 3
    codes = _planted_codes(K, 50)
    pat = prefix_n_minus_1()
    proc = CEPProcessor(pat, K, TCFG)
    _ = _feed(proc, codes, 0, 30)  # ends mid-prefix (A@28, B@29 held)
    carry = proc.state.carry
    assert bool(np.asarray(carry.bools).any())  # live partial prefix
    path = str(tmp_path / "ck")
    save_checkpoint(proc, path)
    restored = restore_processor(pat, path)
    cont = _feed(proc, codes, 30, 50)
    rest = _feed(restored, codes, 30, 50)
    assert cont == rest
    # The boundary-spanning match (prefix 28-30, suffix D@34) emitted.
    assert any(
        ("pa", [28]) in m and ("sd", [34]) in m for _, m in rest
    )
    assert restored.tier_counters() == proc.tier_counters()


def test_widen_state_with_live_stencil_carry():
    """Migration onto a strictly-wider config embeds the engine half and
    carries the stencil window verbatim — the straddling prefix still
    completes bit-identically."""
    from kafkastreams_cep_tpu.runtime.migrate import migrate_processor

    K = 3
    codes = _planted_codes(K, 50)
    pat = prefix_n_minus_1()
    proc = CEPProcessor(pat, K, TCFG)
    _ = _feed(proc, codes, 0, 30)
    wide = dataclasses.replace(
        TCFG, max_runs=48, slab_entries=128, dewey_depth=20
    )
    migrated = migrate_processor(pat, proc, wide)
    cont = _feed(proc, codes, 30, 50)
    wide_cont = _feed(migrated, codes, 30, 50)
    assert cont == wide_cont
    assert any(
        ("pa", [28]) in m and ("sd", [34]) in m for _, m in wide_cont
    )


def test_tiering_cannot_flip_under_migration():
    from kafkastreams_cep_tpu.runtime.migrate import check_widens

    with pytest.raises(ValueError, match="tiering"):
        check_widens(TCFG, dataclasses.replace(CFG, max_runs=128))


# ---------------------------------------------------------------------------
# Kernel-path parity (interpret mode)
# ---------------------------------------------------------------------------


def _kernel_trace(K, T, seed):
    """A short trace with planted prefix completions (so promotions and
    suffix matches actually exercise the kernel) over mostly noise."""
    rng = np.random.default_rng(seed)
    codes = rng.choice(5, size=(K, T), p=[0.2, 0.2, 0.2, 0.2, 0.2])
    codes[0, 0], codes[0, 1], codes[0, 2], codes[0, 5] = A, B, C, D
    codes[1, 2], codes[1, 3], codes[1, 4], codes[1, 6] = A, B, C, D
    offs = np.broadcast_to(np.arange(T), (K, T))
    return batch_of(codes, offs, np.ones((K, T), bool))


def test_walk_kernel_tiered_parity():
    """Tiered vs untiered on the fused walk-kernel path: the hybrid scan
    drives the same kernel step, promotions ride jnp between steps."""
    K, T = 128, 8
    ev = _kernel_trace(K, T, 3)
    pat = prefix_n_minus_1()
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        b = BatchMatcher(pat, K, KCFG)
        tm = TieredBatchMatcher(pat, K, KCFG)
        assert b.uses_walk_kernel and tm.inner.uses_walk_kernel
        sb, ob = b.scan(b.init_state(), ev)
        st, ot = tm.scan(tm.init_state(), ev)
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"
    g = grid(ob)
    assert g and g == grid(ot)
    assert b.counters(sb) == tm.counters(st)
    assert tm.tier_counters(st)["tier_promotions"] > 0


# ---------------------------------------------------------------------------
# Lazy extraction under tiering
# ---------------------------------------------------------------------------


def test_hybrid_with_lazy_extraction_drains_identically():
    """Tiering composes with the deferred-drain engine: the tiered lazy
    processor emits the untiered lazy processor's exact stream, and a
    pure-stencil pattern is capped to a hybrid so matches keep flowing
    through the handle ring."""
    lazy = dataclasses.replace(
        TCFG, lazy_extraction=True, handle_ring=64
    )
    lazy_u = dataclasses.replace(lazy, tiering=False)
    plan = plan_tiering(lower(sc.strict3()), lazy)
    assert plan.tier == TIER_HYBRID and plan.prefix_len == 2
    K = 4
    codes, _ = random_codes(K, 36, seed=13)
    pu = CEPProcessor(sc.strict3(), K, lazy_u)
    pt = CEPProcessor(sc.strict3(), K, lazy)
    mu = _feed(pu, codes, 0, 36, chunk=12) + [
        (k, [(stg, [e.offset for e in evs])
             for stg, evs in s.as_map().items()])
        for k, s in pu.flush()
    ]
    mt = _feed(pt, codes, 0, 36, chunk=12) + [
        (k, [(stg, [e.offset for e in evs])
             for stg, evs in s.as_map().items()])
        for k, s in pt.flush()
    ]
    assert len(mu) > 0 and mu == mt
    assert pu.counters() == pt.counters()


# ---------------------------------------------------------------------------
# Lazy-chain predicate ordering
# ---------------------------------------------------------------------------


def _conjunct_pattern():
    """Stage predicates built from and_ chains with deliberately
    expensive-first declaration order, so the pass has work to do."""
    from kafkastreams_cep_tpu.pattern.predicate import and_, hint

    expensive = hint(
        lambda k, v, ts, st: (v * v + 3 * v) % 97 != 11, cost=100.0
    )
    cheap_a = hint(lambda k, v, ts, st: v == A, cost=1.0)
    cheap_b = hint(lambda k, v, ts, st: v <= B, cost=1.0)
    return (
        Query()
        .select("first").where(and_(expensive, cheap_a))
        .then()
        .select("second").skip_till_next_match()
        .where(and_(expensive, cheap_b))
        .build()
    )


def test_reordering_preserves_matches_and_tallies():
    """Property: conjunct reordering never changes matches or the
    accept/ignore/reject attribution tallies (commutativity, measured)."""
    attr = dataclasses.replace(CFG, stage_attribution=True)
    tables = lower(_conjunct_pattern())
    tables2, report = apply_lazy_order(tables)
    assert any(r["reordered"] for r in report.values()), report
    # Cheap conjuncts gate expensive ones after the pass.
    first = report["first"]
    assert first["costs"] == sorted(first["costs"])
    K = 6
    b1 = BatchMatcher(tables, K, attr)
    b2 = BatchMatcher(tables2, K, attr)
    for seed in (1, 2, 3):
        codes, rng = random_codes(K, 32, seed)
        s1, s2 = b1.init_state(), b2.init_state()
        for ev in ragged_batches(codes, rng, 16):
            s1, o1 = b1.scan(s1, ev)
            s2, o2 = b2.scan(s2, ev)
            assert grid(o1) == grid(o2)
        assert b1.stage_counters(s1) == b2.stage_counters(s2)
        assert b1.counters(s1) == b2.counters(s2)


def test_profile_drives_conjunct_selectivity():
    """A measured per_stage profile flows into the ordering decision via
    stage selectivity (ties broken by cost either way)."""
    from kafkastreams_cep_tpu.pattern.predicate import and_, hint

    sel = hint(lambda k, v, ts, st: v == A, cost=4.0, selectivity=0.1)
    loose = hint(lambda k, v, ts, st: v < X, cost=4.0)
    m = and_(loose, sel)
    from kafkastreams_cep_tpu.compiler.tiering import order_conjuncts

    ordered, changed = order_conjuncts(m, stage_sel=0.9)
    # The hinted 0.1-selectivity conjunct beats the profiled 0.9 default.
    assert changed and ordered[0] is m.parts[1]


# ---------------------------------------------------------------------------
# No-prune assertion + snapshot schema
# ---------------------------------------------------------------------------


def test_no_prune_assertion_refuses_windowed_prefix():
    pat = (
        Query()
        .select("a").where(sc.value_is(A))
        .then()
        .select("b").skip_till_next_match().where(sc.value_is(B))
        .within(60, "s")
        .build()
    )
    tables = lower(pat)
    faithful = CFG
    enforcing = dataclasses.replace(CFG, enforce_windows=True)
    assert check_no_prune(tables, faithful) is None
    assert "window" in check_no_prune(tables, enforcing)
    assert plan_tiering(tables, faithful).tier == TIER_HYBRID
    plan = plan_tiering(tables, enforcing)
    assert plan.tier == TIER_NFA and "no-prune" in plan.reason


def test_untiered_snapshots_carry_zero_tier_counters():
    """Schema uniformity: every matcher's metrics_snapshot exposes the
    tier counters (zeros when untiered)."""
    K = 4
    b = BatchMatcher(sc.strict3(), K, CFG)
    s, _ = b.scan(
        b.init_state(),
        batch_of(
            np.zeros((K, 4)), np.broadcast_to(np.arange(4), (K, 4)),
            np.ones((K, 4), bool),
        ),
    )
    snap = b.metrics_snapshot(s)
    for n in TIER_COUNTER_NAMES:
        assert snap[n] == 0


# ---------------------------------------------------------------------------
# Chunk-gated hybrid dispatch (ISSUE 16): the skip/run decision is a
# device-side lax.cond per gate_chunk-sized slice — no host round-trip.
# ---------------------------------------------------------------------------


def test_hybrid_gated_scan_never_syncs_host(monkeypatch):
    """Acceptance: zero per-scan host syncs in hybrid gating.  The chunk
    gate decides skip-vs-dispatch on device, so ``scan`` must never call
    ``jax.device_get`` — the engine's only host-sync primitive — and
    pipelined callers keep full dispatch/decode overlap.  Telemetry
    reads do sync, but only when asked, off the scan path."""
    os.environ["CEP_WALK_KERNEL"] = "0"
    K = 4
    tm = TieredBatchMatcher(sc.skip_till_any(), K, TCFG)
    assert tm.plan.tier == TIER_HYBRID
    codes, rng = random_codes(K, 48, seed=7)
    batches = list(ragged_batches(codes, rng, 16))
    st = tm.init_state()
    st, _ = tm.scan(st, batches[0])  # compile outside the counted window
    syncs = []
    real = jax.device_get

    def counting_get(x):
        syncs.append(type(x).__name__)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    for ev in batches[1:]:
        st, out = tm.scan(st, ev)
    # Force all queued device work to finish while the counter is armed:
    # any hidden sync inside scan would already have fired above.
    jax.block_until_ready(jax.tree_util.tree_leaves(st))
    assert syncs == [], syncs
    assert tm.gate_chunks == len(batches) * -(
        -16 // int(TCFG.gate_chunk)
    )
    # Reading the dispatch tally is where the (single) sync lives.
    n = tm.nfa_dispatches
    assert syncs, "nfa_dispatches must be the device read"
    assert 0 <= n <= tm.gate_chunks


def test_gate_chunk_size_is_pure_scheduling():
    """gate_chunk only changes how dispatch is amortised: every chunk
    size yields the untiered engine's exact matches and counters; only
    the gate telemetry differs (ceil(T/C) offered chunks per scan)."""
    os.environ["CEP_WALK_KERNEL"] = "0"
    K = 6
    pat = sc.skip_till_any()
    # total=24 + per-batch sweeps keep the branchy skip-till-any trace
    # drop-free on the shared config (same sizing as the corpus test).
    codes, rng = random_codes(K, 24, seed=23)
    batches = list(ragged_batches(codes, rng, 16))
    ref = BatchMatcher(pat, K, CFG)
    sr = ref.init_state()
    want = []
    for ev in batches:
        sr, o = ref.scan(sr, ev)
        want.append(grid(o))
        sr = ref.sweep(sr)
    assert any(want), "trace must produce matches"
    assert all(ref.counters(sr)[n] == 0 for n in DROP_COUNTERS)
    # One per regime: per-event gating, mid-size (uneven 16/3 tail
    # chunk), and chunk > batch (one gate per scan).  Each size is a
    # distinct compiled program, so the sweep is priced per entry.
    for chunk in (1, 3, 64):
        tm = TieredBatchMatcher(
            pat, K, dataclasses.replace(TCFG, gate_chunk=chunk)
        )
        st = tm.init_state()
        for ev, g in zip(batches, want):
            st, o = tm.scan(st, ev)
            assert grid(o) == g, chunk
            st = tm.sweep(st)
        assert tm.counters(st) == ref.counters(sr), chunk
        assert tm.gate_chunks == len(batches) * -(-16 // chunk)
        assert 0 <= tm.nfa_dispatches <= tm.gate_chunks


def test_pipelined_tiered_dispatch_never_blocks(monkeypatch):
    """Timing guard for the pipelined-overlap fix (PROFILE_r09 §4): with
    the per-scan host gate gone, a pipelined tiered processor's dispatch
    and device phases perform no host sync at all — batch N's scan stays
    in flight while decode pulls batch N-1's outputs.  Phase-tagging the
    sync primitives pins every pull to the decode/gc phases."""
    os.environ["CEP_WALK_KERNEL"] = "0"
    K = 4
    proc = CEPProcessor(
        sc.skip_till_next(), K, TCFG, epoch=0, pipeline=True
    )
    assert proc.batch.plan.tier == TIER_HYBRID
    codes, _ = random_codes(K, 60, seed=3)
    _feed(proc, codes, 0, 10)  # compile outside the guarded window
    current = {"phase": None}
    orig_phase = proc._phase

    class _Tag:
        def __init__(self, name):
            self.name, self.cm = name, orig_phase(name)

        def __enter__(self):
            current["phase"] = self.name
            return self.cm.__enter__()

        def __exit__(self, *exc):
            current["phase"] = None
            return self.cm.__exit__(*exc)

    monkeypatch.setattr(proc, "_phase", _Tag)
    syncs = []
    real_get, real_block = jax.device_get, jax.block_until_ready
    monkeypatch.setattr(
        jax, "device_get",
        lambda x: (syncs.append(("get", current["phase"])), real_get(x))[1],
    )
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (
            syncs.append(("block", current["phase"])), real_block(x)
        )[1],
    )
    matches = _feed(proc, codes, 10, 60)
    blocked = [s for s in syncs if s[1] in ("dispatch", "device", "drain")]
    assert blocked == [], blocked
    # Non-vacuous: real matches decoded inside the guarded window, so
    # the decode pull (batch N-1's outputs, overlapping batch N's
    # in-flight scan — a scalar int(c_n) plus the compacted rows) ran
    # without ever blocking the dispatch side.
    assert len(matches) > 0
