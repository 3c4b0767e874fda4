"""Multi-tenant query bank — shared screen, per-query bit-exactness.

The contract under test (``compiler/multitenant.py`` +
``engine/predmatrix.py`` + ``parallel/tenantbank.py`` +
``runtime/tenant.py``): N queries sharing one predicate matrix and one
stencil screen emit, per query, *bit-identical* matches, emission order,
and loss counters to that query running alone on its own serial matcher
— across the jnp path and the fused walk kernel.  Durability rides the same
checkpoint idioms as the single-query runtime: a live shared-prefix
carry survives save/restore and capacity widening, and the tenant
supervisor recovers a chaos schedule exactly-once.

Workloads here are loss-free by construction (selective begin
predicates): the bank's parity claim vs *untiered* serial matchers is
scoped to runs the narrow engine would not have dropped, the same
precondition as test_tiering/test_migrate.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.parallel.tenantbank import TenantBankMatcher
from kafkastreams_cep_tpu.runtime.migrate import widen_state
from kafkastreams_cep_tpu.runtime.processor import Record
from kafkastreams_cep_tpu.runtime.tenant import (
    TenantCEP,
    TenantSupervisor,
    restore_tenant,
    save_tenant_checkpoint,
)
from kafkastreams_cep_tpu.utils.failpoints import FAILPOINTS, random_schedule
from kafkastreams_cep_tpu.utils.telemetry import render_prometheus

CFG = EngineConfig(
    max_runs=8, slab_entries=24, slab_preds=4, dewey_depth=32, max_walk=8
)

# Zero on all of these certifies the serial reference dropped nothing —
# the precondition scoping the bit-exactness claim (test_tiering's
# DROP_COUNTERS plus dewey/walk capacity).
CAPACITY_COUNTERS = (
    "run_drops", "ver_overflows", "slab_full_drops", "slab_pred_drops",
    "slab_trunc", "handle_overflows",
)


def ge(th):
    return lambda k, v, ts, st, th=th: v["x"] >= th


def lt(th):
    return lambda k, v, ts, st, th=th: v["x"] < th


def q_stencil(a, b, c):
    """Pure strict-contiguity 3-stage query (stencil-tier candidate)."""
    return (
        Query()
        .select("a").where(ge(a)).then()
        .select("b").where(lt(b)).then()
        .select("c").where(ge(c)).build()
    )


def q_hybrid(a, b, z):
    """Strict 2-stage prefix + skip suffix (hybrid-tier candidate)."""
    return (
        Query()
        .select("a").where(ge(a)).then()
        .select("b").where(lt(b)).then()
        .select("z").skip_till_next_match().where(ge(z)).build()
    )


def q_folded():
    """State-dependent predicate — not screenable, lands off-stencil."""
    return (
        Query()
        .select("a").where(ge(8))
        .fold("acc", lambda k, v, curr: curr + v["x"], init=0)
        .then()
        .select("b").skip_till_next_match()
        .where(lambda k, v, ts, st: v["x"] > st.get("acc") % 4).build()
    )


# Thresholds keep begin stages selective (>= 8 on 0..9 ints) so nothing
# overflows max_runs=8 — the loss-free precondition for serial parity.
MIXED = [
    q_stencil(8, 3, 7),   # pure stencil
    q_hybrid(8, 3, 9),    # shares the full 2-stage prefix of query 0
    q_hybrid(9, 1, 7),    # same shape, different prefix
    q_stencil(9, 2, 8),   # second stencil, different prefix
    q_folded(),           # state-dependent: off the shared screen
]


def trace(K, T, seed):
    rng = np.random.default_rng(seed)
    xs = rng.integers(0, 10, size=(K, T)).astype(np.int32)
    base = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T))
    return EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)
        ),
        value={"x": jnp.asarray(xs)},
        ts=base, off=base, valid=jnp.ones((K, T), bool),
    )


def assert_bank_parity(patterns, K, T, n_batches, seed0, cfg=CFG):
    """The core oracle: tenant bank vs one serial matcher per query,
    multi-batch (carry state crosses batch boundaries), bit-exact
    emissions at identical [K, T, R, W] slots plus counter-sum parity."""
    bank = TenantBankMatcher(patterns, K, cfg)
    st = bank.init_state()
    serial = [BatchMatcher(p, K, cfg) for p in patterns]
    sst = [m.init_state() for m in serial]
    for b in range(n_batches):
        ev = trace(K, T, seed0 + b)
        st, out = bank.scan(st, ev)
        for q, m in enumerate(serial):
            sst[q], o1 = m.scan(sst[q], ev)
            for f in ("count", "stage", "off"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(out, f)[q]),
                    np.asarray(getattr(o1, f)),
                    err_msg=f"batch {b} query {q} {f}",
                )
    bc = bank.counters(st)
    assert all(bc[n] == 0 for n in CAPACITY_COUNTERS), (
        f"workload must stay loss-free, got {bc}"
    )
    summed = {k: 0 for k in bc}
    for q, m in enumerate(serial):
        for k, v in m.counters(sst[q]).items():
            summed[k] += v
    # slab_missing is excluded: with every capacity counter zero it marks
    # reference-NPE trace states the *untiered* engine probes and misses —
    # prefix stages executed on the stencil never create them, so tiered
    # engines legitimately report fewer (engine/sizing.py scopes it out of
    # loss accounting for the same reason).  Everything that certifies
    # no-loss must match exactly.
    drop = lambda d: {k: v for k, v in d.items() if k != "slab_missing"}
    assert drop(bc) == drop(summed)
    return bank, st


def test_tenant_bank_matches_serial_jnp(monkeypatch):
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    bank, st = assert_bank_parity(MIXED, K=6, T=24, n_batches=3, seed0=31)
    tiers = {bank.tier_of(q) for q in range(len(MIXED))}
    assert "stencil" in tiers and "hybrid" in tiers, (
        "fixture must exercise a mixed-tier bank, got "
        f"{[bank.tier_of(q) for q in range(len(MIXED))]}"
    )
    tc = bank.tier_counters(st)
    assert tc["prefix_events_screened"] > 0
    assert tc["tier_promotions"] > 0, (
        "hybrid members must actually promote through the shared screen"
    )


def test_tenant_bank_matches_serial_walk_kernel(monkeypatch):
    """Fused walk kernel (interpret mode) on a residual group whose lane
    count hits the kernel block size: 2 same-shape hybrids x 64 lanes."""
    from kafkastreams_cep_tpu.parallel.batch import _select_walk_kernel

    monkeypatch.setenv("CEP_WALK_KERNEL", "interpret")
    patterns = [q_hybrid(8, 3, 9), q_hybrid(9, 1, 7)]
    assert _select_walk_kernel(CFG, 2 * 64) == (True, True)
    assert_bank_parity(patterns, K=64, T=12, n_batches=2, seed0=5)


@pytest.mark.parametrize(
    "overlap,n_shared_groups",
    [
        # The all-shared variant is tier-2 (-m slow, ~16 s); the
        # pairs/none variants keep the planning claim in tier-1
        # (ROADMAP tier-1 budget note, PR 13).
        pytest.param("all", 1, marks=pytest.mark.slow),
        ("pairs", 2),
        ("none", 4),
    ],
    ids=["group-of-N", "groups-of-2", "groups-of-1"],
)
def test_prefix_overlap_group_sizes(monkeypatch, overlap, n_shared_groups):
    """Sharing structure is planned, not accidental: identical prefixes
    collapse to one column set; disjoint prefixes share nothing.  Parity
    holds at every overlap shape."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    if overlap == "all":
        patterns = [q_hybrid(8, 3, 9 - i) for i in range(4)]
    elif overlap == "pairs":
        patterns = [
            q_hybrid(8, 3, 9), q_hybrid(8, 3, 8),
            q_hybrid(9, 1, 9), q_hybrid(9, 1, 8),
        ]
    else:
        # Distinct closures at BOTH prefix stages (eq vs ge differ in
        # bytecode; distinct thresholds differ in closure constants), so
        # column dedup finds nothing to share.
        eq = lambda th: lambda k, v, ts, st, th=th: v["x"] == th

        def q_custom(pa, pb, z):
            return (
                Query()
                .select("a").where(pa).then()
                .select("b").where(pb).then()
                .select("z").skip_till_next_match().where(ge(z)).build()
            )

        patterns = [
            q_custom(ge(8), lt(1), 9), q_custom(ge(9), lt(2), 9),
            q_custom(eq(8), lt(3), 9), q_custom(eq(9), lt(4), 9),
        ]
    bank, _ = assert_bank_parity(patterns, K=4, T=20, n_batches=2, seed0=43)
    stats = bank.bank.stats
    # 4 queries x 2 prefix stages; distinct column count reflects overlap.
    assert stats["prefix_columns_total"] == 8
    assert stats["prefix_columns_distinct"] == 2 * n_shared_groups
    if overlap == "all":
        assert stats["prefix_shared_hit_rate"] == pytest.approx(0.75)
    if overlap == "none":
        assert stats["prefix_shared_hit_rate"] == 0.0


# -- runtime: records in, (query, key, Sequence) out --------------------------


def make_patterns():
    return {
        "spike": q_stencil(8, 3, 7),
        "dip": q_hybrid(8, 3, 9),
        "crash": q_hybrid(9, 1, 7),
    }


def batches(n_batches, per_batch=20, seed=7):
    rng = np.random.default_rng(seed)
    keys = ["alpha", "beta", "gamma"]
    ts = 0
    out = []
    for _ in range(n_batches):
        recs = []
        for _ in range(per_batch):
            ts += int(rng.integers(1, 3))
            recs.append(
                Record(
                    key=keys[int(rng.integers(0, len(keys)))],
                    value={"x": int(rng.integers(0, 10))},
                    timestamp=ts,
                )
            )
        out.append(recs)
    return out


def canon(matches):
    return [
        (qn, k, tuple(sorted(
            (st, e.partition, e.offset)
            for st, evs in seq.as_map().items()
            for e in evs
        )))
        for qn, k, seq in matches
    ]


def test_checkpoint_restore_with_live_prefix_carry(tmp_path):
    """Mid-stream snapshot with a partially-advanced shared prefix: the
    restored bank's future emissions equal the uninterrupted run's."""
    bs = batches(6, seed=7)
    ref = TenantCEP(make_patterns(), 3, CFG)
    ref_matches = [ref.process(b) for b in bs]
    assert sum(len(m) for m in ref_matches) > 0
    assert ref.counters()["run_drops"] == 0

    t = TenantCEP(make_patterns(), 3, CFG)
    for b in bs[:3]:
        t.process(b)
    # The snapshot must carry live screen state, not a quiesced bank.
    assert any(
        bool(np.asarray(c.bools).any()) for c in t.state.carry
    ), "fixture failed to leave a partial prefix pending at the snapshot"
    path = str(tmp_path / "tenant.ckpt")
    save_tenant_checkpoint(t, path)
    t2 = restore_tenant(make_patterns(), path)
    assert t2.per_query_counters() == t.per_query_counters()
    for i, b in enumerate(bs[3:]):
        assert canon(t2.process(b)) == canon(ref_matches[3 + i]), (
            f"post-restore batch {i} diverged"
        )


def test_restore_refuses_mismatched_topology(tmp_path):
    t = TenantCEP(make_patterns(), 3, CFG)
    t.process(batches(1)[0])
    path = str(tmp_path / "tenant.ckpt")
    save_tenant_checkpoint(t, path)
    renamed = dict(make_patterns())
    renamed["burst"] = renamed.pop("crash")
    with pytest.raises(ValueError, match="names"):
        restore_tenant(renamed, path)
    reshaped = dict(make_patterns())
    reshaped["crash"] = q_stencil(9, 1, 7)
    with pytest.raises(ValueError, match="topology|stages"):
        restore_tenant(reshaped, path)


def test_widen_with_live_prefix_carry(monkeypatch):
    """Capacity widening mid-stream: engines widen per residual group,
    the shared-prefix carries copy verbatim, and the wide bank's future
    emissions stay bit-identical on the shared slots."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    import dataclasses

    wide_cfg = dataclasses.replace(
        CFG, max_runs=16, slab_entries=48, max_walk=12
    )
    K, T = 5, 20
    patterns = MIXED[:4]
    prefix, suffix = trace(K, T, 61), trace(K, T, 62)

    narrow = TenantBankMatcher(patterns, K, CFG)
    mid, _ = narrow.scan(narrow.init_state(), prefix)
    assert any(bool(np.asarray(c.bools).any()) for c in mid.carry)
    st_n, out_n = narrow.scan(mid, suffix)
    assert narrow.counters(st_n)["run_drops"] == 0

    wide = TenantBankMatcher(patterns, K, wide_cfg)
    mid_w = jax.device_put(widen_state(mid, CFG, wide_cfg))
    for c_n, c_w in zip(mid.carry, mid_w.carry):
        for a, b in zip(
            jax.tree_util.tree_leaves(c_n), jax.tree_util.tree_leaves(c_w)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st_w, out_w = wide.scan(mid_w, suffix)

    R, W = CFG.max_runs, CFG.max_walk
    np.testing.assert_array_equal(
        np.asarray(out_n.count), np.asarray(out_w.count)[..., :R]
    )
    assert not np.asarray(out_w.count)[..., R:].any()
    for f in ("stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_n, f)),
            np.asarray(getattr(out_w, f))[..., :R, :W],
            err_msg=f,
        )


def test_supervisor_chaos_schedule_exactly_once():
    """Seeded chaos over the device + checkpoint sites: every batch's
    matches are emitted exactly once, in the uninterrupted run's order,
    with recoveries actually exercised."""
    bs = batches(8, seed=19)
    ref = TenantCEP(make_patterns(), 3, CFG)
    ref_matches = [canon(ref.process(b)) for b in bs]
    assert sum(len(m) for m in ref_matches) > 0

    schedule = random_schedule(
        seed=3, horizon=8, rate=0.3,
        sites=("device.dispatch", "device.result", "checkpoint.save"),
    )
    assert schedule, "seed produced an empty schedule; pick another"
    with FAILPOINTS.session(schedule):
        sup = TenantSupervisor(
            make_patterns(), 3, CFG, checkpoint_every=2, max_retries=6
        )
        got = [canon(sup.process(b)) for b in bs]
    assert got == ref_matches
    assert sup.recoveries > 0, "schedule never faulted; chaos was vacuous"
    assert sup.checkpoints > 0
    snap = sup.metrics_snapshot()
    assert snap["recoveries"] == sup.recoveries


def test_per_query_telemetry_labels():
    """metrics_snapshot carries the per_query breakdown and the
    Prometheus renderer emits it as {query="name"} labeled series."""
    t = TenantCEP(make_patterns(), 3, CFG)
    for b in batches(2, seed=23):
        t.process(b)
    snap = t.metrics_snapshot()
    assert set(snap["per_query"]) == {"spike", "dip", "crash"}
    for sub in snap["per_query"].values():
        assert "run_drops" in sub and "tier_promotions" in sub
    text = render_prometheus(snap)
    assert 'cep_run_drops{query="spike"} 0' in text
    assert 'cep_tier_promotions{query="dip"}' in text
    assert f'cep_bank_queries {len(make_patterns())}' in text
