"""Per-stage selectivity & cost attribution (EngineConfig.stage_attribution).

The continuous-profiling contract (ISSUE 6):

1. *Bit-exact across paths*: the per-stage tallies (``stage_counts``) and
   per-stage walk-hop costs (``SlabState.stage_hops``) agree exactly
   between the jnp engine and the per-step walk kernel on a pressured
   trace.
2. *Placement-free*: attribution never changes emissions or any drop
   counter.
3. *Zero device work when off*: every attribution array has zero size.
4. *Conservation*: stage-hop totals equal the walk-class hop totals
   (every hop attributed exactly once), and per-stage tallies obey
   accepts/ignores/rejects <= evals.
5. *Mergeability* (satellite): ShardedMatcher's psum-merge and CEPBank's
   member-merge stay associative with the new counters included.

All kernel runs use interpret mode (CPU CI checks parity, not perf).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch
from kafkastreams_cep_tpu.engine.matcher import (
    STAGE_TALLY_NAMES,
    stage_counter_arrays,
)
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

ATTR_CFG = EngineConfig(
    max_runs=8, slab_entries=16, slab_hot_entries=8, slab_preds=4,
    dewey_depth=8, max_walk=8, stage_attribution=True,
)


def stock_events(K, T, seed):
    rng = np.random.default_rng(seed)
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    vols = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    return EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(vols)},
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )


def _attr_equal(st_a, st_b):
    np.testing.assert_array_equal(
        np.asarray(st_a.stage_counts), np.asarray(st_b.stage_counts),
        err_msg="stage_counts",
    )
    np.testing.assert_array_equal(
        np.asarray(st_a.slab.stage_hops), np.asarray(st_b.slab.stage_hops),
        err_msg="stage_hops",
    )


def test_disabled_attribution_is_zero_size():
    cfg = dataclasses.replace(ATTR_CFG, stage_attribution=False)
    m = BatchMatcher(stock_demo.stock_pattern(), 4, cfg)
    st = m.init_state()
    assert st.stage_counts.shape == (4, 4, 0)
    assert st.slab.stage_hops.shape == (4, 0)
    assert m.stage_counters(st) == {}
    assert m.matcher.stage_counters(st) == {}


def test_attribution_invariants_and_never_changes_matching():
    K, T = 8, 24
    events = stock_events(K, T, 5)
    os.environ["CEP_WALK_KERNEL"] = "0"
    off = BatchMatcher(
        stock_demo.stock_pattern(), K,
        dataclasses.replace(ATTR_CFG, stage_attribution=False),
    )
    on = BatchMatcher(stock_demo.stock_pattern(), K, ATTR_CFG)
    st0, out0 = off.scan(off.init_state(), events)
    st, out1 = on.scan(on.init_state(), events)
    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out0, f)), np.asarray(getattr(out1, f)),
            err_msg=f,
        )
    assert off.counters(st0) == on.counters(st)
    assert off.hot_counters(st0) == on.hot_counters(st)

    arrays = stage_counter_arrays(st)
    assert set(arrays) == set(STAGE_TALLY_NAMES) | {"stage_walk_hops"}
    ev = arrays["stage_evals"]
    for k in ("stage_accepts", "stage_ignores", "stage_rejects"):
        assert (arrays[k] <= ev).all(), k
    assert ev.sum() > 0
    # Every walk hop attributed exactly once: per-stage totals equal the
    # class totals (walk + extract + drain).
    wc = on.walk_counters(st)
    assert int(arrays["stage_walk_hops"].sum()) == sum(wc.values())
    # The roll-up publishes a selectivity per stage.
    report = on.stage_counters(st)
    assert all("selectivity" in row for row in report.values())


def test_walk_kernel_attribution_parity():
    K, T = 128, 12
    events = stock_events(K, T, 21)
    os.environ["CEP_WALK_KERNEL"] = "0"
    ref = BatchMatcher(stock_demo.stock_pattern(), K, ATTR_CFG)
    st_r, out_r = ref.scan(ref.init_state(), events)
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        krn = BatchMatcher(stock_demo.stock_pattern(), K, ATTR_CFG)
        assert krn.uses_walk_kernel
        st_k, out_k = krn.scan(krn.init_state(), events)
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"
    np.testing.assert_array_equal(
        np.asarray(out_r.count), np.asarray(out_k.count)
    )
    _attr_equal(st_r, st_k)
    assert int(np.asarray(st_r.slab.stage_hops).sum()) > 0


def test_lazy_drain_hops_are_attributed():
    K, T = 8, 24
    events = stock_events(K, T, 11)
    os.environ["CEP_WALK_KERNEL"] = "0"
    cfg = dataclasses.replace(
        ATTR_CFG, lazy_extraction=True, handle_ring=64,
        slab_entries=32, slab_hot_entries=8,
    )
    m = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
    st, _ = m.scan(m.init_state(), events)
    st, drained = m.drain(st)
    arrays = stage_counter_arrays(st)
    wc = m.walk_counters(st)
    assert wc["drain_hops"] > 0
    assert int(arrays["stage_walk_hops"].sum()) == sum(wc.values())


def test_checkpoint_and_widen_roundtrip_with_attribution(tmp_path):
    from kafkastreams_cep_tpu.runtime import CEPProcessor, Record, checkpoint
    from kafkastreams_cep_tpu.runtime.migrate import (
        check_widens,
        widen_state,
    )

    os.environ["CEP_WALK_KERNEL"] = "0"
    proc = CEPProcessor(stock_demo.stock_pattern(), 4, ATTR_CFG, epoch=0)
    rng = np.random.default_rng(3)
    recs = [
        Record(int(k), {"price": int(p), "volume": int(v)}, i)
        for i, (k, p, v) in enumerate(
            zip(rng.integers(0, 4, 48), rng.integers(90, 131, 48),
                rng.integers(600, 1101, 48))
        )
    ]
    proc.process(recs)
    path = str(tmp_path / "a.ckpt")
    checkpoint.save_checkpoint(proc, path)
    proc2 = checkpoint.restore_processor(stock_demo.stock_pattern(), path)
    _attr_equal(proc.state, proc2.state)

    wide = dataclasses.replace(
        ATTR_CFG, max_runs=16, slab_entries=24, slab_hot_entries=8
    )
    widened = widen_state(proc.state, ATTR_CFG, wide)
    np.testing.assert_array_equal(
        np.asarray(proc.state.stage_counts), widened.stage_counts
    )
    np.testing.assert_array_equal(
        np.asarray(proc.state.slab.stage_hops), widened.slab.stage_hops
    )
    # Flipping attribution is a shape change with no live embedding.
    with pytest.raises(ValueError, match="stage_attribution"):
        check_widens(
            ATTR_CFG,
            dataclasses.replace(wide, stage_attribution=False),
        )


# ---------------------------------------------------------------------------
# Merge paths (satellite): psum-merge and member-merge stay associative
# with the per-stage / per-key counters included.
# ---------------------------------------------------------------------------


def test_sharded_psum_merge_matches_lane_sum():
    from kafkastreams_cep_tpu.parallel import ShardedMatcher, key_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    K, T = 8, 24
    events = stock_events(K, T, 13)
    os.environ["CEP_WALK_KERNEL"] = "0"
    mesh = key_mesh(jax.devices()[:4])
    sharded = ShardedMatcher(stock_demo.stock_pattern(), K, mesh, ATTR_CFG)
    st, _ = sharded.scan(
        sharded.init_state(), sharded.shard_events(events)
    )
    # The psum-merged roll-up must equal the host-side per-lane sum — the
    # merge is integer addition over disjoint lane blocks, so any shard
    # grouping gives the same totals (associativity).
    merged = sharded.stage_counters(st)
    host = {}
    arrays = stage_counter_arrays(st)
    from kafkastreams_cep_tpu.engine.matcher import stage_report

    host = stage_report(arrays, sharded.names)
    assert merged == host
    assert any(row["stage_evals"] for row in merged.values())
    snap = sharded.metrics_snapshot(st)
    assert snap["per_stage"] == merged


def test_bank_member_merge_is_associative():
    from kafkastreams_cep_tpu.runtime import Record
    from kafkastreams_cep_tpu.runtime.bank import CEPBank

    os.environ["CEP_WALK_KERNEL"] = "0"
    bank = CEPBank(
        {"a": stock_demo.stock_pattern(), "b": stock_demo.stock_pattern()},
        4, ATTR_CFG, epoch=0,
    )
    rng = np.random.default_rng(17)
    recs = [
        Record(int(k), {"price": int(p), "volume": int(v)}, i)
        for i, (k, p, v) in enumerate(
            zip(rng.integers(0, 4, 40), rng.integers(90, 131, 40),
                rng.integers(600, 1101, 40))
        )
    ]
    bank.process(recs)
    snap = bank.metrics_snapshot()
    members = [
        p.batch.stage_counters(p.state) for p in bank.processors.values()
    ]
    for stage, row in snap["per_stage"].items():
        for metric in ("stage_evals", "stage_accepts", "stage_walk_hops"):
            assert row[metric] == sum(m[stage][metric] for m in members), (
                stage, metric,
            )
    # Associativity of the underlying registry merge with the new
    # counters present: (a ⊕ b) equals (b ⊕ a) on every counter.
    procs = list(bank.processors.values())
    ab = procs[0].metrics.registry.merge(procs[1].metrics.registry)
    ba = procs[1].metrics.registry.merge(procs[0].metrics.registry)
    a_snap, b_snap = ab.snapshot(), ba.snapshot()
    assert {
        k: v for k, v in a_snap.items() if not isinstance(v, dict)
    } == {k: v for k, v in b_snap.items() if not isinstance(v, dict)}


def test_per_key_heavy_hitters():
    from kafkastreams_cep_tpu.runtime import CEPProcessor, Record

    os.environ["CEP_WALK_KERNEL"] = "0"
    proc = CEPProcessor(stock_demo.stock_pattern(), 4, ATTR_CFG, epoch=0)
    rng = np.random.default_rng(23)
    # Key "hot" gets 10x the traffic of the others — it must rank first.
    recs = []
    t = 0
    for _ in range(200):
        key = "hot" if rng.random() < 0.7 else f"cold{rng.integers(3)}"
        recs.append(
            Record(
                key,
                {"price": int(rng.integers(90, 131)),
                 "volume": int(rng.integers(600, 1101))},
                t,
            )
        )
        t += 1
    proc.process(recs)
    pk = proc.per_key_cost(top_k=4)
    assert pk["total_hops"] > 0
    assert pk["top"] and pk["top"][0]["key"] == "hot"
    assert pk["top"][0]["share"] >= max(e["share"] for e in pk["top"][1:])
    snap = proc.metrics_snapshot()
    assert snap["per_key"]["top"][0]["key"] == "hot"


# ---------------------------------------------------------------------------
# Measured per-conjunct selectivity (ISSUE 16 satellite): under
# stage_attribution every consuming-edge conjunct is tallied marginally
# (unconditioned, order-independent) on device and surfaces through
# stage_counters / metrics_snapshot per_stage.
# ---------------------------------------------------------------------------


def _pricey(k, v, ts, st):
    return v["price"] * 7 % 5 != 2


def _cheap(k, v, ts, st):
    return v["price"] > 110


def _conjunct_stock_pattern():
    from kafkastreams_cep_tpu import Query
    from kafkastreams_cep_tpu.pattern.predicate import and_, hint

    return (
        Query()
        .select("rise")
        .where(and_(hint(_pricey, cost=50.0), hint(_cheap, cost=1.0)))
        .then()
        .select("dip").skip_till_next_match()
        .where(lambda k, v, ts, st: v["price"] < 100)
        .build()
    )


def test_measured_conjunct_tally_is_exact_and_in_snapshot():
    os.environ["CEP_WALK_KERNEL"] = "0"
    pat = _conjunct_stock_pattern()
    K, T = 4, 24
    m = BatchMatcher(pat, K, ATTR_CFG)
    st = m.init_state()
    prices = []
    for seed in (1, 2):
        ev = stock_events(K, T, seed)
        prices.append(np.asarray(ev.value["price"]))
        st, _ = m.scan(st, ev)
    allp = np.concatenate(prices, axis=None).astype(np.int64)
    report = m.stage_counters(st)
    cj = report["rise"]["conjuncts"]
    assert len(cj) == 2 and len(report["dip"]["conjuncts"]) == 1
    by = {
        ("pricey" if "_pricey" in key else "cheap"): row
        for key, row in cj.items()
    }
    # Row 0 of the tally: every conjunct is offered every valid event —
    # the marginal (order-independent) denominator, identical per slot.
    assert all(row["evals"] == allp.size for row in by.values())
    assert by["cheap"]["accepts"] == int((allp > 110).sum())
    assert by["pricey"]["accepts"] == int((allp * 7 % 5 != 2).sum())
    for row in by.values():
        assert row["selectivity"] == pytest.approx(
            row["accepts"] / row["evals"]
        )

    # The processor snapshot carries the same rows under per_stage.
    from kafkastreams_cep_tpu.runtime import CEPProcessor, Record

    proc = CEPProcessor(pat, 4, ATTR_CFG, epoch=0)
    proc.process(
        [
            Record(int(i % 4), {"price": int(p), "volume": 800}, i)
            for i, p in enumerate(
                np.linspace(90, 130, 40).astype(int)
            )
        ]
    )
    snap = proc.metrics_snapshot()
    rows = snap["per_stage"]["rise"]["conjuncts"]
    assert set(rows) == set(cj)
    assert all(row["evals"] == 40 for row in rows.values())
