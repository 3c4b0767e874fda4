"""Shard fault tolerance: lane repartitioning, evacuation, rebalancing.

The tentpole claim (``runtime/migrate.py`` module comment): a lane
permutation is a *pure relabeling* — every state leaf carries a leading
``[K]`` lane axis, the engine is a ``vmap`` of a per-lane step, and lane
identity is internal (keys route through host maps, matches emit by key)
— so permuting state rows plus every lane-indexed host structure yields
bit-identical observable behavior.  Tested here as scan-commutes-with-
permutation on the jnp and interpret-kernel walk paths, the two-tier
slab, a live (undrained) lazy handle ring, and the tiered stencil carry;
then at the processor level (``move_lanes``) and the supervisor level
(shard evacuation onto a surviving sub-mesh, straggler declaration, and
skew-triggered hot-key rebalancing — exactly-once throughout).
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import EngineConfig, capacity_counters
from kafkastreams_cep_tpu.parallel import ShardLost, key_mesh, surviving_mesh
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.runtime import (
    CEPProcessor,
    Record,
    ShardPolicy,
    Supervisor,
    move_lanes,
    plan_rebalance,
    repartition_state,
)
from kafkastreams_cep_tpu.runtime.migrate import canonical_state
from kafkastreams_cep_tpu.utils import failpoints as fp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo
from test_migrate import assert_state_equal, stock_events

CFG = EngineConfig(
    max_runs=16, slab_entries=32, slab_preds=16, dewey_depth=32, max_walk=16
)


def _perm(k):
    """A seeded non-trivial permutation of range(k)."""
    return np.random.default_rng(k).permutation(k)


# -- repartition_state: scan commutes with any lane permutation --------------


def _scan_permute_scan(cfg, K=8, T=10, drain=False):
    """Continue-scan on a permuted state (with identically permuted
    events) must equal the permuted continuation of the original —
    canonical state bit-equal per lane, outputs row-permuted, summed
    counters unchanged."""
    PERM = _perm(K)
    prefix = stock_events(K, T, seed=31)
    suffix = stock_events(K, T, seed=131, t0=T)
    m = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
    mid, _ = m.scan(m.init_state(), prefix)
    st_a, out_a = m.scan(mid, suffix)

    mid_p = jax.device_put(repartition_state(mid, PERM))
    suffix_p = jax.device_put(repartition_state(suffix, PERM))
    st_b, out_b = m.scan(mid_p, suffix_p)

    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_a, f))[PERM],
            np.asarray(getattr(out_b, f)),
            err_msg=f"out.{f}",
        )
    assert_state_equal(
        jax.device_put(repartition_state(st_a, PERM)), st_b, msg="repart"
    )
    assert m.counters(st_a) == m.counters(st_b)  # lane sums are invariant
    assert not any(capacity_counters(m.counters(st_b)).values())
    if drain:
        st_a, d_a = m.drain(st_a)
        st_b, d_b = m.drain(st_b)
        for f in d_a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(d_a, f))[PERM],
                np.asarray(getattr(d_b, f)),
                err_msg=f"drain.{f}",
            )
        assert_state_equal(
            jax.device_put(repartition_state(st_a, PERM)), st_b,
            msg="repart-drained",
        )


def test_repartition_parity_jnp():
    os.environ["CEP_WALK_KERNEL"] = "0"
    _scan_permute_scan(CFG)


def test_repartition_parity_walk_kernel_interpret():
    """The fused Pallas walk kernel sees permuted rows as ordinary lanes
    (interpret mode: CPU CI checks parity, not perf; K=128 is the
    kernel's minimum lane block)."""
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        _scan_permute_scan(CFG, K=128)
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"


def test_repartition_parity_two_tier_slab():
    os.environ["CEP_WALK_KERNEL"] = "0"
    _scan_permute_scan(dataclasses.replace(CFG, slab_hot_entries=8))


def test_repartition_parity_live_handle_ring():
    """Lazy extraction with pinned, undrained handles: the ring rows
    permute with their lanes and drain to row-permuted matches."""
    os.environ["CEP_WALK_KERNEL"] = "0"
    lazy = dataclasses.replace(CFG, lazy_extraction=True, handle_ring=64)
    _scan_permute_scan(lazy, drain=True)


def test_repartition_rejects_non_permutations():
    m = BatchMatcher(stock_demo.stock_pattern(), 4, CFG)
    st = m.init_state()
    with pytest.raises(ValueError, match="permutation"):
        repartition_state(st, [0, 1, 1, 2])
    with pytest.raises(ValueError, match="lane axis"):
        repartition_state(st, [0, 1])  # wrong K


# -- plan_rebalance ----------------------------------------------------------


def test_plan_rebalance_spreads_hot_lanes():
    perm = plan_rebalance([50, 50, 1, 1], 2)
    assert perm is not None
    loads = np.array([50, 50, 1, 1])[perm].reshape(2, 2).sum(axis=1)
    assert loads.max() == 51  # one hot lane per shard
    assert sorted(perm.tolist()) == [0, 1, 2, 3]


def test_plan_rebalance_no_improvement_returns_none():
    assert plan_rebalance([1, 1, 1, 1], 2) is None  # already balanced
    assert plan_rebalance([100, 1, 1, 1], 2) is None  # dominated: no gain
    assert plan_rebalance([5, 4, 3], 2) is None  # K % n != 0
    assert plan_rebalance([5, 4], 1) is None  # nothing to spread across


def test_plan_rebalance_is_deterministic():
    a = plan_rebalance([9, 9, 2, 2, 1, 1, 0, 0], 4)
    b = plan_rebalance([9, 9, 2, 2, 1, 1, 0, 0], 4)
    assert a is not None and np.array_equal(a, b)


# -- surviving_mesh ----------------------------------------------------------


def test_surviving_mesh_drops_dead_and_keeps_divisibility():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = key_mesh(jax.devices()[:8])
    dead_dev = mesh.devices.flat[3]
    sub = surviving_mesh(mesh, [3], num_lanes=16)
    # 7 survivors do not divide 16 lanes; the largest divisor wins.
    assert int(sub.devices.size) == 4
    assert dead_dev not in list(sub.devices.flat)
    assert sub.axis_names == mesh.axis_names
    sub2 = surviving_mesh(mesh, [0, 1, 2, 3, 4, 5], num_lanes=16)
    assert int(sub2.devices.size) == 2
    with pytest.raises(ValueError):
        surviving_mesh(mesh, range(8), num_lanes=16)


# -- move_lanes: processor-level pure relabeling -----------------------------


def _stream(keys, n, seed, start=0):
    rng = np.random.default_rng(seed)
    offs = {k: start for k in keys}
    out = []
    for i in range(n):
        k = keys[int(rng.integers(len(keys)))]
        out.append(Record(k, int(rng.integers(0, 5)), 1000 + start * 8 + i,
                          offset=offs[k]))
        offs[k] += 1
    return out


def _canon(matches):
    return sorted(
        (k, tuple(sorted(
            (stage, tuple(e.offset for e in evs))
            for stage, evs in seq.as_map().items()
        )))
        for k, seq in matches
    )


@pytest.mark.parametrize("tiered", [False, True])
def test_move_lanes_processor_parity(tiered):
    """A moved processor matches bit-identically to the unmoved one —
    same emissions, same canonical state (row-permuted), same counters —
    including the tiered stencil carry (``EngineConfig.tiering``), whose
    per-lane prefix state rides the same permutation."""
    cfg = sc.default_config(tiering=tiered, **SUP_DIMS)
    keys = ["k0", "k1", "k2", "k3"]
    pat = sc.skip_till_any
    a = CEPProcessor(pat(), 4, cfg, gc_interval=0)
    b = CEPProcessor(pat(), 4, cfg, gc_interval=0)
    head = _stream(keys, 24, seed=5)
    tail = _stream(keys, 24, seed=6, start=6)
    ma = list(a.process(head))
    mb = list(b.process(head))
    if tiered:
        assert getattr(a.state, "carry", None) is not None
    perm = np.array([2, 0, 3, 1])
    b = move_lanes(pat(), b, perm)
    assert b._lane_of == {k: int(np.argsort(perm)[a._lane_of[k]])
                          for k in keys}
    ma += a.process(tail) + a.flush()
    mb += b.process(tail) + b.flush()
    assert _canon(ma) == _canon(mb)
    assert_state_equal(
        jax.device_put(repartition_state(canonical_state(a.state), perm)),
        canonical_state(b.state),
        msg="move_lanes",
    )
    assert a.counters() == b.counters()
    assert not any(b.counters().values())


def test_move_lanes_fault_leaves_old_processor_intact():
    """The ``rebalance.move`` fault site fires before any state moves: a
    failed move must leave the old processor (and assignment) usable."""
    proc = CEPProcessor(sc.skip_till_any(), 2, sc.default_config(),
                        gc_interval=0)
    proc.process(_stream(["k0", "k1"], 8, seed=1))
    lanes_before = dict(proc._lane_of)
    with fp.FAILPOINTS.session({"rebalance.move": [0]}):
        with pytest.raises(fp.InjectedIOError):
            move_lanes(sc.skip_till_any(), proc, [1, 0])
    assert proc._lane_of == lanes_before
    more = proc.process(_stream(["k0", "k1"], 8, seed=2, start=4))
    assert isinstance(more, list)  # still processes after the failed move


# -- supervisor: evacuation, stragglers, rebalancing -------------------------


KEYS4 = ["k0", "k1", "k2", "k3"]

# Wide enough that these streams are loss-free: the exactly-once and
# bit-parity claims are only meaningful when nothing was dropped anyway.
SUP_DIMS = dict(
    max_runs=64, slab_entries=96, slab_preds=12, dewey_depth=24, max_walk=12
)
SUP_CFG = sc.default_config(**SUP_DIMS)


def _skew_batches(seed):
    """Warmup batch touches all four lanes; afterwards only k0/k1 —
    shard 0 of a 2-device mesh takes ~all the work."""
    rng = np.random.default_rng(seed)
    offs = {k: 0 for k in KEYS4}
    batches = []
    for i in range(8):
        recs = []
        for j in range(8):
            k = KEYS4[int(rng.integers(2))] if i else KEYS4[j % 4]
            recs.append(Record(k, int(rng.integers(0, 5)),
                               1000 + 8 * i + j, offset=offs[k]))
            offs[k] += 1
        batches.append(recs)
    return batches


def _oracle(batches, cfg=None, pat=sc.skip_till_any):
    proc = CEPProcessor(pat(), 4, cfg or SUP_CFG, gc_interval=0)
    out = []
    for b in batches:
        out += proc.process(b)
    out += proc.flush()
    return proc, out


_SKEW_WANT = {}


def _skew_want(seed):
    """Canonical fault-free matches for ``_skew_batches(seed)`` — two
    tests replay the same stream, so the oracle run is shared."""
    if seed not in _SKEW_WANT:
        _SKEW_WANT[seed] = _canon(_oracle(_skew_batches(seed))[1])
    return _SKEW_WANT[seed]


def _mesh2():
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    return key_mesh(jax.devices()[:2])


def _meshed_supervisor(tmp_path, mesh, **kw):
    return Supervisor(
        sc.skip_till_any(), 4, SUP_CFG,
        checkpoint_path=str(tmp_path / "s.ckpt"),
        journal_path=str(tmp_path / "s.jrnl"),
        checkpoint_every=2, gc_interval=0, mesh=mesh, **kw,
    )


def test_supervisor_evacuates_lost_shard(tmp_path):
    """A ShardLost out of the meshed dispatch (the ``shard.dispatch``
    failpoint) evacuates onto the surviving sub-mesh and continues
    degraded — final state and emissions bit-identical to a fault-free
    single-device run, exactly once."""
    mesh = _mesh2()
    batches = [_stream(KEYS4, 8, seed=40 + i, start=2 * i)
               for i in range(4)]
    sup = _meshed_supervisor(tmp_path, mesh)
    got = list(sup.process(batches[0]))
    with fp.FAILPOINTS.session(
        {"shard.dispatch": [0]},
        exc=lambda: ShardLost("injected device loss", shard=1),
    ):
        got += sup.process(batches[1])
    assert sup.evacuations == 1
    assert int(sup._mesh().devices.size) == 1  # degraded
    for b in batches[2:]:
        got += sup.process(b)
    got += sup.processor.flush()
    oracle_proc, want = _oracle(batches)
    assert _canon(got) == _canon(want)
    assert_state_equal(
        canonical_state(sup.processor.state),
        canonical_state(oracle_proc.state),
        msg="post-evacuation",
    )
    assert not any(sup.processor.counters().values())
    snap = sup.metrics_snapshot(per_lane=False)
    assert snap["evacuations"] == 1
    assert snap["phases"]["evacuate"]["count"] == 1


def test_supervisor_unmeshed_shard_loss_crashes(tmp_path):
    """With no mesh there is nothing to evacuate onto: ShardLost
    propagates like any exhausted-retries crash."""
    sup = Supervisor(
        sc.skip_till_any(), 2, SUP_CFG,
        checkpoint_path=str(tmp_path / "u.ckpt"), gc_interval=0,
        shard_policy=ShardPolicy(),
    )
    with fp.FAILPOINTS.session(
        {"device.dispatch": [0, 1]},
        exc=lambda: ShardLost("injected", shard=0),
    ):
        with pytest.raises(ShardLost):
            sup.process(_stream(["k0", "k1"], 8, seed=3))
    assert sup.evacuations == 0


def test_supervisor_shard_probe_routes_generic_error_to_evacuation(tmp_path):
    """A generic device error plus an external probe report of a dead
    shard evacuates instead of recovering onto the dead mesh."""
    mesh = _mesh2()
    batches = [_stream(KEYS4, 8, seed=60 + i, start=2 * i)
               for i in range(3)]
    sup = _meshed_supervisor(tmp_path, mesh, shard_probe=lambda: [0])
    got = list(sup.process(batches[0]))
    with fp.FAILPOINTS.session({"device.dispatch": [0]}):
        got += sup.process(batches[1])
    assert sup.evacuations == 1 and sup.recoveries == 0
    got += sup.process(batches[2]) + sup.processor.flush()
    _, want = _oracle(batches)
    assert _canon(got) == _canon(want)


def test_evacuation_span_and_stall_exemplar_carry_batch_correlation(tmp_path):
    """ISSUE 18 satellite: the evacuation trace span AND the latency
    ledger's ``stall.evacuate`` exemplar carry the correlation id of the
    batch the evacuation rolled back.  Evacuation rebuilds the processor
    from checkpoint + journal replay, so the ledger survives through its
    durable state (the checkpoint header), not by reference — committed
    observations from before the fault must still be present after."""
    from kafkastreams_cep_tpu.utils.telemetry import InMemoryTraceSink

    mesh = _mesh2()
    batches = [_stream(KEYS4, 8, seed=90 + i, start=2 * i)
               for i in range(2)]
    sink = InMemoryTraceSink()
    sup = _meshed_supervisor(tmp_path, mesh, trace_sink=sink, latency=True)
    sup.process(batches[0])
    with fp.FAILPOINTS.session(
        {"shard.dispatch": [0]},
        exc=lambda: ShardLost("injected device loss", shard=1),
    ):
        sup.process(batches[1])
    assert sup.evacuations == 1
    span = sink.spans("evacuate")[0]
    corr = span["corr"]
    twins = [
        s for s in sink.spans("supervisor.batch") if s["corr"] == corr
    ]
    assert len(twins) == 1  # resolves to exactly one real batch span
    ex = sup.processor.ledger.exemplars["stall.evacuate"]
    assert ex["corr"] == corr and ex["seconds"] > 0
    snap = sup.metrics_snapshot(per_lane=False)
    assert snap["latency"]["stalls"]["evacuate"]["count"] == 1
    # Batches committed before AND after the evacuation land in one
    # uninterrupted ledger.
    assert snap["latency"]["batches"] >= 2


def test_supervisor_straggler_declaration_and_evacuation(tmp_path):
    """Latency watermarks breaching factor x peer-median for
    ``straggler_streak`` observations declare the shard; the next batch
    boundary evacuates it (state parity preserved — evacuation is the
    same restore-replay spine as recovery)."""
    mesh = _mesh2()
    policy = ShardPolicy(straggler_factor=2.0, straggler_window=4,
                         straggler_streak=3)
    batches = [_stream(KEYS4, 8, seed=80 + i, start=2 * i)
               for i in range(3)]
    sup = _meshed_supervisor(tmp_path, mesh, shard_policy=policy)
    got = list(sup.process(batches[0]))
    declared = False
    for _ in range(5):
        sup.observe_shard_latency(0, 0.010)
        declared = sup.observe_shard_latency(1, 0.200) or declared
    assert declared and sup.stragglers == 1
    got += sup.process(batches[1])  # boundary: evacuation happens here
    assert sup.evacuations == 1
    assert not sup._lagging
    got += sup.process(batches[2]) + sup.processor.flush()
    _, want = _oracle(batches)
    assert _canon(got) == _canon(want)


def test_supervisor_hot_key_rebalance_lossfree(tmp_path):
    """The skew demo: one key takes ~all the work; at a checkpoint
    boundary the per-key heavy-hitter window trips the policy and hot
    lanes move — zero dropped or duplicated matches, counters clean."""
    mesh = _mesh2()
    policy = ShardPolicy(rebalance_skew=1.2, rebalance_min_hops=8,
                         rebalance_streak=1, rebalance_cooldown=0)
    sup = _meshed_supervisor(tmp_path, mesh, shard_policy=policy)
    batches = _skew_batches(seed=9)
    got = []
    for b in batches:
        got += sup.process(b)
    got += sup.processor.flush()
    assert sup.rebalances >= 1
    assert sup.lanes_moved >= 1
    assert _canon(got) == _skew_want(9)  # nothing dropped, nothing doubled
    assert not any(sup.processor.counters().values())
    snap = sup.metrics_snapshot(per_lane=False)
    assert snap["rebalances"] == sup.rebalances
    assert snap["lanes_moved"] == sup.lanes_moved
    assert snap["phases"]["rebalance"]["count"] >= 1


def test_supervisor_rebalance_move_fault_keeps_old_assignment(tmp_path):
    """An armed ``rebalance.move`` makes the move fail AFTER the decision:
    the supervisor counts the failure, keeps the old assignment, and the
    stream stays exactly-once."""
    mesh = _mesh2()
    policy = ShardPolicy(rebalance_skew=1.2, rebalance_min_hops=8,
                         rebalance_streak=1, rebalance_cooldown=0)
    sup = _meshed_supervisor(tmp_path, mesh, shard_policy=policy)
    batches = _skew_batches(seed=9)  # same stream that trips the policy
    got = []
    with fp.FAILPOINTS.session({"rebalance.move": list(range(99))}):
        for b in batches:
            got += sup.process(b)
    got += sup.processor.flush()
    assert sup.rebalances == 0
    assert sup.rebalance_failures >= 1
    assert _canon(got) == _skew_want(9)
