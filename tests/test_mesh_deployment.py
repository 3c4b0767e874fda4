"""The stock deployment on a four-device mesh (``CEPProcessor(mesh=4)``).

The benchmark's ``stock-131072x4`` configuration and ``stock-demo-backlog``
traffic, cut to 512 lanes (128 a device) on four of the suite's CPU
devices, fed through ``process_columns`` in calls of 8 steps.  Every key's
emissions equal the frozen reference (``benchmark/reference/nfa.py``)
whole, and the whole emitted list equals an unmeshed processor's, order
included.  Also: the mesh given as a device count or as a ``Mesh``; the
decode past one shard's share of the budget; no compile after the warm-up;
the state donated to the scan and the sweep; the sweep by lane blocks.
"""

import os
import sys

import jax
import numpy as np
import pytest

from kafkastreams_cep_tpu.engine import EngineConfig
from kafkastreams_cep_tpu.parallel import BatchMatcher, ShardedMatcher
from kafkastreams_cep_tpu.parallel.sharding import key_mesh
from kafkastreams_cep_tpu.runtime import CEPProcessor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "examples"))

import stock_demo  # noqa: E402
from harness import spec  # noqa: E402
from harness.check import canon_program, reference_matches  # noqa: E402
from harness.drive import Feeder  # noqa: E402
from harness.traffic import generate  # noqa: E402

CONF = spec.load_json(os.path.join(BENCH, "configs", "stock-131072x4.json"))
TRAFFIC = spec.load_json(
    os.path.join(BENCH, "traffic", "stock-demo-backlog.json"))
K, CALL, CALLS = 512, 8, 6
SEED = 2147483713


def _processor(**over):
    kw = dict(CONF["processor"], **over)
    return CEPProcessor(
        spec.build_query(CONF["pattern"]), K, spec.engine_config(CONF), **kw)


def _feed(proc, feeder, calls=CALLS):
    out = []
    for c in range(calls):
        out += proc.process_columns(*feeder.columns(c * CALL, CALL))
    return out


def _canon(matches):
    return [(int(key), canon_program(seq)) for key, seq in matches]


@pytest.fixture(scope="module")
def stream():
    return generate(TRAFFIC, K, SEED)


@pytest.fixture(scope="module")
def unmeshed(stream):
    """The unmeshed processor's emissions, and the reference's, per key."""
    proc = _processor(mesh=None)
    got = _feed(proc, Feeder(stream, CALL))
    assert not any(proc.counters().values())
    want = {
        lane: reference_matches(
            CONF["pattern"], True, stream, lane, CALL * CALLS)
        for lane in range(K)
    }
    return _canon(got), want


@pytest.mark.parametrize("mesh,budget", [
    ("count", None),
    ("mesh", None),
    # 16 rows, 4 a shard: every call's matches overflow a shard's share.
    ("count", 16),
], ids=["mesh=4", "mesh=key_mesh", "past-a-shard-budget"])
def test_mesh_emits_the_reference_and_the_unmeshed_order(
        stream, unmeshed, mesh, budget):
    over = {"mesh": 4 if mesh == "count" else key_mesh(jax.devices()[:4])}
    if budget is not None:
        over["decode_budget"] = budget
    proc = _processor(**over)
    assert isinstance(proc.batch, ShardedMatcher)
    assert proc.mesh.devices.size == 4
    got = _canon(_feed(proc, Feeder(stream, CALL)))
    want_all, want_ref = unmeshed
    assert got == want_all  # every match, in the unmeshed order
    per_key = {lane: [] for lane in range(K)}
    for key, match in got:
        per_key[key].append(match)
    assert per_key == want_ref
    assert sum(len(v) for v in want_ref.values()) >= 200
    assert not any(proc.counters().values())
    m = proc.metrics
    if budget is None:
        assert m.decode_fallbacks == 0
        assert 0 < m.shard_matches_min <= m.shard_matches_max
    else:
        assert m.decode_fallbacks == CALLS
    assert m.shard_put_seconds > 0


def test_mesh_calls_compile_nothing_after_the_warm_up(stream):
    proc = _processor()
    feeder = Feeder(stream, CALL)
    _feed(proc, feeder, calls=1)  # the warm-up
    names = []
    armed = [True]

    def listener(event, duration, **kw):
        if armed[0] and event.endswith(("jaxpr_to_mlir_module_duration",
                                        "backend_compile_duration")):
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for c in (1, 2):
            proc.process_columns(*feeder.columns(c * CALL, CALL))
    finally:
        armed[0] = False
    assert names == []


@pytest.mark.parametrize("sharded", [False, True], ids=["batch", "sharded"])
@pytest.mark.parametrize("call", ["scan", "sweep"])
def test_scan_and_sweep_consume_the_state(sharded, call):
    lanes = 16
    cfg = EngineConfig(max_runs=8, slab_entries=16, slab_preds=4,
                       dewey_depth=8, max_walk=8)
    if sharded:
        m = ShardedMatcher(stock_demo.stock_pattern(), lanes,
                           key_mesh(jax.devices()[:4]), cfg)
    else:
        m = BatchMatcher(stock_demo.stock_pattern(), lanes, cfg)
    old = m.init_state()
    if call == "scan":
        ev = _events(lanes, 4)
        if sharded:
            ev = m.shard_events(ev)
        new, _ = m.scan(old, ev)
    else:
        new = m.sweep(old)
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(old))
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(new))


def _events(lanes, steps):
    from kafkastreams_cep_tpu.engine import EventBatch

    rng = np.random.default_rng(5)
    i32 = np.int32
    return EventBatch(
        key=np.broadcast_to(np.arange(lanes, dtype=i32)[:, None],
                            (lanes, steps)),
        value={"price": rng.integers(90, 131, (lanes, steps)).astype(i32),
               "volume": rng.integers(600, 1101, (lanes, steps)).astype(i32)},
        ts=np.broadcast_to(np.arange(steps, dtype=i32) * 2, (lanes, steps)),
        off=np.broadcast_to(np.arange(steps, dtype=i32), (lanes, steps)),
        valid=np.ones((lanes, steps), bool),
    )


def test_sweep_by_lane_blocks_equals_the_whole_sweep(monkeypatch):
    """A state over twice ``SWEEP_BLOCK_BYTES`` sweeps block by block,
    each block written back over its own lanes: the same state as one
    whole sweep."""
    from kafkastreams_cep_tpu.parallel import batch

    lanes = 16
    cfg = EngineConfig(max_runs=8, slab_entries=16, slab_preds=4,
                       dewey_depth=8, max_walk=8)
    m = BatchMatcher(stock_demo.stock_pattern(), lanes, cfg)
    state, _ = m.scan(m.init_state(), _events(lanes, 12))
    state = jax.device_get(state)

    def sweep(s):
        return batch.sweep_lanes(s, cfg.max_walk, True)

    whole = jax.jit(sweep)(state)
    size = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    monkeypatch.setattr(batch, "SWEEP_BLOCK_BYTES", size // 5)
    assert batch._sweep_blocks(state) == 8
    blocked = jax.jit(sweep)(state)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(blocked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
