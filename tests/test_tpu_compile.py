"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Interpret mode on the CPU cannot see Mosaic's layout rules or the chip's
VMEM limit; the TPU compiler, installed here, can.  Each case lowers one
program of the served path for devices of a described ``v5e:2x2``
topology and asserts the kernel survived as a ``tpu_custom_call``.
Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture (never at import,
in ``skipif`` or in ``parametrize``): only one process may load the TPU
library, and every xdist worker imports this file.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))

import stock_demo
from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch, TPUMatcher
from kafkastreams_cep_tpu.parallel import ShardedMatcher
from kafkastreams_cep_tpu.parallel.batch import broadcast_state, kernel_lane_step
from kafkastreams_cep_tpu.parallel.tiered import TieredBatchMatcher

K = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _state(matcher, lanes, sharding):
    state = jax.eval_shape(
        lambda: broadcast_state(matcher.init_state(), lanes)
    )
    return _shapes(state, sharding)


def _events(lanes, steps, sharding):
    lead = (lanes,) if steps is None else (lanes, steps)
    i32 = jax.ShapeDtypeStruct(lead, jnp.int32)
    ev = EventBatch(
        key=i32, value={"price": i32, "volume": i32}, ts=i32, off=i32,
        valid=jax.ShapeDtypeStruct(lead, jnp.bool_),
    )
    return _shapes(ev, sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "features",
    [
        {},
        {"slab_hot_entries": 16, "lazy_extraction": True,
         "stage_attribution": True},
    ],
    ids=["default", "hot-lazy-attribution"],
)
def test_walk_kernel_step_compiles(one_chip, features):
    cfg = dataclasses.replace(EngineConfig(), **features)
    matcher = TPUMatcher(stock_demo.stock_pattern(), cfg)
    step = jax.jit(kernel_lane_step(matcher._phases))
    _assert_kernel(step.lower(
        _state(matcher, K, one_chip), _events(K, None, one_chip)
    ))


def test_sharded_scan_compiles_on_four_chips(topo, monkeypatch):
    # This process sees the CPU, so the matcher's own platform probe would
    # pick the jnp path: ask for the compiled kernel explicitly.
    monkeypatch.setenv("CEP_WALK_KERNEL", "1")
    mesh = Mesh(topo.devices[:4], ("keys",))
    lanes = 4 * K
    sharded = ShardedMatcher(stock_demo.stock_pattern(), lanes, mesh)
    assert sharded.uses_walk_kernel
    spread = NamedSharding(mesh, PartitionSpec("keys"))
    _assert_kernel(sharded.scan.lower(
        _state(sharded.matcher, lanes, spread), _events(lanes, 8, spread)
    ))


def test_tiered_scan_fits_one_chip_at_the_prefix_cell(one_chip, monkeypatch):
    """The tiered programs of ``prefix-131072`` at the cell's own shapes
    (131,072 lanes, calls of 8 steps, the autosized capacity).  The suffix
    scan's arguments, outputs and temporaries are what the configuration's
    unreduced lane count rests on: 2,269,642,752 + 2,804,941,824 +
    6,000,307,200 B (11.07 GB) when this case was written, of the v5e's
    16 GiB."""
    from harness import spec

    monkeypatch.setenv("CEP_WALK_KERNEL", "1")
    conf = spec.load_json(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "prefix-131072.json"))
    lanes, steps = int(conf["lanes"]), 8
    tiered = TieredBatchMatcher(
        spec.build_query(conf["pattern"]), lanes, spec.engine_config(conf))
    assert tiered.plan.tier == "hybrid" and tiered.uses_walk_kernel
    state = _shapes(jax.eval_shape(tiered.init_state), one_chip)
    i32 = jax.ShapeDtypeStruct((lanes, steps), jnp.int32)
    events = _shapes(EventBatch(
        key=i32, value={"code": i32}, ts=i32, off=i32,
        valid=jax.ShapeDtypeStruct((lanes, steps), jnp.bool_),
    ), one_chip)
    promo = _shapes(
        jax.eval_shape(tiered._prefix.scan, state.carry, events)[1], one_chip)
    # The trace names the programs apart from the untiered ``jit_scan``.
    prefix = tiered._prefix.scan.lower(state.carry, events).compile()
    assert "HloModule jit_stencil_prefix_scan" in prefix.as_text()
    compiled = tiered._hybrid_scan_jit.lower(
        state.engine, events, promo).compile()
    text = compiled.as_text()
    assert "HloModule jit_tiered_suffix_scan" in text
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30
