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

import stock_demo
from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch, TPUMatcher
from kafkastreams_cep_tpu.ops import scan_kernel
from kafkastreams_cep_tpu.parallel import ShardedMatcher
from kafkastreams_cep_tpu.parallel.batch import broadcast_state, kernel_lane_step

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


def test_whole_scan_kernel_compiles(one_chip):
    matcher = TPUMatcher(stock_demo.stock_pattern(), EngineConfig())
    scan = jax.jit(scan_kernel.build_scan(matcher.tables, matcher.config))
    _assert_kernel(scan.lower(
        _state(matcher, K, one_chip), _events(K, 8, one_chip)
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
