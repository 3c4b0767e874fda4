"""Live-state migration (runtime/migrate.py) — the embedding property.

The contract: widening any state dimension (run queue R, slab E, pointer
lists MP, Dewey width D, walk bound W — alone or combined) embeds the
live state such that the wide engine's future evolution is bit-identical
to the narrow engine's for as long as the narrow engine would not have
dropped — same emissions at the same run slots, same slab placement,
same counters — and the final narrow state re-embeds into exactly the
final wide state.  Checked over randomized traces on the jnp path, and
jnp-vs-Pallas-kernel on a migrated state (interpret mode; CPU CI checks
parity, not perf).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import (
    EngineConfig,
    EventBatch,
    capacity_counters,
)
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.runtime import (
    CEPProcessor,
    Record,
    migrate_processor,
    widen_state,
)
from kafkastreams_cep_tpu.runtime.migrate import canonical_state, check_widens

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

# Narrow-but-sufficient on the traces below: the embedding claim is only
# bit-exact while the narrow side does not drop, so the property runs
# assert all-zero narrow counters as a precondition.
NARROW = EngineConfig(
    max_runs=16, slab_entries=32, slab_preds=16, dewey_depth=32, max_walk=16
)

WIDENINGS = {
    "runs": dict(max_runs=32),
    "slab": dict(slab_entries=64),
    "preds": dict(slab_preds=32),
    "dewey": dict(dewey_depth=48),
    "walk": dict(max_walk=24),
    "combined": dict(
        max_runs=32, slab_entries=64, slab_preds=32, dewey_depth=48,
        max_walk=24,
    ),
}


def stock_events(K, T, seed, t0=0):
    rng = np.random.default_rng(seed)
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    vols = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    return EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)
        ),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(vols)},
        ts=jnp.broadcast_to(
            (t0 + jnp.arange(T, dtype=jnp.int32))[None, :] * 2, (K, T)
        ),
        off=jnp.broadcast_to(
            (t0 + jnp.arange(T, dtype=jnp.int32))[None, :], (K, T)
        ),
        valid=jnp.ones((K, T), bool),
    )


def assert_state_equal(a, b, msg=""):
    """Bit-equality of the observable state (dead run slots, free slab
    rows, and pointer slots beyond npreds hold implementation-dependent
    residue the engine can never read — canonical_state nulls them)."""
    a, b = canonical_state(a), canonical_state(b)
    fa, _ = jax.tree_util.tree_flatten(a)
    fb, _ = jax.tree_util.tree_flatten(b)
    for i, (x, y) in enumerate(zip(fa, fb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{msg} leaf {i}"
        )


@pytest.mark.parametrize(
    "dim,seed",
    # Each dim alone on one randomized trace; the combined widening on a
    # second trace too (it subsumes the per-dim interactions).  The
    # combined runs are tier-2 (-m slow, ~11 s each): the per-dim params
    # keep the pure-embedding claim in tier-1 (ROADMAP tier-1 budget
    # note, PR 13).
    [
        (d, 3) if d != "combined"
        else pytest.param(d, 3, marks=pytest.mark.slow)
        for d in sorted(WIDENINGS)
    ] + [pytest.param("combined", 17, marks=pytest.mark.slow)],
)
def test_widening_is_pure_embedding(dim, seed):
    """Prefix on narrow -> widen -> suffix on wide == suffix on narrow:
    emissions bit-identical on the shared run slots, nothing beyond them,
    and embed(final_narrow) == final_wide exactly."""
    K, T = 8, 12
    wide_cfg = dataclasses.replace(NARROW, **WIDENINGS[dim])
    prefix = stock_events(K, T, seed)
    suffix = stock_events(K, T, seed + 100, t0=T)

    narrow = BatchMatcher(stock_demo.stock_pattern(), K, NARROW)
    mid, _ = narrow.scan(narrow.init_state(), prefix)
    mid_h = jax.device_get(mid)  # the next scan consumes ``mid``
    st_n, out_n = narrow.scan(mid, suffix)
    assert not any(capacity_counters(narrow.counters(st_n)).values()), (
        "precondition: the narrow run must be loss-free for bit-exactness"
    )

    wide = BatchMatcher(stock_demo.stock_pattern(), K, wide_cfg)
    mid_w = jax.device_put(widen_state(mid_h, NARROW, wide_cfg))
    st_w, out_w = wide.scan(mid_w, suffix)

    R = NARROW.max_runs
    np.testing.assert_array_equal(
        np.asarray(out_n.count), np.asarray(out_w.count)[..., :R]
    )
    assert not np.asarray(out_w.count)[..., R:].any()
    W = NARROW.max_walk
    for f in ("stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_n, f)),
            np.asarray(getattr(out_w, f))[..., :R, :W],
            err_msg=f,
        )
    assert_state_equal(
        jax.device_put(widen_state(st_n, NARROW, wide_cfg)), st_w,
        msg=f"widen[{dim}]",
    )


@pytest.mark.slow
def test_kernel_and_jnp_paths_agree_on_migrated_state():
    """A migrated state is an ordinary engine state: the fused Pallas walk
    kernel and the jnp pass must stay bit-identical running it.

    Tier-2 (``-m slow``): interpret-mode Pallas executes per step in
    Python and this is the single most expensive test in the suite
    (~166 s); the jnp migrate tests above keep tier-1 coverage
    (ROADMAP tier-1 budget note, PR 13)."""
    K, T = 128, 10
    wide_cfg = dataclasses.replace(NARROW, **WIDENINGS["combined"])
    prefix = stock_events(K, T, 7)
    suffix = stock_events(K, T, 107, t0=T)
    os.environ["CEP_WALK_KERNEL"] = "0"
    narrow = BatchMatcher(stock_demo.stock_pattern(), K, NARROW)
    mid, _ = narrow.scan(narrow.init_state(), prefix)
    mid_w = widen_state(mid, NARROW, wide_cfg)  # host: each scan consumes
    wide_ref = BatchMatcher(stock_demo.stock_pattern(), K, wide_cfg)
    st_r, out_r = wide_ref.scan(jax.device_put(mid_w), suffix)
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        wide_krn = BatchMatcher(stock_demo.stock_pattern(), K, wide_cfg)
        assert wide_krn.uses_walk_kernel
        st_k, out_k = wide_krn.scan(jax.device_put(mid_w), suffix)
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"
    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_r, f)), np.asarray(getattr(out_k, f)),
            err_msg=f,
        )
    assert_state_equal(st_r, st_k, msg="kernel-vs-jnp")
    assert wide_ref.counters(st_r) == wide_krn.counters(st_k)


def test_two_tier_slab_widens_with_hot_window_intact():
    """Widening E with the hot window kept: placement (and therefore the
    whole state) stays bit-exact — appended slots are free overflow rows
    that neither allocation-before-full nor demotion can see."""
    K, T = 8, 12
    narrow = dataclasses.replace(NARROW, slab_hot_entries=8)
    wide_cfg = dataclasses.replace(narrow, slab_entries=64)
    prefix = stock_events(K, T, 11)
    suffix = stock_events(K, T, 111, t0=T)
    a = BatchMatcher(stock_demo.stock_pattern(), K, narrow)
    mid, _ = a.scan(a.init_state(), prefix)
    mid_h = jax.device_get(mid)  # the next scan consumes ``mid``
    st_n, out_n = a.scan(mid, suffix)
    assert not any(capacity_counters(a.counters(st_n)).values())
    b = BatchMatcher(stock_demo.stock_pattern(), K, wide_cfg)
    st_w, out_w = b.scan(
        jax.device_put(widen_state(mid_h, narrow, wide_cfg)), suffix
    )
    np.testing.assert_array_equal(
        np.asarray(out_n.count), np.asarray(out_w.count)
    )
    assert_state_equal(
        jax.device_put(widen_state(st_n, narrow, wide_cfg)), st_w,
        msg="two-tier",
    )


def test_handle_ring_widens_with_pending_handles():
    """Lazy extraction: widening (handle_ring alone, and combined with
    every other dim) with a NON-EMPTY handle ring embeds the pending
    handles — the wide engine drains them to bit-identical matches and
    keeps matching identically afterwards."""
    lazy_narrow = dataclasses.replace(
        NARROW, lazy_extraction=True, handle_ring=64
    )
    widenings = dict(
        ring=dict(handle_ring=96),
        combined=dict(handle_ring=96, **WIDENINGS["combined"]),
    )
    K, T = 8, 12
    prefix = stock_events(K, T, 23)
    suffix = stock_events(K, T, 123, t0=T)
    os.environ["CEP_WALK_KERNEL"] = "0"
    narrow = BatchMatcher(stock_demo.stock_pattern(), K, lazy_narrow)
    mid, _ = narrow.scan(narrow.init_state(), prefix)  # NOT drained
    assert int(jnp.sum(mid.hr_count)) > 0
    mid_h = jax.device_get(mid)  # the next scan consumes ``mid``
    st_n, _ = narrow.scan(mid, suffix)
    st_n, d_n = narrow.drain(st_n)
    assert not any(capacity_counters(narrow.counters(st_n)).values())
    for name, w in widenings.items():
        wide_cfg = dataclasses.replace(lazy_narrow, **w)
        wide = BatchMatcher(stock_demo.stock_pattern(), K, wide_cfg)
        mid_w = jax.device_put(widen_state(mid_h, lazy_narrow, wide_cfg))
        st_w, _ = wide.scan(mid_w, suffix)
        st_w, d_w = wide.drain(st_w)
        HB, W0 = lazy_narrow.handle_ring, lazy_narrow.max_walk
        for f in d_n._fields:
            a = np.asarray(getattr(d_n, f))
            b = np.asarray(getattr(d_w, f))
            if b.ndim == 3:  # [K, HB', W'] hop rows
                assert (b[:, :HB, W0:] == -1).all(), f"{name}: drain.{f}"
                b = b[:, :HB, :W0]
            else:
                b = b[:, :HB]
            np.testing.assert_array_equal(
                a, b, err_msg=f"{name}: drain.{f}"
            )
            assert not (np.asarray(getattr(d_w, f))[:, HB:] > 0).any() \
                if f == "count" else True
        assert narrow.counters(st_n) == wide.counters(st_w), name


def test_check_widens_refusals():
    with pytest.raises(ValueError, match="shrink"):
        check_widens(NARROW, dataclasses.replace(NARROW, max_runs=8))
    with pytest.raises(ValueError, match="semantics"):
        check_widens(
            NARROW,
            dataclasses.replace(NARROW, max_runs=32, enforce_windows=True),
        )
    with pytest.raises(ValueError, match="equals"):
        check_widens(NARROW, NARROW)


def test_migrate_processor_preserves_history_and_counters():
    """Processor-level migration: a processor that already dropped keeps
    its counters (migration never forgives past loss), its key->lane map,
    its event mirror, and keeps matching across the boundary."""
    tiny = EngineConfig(
        max_runs=4, slab_entries=16, slab_preds=2, dewey_depth=8, max_walk=8
    )
    proc = CEPProcessor(sc.skip_till_any(), 2, tiny, gc_interval=0)
    storm = [sc.A, sc.B] + [sc.C, sc.D] * 4
    for i, v in enumerate(storm):
        proc.process([Record("k", v, 1000 + i, offset=i)])
    before = proc.counters()
    assert before["run_drops"] > 0
    wide = EngineConfig(
        max_runs=32, slab_entries=64, slab_preds=8, dewey_depth=16,
        max_walk=16,
    )
    proc2 = migrate_processor(sc.skip_till_any(), proc, wide)
    assert proc2.counters() == before
    assert proc2._lane_of == proc._lane_of
    assert proc2._next_offset.tolist() == proc._next_offset.tolist()
    n = len(storm)
    out = []
    for i, v in enumerate([sc.A, sc.B, sc.C, sc.D]):
        out += proc2.process([Record("k", v, 5000 + i, offset=n + i)])
    assert len(out) >= 1  # live and matching at the new width
    assert proc2.counters()["run_drops"] == before["run_drops"]  # no new loss


def test_migrate_refuses_pending_pipelined_batch():
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), pipeline=True, gc_interval=0
    )
    proc.process([Record("k", sc.A, 1, offset=0)])
    wide = dataclasses.replace(sc.default_config(), max_runs=64)
    with pytest.raises(ValueError, match="flush"):
        migrate_processor(sc.strict3(), proc, wide)
    proc.flush()
    migrate_processor(sc.strict3(), proc, wide)  # clean after flush
