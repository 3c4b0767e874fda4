"""Two-tier slab (EngineConfig.slab_hot_entries) — property and parity
suites.

The two-tier layout claims (ops/slab.py "Two-tier layout"):

1. *Placement-only*: matches, emissions, and every overflow/drop counter
   are bit-identical to the single-tier engine; only the slot an entry
   occupies may differ.
2. *Promotion invariant*: a newly created entry always lands in the hot
   tier (slots ``[0, E_hot)``).
3. *Demotion invariant*: when the hot tier is full, the least-recent
   (minimum event offset, lowest index on ties) hot entry moves to a free
   overflow slot with its refcount and pointer list intact, and a drop
   happens only when the WHOLE slab is full.
4. *Counter accounting*: every active walk hop is classified exactly once
   (hot_hits + hot_misses = active hops; overflow_walks counts the
   hot-miss -> overflow-hit subset), and the Pallas walk kernel agrees
   with the jnp path bit-for-bit.

All kernel runs use ``interpret=True`` (CPU CI checks parity, not perf).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch, TPUMatcher
from kafkastreams_cep_tpu.ops import dewey_ops
from kafkastreams_cep_tpu.ops import slab as slab_mod
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher

from test_slab_batched import assert_slab_equal

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

E, MP, D, W = 16, 4, 6, 8
EH = 8


def ver(*comps):
    v, l = dewey_ops.make(comps, D)
    return jnp.asarray(v), jnp.asarray(l)


def put_chain(slab, n, hot_entries, start_off=0):
    """n chained entries at offsets start_off.. (stage cycles 0..2)."""
    v1, l1 = ver(1)
    slab = slab_mod.put_first(
        slab, 0, start_off, v1, l1, hot_entries=hot_entries
    )
    v10, l10 = ver(1, 0)
    for i in range(1, n):
        slab = slab_mod.put(
            slab, i % 3, start_off + i, (i - 1) % 3, start_off + i - 1,
            v10, l10, hot_entries=hot_entries,
        )
    return slab


# ---------------------------------------------------------------------------
# Slab-level properties (jnp path)
# ---------------------------------------------------------------------------


def test_new_entries_land_hot_until_full():
    slab = slab_mod.make(E, MP, D)
    slab = put_chain(slab, EH, hot_entries=EH)
    live = np.flatnonzero(np.asarray(slab.stage) >= 0)
    assert live.tolist() == list(range(EH))  # promotion invariant
    assert int(slab.demotions) == 0


def test_demotion_moves_least_recent_hot_entry():
    slab = slab_mod.make(E, MP, D)
    slab = put_chain(slab, EH, hot_entries=EH)  # hot tier now full
    before = {
        (int(s), int(o))
        for s, o in zip(np.asarray(slab.stage), np.asarray(slab.off))
        if s >= 0
    }
    v10, l10 = ver(1, 0)
    slab = slab_mod.put(
        slab, 2, EH, (EH - 1) % 3, EH - 1, v10, l10, hot_entries=EH
    )  # needs a slot -> demotes
    assert int(slab.demotions) == 1
    stage = np.asarray(slab.stage)
    off = np.asarray(slab.off)
    # The new entry is hot; the demoted one is the min-off entry (off=0),
    # now resident in the overflow tier with nothing lost.
    hot = {(int(s), int(o)) for s, o in zip(stage[:EH], off[:EH]) if s >= 0}
    ovf = {(int(s), int(o)) for s, o in zip(stage[EH:], off[EH:]) if s >= 0}
    assert (2, EH) in hot
    assert ovf == {(0, 0)}
    assert hot | ovf == before | {(2, EH)}


def test_demoted_entry_keeps_refs_and_pointers():
    slab = slab_mod.make(E, MP, D)
    slab = put_chain(slab, EH, hot_entries=EH)
    # Bump the victim's refcount so the move has something to preserve.
    v1, l1 = ver(1)
    slab = slab_mod.branch(
        slab, 0, 0, v1, l1, max_walk=1, hot_entries=EH
    )
    refs0 = int(slab.refs[0])
    np0 = int(slab.npreds[0])
    pv0 = np.asarray(slab.pver[0])
    v10, l10 = ver(1, 0)
    slab = slab_mod.put(
        slab, 2, EH, (EH - 1) % 3, EH - 1, v10, l10, hot_entries=EH
    )
    e = int(np.flatnonzero(
        (np.asarray(slab.stage) == 0) & (np.asarray(slab.off) == 0)
    )[0])
    assert e >= EH  # demoted
    assert int(slab.refs[e]) == refs0
    assert int(slab.npreds[e]) == np0
    np.testing.assert_array_equal(np.asarray(slab.pver[e]), pv0)


def test_full_drop_only_when_whole_slab_full():
    small_e = 12  # hot 8 + overflow 4
    slab = slab_mod.make(small_e, MP, D)
    slab = put_chain(slab, small_e, hot_entries=EH)
    assert int(slab.full_drops) == 0
    assert int(slab.demotions) == small_e - EH
    v10, l10 = ver(1, 0)
    slab = slab_mod.put(
        slab, 2, small_e, (small_e - 1) % 3, small_e - 1, v10, l10,
        hot_entries=EH,
    )
    assert int(slab.full_drops) == 1  # now, and only now


def test_hot_miss_overflow_hit_walk_path():
    """A chain whose head stays hot but whose tail was demoted: the
    extraction walk must resolve the tail in the overflow tier (counted in
    overflow_walks) and still extract the identical match."""
    slab = slab_mod.make(E, MP, D)
    n = EH + 4  # 4 oldest entries get demoted
    slab = put_chain(slab, n, hot_entries=EH)
    assert int(slab.demotions) == 4
    # Same chain on a single-tier slab for the expected extraction.
    ref = put_chain(slab_mod.make(E, MP, D), n, hot_entries=0)
    v10, l10 = ver(1, 0)
    # Walk bound must cover the whole chain so the walk descends past the
    # hot window into the demoted tail.
    slab, st, off, cnt = slab_mod.peek(
        slab, (n - 1) % 3, n - 1, v10, l10, max_walk=2 * W, remove=False,
        hot_entries=EH,
    )
    ref, st_r, off_r, cnt_r = slab_mod.peek(
        ref, (n - 1) % 3, n - 1, v10, l10, max_walk=2 * W, remove=False,
    )
    assert int(cnt) == int(cnt_r)
    np.testing.assert_array_equal(np.asarray(st), np.asarray(st_r))
    np.testing.assert_array_equal(np.asarray(off), np.asarray(off_r))
    assert int(slab.overflow_walks) > 0
    # Accounting: every active hop classified exactly once.
    assert int(slab.hot_hits) + int(slab.hot_misses) == int(cnt)
    assert int(slab.overflow_walks) <= int(slab.hot_misses)


def test_tier_lookup_equivalence_random_ops():
    """Randomized put/branch/peek sequences: the two-tier slab must agree
    with the single-tier slab on every output, every drop counter, and the
    live-entry key set (placement-only difference)."""
    rng = np.random.default_rng(77)
    for trial in range(8):
        s2 = slab_mod.make(E, MP, D)
        s1 = slab_mod.make(E, MP, D)
        off_ctr = 0
        for step in range(30):
            op = rng.integers(0, 4)
            stage = int(rng.integers(0, 3))
            vv, vl = ver(*(int(x) for x in rng.integers(1, 3, size=2)))
            if op == 0:
                s2 = slab_mod.put_first(
                    s2, stage, off_ctr, vv, vl, hot_entries=EH
                )
                s1 = slab_mod.put_first(s1, stage, off_ctr, vv, vl)
                off_ctr += 1
            elif op == 1 and off_ctr:
                prev = int(rng.integers(0, off_ctr))
                s2 = slab_mod.put(
                    s2, stage, off_ctr, prev % 3, prev, vv, vl,
                    hot_entries=EH,
                )
                s1 = slab_mod.put(
                    s1, stage, off_ctr, prev % 3, prev, vv, vl
                )
                off_ctr += 1
            elif op == 2 and off_ctr:
                tgt = int(rng.integers(0, off_ctr))
                s2 = slab_mod.branch(
                    s2, tgt % 3, tgt, vv, vl, max_walk=W, hot_entries=EH
                )
                s1 = slab_mod.branch(s1, tgt % 3, tgt, vv, vl, max_walk=W)
            elif op == 3 and off_ctr:
                tgt = int(rng.integers(0, off_ctr))
                s2, st2, of2, n2 = slab_mod.peek(
                    s2, tgt % 3, tgt, vv, vl, max_walk=W, remove=True,
                    hot_entries=EH,
                )
                s1, st1, of1, n1 = slab_mod.peek(
                    s1, tgt % 3, tgt, vv, vl, max_walk=W, remove=True
                )
                assert int(n2) == int(n1), f"trial {trial} step {step}"
                np.testing.assert_array_equal(
                    np.asarray(st2), np.asarray(st1)
                )
                np.testing.assert_array_equal(
                    np.asarray(of2), np.asarray(of1)
                )
        for c in ("full_drops", "pred_drops", "missing", "trunc"):
            assert int(getattr(s2, c)) == int(getattr(s1, c)), (trial, c)
        live2 = {
            (int(s), int(o))
            for s, o in zip(np.asarray(s2.stage), np.asarray(s2.off))
            if s >= 0
        }
        live1 = {
            (int(s), int(o))
            for s, o in zip(np.asarray(s1.stage), np.asarray(s1.off))
            if s >= 0
        }
        assert live2 == live1, trial


# ---------------------------------------------------------------------------
# Kernel parity (interpret mode)
# ---------------------------------------------------------------------------


def stock_events(K, T, seed):
    rng = np.random.default_rng(seed)
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    vols = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    return EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)
        ),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(vols)},
        ts=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)
        ),
        off=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)
        ),
        valid=jnp.ones((K, T), bool),
    )


# E=16 with an 8-row hot tier under the match-dense stock trace: every
# behavior fires — demotions, overflow-resident walks, full drops, prunes.
PRESSURE_CFG = EngineConfig(
    max_runs=8, slab_entries=16, slab_hot_entries=8, slab_preds=4,
    dewey_depth=8, max_walk=8,
)

SLAB_FIELDS = (
    "stage", "off", "refs", "npreds", "full_drops", "pred_drops",
    "missing", "trunc", "hot_hits", "hot_misses", "overflow_walks",
    "demotions",
)


def assert_same_run(ref, out_r, st_r, krn, out_k, st_k):
    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_r, f)), np.asarray(getattr(out_k, f)),
            err_msg=f,
        )
    for f in SLAB_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_r.slab, f)),
            np.asarray(getattr(st_k.slab, f)), err_msg=f"slab.{f}",
        )
    assert ref.counters(st_r) == krn.counters(st_k)
    assert ref.hot_counters(st_r) == krn.hot_counters(st_k)


def test_walk_kernel_two_tier_parity_under_pressure():
    K, T = 128, 24
    events = stock_events(K, T, 21)
    os.environ["CEP_WALK_KERNEL"] = "0"
    ref = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_CFG)
    st_r, out_r = ref.scan(ref.init_state(), events)
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        krn = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_CFG)
        assert krn.uses_walk_kernel
        st_k, out_k = krn.scan(krn.init_state(), events)
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"
    assert_same_run(ref, out_r, st_r, krn, out_k, st_k)
    hot = ref.hot_counters(st_r)
    assert hot["slab_demotions"] > 0, "pressure config must demote"
    assert hot["slab_overflow_walks"] > 0, "overflow walks must fire"
    assert ref.counters(st_r)["slab_full_drops"] > 0, "drops must fire"


@pytest.mark.slow
def test_two_tier_vs_single_tier_engine_bit_exact():
    """The placement-only claim at engine level: same trace, same shapes,
    hot window on vs off — emissions and drop counters bit-identical.

    Tier-2 (``-m slow``, ~18 s): the walk-kernel parity under pressure
    above keeps the two-tier claim in tier-1 (ROADMAP tier-1
    budget note, PR 13)."""
    K, T = 8, 48
    events = stock_events(K, T, 5)
    os.environ["CEP_WALK_KERNEL"] = "0"
    single = BatchMatcher(
        stock_demo.stock_pattern(), K,
        dataclasses.replace(PRESSURE_CFG, slab_hot_entries=0),
    )
    two = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_CFG)
    st_s, out_s = single.scan(single.init_state(), events)
    st_t, out_t = two.scan(two.init_state(), events)
    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_s, f)), np.asarray(getattr(out_t, f)),
            err_msg=f,
        )
    assert single.counters(st_s) == two.counters(st_t)
    # Live-entry key sets equal lane by lane (placement may differ).
    st0, of0 = np.asarray(st_s.slab.stage), np.asarray(st_s.slab.off)
    st1, of1 = np.asarray(st_t.slab.stage), np.asarray(st_t.slab.off)
    for k in range(K):
        a = {(int(s), int(o)) for s, o in zip(st0[k], of0[k]) if s >= 0}
        b = {(int(s), int(o)) for s, o in zip(st1[k], of1[k]) if s >= 0}
        assert a == b, k


def test_sequential_slab_two_tier_matches_batched_placement():
    """sequential_slab=True (literal reference op order) must place every
    entry in the same slot as the batched path — the allocation policy is
    deterministic.  (Residency telemetry may differ by a few hops: the
    sequential path interleaves puts and walks per run, so an entry's tier
    AT WALK TIME can legitimately differ; demotion counts cannot.)"""
    K, T = 4, 32
    events = stock_events(K, T, 9)
    os.environ["CEP_WALK_KERNEL"] = "0"
    bat = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_CFG)
    seq = BatchMatcher(
        stock_demo.stock_pattern(), K,
        dataclasses.replace(PRESSURE_CFG, sequential_slab=True),
    )
    st_b, out_b = bat.scan(bat.init_state(), events)
    st_q, out_q = seq.scan(seq.init_state(), events)
    for f in ("count", "stage", "off"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_b, f)), np.asarray(getattr(out_q, f)),
            err_msg=f,
        )
    np.testing.assert_array_equal(
        np.asarray(st_b.slab.stage), np.asarray(st_q.slab.stage)
    )
    np.testing.assert_array_equal(
        np.asarray(st_b.slab.off), np.asarray(st_q.slab.off)
    )
    assert bat.counters(st_b) == seq.counters(st_q)


# ---------------------------------------------------------------------------
# Config + sizing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [4, 7, 16, 24])
def test_invalid_hot_entries_rejected(bad):
    cfg = EngineConfig(
        max_runs=8, slab_entries=16, slab_hot_entries=bad, slab_preds=4,
        dewey_depth=8, max_walk=8,
    )
    if bad % 8 == 0 and 0 < bad < 16:
        TPUMatcher(stock_demo.stock_pattern(), cfg)  # valid: builds
    else:
        with pytest.raises(ValueError, match="slab_hot_entries"):
            TPUMatcher(stock_demo.stock_pattern(), cfg)


def test_suggest_hot_entries_policy():
    from kafkastreams_cep_tpu.engine.sizing import suggest_hot_entries

    assert suggest_hot_entries(16, 8) == 0  # small slab: single tier
    assert suggest_hot_entries(24, 8) == 0
    e = suggest_hot_entries(64, 8)
    assert 0 < e < 64 and e % 8 == 0
    assert suggest_hot_entries(32, 100) == 24  # clamped below E
