"""Fused Pallas walk kernel vs the sequential slab ops it replaces.

Ground truth is the per-op sequential path — ``slab.branch`` for increment
walkers and ``slab.peek(remove=True)`` for removal/extraction walkers,
applied one walker at a time in queue order per lane (the reference's
order, ``NFA.java:102-123``).  The kernel runs in interpreter mode on CPU
(the suite's platform); the real-chip path is exercised by the benchmarks
and the engine A/B test.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch, StepOutput
from kafkastreams_cep_tpu.ops import dewey_ops
from kafkastreams_cep_tpu.ops import slab as slab_mod
from kafkastreams_cep_tpu.ops.walk_kernel import LANE_BLOCK, walk_pass_kernel
from kafkastreams_cep_tpu.parallel.batch import (
    BatchMatcher,
    _select_walk_kernel,
)
from kafkastreams_cep_tpu.runtime.migrate import canonical_state

from test_slab_batched import assert_slab_equal, seed_slab

E, MP, D, W = 16, 4, 6, 8
OUT_BASE, OUT_ROWS = 4, 4  # candidate rows [4, 8) may emit
PW = OUT_BASE + OUT_ROWS


def random_walkers(rng):
    """One lane's candidate walker set in the engine's layout: increment
    walkers first, then remove walkers, the final ``OUT_ROWS`` extracting."""
    en = rng.random(PW) < 0.5
    stage = rng.integers(0, 4, size=PW).astype(np.int32)
    off = rng.integers(0, 5, size=PW).astype(np.int32)
    vers, vlens = [], []
    for _ in range(PW):
        comps = tuple(rng.integers(1, 3, size=rng.integers(1, 4)))
        v, l = dewey_ops.make(comps, D)
        vers.append(v)
        vlens.append(l)
    is_remove = np.arange(PW) >= 2  # rows [0,2): branch; [2,PW): remove
    want_out = np.arange(PW) >= OUT_BASE
    return dict(
        en=en, stage=stage, off=off,
        ver=np.stack(vers).astype(np.int32),
        vlen=np.asarray(vlens, np.int32),
        is_remove=is_remove, want_out=want_out,
    )


def sequential_lane(slab, wk):
    """Queue-order per-walker ground truth for one lane."""
    out_stage = np.full((OUT_ROWS, W), -1, np.int32)
    out_off = np.full((OUT_ROWS, W), -1, np.int32)
    count = np.zeros((OUT_ROWS,), np.int32)
    for p in range(PW):
        if not wk["en"][p]:
            continue
        if wk["is_remove"][p]:
            slab, st, of, cnt = slab_mod.peek(
                slab, int(wk["stage"][p]), int(wk["off"][p]),
                jnp.asarray(wk["ver"][p]), jnp.asarray(wk["vlen"][p]),
                W, remove=True, enable=True,
            )
            if wk["want_out"][p]:
                r = p - OUT_BASE
                out_stage[r] = np.asarray(st)
                out_off[r] = np.asarray(of)
                count[r] = int(cnt)
        else:
            slab = slab_mod.branch(
                slab, int(wk["stage"][p]), int(wk["off"][p]),
                jnp.asarray(wk["ver"][p]), jnp.asarray(wk["vlen"][p]),
                W, enable=True,
            )
    return slab, out_stage, out_off, count


def batch_lanes(lanes, field):
    return jnp.asarray(np.stack([l[field] for l in lanes]))


@pytest.mark.parametrize("seed", range(4))
def test_kernel_matches_sequential(seed):
    rng = np.random.default_rng(400 + seed)
    K = LANE_BLOCK
    # A handful of distinct lane slabs tiled over the block (the kernel is
    # elementwise over lanes; distinct-per-lane content catches cross-lane
    # mixups, full-K distinctness only costs test time).
    n_distinct = 8
    slabs, wksets, seq = [], [], []
    for i in range(n_distinct):
        s = seed_slab(rng)
        wk = random_walkers(rng)
        slabs.append(s)
        wksets.append(wk)
        seq.append(sequential_lane(s, wk))
    reps = K // n_distinct
    slab_K = jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(
            np.tile(np.stack([np.asarray(x) for x in xs]), (reps,) + (1,) * xs[0].ndim)
        ),
        *slabs,
    )
    wk_K = {f: jnp.tile(batch_lanes(wksets, f), (reps,) + (1,) * (batch_lanes(wksets, f).ndim - 1)) for f in wksets[0]}

    new_slab, out_stage, out_off, count = walk_pass_kernel(
        slab_K, wk_K["en"], wk_K["stage"], wk_K["off"], wk_K["ver"],
        wk_K["vlen"], wk_K["is_remove"], wk_K["want_out"],
        max_walk=W, out_base=OUT_BASE, out_rows=OUT_ROWS, interpret=True,
    )

    for i in range(n_distinct):
        exp_slab, exp_st, exp_of, exp_ct = seq[i]
        for rep in (0, reps - 1):
            lane = rep * n_distinct + i
            got = jax.tree_util.tree_map(lambda x: x[lane], new_slab)
            # Sequential pads counters differently only in untouched fields.
            assert_slab_equal(exp_slab, got, f"seed={seed} lane={lane}")
            np.testing.assert_array_equal(
                np.asarray(out_stage[lane]), exp_st,
                err_msg=f"seed={seed} lane={lane} out_stage",
            )
            np.testing.assert_array_equal(
                np.asarray(out_off[lane]), exp_of,
                err_msg=f"seed={seed} lane={lane} out_off",
            )
            np.testing.assert_array_equal(
                np.asarray(count[lane]), exp_ct,
                err_msg=f"seed={seed} lane={lane} count",
            )


def test_kernel_put_phase_matches_sequential():
    """The in-kernel consuming-put phase vs slab.put/put_first applied one
    op at a time in rank order — including the rare branches: slab-full
    drops, pointer-list overflow, mid-rank put_first reset, and chained
    puts with missing predecessors."""
    from kafkastreams_cep_tpu.ops.slab import PutOps

    K = LANE_BLOCK
    PP = 10
    n_distinct = 8
    rng = np.random.default_rng(900)
    lanes = []
    for i in range(n_distinct):
        slab = seed_slab(rng)
        # Tiny slabs/pointer lists so full/pred drops actually fire.
        ops = dict(
            en=rng.random(PP) < 0.8,
            first=rng.random(PP) < 0.4,
            cur_stage=rng.integers(0, 3, size=PP).astype(np.int32),
            prev_stage=rng.integers(0, 3, size=PP).astype(np.int32),
            prev_off=rng.integers(0, 6, size=PP).astype(np.int32),
        )
        vers, vlens = [], []
        for _ in range(PP):
            comps = tuple(rng.integers(1, 3, size=rng.integers(1, 3)))
            v, l = dewey_ops.make(comps, D)
            vers.append(v)
            vlens.append(l)
        ops["ver"] = np.stack(vers).astype(np.int32)
        ops["vlen"] = np.asarray(vlens, np.int32)
        lanes.append((slab, ops))

    ev_off = 9  # current event offset, shared by every put of the step

    def sequential(slab, ops):
        for p in range(PP):
            if not ops["en"][p]:
                continue
            if ops["first"][p]:
                slab = slab_mod.put_first(
                    slab, int(ops["cur_stage"][p]), ev_off,
                    jnp.asarray(ops["ver"][p]), jnp.asarray(ops["vlen"][p]),
                )
            else:
                slab = slab_mod.put(
                    slab, int(ops["cur_stage"][p]), ev_off,
                    int(ops["prev_stage"][p]), int(ops["prev_off"][p]),
                    jnp.asarray(ops["ver"][p]), jnp.asarray(ops["vlen"][p]),
                )
        return slab

    seq = [sequential(s, o) for s, o in lanes]

    reps = K // n_distinct
    tile = lambda arrs: jnp.asarray(
        np.tile(np.stack(arrs), (reps,) + (1,) * arrs[0].ndim)
    )
    slab_K = jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(
            np.tile(np.stack([np.asarray(x) for x in xs]),
                    (reps,) + (1,) * xs[0].ndim)
        ),
        *[s for s, _ in lanes],
    )
    put_ops = PutOps(
        en=tile([o["en"] for _, o in lanes]),
        first=tile([o["first"] for _, o in lanes]),
        cur_stage=tile([o["cur_stage"] for _, o in lanes]),
        prev_stage=tile([o["prev_stage"] for _, o in lanes]),
        prev_off=tile([o["prev_off"] for _, o in lanes]),
        ver=tile([o["ver"] for _, o in lanes]),
        vlen=tile([o["vlen"] for _, o in lanes]),
    )
    # No walkers: the kernel applies only the put phase.
    zeros = jnp.zeros((K, 1), jnp.int32)
    new_slab, _, _, _ = walk_pass_kernel(
        slab_K,
        jnp.zeros((K, 3), bool), jnp.zeros((K, 3), jnp.int32),
        jnp.zeros((K, 3), jnp.int32), jnp.zeros((K, 3, D), jnp.int32),
        jnp.zeros((K, 3), jnp.int32), jnp.zeros((K, 3), bool),
        jnp.zeros((K, 3), bool),
        max_walk=W, out_base=2, out_rows=1, interpret=True,
        put_ops=put_ops, ev_off=jnp.full((K,), ev_off, jnp.int32),
    )
    for i in range(n_distinct):
        for rep in (0, reps - 1):
            lane = rep * n_distinct + i
            got = jax.tree_util.tree_map(lambda x: x[lane], new_slab)
            assert_slab_equal(seq[i], got, f"lane={lane}")


# ---------------------------------------------------------------------------
# The whole batched step: BatchMatcher on the walk kernel vs the jnp engine
# ---------------------------------------------------------------------------


def _x_events(xs, ts_mult=1):
    K_, T = xs.shape
    steps = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K_, T))
    return EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K_, dtype=jnp.int32)[:, None], (K_, T)
        ),
        value={"x": jnp.asarray(xs)},
        ts=steps * ts_mult,
        off=steps,
        valid=jnp.ones((K_, T), bool),
    )


def _typed_float_folds():
    return (
        Query()
        .select("a").where(lambda k, v, ts, st: v["x"] > 0)
        .fold("ema", lambda k, v, curr: 0.5 * curr + 0.25 * v["x"], init=0.0)
        .fold("n", lambda k, v, curr: curr + 1, init=0)
        .then()
        .select("b").skip_till_next_match()
        .where(lambda k, v, ts, st: (st.get("ema") > 0.7) & (st.get("n") >= 1))
        .build()
    )


def _windowed_pair():
    return (
        Query()
        .select("a").where(lambda k, v, ts, st: v["x"] == 1)
        .then()
        .select("b").skip_till_next_match()
        .where(lambda k, v, ts, st: v["x"] == 2)
        .within(5, "ms")
        .build()
    )


def _strict_chain():
    return (
        Query()
        .select("a").where(lambda k, v, ts, st: v["x"] == 1)
        .then()
        .select("b").where(lambda k, v, ts, st: v["x"] == 2)
        .then()
        .select("c").where(lambda k, v, ts, st: v["x"] == 3)
        .build()
    )


_SMALL = dict(
    max_runs=8, slab_entries=24, slab_preds=4, dewey_depth=8, max_walk=8
)


@pytest.mark.parametrize(
    "pattern,extra,seed,high,ts_mult",
    [
        (_typed_float_folds, {}, 11, 6, 1),
        (_windowed_pair, {"enforce_windows": True}, 13, 4, 3),
        (_strict_chain, {}, 17, 5, 1),
    ],
    ids=["typed-float-folds", "enforced-5ms-window", "strict-3-chain"],
)
def test_walk_kernel_matches_jnp_batch(
    monkeypatch, pattern, extra, seed, high, ts_mult
):
    """``BatchMatcher`` with the walk pass on the kernel (interpret mode)
    against the all-jnp engine: every ``StepOutput`` field and every
    engine-state leaf bit-equal after one ``[K, T]`` scan.  States are
    compared through ``canonical_state``: slots the engine never reads
    hold implementation-dependent residue."""
    cfg = EngineConfig(**_SMALL, **extra)
    rng = np.random.default_rng(seed)
    xs = rng.integers(0, high, size=(LANE_BLOCK, 14)).astype(np.int32)
    events = _x_events(xs, ts_mult)
    runs = {}
    for mode in ("0", "interpret"):
        monkeypatch.setenv("CEP_WALK_KERNEL", mode)
        m = BatchMatcher(pattern(), LANE_BLOCK, cfg)
        assert m.uses_walk_kernel == (mode == "interpret")
        runs[mode] = m.scan(m.init_state(), events)
    (st_r, out_r), (st_k, out_k) = runs["0"], runs["interpret"]
    assert int(np.asarray(out_r.count).sum()) > 0  # the trace matches
    for f in StepOutput._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_k, f)), np.asarray(getattr(out_r, f)),
            err_msg=f,
        )
    leaves_k = jax.tree_util.tree_leaves_with_path(canonical_state(st_k))
    leaves_r = jax.tree_util.tree_leaves(canonical_state(st_r))
    assert len(leaves_k) == len(leaves_r)
    for (path, a), b in zip(leaves_k, leaves_r):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# The one device-path decision: _select_walk_kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "mode,backend,lanes,sequential,want,warns",
    [
        ("auto", "cpu", LANE_BLOCK, False, (False, False), False),
        ("auto", "tpu", LANE_BLOCK, False, (True, False), False),
        ("auto", "tpu", LANE_BLOCK + 8, False, (False, False), False),
        ("0", "tpu", LANE_BLOCK, False, (False, False), False),
        ("1", "cpu", LANE_BLOCK, False, (True, False), False),
        ("1", "tpu", LANE_BLOCK + 8, False, (False, False), True),
        ("interpret", "cpu", 2 * LANE_BLOCK, False, (True, True), False),
        ("interpret", "cpu", LANE_BLOCK + 8, False, (False, False), True),
        ("interpret", "cpu", LANE_BLOCK, True, (False, False), True),
    ],
    ids=[
        "auto-cpu-jnp", "auto-tpu-kernel", "auto-tpu-ragged-jnp",
        "off-jnp", "forced-kernel", "forced-ragged-jnp-warns",
        "interpret-kernel", "interpret-ragged-jnp-warns",
        "interpret-sequential-slab-jnp-warns",
    ],
)
def test_select_walk_kernel(
    monkeypatch, caplog, mode, backend, lanes, sequential, want, warns
):
    """``CEP_WALK_KERNEL`` (``auto`` by default) x backend x lane count x
    ``sequential_slab`` -> ``(use_kernel, interpret)``.  An explicit
    request the matcher cannot honour falls back to jnp with a warning;
    ``auto`` falls back silently."""
    monkeypatch.setenv("CEP_WALK_KERNEL", mode)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = EngineConfig(sequential_slab=sequential)
    with caplog.at_level(
        logging.WARNING, logger="kafkastreams_cep_tpu.parallel.batch"
    ):
        assert _select_walk_kernel(cfg, lanes) == want
    warned = [r for r in caplog.records if "CEP_WALK_KERNEL" in r.message]
    assert bool(warned) == warns
