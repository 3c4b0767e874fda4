"""Lazy match extraction (EngineConfig.lazy_extraction) — differential
parity and robustness suites.

The contract (engine/matcher.py):

1. *Match parity*: with a handle ring sized for the trace
   (``handle_overflows == 0``), the drained match set — sequences, event
   offsets, completion order — is identical to the eager engine's, on the
   jnp path and the fused walk-kernel path.
2. *Loss parity*: every pre-existing loss counter is bit-identical to the
   eager engine on loss-free traces, and ``handle_overflows`` preserves
   the all-zero ⇒ loss-free contract (a full ring drops the match and
   counts it — never silent).
3. *Hop accounting*: the W-hop extraction walks move off the per-step
   critical path verbatim — ``extract_hops`` goes to zero and the same
   hops reappear as ``drain_hops`` in the batched drain pass.
4. *Robustness*: pinned handles survive the maintenance sweep
   (mark-sweep roots + version renorm), checkpoint/restore with a
   non-empty ring, and state migration (tests/test_migrate.py).

All kernel runs use interpret mode (CPU CI checks parity, not perf).
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
    MatcherSession,
    TPUMatcher,
)
from kafkastreams_cep_tpu.parallel.batch import BatchMatcher
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

# Loss-free on the traces below (preconditions asserted): the lazy slab
# holds completed chains until drain, so E carries headroom over the
# eager working set.
CFG = EngineConfig(
    max_runs=16, slab_entries=64, slab_preds=8, dewey_depth=12, max_walk=12,
    handle_ring=64,
)
LAZY = dataclasses.replace(CFG, lazy_extraction=True)


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


def eager_matches(out):
    """Eager StepOutput -> per-lane ordered (stage-tuple, off-tuple) lists
    in (t, r) emission order."""
    c = np.asarray(out.count)
    st, of = np.asarray(out.stage), np.asarray(out.off)
    K, T, R = c.shape
    per_lane = []
    for k in range(K):
        rows = []
        for t in range(T):
            for r in range(R):
                n = int(c[k, t, r])
                if n:
                    rows.append(
                        (tuple(st[k, t, r, :n]), tuple(of[k, t, r, :n]))
                    )
        per_lane.append(rows)
    return per_lane


def drained_matches(dout):
    """DrainOutput -> per-lane ordered lists (ring order = completion
    order)."""
    c = np.asarray(dout.count)
    st, of = np.asarray(dout.stage), np.asarray(dout.off)
    K, HB = c.shape
    per_lane = []
    for k in range(K):
        rows = []
        for h in range(HB):
            n = int(c[k, h])
            if n:
                rows.append((tuple(st[k, h, :n]), tuple(of[k, h, :n])))
        per_lane.append(rows)
    return per_lane


def live_keys(slab):
    st, of = np.asarray(slab.stage), np.asarray(slab.off)
    return [
        {(int(s), int(o)) for s, o in zip(st[k], of[k]) if s >= 0}
        for k in range(st.shape[0])
    ]


# ---------------------------------------------------------------------------
# jnp-path differential parity
# ---------------------------------------------------------------------------


def test_lazy_drain_matches_eager_jnp():
    # One matcher pair serves all seeds (compiles dominate CPU CI time).
    K, T = 8, 32
    os.environ["CEP_WALK_KERNEL"] = "0"
    eager = BatchMatcher(stock_demo.stock_pattern(), K, CFG)
    lazy = BatchMatcher(stock_demo.stock_pattern(), K, LAZY)
    for seed in (3, 11, 29):
        events = stock_events(K, T, seed)
        st_e, out_e = eager.scan(eager.init_state(), events)
        st_l, out_l = lazy.scan(lazy.init_state(), events)

        # The lazy scan emits nothing in-step; all ring handles.
        assert int(jnp.sum(out_l.count)) == 0, seed
        assert int(jnp.sum(st_l.hr_count)) > 0, seed
        st_l, dout = lazy.drain(st_l)
        assert int(jnp.sum(st_l.hr_count)) == 0, seed  # drain clears

        # Match parity: identical sequences in completion order.
        assert eager_matches(out_e) == drained_matches(dout), seed
        # Loss parity: bit-identical counters, handle_overflows zero.
        assert eager.counters(st_e) == lazy.counters(st_l), seed
        assert lazy.counters(st_l)["handle_overflows"] == 0, seed
        # Hop accounting: extraction hops moved verbatim to the drain.
        we, wl = eager.walk_counters(st_e), lazy.walk_counters(st_l)
        assert we["extract_hops"] > 0 and we["drain_hops"] == 0, seed
        assert wl["extract_hops"] == 0, seed
        assert wl["drain_hops"] == we["extract_hops"], seed
        assert wl["walk_hops"] == we["walk_hops"], seed
        # Slab content parity (placement may differ — two-tier claim).
        assert live_keys(st_e.slab) == live_keys(st_l.slab), seed


@pytest.mark.parametrize(
    "pattern,codes",
    [
        # skip_till_any exercises the richest walker mix tier-1; the
        # strict/kleene variants ride the slow marker (compile-bound).
        (sc.skip_till_any, [0, 4, 1, 2, 4, 2, 3, 1, 2, 3]),
        pytest.param(
            sc.strict3, [0, 1, 2, 0, 1, 2, 4, 0, 1, 2],
            marks=pytest.mark.slow,
        ),
        pytest.param(
            sc.kleene_one_or_more, [0, 1, 2, 2, 3, 0, 1, 2, 3, 4],
            marks=pytest.mark.slow,
        ),
    ],
)
def test_lazy_session_matches_eager_per_event(pattern, codes):
    """MatcherSession drains per event, so the oracle-style match() API
    returns identical matches at identical events under both modes."""
    eager = MatcherSession(TPUMatcher(pattern(), CFG))
    lazy = MatcherSession(TPUMatcher(pattern(), LAZY))
    for t, v in enumerate(codes):
        me = eager.match(None, v, 10 * t, offset=t)
        ml = lazy.match(None, v, 10 * t, offset=t)
        assert [m.as_map() for m in me] == [m.as_map() for m in ml], t
    ce, cl = eager.counters(), lazy.counters()
    assert ce == cl


@pytest.mark.slow
def test_lazy_sequential_slab_matches_batched():
    """sequential_slab=True (the reference's literal op order) under lazy
    extraction: identical handles, identical drained matches."""
    K, T = 4, 24
    events = stock_events(K, T, 17)
    os.environ["CEP_WALK_KERNEL"] = "0"
    bat = BatchMatcher(stock_demo.stock_pattern(), K, LAZY)
    seq = BatchMatcher(
        stock_demo.stock_pattern(), K,
        dataclasses.replace(LAZY, sequential_slab=True),
    )
    st_b, _ = bat.scan(bat.init_state(), events)
    st_q, _ = seq.scan(seq.init_state(), events)
    np.testing.assert_array_equal(
        np.asarray(st_b.hr_count), np.asarray(st_q.hr_count)
    )
    st_b, d_b = bat.drain(st_b)
    st_q, d_q = seq.drain(st_q)
    assert drained_matches(d_b) == drained_matches(d_q)
    assert bat.counters(st_b) == seq.counters(st_q)


def test_stacked_bank_lazy_drain():
    """One drain pass serves every member of a stacked bank (the drain is
    table-free): drained matches equal the eager stacked outputs."""
    from kafkastreams_cep_tpu.parallel.stacked import StackedBankMatcher

    def q(i):
        lo, hi = 95 + i * 5, 120 - i * 3
        from kafkastreams_cep_tpu import Query

        return (
            Query()
            .select("a").where(lambda k, v, ts, st, lo=lo: v["price"] < lo)
            .then()
            .select("b").skip_till_next_match()
            .where(lambda k, v, ts, st, hi=hi: v["price"] > hi)
            .build()
        )

    K, T = 4, 16
    rng = np.random.default_rng(13)
    prices = rng.integers(80, 141, size=(K, T)).astype(np.int32)
    events = EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)
        ),
        value={"price": jnp.asarray(prices)},
        ts=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)
        ),
        off=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)
        ),
        valid=jnp.ones((K, T), bool),
    )
    os.environ["CEP_WALK_KERNEL"] = "0"
    cfg = EngineConfig(
        max_runs=8, slab_entries=32, slab_preds=4, dewey_depth=8,
        max_walk=8, handle_ring=32,
    )
    patterns = [q(0), q(1)]
    eager = StackedBankMatcher(patterns, K, cfg)
    st_e, out_e = eager.scan(eager.init_state(), events)
    lazy = StackedBankMatcher(
        patterns, K, dataclasses.replace(cfg, lazy_extraction=True)
    )
    st_l, _ = lazy.scan(lazy.init_state(), events)
    st_l, dout = lazy.drain(st_l)
    # out_e is [Q, K, T, R, W]; dout is [Q*K, HB, ...] (query-major).
    Q = len(patterns)
    flat_eager = eager_matches(
        type(out_e)(*[
            np.asarray(x).reshape((Q * K,) + x.shape[2:]) for x in out_e
        ])
    )
    assert flat_eager == drained_matches(dout)
    assert eager.counters(st_e) == lazy.counters(st_l)
    """A ring too small for the trace drops matches — counted, never
    silent (the all-zero ⇒ loss-free contract)."""
    K, T = 4, 32
    events = stock_events(K, T, 5)
    os.environ["CEP_WALK_KERNEL"] = "0"
    tiny = dataclasses.replace(LAZY, handle_ring=8)
    eager = BatchMatcher(stock_demo.stock_pattern(), K, CFG)
    st_e, out_e = eager.scan(eager.init_state(), events)
    lazy = BatchMatcher(stock_demo.stock_pattern(), K, tiny)
    st_l, _ = lazy.scan(lazy.init_state(), events)
    st_l, dout = lazy.drain(st_l)
    ovf = lazy.counters(st_l)["handle_overflows"]
    assert ovf > 0
    n_eager = sum(len(r) for r in eager_matches(out_e))
    n_lazy = sum(len(r) for r in drained_matches(dout))
    assert n_lazy < n_eager  # the dropped matches are really gone…
    assert n_lazy + ovf >= n_eager  # …and every loss was counted


def test_sweep_preserves_pinned_handles():
    """The maintenance sweep (mark-sweep + version renorm) must not
    reclaim a pinned-but-undrained chain: handles are liveness roots and
    their versions renormalize with the slab's."""
    K, T = 4, 24
    events = stock_events(K, T, 13)
    os.environ["CEP_WALK_KERNEL"] = "0"
    lazy = BatchMatcher(stock_demo.stock_pattern(), K, LAZY)
    st, _ = lazy.scan(lazy.init_state(), events)
    assert int(jnp.sum(st.hr_count)) > 0
    st = jax.device_get(st)  # host: the drain and the sweep each consume
    _, want = lazy.drain(jax.device_put(st))  # reference drain, no sweep
    swept = lazy.sweep(jax.device_put(st))  # sweep WITH pending handles
    _, got = lazy.drain(swept)
    assert drained_matches(want) == drained_matches(got)


# ---------------------------------------------------------------------------
# Kernel-path parity (interpret mode)
# ---------------------------------------------------------------------------

PRESSURE_LAZY = EngineConfig(
    max_runs=8, slab_entries=16, slab_hot_entries=8, slab_preds=4,
    dewey_depth=8, max_walk=8, lazy_extraction=True, handle_ring=32,
)

SLAB_FIELDS = (
    "stage", "off", "refs", "npreds", "full_drops", "pred_drops",
    "missing", "trunc", "hot_hits", "hot_misses", "overflow_walks",
    "demotions", "walk_hops", "extract_hops", "drain_hops",
)


def assert_lazy_same_run(ref, st_r, d_r, krn, st_k, d_k):
    for f in d_r._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(d_r, f)), np.asarray(getattr(d_k, f)),
            err_msg=f"drain.{f}",
        )
    for f in SLAB_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_r.slab, f)),
            np.asarray(getattr(st_k.slab, f)), err_msg=f"slab.{f}",
        )
    assert ref.counters(st_r) == krn.counters(st_k)
    assert ref.hot_counters(st_r) == krn.hot_counters(st_k)
    assert ref.walk_counters(st_r) == krn.walk_counters(st_k)


def test_walk_kernel_lazy_parity_under_pressure():
    K, T = 128, 12
    events = stock_events(K, T, 21)
    os.environ["CEP_WALK_KERNEL"] = "0"
    ref = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_LAZY)
    st_r, _ = ref.scan(ref.init_state(), events)
    st_r, d_r = ref.drain(st_r)
    os.environ["CEP_WALK_KERNEL"] = "interpret"
    try:
        krn = BatchMatcher(stock_demo.stock_pattern(), K, PRESSURE_LAZY)
        assert krn.uses_walk_kernel
        st_k, _ = krn.scan(krn.init_state(), events)
        st_k, d_k = krn.drain(st_k)  # kernel drain path
    finally:
        os.environ["CEP_WALK_KERNEL"] = "0"
    assert_lazy_same_run(ref, st_r, d_r, krn, st_k, d_k)
    assert ref.hot_counters(st_r)["slab_demotions"] > 0
    assert ref.walk_counters(st_r)["drain_hops"] > 0


# ---------------------------------------------------------------------------
# Acceptance: the perf model, measured on CPU (platform-independent)
# ---------------------------------------------------------------------------


def _hit_rate(hot):
    hops = hot["slab_hot_hits"] + hot["slab_hot_misses"]
    return hot["slab_hot_hits"] / hops if hops else 1.0


def test_lazy_takes_extraction_off_the_step_critical_path():
    """The acceptance measurement (CPU; hop counts/rates are
    platform-independent): headline shapes with the slab sized loss-free
    for the match-dense stock trace at E_hot=16, drained at the
    processor's cadence.  Pins what PROFILE_r07.md records:

    * per-step device walk hops drop >= 40% (measured ~50% — extraction
      was ~half the step's hop budget and moves to the drain verbatim);
    * the moved hops are conserved: ``drain_hops`` equals the eager
      engine's ``extract_hops`` exactly;
    * matches and every loss counter are bit-identical.

    The ISSUE's companion hypothesis — that the step-phase hot-hit rate
    rises toward ~1.0 — measured FALSE on this trace: the remaining
    branch/dead walkers start at run *pointer* events (older than the hot
    window) and skip the extraction walks' hot head-of-chain hops, so the
    residual step mix is slightly colder (~0.31 vs ~0.44).  The ~1.0
    regime claim is pinned where it actually holds, on short-walk traces
    (strict3, test below), and PROFILE_r07.md names the residual deep
    walkers as the next leverage.
    """
    K, T, CH = 4, 128, 16
    events = stock_events(K, T, 42)
    os.environ["CEP_WALK_KERNEL"] = "0"
    shapes = dict(
        max_runs=24, slab_entries=96, slab_preds=8, dewey_depth=12,
        max_walk=12, slab_hot_entries=16,
    )

    def chunks(ev):
        for t0 in range(0, T, CH):
            yield jax.tree_util.tree_map(lambda x: x[:, t0:t0 + CH], ev)

    eager = BatchMatcher(
        stock_demo.stock_pattern(), K, EngineConfig(**shapes)
    )
    st_e, n_e = eager.init_state(), 0
    for c in chunks(events):
        st_e, out = eager.scan(st_e, c)
        n_e += int(jnp.sum(out.count > 0))
        st_e = eager.sweep(st_e)

    lazy = BatchMatcher(
        stock_demo.stock_pattern(), K,
        EngineConfig(**shapes, lazy_extraction=True, handle_ring=512),
    )
    st_l, n_l, hh, hm = lazy.init_state(), 0, 0, 0
    for c in chunks(events):
        pre = lazy.hot_counters(st_l)
        st_l, _ = lazy.scan(st_l, c)
        post = lazy.hot_counters(st_l)
        hh += post["slab_hot_hits"] - pre["slab_hot_hits"]
        hm += post["slab_hot_misses"] - pre["slab_hot_misses"]
        st_l, d = lazy.drain(st_l)
        n_l += int(jnp.sum(d.count > 0))
        st_l = lazy.sweep(st_l)

    # Parity first — the perf numbers mean nothing without it.
    assert n_e == n_l and n_e > 0
    assert eager.counters(st_e) == lazy.counters(st_l)
    assert lazy.counters(st_l)["handle_overflows"] == 0

    we, wl = eager.walk_counters(st_e), lazy.walk_counters(st_l)
    step_hops_eager = we["walk_hops"] + we["extract_hops"]
    step_hops_lazy = wl["walk_hops"] + wl["extract_hops"]
    reduction = 1 - step_hops_lazy / step_hops_eager
    assert reduction >= 0.40, (we, wl)
    # Conservation: the extraction work moved, it did not disappear.
    assert wl["extract_hops"] == 0
    assert wl["drain_hops"] == we["extract_hops"]
    assert wl["walk_hops"] == we["walk_hops"]
    # The measured step-phase rate delta PROFILE_r07 documents.
    rate_eager = _hit_rate(eager.hot_counters(st_e))
    rate_lazy = hh / (hh + hm)
    assert 0.3 < rate_eager < 0.7, rate_eager  # adversarial baseline
    assert rate_lazy > rate_eager - 0.2, (rate_eager, rate_lazy)


def test_lazy_keeps_short_walk_traces_in_the_hot_regime():
    """strict3 (PROFILE_r06: hot-hit rate 1.000 at E_hot=16): lazy
    extraction must keep the 1.0 step rate AND still move its extraction
    hops to the drain pass."""
    rng = np.random.default_rng(9)
    K, T = 8, 64
    codes = rng.integers(0, 5, size=(K, T)).astype(np.int32)
    events = EventBatch(
        key=jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)
        ),
        value=jnp.asarray(codes),
        ts=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)
        ),
        off=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)
        ),
        valid=jnp.ones((K, T), bool),
    )
    os.environ["CEP_WALK_KERNEL"] = "0"
    shapes = dict(
        max_runs=16, slab_entries=64, slab_hot_entries=16, slab_preds=8,
        dewey_depth=8, max_walk=8,
    )
    eager = BatchMatcher(sc.strict3(), K, EngineConfig(**shapes))
    st_e, out_e = eager.scan(eager.init_state(), events)
    lazy = BatchMatcher(
        sc.strict3(), K,
        EngineConfig(**shapes, lazy_extraction=True, handle_ring=64),
    )
    st_l, _ = lazy.scan(lazy.init_state(), events)
    rate_step = _hit_rate(lazy.hot_counters(st_l))  # before drain hops
    st_l, dout = lazy.drain(st_l)
    assert eager_matches(out_e) == drained_matches(dout)
    assert rate_step == 1.0
    we, wl = eager.walk_counters(st_e), lazy.walk_counters(st_l)
    if we["extract_hops"]:
        assert wl["extract_hops"] == 0
        assert wl["drain_hops"] == we["extract_hops"]


# ---------------------------------------------------------------------------
# Processor / runtime integration
# ---------------------------------------------------------------------------


def _mk_batches(n_batches, n, K, seed):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        keys = rng.integers(0, K, size=n)
        prices = rng.integers(90, 131, size=n)
        vols = rng.integers(600, 1101, size=n)
        out.append(
            [
                Record(
                    int(keys[i]),
                    {"price": int(prices[i]), "volume": int(vols[i])},
                    b * n + i,
                )
                for i in range(n)
            ]
        )
    return out


def _canon(ms):
    return [
        (
            k,
            tuple(
                (s, tuple(e.offset for e in evs))
                for s, evs in m.as_map().items()
            ),
        )
        for k, m in ms
    ]


BIG = EngineConfig(
    max_runs=32, slab_entries=128, slab_preds=16, dewey_depth=24,
    max_walk=16, handle_ring=256,
)
BIG_LAZY = dataclasses.replace(BIG, lazy_extraction=True)


def _run_proc(config, batches, K, **kw):
    proc = CEPProcessor(
        stock_demo.stock_pattern(), K, config, epoch=0, **kw
    )
    out = []
    for b in batches:
        out += proc.process(b)
    out += proc.flush()
    return proc, out


@pytest.mark.slow
def test_processor_lazy_emission_order_parity():
    # Tier-2 (-m slow, ~34 s): test_lazy_drain_matches_eager_jnp and
    # the pressure-parity pair keep lazy-vs-eager coverage in tier-1
    # (ROADMAP tier-1 budget note, PR 13).
    os.environ["CEP_WALK_KERNEL"] = "0"
    K = 4
    batches = _mk_batches(4, 64, K, 7)
    pe, me = _run_proc(BIG, batches, K)
    pl, ml = _run_proc(BIG_LAZY, batches, K)
    # Bit-identical counters (the shared drops are identical too) and
    # identical matches in identical order.
    assert pe.counters() == pl.counters()
    assert _canon(me) == _canon(ml)  # content AND order
    # Pipelined mode: same matches, one call later.  (Deferred drain
    # cadence is covered by test_checkpoint_restore_with_pending_handles
    # at drain_interval=4.)
    _, mp = _run_proc(BIG_LAZY, batches, K, pipeline=True)
    assert _canon(mp) == _canon(me)


def test_checkpoint_restore_with_pending_handles(tmp_path):
    """A checkpoint taken between match completion and drain carries the
    ring; the restored processor drains it to the identical matches."""
    from kafkastreams_cep_tpu.runtime.checkpoint import (
        restore_processor,
        save_checkpoint,
    )

    os.environ["CEP_WALK_KERNEL"] = "0"
    K = 4
    batches = _mk_batches(3, 32, K, 19)
    # Reference: one continuous lazy processor.
    _, want = _run_proc(BIG_LAZY, batches, K, drain_interval=4)

    proc = CEPProcessor(
        stock_demo.stock_pattern(), K, BIG_LAZY, epoch=0, drain_interval=4
    )
    got = []
    for b in batches[:2]:
        got += proc.process(b)
    assert int(jnp.sum(proc.state.hr_count)) > 0  # non-empty ring
    path = str(tmp_path / "ring.ckpt")
    save_checkpoint(proc, path)
    restored = restore_processor(stock_demo.stock_pattern(), path)
    assert int(jnp.sum(restored.state.hr_count)) > 0  # ring survived
    got += restored.process(batches[2])
    got += restored.flush()
    assert sorted(_canon(got)) == sorted(_canon(want))


def test_probe_and_suggest_size_the_ring():
    from kafkastreams_cep_tpu.compiler.tables import lower
    from kafkastreams_cep_tpu.engine import probe, suggest

    os.environ["CEP_WALK_KERNEL"] = "0"
    K, T = 4, 24
    events = stock_events(K, T, 3)
    report = probe(stock_demo.stock_pattern(), events, BIG, sweep_every=12)
    assert report.max_matches_chunk > 0
    cfg = suggest(lower(stock_demo.stock_pattern()), report)
    assert cfg.handle_ring >= 8 and cfg.handle_ring % 8 == 0
    # The derived ring is loss-free at the probed cadence, by construction.
    lazy_cfg = dataclasses.replace(
        cfg, lazy_extraction=True,
        slab_entries=max(cfg.slab_entries, 2 * report.max_live_entries),
    )
    lazy_report = probe(
        stock_demo.stock_pattern(), events, lazy_cfg, sweep_every=16
    )
    assert lazy_report.counters["handle_overflows"] == 0


def test_escalation_grows_the_ring():
    from kafkastreams_cep_tpu.engine import escalate

    grown = escalate(LAZY, {"handle_overflows": 5})
    assert grown is not None and grown.handle_ring > LAZY.handle_ring
