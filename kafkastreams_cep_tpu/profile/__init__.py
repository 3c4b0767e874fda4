"""Programmatic profiler CLI — ``python -m kafkastreams_cep_tpu.profile``.

Folds the three hand-run profiling scripts (``profile_step.py``,
``profile_phases.py``, ``profile_ablate.py`` — kept as thin wrappers at
the repo root) into one entry point that emits **structured PROFILE
JSON**: exactly one JSON object on stdout, all diagnostics on stderr, so
the PROFILE_r0x reports and the bench regression gate can consume
profiler output programmatically instead of scraping logs.

Subcommands
-----------

``step``         K-scaling of the headline scan (flat step time ⇒
                 dispatch/op-count bound, linear ⇒ bandwidth bound).
``phases``       standalone batched slab-kernel timings with XLA
                 bytes/flops estimates (out-of-context — see ``ablate``).
``ablate``       the in-context ablation (chain → +puts → +branch →
                 +walks), each variant in its own process.
``selectivity``  the continuous-profiling readout (ISSUE 6): per-stage
                 selectivity & cost (``EngineConfig.stage_attribution``),
                 per-key heavy hitters, and the measured A/B overhead of
                 attribution on the same trace — the numbers PROFILE_r08
                 records and the ≤3 %-overhead acceptance bound checks.
``latency``      end-to-end latency attribution (ISSUE 18): drives a
                 ledger-instrumented ``CEPProcessor`` over synthetic
                 stock batches and reports per-segment percentiles
                 (reorder_hold/queue/device/drain_defer/e2e_total), SLO
                 burn, XLA ``cost_analysis()`` device-time attribution
                 for the compiled scan, and (``--trace-dir``) an
                 optional ``jax.profiler`` trace capture.

Every subcommand accepts ``--k/--t/--reps`` size knobs, so the tier-1
smoke test can drive tiny shapes on CI (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _stock_pattern():
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
            "examples",
        ),
    )
    import stock_demo

    return stock_demo.stock_pattern()


def _stock_events(K: int, T: int, seed: int = 42):
    import jax.numpy as jnp
    import numpy as np

    from kafkastreams_cep_tpu.engine import EventBatch

    rng = np.random.default_rng(seed)
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    volumes = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    return EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(volumes)},
        ts=jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)
        ),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )


def _timed_scan(batch, state0, events, reps: int):
    """(best seconds, compile seconds) of ``batch.scan`` on ``events``."""
    import jax

    t0 = time.perf_counter()
    state, out = batch.scan(state0, events)
    jax.block_until_ready(out.count)
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        state, out = batch.scan(state0, events)
        jax.block_until_ready(out.count)
        best = min(best, time.perf_counter() - t0)
    return best, compile_s, state


# ---------------------------------------------------------------------------
# step — K-scaling (port of profile_step.py)
# ---------------------------------------------------------------------------


def run_step(args) -> Dict[str, Any]:
    from kafkastreams_cep_tpu.engine import EngineConfig
    from kafkastreams_cep_tpu.parallel import BatchMatcher

    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    pattern = _stock_pattern()
    ks = [int(x) for x in args.k.split(",")]
    T = args.t
    points: List[Dict[str, Any]] = []
    for K in ks:
        batch = BatchMatcher(pattern, K, cfg)
        events = _stock_events(K, T)
        best, comp, _ = _timed_scan(batch, batch.init_state(), events,
                                    args.reps)
        pt = {
            "k": K,
            "t": T,
            "scan_ms": round(best * 1e3, 3),
            "ms_per_step": round(best / T * 1e3, 4),
            "evps": round(K * T / best, 1),
            "compile_s": round(comp, 2),
        }
        points.append(pt)
        _log(
            f"K={K:6d} T={T}: scan {pt['scan_ms']:8.1f} ms "
            f"({pt['ms_per_step']:6.2f} ms/step, {pt['evps'] / 1e3:8.0f}K "
            f"ev/s) [compile {comp:.0f}s]"
        )
    return {"profile": "step", "points": points}


# ---------------------------------------------------------------------------
# phases — standalone slab kernels (port of profile_phases.py)
# ---------------------------------------------------------------------------


def run_phases(args) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafkastreams_cep_tpu.ops import slab as slab_mod

    K = args.k if isinstance(args.k, int) else int(args.k.split(",")[0])
    R, E, MP, D, W = 24, 48, 8, 12, 12
    H = 2
    RH, PW = R * H, 3 * R
    rng = np.random.default_rng(0)
    i32 = jnp.int32

    def mk_slab():
        # Random content over a make()-shaped slab (internally inconsistent
        # — see `ablate` for in-context numbers); building on make() keeps
        # this in sync with SlabState's counter fields.
        one = slab_mod.make(E, MP, D)
        base = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (K,) + x.shape), one
        )
        n_live = E // 2
        stage = np.full((K, E), -1, np.int32)
        stage[:, :n_live] = rng.integers(0, 4, (K, n_live))
        off = np.full((K, E), -1, np.int32)
        off[:, :n_live] = rng.integers(0, 100, (K, n_live))
        return base._replace(
            stage=jnp.asarray(stage),
            off=jnp.asarray(off),
            refs=jnp.asarray(rng.integers(0, 3, (K, E)), i32),
            npreds=jnp.asarray(rng.integers(0, MP, (K, E)), i32),
            pstage=jnp.asarray(rng.integers(-1, 4, (K, E, MP)), i32),
            poff=jnp.asarray(rng.integers(0, 100, (K, E, MP)), i32),
            pver=jnp.asarray(rng.integers(0, 3, (K, E, MP, D)), i32),
            pvlen=jnp.asarray(rng.integers(1, 4, (K, E, MP)), i32),
        )

    results: Dict[str, Any] = {}

    def bench(name, fn, *fargs):
        jfn = jax.jit(fn)
        ca = {}
        try:
            comp = jfn.lower(*fargs).compile()
            c = comp.cost_analysis()
            if isinstance(c, list):
                c = c[0]
            ca = c or {}
        except Exception:
            pass
        out = jfn(*fargs)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(args.reps, 1)):
            t0 = time.perf_counter()
            out = jfn(*fargs)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        row = {
            "ms": round(best * 1e3, 3),
            "bytes_accessed": ca.get("bytes accessed", 0),
            "flops": ca.get("flops", 0),
        }
        results[name] = row
        _log(
            f"{name:16s}: {best * 1e3:7.2f} ms   "
            f"bytes={row['bytes_accessed']:.2e} flops={row['flops']:.2e}"
        )

    slab = mk_slab()
    off = jnp.asarray(rng.integers(100, 200, (K,)), i32)
    ops = slab_mod.PutOps(
        en=jnp.asarray(rng.random((K, RH)) < 0.1),
        first=jnp.asarray(rng.random((K, RH)) < 0.3),
        cur_stage=jnp.asarray(rng.integers(0, 4, (K, RH)), i32),
        prev_stage=jnp.asarray(rng.integers(-1, 4, (K, RH)), i32),
        prev_off=jnp.asarray(rng.integers(0, 100, (K, RH)), i32),
        ver=jnp.asarray(rng.integers(0, 3, (K, RH, D)), i32),
        vlen=jnp.asarray(rng.integers(1, 4, (K, RH)), i32),
    )
    bench(
        "puts_batched",
        jax.vmap(lambda s, o, f: slab_mod.puts_batched(s, o, f)),
        slab, ops, off,
    )

    en_b = jnp.asarray(rng.random((K, R)) < 0.15)
    st_b = jnp.asarray(rng.integers(0, 4, (K, R)), i32)
    off_b = jnp.asarray(rng.integers(0, 100, (K, R)), i32)
    ver_b = jnp.asarray(rng.integers(0, 3, (K, R, D)), i32)
    vlen_b = jnp.asarray(rng.integers(1, 4, (K, R)), i32)
    bench(
        "branch_batched",
        jax.vmap(
            lambda s, e, st, o, v, vl: slab_mod.branch_batched(
                s, e, st, o, v, vl, W
            )
        ),
        slab, en_b, st_b, off_b, ver_b, vlen_b,
    )

    en_w = jnp.asarray(rng.random((K, PW)) < 0.15)
    st_w = jnp.asarray(rng.integers(0, 4, (K, PW)), i32)
    off_w = jnp.asarray(rng.integers(0, 100, (K, PW)), i32)
    ver_w = jnp.asarray(rng.integers(0, 3, (K, PW, D)), i32)
    vlen_w = jnp.asarray(rng.integers(1, 4, (K, PW)), i32)
    is_rm = jnp.concatenate(
        [jnp.zeros((K, R), bool), jnp.ones((K, 2 * R), bool)], axis=1
    )
    want = jnp.concatenate(
        [jnp.zeros((K, 2 * R), bool), jnp.ones((K, R), bool)], axis=1
    )
    bench(
        "walks_batched",
        jax.vmap(
            lambda s, e, st, o, v, vl, ir, wo: slab_mod.walks_batched(
                s, e, st, o, v, vl, ir, wo, W
            )
        ),
        slab, en_w, st_w, off_w, ver_w, vlen_w, is_rm, want,
    )
    gate = _measure_dispatch_gate(K, args.t, args.reps)
    return {
        "profile": "phases", "k": K, "kernels": results,
        "dispatch_gate": gate,
    }


def _measure_dispatch_gate(K: int, T: int, reps: int) -> Dict[str, Any]:
    """Measured chunk-gate elision (ISSUE 18 satellite): scan a tiered
    matcher and read back the PR 10 ``gate_chunks`` / ``nfa_dispatches``
    dispatch accounting as a fraction.  On a chunk-gated hybrid plan the
    fraction is NFA chunks actually dispatched over chunks offered
    (< 1.0 means the gate elided work); on whole-batch plans (pure NFA,
    stencil, whole-scan kernel) ``gate_chunks`` stays 0 and the fraction
    falls back to dispatches per scan call.  The stock pattern plans
    pure-NFA (no strict prefix), so this uses a strict-prefix + Kleene
    shape that plans hybrid, over a sparse trace where most chunks
    promote nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kafkastreams_cep_tpu import Query
    from kafkastreams_cep_tpu.engine import EngineConfig, EventBatch
    from kafkastreams_cep_tpu.parallel.tiered import TieredBatchMatcher

    def val(code):
        return lambda k, v, ts, st: v == code

    pattern = (
        Query()
        .select("a").where(val(0))
        .then()
        .select("b").where(val(1))
        .then()
        .select("c").one_or_more().where(val(2))
        .then()
        .select("d").where(val(3))
        .build()
    )
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12, tiering=True,
    )
    batch = TieredBatchMatcher(pattern, K, cfg)
    # Noise everywhere, a full a,b,c,d match planted at the head of every
    # OTHER gate_chunk-sized segment: promoting chunks must dispatch,
    # quiet chunks must be elided, so the measured fraction sits mid-range
    # by construction (~0.5) instead of degenerating to 0 or 1.
    C = max(int(cfg.gate_chunk), 1)
    vals = np.full((K, T), 4, np.int32)
    for c0 in range(0, T, 2 * C):
        if c0 + 4 <= T:
            vals[:, c0:c0 + 4] = np.array([0, 1, 2, 3], np.int32)
    i32 = jnp.int32
    events = EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=i32)[:, None], (K, T)),
        value=jnp.asarray(vals),
        ts=jnp.broadcast_to(jnp.arange(T, dtype=i32)[None, :] * 2, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=i32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )
    state = batch.init_state()
    out = None
    for _ in range(max(reps, 1)):
        state, out = batch.scan(state, events)
    jax.block_until_ready(jax.tree_util.tree_leaves(out))
    calls = int(batch.scan_calls)
    chunks = int(batch.gate_chunks)
    dispatches = int(batch.nfa_dispatches)  # the one host sync
    denom = chunks if chunks else calls
    row = {
        "tier": str(batch.plan.tier),
        "scan_calls": calls,
        "gate_chunks": chunks,
        "nfa_dispatches": dispatches,
        "nfa_dispatch_fraction": (
            round(dispatches / denom, 4) if denom else None
        ),
    }
    _log(
        f"dispatch_gate: tier={row['tier']} chunks={chunks} "
        f"nfa_dispatches={dispatches} fraction={row['nfa_dispatch_fraction']}"
    )
    return row


# ---------------------------------------------------------------------------
# ablate — in-context ablation (port of profile_ablate.py)
# ---------------------------------------------------------------------------

_ABLATE_VARIANTS = ("A", "B", "C", "D")


def _run_ablate_variant(which: str, K: int, T: int, reps: int) -> float:
    import jax
    import jax.numpy as jnp

    from kafkastreams_cep_tpu.engine import EngineConfig
    from kafkastreams_cep_tpu.ops import slab as slab_mod
    from kafkastreams_cep_tpu.parallel import BatchMatcher

    real = {
        "puts": slab_mod.puts_batched,
        "branch": slab_mod.branch_batched,
        "walks": slab_mod.walks_batched,
    }

    def noop_puts(slab, ops, off, **kw):
        return slab

    def noop_branch(slab, en, stage, off, ver, vlen, max_walk, **kw):
        return slab

    def noop_walks(slab, en, stage, off, ver, vlen, is_remove, want_out,
                   max_walk, collect=True, **kw):
        P = jnp.asarray(stage).shape[0]
        i32 = jnp.int32
        return (
            slab,
            jnp.full((P, max_walk), -1, i32),
            jnp.full((P, max_walk), -1, i32),
            jnp.zeros((P,), i32),
        )

    patch = {
        "A": {"puts": noop_puts, "branch": noop_branch, "walks": noop_walks},
        "B": {"puts": "real", "branch": noop_branch, "walks": noop_walks},
        "C": {"puts": "real", "branch": "real", "walks": noop_walks},
        "D": {"puts": "real", "branch": "real", "walks": "real"},
    }[which]
    for k, v in patch.items():
        setattr(slab_mod, k + "_batched", real[k] if v == "real" else v)
    try:
        cfg = EngineConfig(
            max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
            max_walk=12,
        )
        batch = BatchMatcher(_stock_pattern(), K, cfg)
        events = _stock_events(K, T)
        best, comp, _ = _timed_scan(batch, batch.init_state(), events, reps)
        _log(f"ablate[{which}]: best {best * 1e3:.1f} ms (compile {comp:.1f}s)")
        return best
    finally:
        for k, fn in real.items():
            setattr(slab_mod, k + "_batched", fn)


def run_ablate(args) -> Dict[str, Any]:
    K = args.k if isinstance(args.k, int) else int(args.k.split(",")[0])
    T = args.t
    if args.variant:
        best = _run_ablate_variant(args.variant, K, T, args.reps)
        return {"profile": "ablate-variant", "variant": args.variant,
                "best_s": best}
    # Each variant in its own process (four matchers + executables do not
    # share HBM on a real chip; also isolates the monkeypatch).  The
    # children run one at a time, and this parent never initializes a JAX
    # backend (only config updates and imports reach it), so each child
    # gets the chip to itself.
    import subprocess

    results: Dict[str, float] = {}
    for v in _ABLATE_VARIANTS:
        cmd = [
            sys.executable, "-m", "kafkastreams_cep_tpu.profile", "ablate",
            "--variant", v, "--k", str(K), "--t", str(T),
            "--reps", str(args.reps),
        ]
        out = subprocess.run(cmd, capture_output=True, text=True)
        for line in out.stderr.splitlines():
            if "WARNING" not in line:
                _log(line)
        try:
            doc = json.loads(out.stdout.strip().splitlines()[-1])
            results[v] = float(doc["best_s"])
        except Exception:
            _log(f"ablate[{v}]: no result (rc={out.returncode})")
    if len(results) < 4:
        return {"profile": "ablate", "error": "incomplete", "raw": results}
    a, b, c, d = (results[v] for v in _ABLATE_VARIANTS)
    per_step = lambda t: round(t / T * 1e3, 3)
    breakdown = {
        "chain_compaction": {"ms_per_step": per_step(a),
                             "share": round(a / d, 4)},
        "puts_batched": {"ms_per_step": per_step(b - a),
                         "share": round((b - a) / d, 4)},
        "branch_walks": {"ms_per_step": per_step(c - b),
                         "share": round((c - b) / d, 4)},
        "walks_batched": {"ms_per_step": per_step(d - c),
                          "share": round((d - c) / d, 4)},
    }
    _log(f"ablation K={K} T={T}: total {per_step(d):.2f} ms/step")
    return {
        "profile": "ablate", "k": K, "t": T,
        "total_ms_per_step": per_step(d), "breakdown": breakdown,
    }


# ---------------------------------------------------------------------------
# selectivity — the continuous-profiling readout (ISSUE 6)
# ---------------------------------------------------------------------------


def run_selectivity(args) -> Dict[str, Any]:
    import dataclasses

    import numpy as np

    from kafkastreams_cep_tpu.engine import EngineConfig
    from kafkastreams_cep_tpu.engine.matcher import per_lane_counter_arrays
    from kafkastreams_cep_tpu.parallel import BatchMatcher

    K = args.k if isinstance(args.k, int) else int(args.k.split(",")[0])
    T = args.t
    pattern = _stock_pattern()
    base = EngineConfig(
        max_runs=args.runs, slab_entries=args.slab, slab_preds=8,
        dewey_depth=12, max_walk=12,
    )
    events = _stock_events(K, T, seed=args.seed)

    off_b = BatchMatcher(pattern, K, base)
    best_off, comp_off, _ = _timed_scan(
        off_b, off_b.init_state(), events, args.reps
    )
    on_cfg = dataclasses.replace(base, stage_attribution=True)
    on_b = BatchMatcher(pattern, K, on_cfg)
    best_on, comp_on, state = _timed_scan(
        on_b, on_b.init_state(), events, args.reps
    )
    overhead = (best_on - best_off) / best_off * 100.0

    # Per-query compiler-tiering tag (ISSUE 7): which tier this query
    # would execute on, plus the lazy-chain conjunct ordering the pass
    # derives from THIS run's measured per-stage selectivity.
    from kafkastreams_cep_tpu.compiler.tables import lower
    from kafkastreams_cep_tpu.compiler.tiering import (
        apply_lazy_order,
        plan_tiering,
    )

    per_stage = on_b.stage_counters(state)
    tables = lower(pattern)
    _, lazy_report = apply_lazy_order(tables, per_stage)
    tier_tag = {
        "stock": {
            **plan_tiering(tables, base).describe(),
            "lazy_order": lazy_report,
        }
    }
    arrays = per_lane_counter_arrays(state)
    hops = (
        arrays["walk_hops"] + arrays["extract_hops"] + arrays["drain_hops"]
    ).reshape(-1)
    total = int(hops.sum())
    order = np.argsort(hops, kind="stable")[::-1][:8]
    per_key = {
        "total_hops": total,
        "top": [
            {
                "key": str(int(l)),  # bare matcher: key == lane id
                "lane": int(l),
                "hops": int(hops[l]),
                "share": round(hops[l] / total, 4) if total else 0.0,
            }
            for l in order
            if hops[l] > 0
        ],
    }
    _log(
        f"selectivity (K={K}, T={T}): attribution off "
        f"{K * T / best_off / 1e3:.0f}K ev/s vs on "
        f"{K * T / best_on / 1e3:.0f}K ev/s — overhead {overhead:.2f}%"
    )
    for stage, row in per_stage.items():
        _log(f"  stage {stage}: {row}")
    return {
        "profile": "selectivity",
        "k": K,
        "t": T,
        "evps_attr_off": round(K * T / best_off, 1),
        "evps_attr_on": round(K * T / best_on, 1),
        "overhead_pct": round(overhead, 2),
        "per_stage": per_stage,
        "per_key": per_key,
        # tier=stencil|hybrid|nfa per query + the lazy-chain conjunct
        # order derived from the measured selectivity above.
        "tier": tier_tag,
        "compile_s": {"off": round(comp_off, 2), "on": round(comp_on, 2)},
    }


# ---------------------------------------------------------------------------
# latency — end-to-end latency attribution (ISSUE 18)
# ---------------------------------------------------------------------------


def _cost_analysis(jfn, *fargs) -> Dict[str, Any]:
    """XLA cost-analysis row for one compiled program ({} when the
    backend exposes none — e.g. some CPU builds)."""
    try:
        comp = jfn.lower(*fargs).compile()
        c = comp.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        ca = c or {}
    except Exception:
        return {}
    row = {
        "bytes_accessed": ca.get("bytes accessed", 0),
        "flops": ca.get("flops", 0),
    }
    if "optimal_seconds" in ca:
        row["optimal_seconds"] = ca["optimal_seconds"]
    return row


def run_latency(args) -> Dict[str, Any]:
    import numpy as np

    from kafkastreams_cep_tpu.engine import EngineConfig
    from kafkastreams_cep_tpu.runtime.ingest import IngestPolicy
    from kafkastreams_cep_tpu.runtime.processor import CEPProcessor, Record
    from kafkastreams_cep_tpu.utils.latency import LatencyLedger, SLOTracker

    K = args.k if isinstance(args.k, int) else int(args.k.split(",")[0])
    T = args.t
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    ingest = (
        IngestPolicy(grace_ms=args.grace_ms, reorder_depth=max(4 * K * T, 64))
        if args.grace_ms > 0
        else None
    )
    ledger = LatencyLedger(
        slo=SLOTracker(threshold_s=args.slo_ms / 1e3)
    )
    proc = CEPProcessor(
        _stock_pattern(), K, cfg, ingest=ingest, latency=ledger,
        drain_interval=args.drain_interval,
    )
    rng = np.random.default_rng(args.seed)
    tracing = False
    if args.trace_dir:
        import jax

        try:
            jax.profiler.start_trace(args.trace_dir)
            tracing = True
        except Exception as e:
            _log(f"latency: trace capture unavailable ({e})")
    matches = 0
    try:
        ts = 0
        for _ in range(args.batches):
            records = []
            for i in range(K * T):
                ts += int(rng.integers(1, 3))
                records.append(Record(
                    key=int(i % K),
                    value={
                        "price": int(rng.integers(90, 131)),
                        "volume": int(rng.integers(600, 1101)),
                    },
                    timestamp=ts,
                ))
            matches += len(proc.process(records))
        matches += len(proc.flush())
    finally:
        if tracing:
            import jax

            jax.profiler.stop_trace()
    snap = proc.metrics_snapshot(per_lane=False)
    lat = snap.get("latency") or {}
    segments = {
        name: {
            k: seg[k]
            for k in ("count", "p50", "p95", "p99", "p999")
            if k in seg
        }
        for name, seg in (lat.get("segments") or {}).items()
    }
    device_cost = {
        "scan": _cost_analysis(proc.batch.scan, proc.state,
                               _stock_events(K, T)),
    }
    for name, seg in segments.items():
        _log(
            f"latency[{name}]: n={seg.get('count', 0)} "
            f"p50={seg.get('p50')} p99={seg.get('p99')}"
        )
    return {
        "profile": "latency",
        "k": K,
        "t": T,
        "batches": args.batches,
        "drain_interval": args.drain_interval,
        "grace_ms": args.grace_ms,
        "matches": matches,
        "segments": segments,
        "slo": lat.get("slo"),
        "exemplars": lat.get("exemplars"),
        "device_cost": device_cost,
        "trace_dir": args.trace_dir or None,
    }


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m kafkastreams_cep_tpu.profile",
        description=__doc__.split("\n\n")[0],
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp, k_default):
        sp.add_argument("--k", default=k_default,
                        help="lane count (step: comma list)")
        sp.add_argument("--t", type=int, default=int(
            os.environ.get("PROF_T", "32")))
        sp.add_argument("--reps", type=int, default=2)
        sp.add_argument("--seed", type=int, default=42)

    common(sub.add_parser("step"), "512,4096,16384")
    common(sub.add_parser("phases"), "4096")
    sp = sub.add_parser("ablate")
    common(sp, "4096")
    sp.add_argument("--variant", choices=_ABLATE_VARIANTS, default=None)
    sp = sub.add_parser("selectivity")
    common(sp, "256")
    sp.add_argument("--runs", type=int, default=16)
    sp.add_argument("--slab", type=int, default=32)
    sp = sub.add_parser("latency")
    common(sp, "64")
    sp.add_argument("--batches", type=int, default=4)
    sp.add_argument("--grace-ms", type=int, default=0,
                    help="reorder grace (0 = no ingest guard)")
    sp.add_argument("--drain-interval", type=int, default=1)
    sp.add_argument("--slo-ms", type=float, default=1000.0,
                    help="e2e SLO threshold for burn-rate tracking")
    sp.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace into this dir")

    args = p.parse_args(argv)
    # Normalize --k for single-int subcommands.
    if args.cmd != "step":
        try:
            args.k = int(str(args.k).split(",")[0])
        except ValueError:
            p.error(f"--k must be an integer for {args.cmd}")
    from kafkastreams_cep_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = {
        "step": run_step,
        "phases": run_phases,
        "ablate": run_ablate,
        "selectivity": run_selectivity,
        "latency": run_latency,
    }[args.cmd](args)
    print(json.dumps(out), flush=True)
    return 0
