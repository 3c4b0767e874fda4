"""Programmatic profiler CLI — ``python -m kafkastreams_cep_tpu.profile``.

One entry point that emits **structured PROFILE JSON**: exactly one JSON
object on stdout, all diagnostics on stderr, so reports and the bench
regression gate can consume profiler output programmatically instead of
scraping logs.

Subcommands
-----------

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
# selectivity — the continuous-profiling readout (ISSUE 6)
# ---------------------------------------------------------------------------


def run_selectivity(args) -> Dict[str, Any]:
    import dataclasses

    import numpy as np

    from kafkastreams_cep_tpu.engine import EngineConfig
    from kafkastreams_cep_tpu.engine.matcher import per_lane_counter_arrays
    from kafkastreams_cep_tpu.parallel import BatchMatcher

    K = args.k
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

    K = args.k
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
        sp.add_argument("--k", type=int, default=k_default,
                        help="lane count")
        sp.add_argument("--t", type=int, default=int(
            os.environ.get("PROF_T", "32")))
        sp.add_argument("--reps", type=int, default=2)
        sp.add_argument("--seed", type=int, default=42)

    sp = sub.add_parser("selectivity")
    common(sp, 256)
    sp.add_argument("--runs", type=int, default=16)
    sp.add_argument("--slab", type=int, default=32)
    sp = sub.add_parser("latency")
    common(sp, 64)
    sp.add_argument("--batches", type=int, default=4)
    sp.add_argument("--grace-ms", type=int, default=0,
                    help="reorder grace (0 = no ingest guard)")
    sp.add_argument("--drain-interval", type=int, default=1)
    sp.add_argument("--slo-ms", type=float, default=1000.0,
                    help="e2e SLO threshold for burn-rate tracking")
    sp.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace into this dir")

    args = p.parse_args(argv)
    from kafkastreams_cep_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = {
        "selectivity": run_selectivity,
        "latency": run_latency,
    }[args.cmd](args)
    print(json.dumps(out), flush=True)
    return 0
