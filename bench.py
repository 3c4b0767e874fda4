"""Benchmark harness: events/sec/chip on the SASE stock pattern.

Prints ONE JSON line to stdout:
``{"metric": ..., "value": N, "unit": "events/s", "vs_baseline": N}``.

* **Headline config** (BASELINE.json configs[0]/[2] hybrid): the stock query
  over ``K`` vmapped key lanes × ``T`` scanned events per lane on one chip —
  the production dispatch shape (``parallel/batch.py``).
* **Parity gate**: before timing, the 8-event demo trace must reproduce the
  reference README's 4 match sequences exactly (README.md:93-96) through
  the same engine; a parity failure aborts the bench.
* **vs_baseline**: the reference publishes no numbers (BASELINE.md), so the
  ratio is measured against this repo's host oracle (``nfa/oracle.py``) — a
  faithful single-event-loop reimplementation of the reference engine
  (``NFA.java:94-289``) whose store-bound Java original is in the same
  throughput class (BASELINE.md "derived cost notes").

Environment knobs: ``CEP_BENCH_K`` (lanes, default 4096), ``CEP_BENCH_T``
(events/lane/scan, default 256), ``CEP_BENCH_REPS`` (timed scans, default
5; min + spread reported), ``CEP_BENCH_ORACLE_N`` (oracle-timed events,
default 1000 — the oracle's unbounded state makes its per-event cost
grow), ``CEP_BENCH_LOSSFREE_K`` / ``_CYCLES`` / ``_PARITY`` (the
zero-counters staircase line; parity replays one lane through the host
oracle, ~2 min), ``CEP_BENCH_STENCIL_N`` / ``CEP_BENCH_STENCIL_INNER``
(strict-SEQ stencil events and in-dispatch repeats), ``CEP_BENCH_EXTRAS``
/ ``CEP_BENCH_BUDGET_S`` / ``CEP_BENCH_{KLEENE,BANK,SHARD}_*`` (configs
2-4), ``CEP_BENCH_HOT_ENTRIES`` (two-tier hot-window headline rerun,
default 16, 0 skips), ``CEP_BENCH_LAZY`` (lazy-extraction A/B on the
headline trace, default 1; ``CEP_BENCH_LAZY_{CHUNK,RING,E}`` set the
drain cadence, handle-ring size, and slab headroom),
``CEP_BENCH_FRONTIER`` ("E:EH,E:EH,…" — the (E, E_hot) frontier sweep,
off by default), ``CEP_BENCH_OOO`` (graceful-ingestion A/B: in-order vs
bounded-skew shuffled arrival through the watermark reorder buffer,
default 1; ``CEP_BENCH_OOO_{K,B,BATCHES,GRACE}`` size it),
``CEP_BENCH_METRICS=1`` (run the headline config
under the telemetry Reporter and print the per-phase p50/p99 block;
``CEP_BENCH_METRICS_{K,T,BATCHES}`` size it), ``CEP_BENCH_TIER``
(compiler-tiering A/B: untiered vs tiered on a strict-prefix-dominated
match-sparse trace, default 1; ``CEP_BENCH_TIER_{K,T,CHUNK,REPS}`` size
it), ``CEP_BENCH_SHARDF`` (shard fault tolerance probes: kill-one-shard
evacuation latency + degraded throughput, and the hot-key rebalance
loss contract, default 1 when >= 2 devices; ``CEP_BENCH_SHARDF_{K,B}``
size them), ``CEP_BENCH_TENANTS`` (multi-tenant bank sweep: N
Zipf-overlapping strict-sequence queries on the shared stencil screen vs
the naive-fused stacked bank, default 1;
``CEP_BENCH_TENANTS_{N,K,T,REPS,POOL,FUSED_MAX}`` size it),
``CEP_BENCH_ADAPT`` (adaptive recompilation: hybrid sweep under the
chunk-gated scan + drift A/B with/without ``AdaptPolicy`` replanning,
default 1; ``CEP_BENCH_ADAPT_{K,T,CHUNK,REPS,DRIFT_B}`` size it),
``CEP_BENCH_TENANT_ISO`` (per-tenant isolation: compliant-tenant
throughput with one quota-limited flooding tenant, shed accounting, and
quarantine-entry latency, default 1;
``CEP_BENCH_TENANT_ISO_{K,B,BATCHES}`` size it), ``CEP_BENCH_LATENCY``
(end-to-end latency attribution: ledger on/off parity + overhead,
per-segment p50/p99, drain-cadence and reorder-grace A/Bs, default 1;
``CEP_BENCH_LATENCY_{K,B,BATCHES,GRACE,DRAIN,RING}`` size it),
``CEP_BENCH_OVERLOAD`` (brownout ladder under flood: goodput with and
without the controller, auditable shed accounting, brownout batch-time
tail, recovery-to-L0, default 1;
``CEP_BENCH_OVERLOAD_{K,B,BATCHES,SUB,DEPTH}`` size it).
``JAX_PLATFORMS=cpu`` runs it on the CPU.

All diagnostics go to stderr; stdout carries only the JSON line.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "examples"))

import stock_demo
from kafkastreams_cep_tpu import OracleNFA, Query
from kafkastreams_cep_tpu.engine import (
    EngineConfig,
    EventBatch,
    StencilMatcher,
    autosize,
)
from kafkastreams_cep_tpu.engine.sizing import capacity_counters
from kafkastreams_cep_tpu.parallel import BatchMatcher
from kafkastreams_cep_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parity_gate():
    """The engine must reproduce the README's 4 stock matches exactly."""
    lines = stock_demo.run()
    if lines != stock_demo.EXPECTED:
        log(f"PARITY FAILURE: {lines}")
        raise SystemExit(2)
    log("parity gate: README 4-sequence output reproduced exactly")


def make_batch(rng, K, T):
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    volumes = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    return EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(volumes)},
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )


def staircase_trace(K, cycles, cyc_len=24):
    """A calibrated stock-pattern trace whose matching activity is bounded
    per cycle, so a finite engine config is *loss-free* (all six overflow
    counters exactly zero) over the whole stream.

    Decreasing price staircase: cycle c's runs' ``avg`` fold always exceeds
    every later price, so no run takes outside its own cycle (the demo
    fold ``avg=(avg+price)//2`` otherwise converges just below the take
    price and keeps matching forever).  Increasing take-volume staircase:
    cycle c's completion volume is below its own runs' ``0.8*volume``
    threshold but at or above every older cycle's, so lineages complete
    only in their own cycle.  Lane k shifts all prices by +k (comparisons
    are relative, so the match structure is preserved while lane values
    differ).
    """
    assert cycles <= 70
    evs = []
    for c in range(cycles):
        S = 2000 - 20 * c
        P = S + 2
        tv = 100 + 10 * c  # take volume; completion threshold 0.8*tv
        cv = 79 + 8 * c  # completes cycle c's lineages only
        cyc = [(S, 1200), (P, tv), (P, tv), (S - 5, cv)]
        cyc += [(500, 900)] * (cyc_len - len(cyc))
        evs += cyc
    tr = np.array(evs, dtype=np.int32)  # [T, 2]
    T = tr.shape[0]
    prices = tr[None, :, 0] + np.arange(K, dtype=np.int32)[:, None]
    volumes = np.broadcast_to(tr[None, :, 1], (K, T)).copy()
    return EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(volumes)},
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )


def _oracle_lane_matches(prices, volumes):
    """Ground-truth per-event match lists for one lane via the host oracle."""
    from kafkastreams_cep_tpu import OracleNFA

    oracle = OracleNFA.from_pattern(stock_demo.stock_pattern())
    per_event = []
    for t in range(len(prices)):
        ms = oracle.match(
            None,
            {"price": int(prices[t]), "volume": int(volumes[t])},
            2 * t,
            offset=t,
        )
        per_event.append(
            [
                {name: [e.offset for e in evs] for name, evs in m.as_map().items()}
                for m in ms
            ]
        )
    return per_event


def bench_lossfree(K, cycles, reps):
    """Loss-free at scale: the stock pattern on the staircase trace with a
    config sized so ALL six overflow counters are exactly zero over the
    stream, plus sampled-lane exact match parity against the host oracle
    (``KVSharedVersionedBuffer.java:86-89`` — the reference never drops;
    this line demonstrates the engine fast AND match-identical)."""
    events = staircase_trace(K, cycles)
    T = int(events.ts.shape[1])
    # Round-4 hand calibration, now only the autosize seed (and the
    # CEP_BENCH_AUTOSIZE=0 fallback for smoke runs): the shipped config is
    # DERIVED by probing a 128-lane sample of the same trace
    # (engine/sizing.py — the reference needs no sizing, heap-backed
    # stores; this is the array-engine analog).
    seed_cfg = EngineConfig(
        max_runs=48, slab_entries=112, slab_preds=8, dewey_depth=10,
        max_walk=10,
    )
    if os.environ.get("CEP_BENCH_AUTOSIZE", "1") != "0":
        sample = staircase_trace(min(K, 128), cycles)
        cfg = autosize(
            stock_demo.stock_pattern(), sample, start=seed_cfg,
            margin=1.4, sweep_every=T,
        )
        log(f"lossfree: autosized config {cfg}")
    else:
        cfg = seed_cfg
    batch = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
    state0 = batch.init_state()

    t0 = time.perf_counter()
    state, out = batch.scan(state0, events)
    jax.block_until_ready(out.count)
    compile_s = time.perf_counter() - t0
    counters = batch.counters(state)
    lossfree = all(v == 0 for v in counters.values())
    if not lossfree:
        log(f"lossfree: COUNTERS NOT ZERO: {counters}")

    # Exact parity vs the host oracle.  Lane price shifts preserve every
    # comparison, so all K lanes must emit identical match structures: one
    # full-stream oracle lane (the slow part — the oracle's state grows
    # like the reference's) plus a vectorized all-lanes-identical check
    # extends exactness to every lane.  CEP_BENCH_LOSSFREE_PARITY=0 skips
    # the oracle replay for quick runs.
    names = batch.names
    stage_np = np.asarray(out.stage)
    off_np = np.asarray(out.off)
    count_np = np.asarray(out.count)
    prices = np.asarray(events.value["price"])
    volumes = np.asarray(events.value["volume"])
    parity = True
    lanes_identical = bool(
        (stage_np == stage_np[:1]).all()
        and (off_np == off_np[:1]).all()
        and (count_np == count_np[:1]).all()
    )
    if not lanes_identical:
        parity = False
        log("lossfree: PARITY MISMATCH: lanes differ (should be isomorphic)")
    if parity and os.environ.get("CEP_BENCH_LOSSFREE_PARITY", "1") != "0":
        lane = 0
        expected = _oracle_lane_matches(prices[lane], volumes[lane])
        got_all = _decode_lane(out, names, lane)
        for t in range(T):
            got = got_all[t]
            if got != expected[t]:
                parity = False
                log(
                    f"lossfree: PARITY MISMATCH lane {lane} t {t}: "
                    f"engine {got} oracle {expected[t]}"
                )
                break
        if parity:
            log(
                "lossfree: oracle parity exact over the full stream "
                f"(lane 0 replayed; all {K} lanes emit identically)"
            )

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, out = batch.scan(state0, events)
        jax.block_until_ready(out.count)
        times.append(time.perf_counter() - t0)
    best = min(times)
    spread = (max(times) - best) / best * 100 if reps > 1 else 0.0
    log(
        f"lossfree (stock staircase, {K} lanes x {T} events, all counters "
        f"zero={lossfree}): {K * T / best / 1e3:.0f}K ev/s "
        f"(min of {reps}, spread {spread:.0f}%, compile {compile_s:.1f}s)"
    )
    return K * T / best, lossfree, parity


def _decode_lane(out, names, lane):
    """Engine emissions of one lane as per-event lists of name->offsets
    dicts (the oracle's ``as_map`` structure; same decode the loss-free
    parity check uses)."""
    stage_np = np.asarray(out.stage[lane])  # [T, R, W]
    off_np = np.asarray(out.off[lane])
    count_np = np.asarray(out.count[lane])  # [T, R]
    T, R = count_np.shape
    per_event = []
    for t in range(T):
        got = []
        for r in range(R):
            n = int(count_np[t, r])
            if n == 0:
                continue
            m: dict = {}
            for w in range(n):
                m.setdefault(names[int(stage_np[t, r, w])], []).append(
                    int(off_np[t, r, w])
                )
            got.append(m)
        per_event.append(got)
    return per_event


def _freeze(m):
    return tuple(sorted((k, tuple(v)) for k, v in m.items()))


def measure_recall(out, names, prices, volumes, lanes):
    """Match recall/precision vs the host oracle on sampled lanes.

    The reference never drops (``KVSharedVersionedBuffer.java:86-89``);
    the headline config does (counted).  This quantifies the effect in
    match space: recall = fraction of oracle matches the engine emitted,
    precision = fraction of engine emissions the oracle agrees with —
    per-event multiset intersection, so order inside an event is free but
    nothing can be claimed across events."""
    from collections import Counter

    tot_o = tot_e = tot_hit = 0
    for lane in lanes:
        want = _oracle_lane_matches(prices[lane], volumes[lane])
        got = _decode_lane(out, names, lane)
        for t in range(len(want)):
            co = Counter(_freeze(m) for m in want[t])
            ce = Counter(_freeze(m) for m in got[t])
            tot_o += sum(co.values())
            tot_e += sum(ce.values())
            tot_hit += sum((co & ce).values())
    recall = tot_hit / tot_o if tot_o else 1.0
    precision = tot_hit / tot_e if tot_e else 1.0
    return recall, precision, tot_o


def bench_engine(K, T, reps):
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12, max_walk=12
    )
    batch = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
    state0 = batch.init_state()
    rng = np.random.default_rng(42)
    events = make_batch(rng, K, T)

    t0 = time.perf_counter()
    state, out = batch.scan(state0, events)
    jax.block_until_ready(out.count)
    compile_s = time.perf_counter() - t0
    # Cold/warm labels make round-over-round numbers comparable at a
    # glance (a warm persistent cache swings compile seconds wildly and
    # must never be misread as an engine change).
    cache = "warm-cache" if compile_s < 15 else "cold-cache"
    log(f"engine: compile+first scan {compile_s:.1f}s ({cache}) "
        f"on {jax.devices()[0]}")

    times = []
    for i in range(reps):
        t0 = time.perf_counter()
        state, out = batch.scan(state0, events)
        jax.block_until_ready(out.count)
        dt = time.perf_counter() - t0
        times.append(dt)
        log(f"engine: scan {i + 1}/{reps}: {dt * 1e3:.1f} ms "
            f"({K * T / dt / 1e6:.2f}M ev/s)")
    best = min(times)
    spread = (max(times) - best) / best * 100 if reps > 1 else 0.0
    log(f"engine: best {best * 1e3:.1f} ms of {reps} reps, spread "
        f"{spread:.1f}% over best")
    counters = batch.counters(state)
    log(f"engine: counters {counters} (capacity drops are policy, counted; "
        "the lossfree line below runs with all counters zero)")
    matches = int(jnp.sum(out.count > 0))
    log(f"engine: {matches} run-slots completed matches in final scan")
    # The headline trace is adversarial for loss-free operation: probing it
    # (engine/sizing.py) demands E=192/MP=32/D=48 — past the walk kernel's
    # VMEM budget — because the converging avg fold keeps every lane
    # match-dense for the whole scan (the reference holds the same state
    # heap-side, 37K matches/1000 events on one lane).  So the headline
    # number carries an explicit match recall against the oracle on
    # sampled lanes instead of a counters_zero claim.
    n_lanes = int(os.environ.get("CEP_BENCH_RECALL_LANES", "2"))
    recall = precision = None
    if n_lanes > 0:
        prices = np.asarray(events.value["price"])
        volumes = np.asarray(events.value["volume"])
        lanes = list(range(0, K, max(K // n_lanes, 1)))[:n_lanes]
        t0 = time.perf_counter()  # host-timed (oracle replay + host decode)
        recall, precision, n_oracle = measure_recall(
            out, batch.names, prices, volumes, lanes
        )
        log(
            f"engine: recall {recall:.4f} / precision {precision:.4f} vs "
            f"oracle on {len(lanes)} sampled lanes ({n_oracle} oracle "
            f"matches, {time.perf_counter() - t0:.1f}s)"
        )
        # Recall is a capacity knob, not an engine property: one larger
        # configuration shows the throughput/recall tradeoff on the same
        # trace (CEP_BENCH_RECALL_CURVE=0 skips).  Runs on a 1024-lane
        # slice — the R=64/W=16 match outputs at the full lane count are
        # multi-GB (a full-shape attempt RESOURCE_EXHAUSTED the chip) and
        # the per-event rate + sampled recall don't need more lanes.
        if os.environ.get("CEP_BENCH_RECALL_CURVE", "1") != "0":
            try:
                K2 = min(K, 1024)
                ev2 = jax.tree_util.tree_map(lambda x: x[:K2], events)
                lanes2 = [l for l in lanes if l < K2] or [0]
                big = EngineConfig(
                    max_runs=64, slab_entries=128, slab_preds=8,
                    dewey_depth=16, max_walk=16,
                )
                bb = BatchMatcher(stock_demo.stock_pattern(), K2, big)
                bs0 = bb.init_state()
                bstate, bout = bb.scan(bs0, ev2)
                jax.block_until_ready(bout.count)
                bbest = float("inf")
                for _ in range(max(reps - 2, 1)):
                    t0 = time.perf_counter()
                    bstate, bout = bb.scan(bs0, ev2)
                    jax.block_until_ready(bout.count)
                    bbest = min(bbest, time.perf_counter() - t0)
                r2, p2, _ = measure_recall(
                    bout, bb.names, prices, volumes, lanes2
                )
                log(
                    f"engine[R=64,E=128,W=16, {K2} lanes]: "
                    f"{K2 * T / bbest / 1e3:.0f}K ev/s, recall {r2:.4f} / "
                    f"precision {p2:.4f} — the capacity/recall tradeoff "
                    "on the same trace"
                )
                del bb, bs0, bstate, bout
            except Exception as e:  # never break the headline
                log(f"recall-curve point failed: {type(e).__name__}: {e}")

    # Two-tier hot-window headline (ISSUE 1): the same trace and shapes
    # with slab_hot_entries = CEP_BENCH_HOT_ENTRIES (default 16, 0 skips).
    # Matches are bit-identical by construction (parity suites); reported
    # here are the speed delta and the residency telemetry that explains
    # it (hot-hit rate = the fraction of walk hops that paid an E_hot-sized
    # reduce instead of an E-sized one).
    hot_n = int(os.environ.get("CEP_BENCH_HOT_ENTRIES", "16"))
    lazy_metrics = None
    hot_metrics = None
    if hot_n > 0 and hot_n % 8 == 0 and hot_n < cfg.slab_entries:
        try:
            import dataclasses

            hcfg = dataclasses.replace(cfg, slab_hot_entries=hot_n)
            hb = BatchMatcher(stock_demo.stock_pattern(), K, hcfg)
            hs0 = hb.init_state()
            hstate, hout = hb.scan(hs0, events)
            jax.block_until_ready(hout.count)
            hbest = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                hstate, hout = hb.scan(hs0, events)
                jax.block_until_ready(hout.count)
                hbest = min(hbest, time.perf_counter() - t0)
            hcounters = hb.counters(hstate)
            hhot = hb.hot_counters(hstate)
            hops = hhot["slab_hot_hits"] + hhot["slab_hot_misses"]
            hit_rate = hhot["slab_hot_hits"] / hops if hops else 1.0
            hmatches = int(jnp.sum(hout.count > 0))
            hot_evps = K * T / hbest
            log(
                f"engine[hot E_hot={hot_n}]: {hbest * 1e3:.1f} ms "
                f"({hot_evps / 1e6:.2f}M ev/s, {hot_evps / (K * T / best):.2f}x "
                f"single-tier), hot-hit rate {hit_rate:.3f}, "
                f"{hmatches} match slots (single-tier: {matches}), "
                f"hot counters {hhot}"
            )
            if hcounters != counters:
                log(
                    "engine[hot]: WARNING drop counters diverged from "
                    f"single-tier: {hcounters} vs {counters}"
                )
            hot_metrics = {
                "hot_entries": hot_n,
                "evps": round(hot_evps, 1),
                "speedup_vs_single_tier": round(hot_evps / (K * T / best), 3),
                "hot_hit_rate": round(hit_rate, 4),
                "match_slots": hmatches,
                "match_slots_single_tier": matches,
                "hot_counters": hhot,
                "counters_match_single_tier": hcounters == counters,
            }
            del hb, hs0, hstate, hout
        except Exception as e:  # never break the headline
            log(f"hot-tier bench failed: {type(e).__name__}: {e}")
    else:
        log(f"engine[hot]: skipped (CEP_BENCH_HOT_ENTRIES={hot_n})")

    # Per-stage attribution A/B (ISSUE 6): the same trace and shapes with
    # stage_attribution=True — reports the measured overhead (acceptance:
    # <= 3% on this headline) and the per-stage selectivity/cost table
    # the compiler-tiering work reads.  CEP_BENCH_ATTR=0 skips.
    attr_metrics = None
    if os.environ.get("CEP_BENCH_ATTR", "1") == "1":
        try:
            import dataclasses as _dc

            acfg = _dc.replace(cfg, stage_attribution=True)
            ab = BatchMatcher(stock_demo.stock_pattern(), K, acfg)
            as0 = ab.init_state()
            astate, aout = ab.scan(as0, events)
            jax.block_until_ready(aout.count)
            abest = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                astate, aout = ab.scan(as0, events)
                jax.block_until_ready(aout.count)
                abest = min(abest, time.perf_counter() - t0)
            attr_evps = K * T / abest
            overhead = (abest - best) / best * 100.0
            per_stage = ab.stage_counters(astate)
            attr_metrics = {
                "evps": round(attr_evps, 1),
                "overhead_pct": round(overhead, 2),
                "within_3pct": overhead <= 3.0,
                "counters_match_baseline": ab.counters(astate) == counters,
                "per_stage": per_stage,
            }
            log(
                f"engine[attribution]: {attr_evps / 1e6:.2f}M ev/s "
                f"({overhead:+.2f}% vs baseline, <=3% bound "
                f"{'OK' if overhead <= 3.0 else 'EXCEEDED'}); per-stage "
                f"selectivity "
                + ", ".join(
                    f"{s}={row['selectivity']}"
                    for s, row in per_stage.items()
                )
            )
            del ab, as0, astate, aout
        except Exception as e:  # never break the headline
            log(f"attribution bench failed: {type(e).__name__}: {e}")
    else:
        log("engine[attribution]: skipped (CEP_BENCH_ATTR=0)")

    # Lazy extraction A/B (ISSUE 4): the same trace eager vs lazy at the
    # same shapes, drained at a processor-like chunk cadence; reports the
    # per-step hop reduction (the device critical-path win), hot-hit-rate
    # delta, and match-slot parity.  CEP_BENCH_LAZY=0 skips.
    if os.environ.get("CEP_BENCH_LAZY", "1") == "1":
        try:
            lazy_metrics = bench_lazy_block(K, T, reps, cfg, events, hot_n)
        except Exception as e:  # never break the headline
            log(f"lazy bench failed: {type(e).__name__}: {e}")
    else:
        log("engine[lazy]: skipped (CEP_BENCH_LAZY=0)")
    # (E, E_hot) frontier sweep hook (PROFILE_r06 next-leverage item 3):
    # CEP_BENCH_FRONTIER="48:16,48:24,64:16" reruns the headline trace at
    # each point; off by default.
    frontier = os.environ.get("CEP_BENCH_FRONTIER", "")
    if frontier:
        try:
            pts = bench_frontier(K, T, reps, events, cfg, frontier)
            if lazy_metrics is not None:
                lazy_metrics["frontier"] = pts
        except Exception as e:
            log(f"frontier sweep failed: {type(e).__name__}: {e}")
    return (K * T / best, spread, counters, recall, precision, hot_metrics,
            lazy_metrics, attr_metrics)


def _chunked_scan(batch, events, chunk, lazy):
    """One chunk-cadence pass over ``events`` (drain between chunks when
    lazy — the processor's cadence), returning ``(state, match_slots)``.
    Every chunk's outputs materialize through a consumed reduction
    (``int(...)``), so the timing caller cannot be fooled by JAX's async
    dispatch."""
    import jax as _jax

    state = batch.init_state()
    n = 0
    T = int(events.ts.shape[1])
    for t0 in range(0, T, chunk):
        ev = _jax.tree_util.tree_map(
            lambda x: x[:, t0:t0 + chunk], events
        )
        state, out = batch.scan(state, ev)
        if lazy:
            state, drained = batch.drain(state)
            n += int(jnp.sum(drained.count > 0))  # consumed reduction
        else:
            n += int(jnp.sum(out.count > 0))  # consumed reduction
    jax.block_until_ready(state.slab.stage)
    return state, n


def bench_lazy_block(K, T, reps, base_cfg, events, hot_n):
    """Eager vs lazy at identical shapes on the headline trace (ISSUE 4).

    Both sides run the same chunk cadence (scan chunk + [drain] per
    chunk) so the comparison isolates WHERE the extraction hops run, not
    how the scan is sliced.  Reported: ev/s both ways, per-step device
    hop reduction (walk_hops + extract_hops, the lockstep critical path),
    drain-hop conservation, hot-hit-rate delta at E_hot=hot_n, and
    match-slot parity; handle_overflows is printed so a too-small ring
    can never masquerade as a win.
    """
    import dataclasses

    chunk = int(os.environ.get("CEP_BENCH_LAZY_CHUNK", "64"))
    ring = int(os.environ.get("CEP_BENCH_LAZY_RING", "512"))
    # Slab headroom for BOTH sides (default 2x the headline E): the lazy
    # engine holds completed chains until the drain, so at the
    # capacity-crushed headline E the two sides shed different branches
    # and parity becomes a drop-policy comparison instead of an
    # extraction-placement one.  CEP_BENCH_LAZY_E=0 keeps the headline E
    # to see exactly that effect (reported, never hidden).
    lazy_e = int(
        os.environ.get("CEP_BENCH_LAZY_E", str(2 * base_cfg.slab_entries))
    )
    ecfg = dataclasses.replace(
        base_cfg,
        slab_hot_entries=hot_n,
        slab_entries=lazy_e or base_cfg.slab_entries,
    )
    lcfg = dataclasses.replace(
        ecfg, lazy_extraction=True, handle_ring=ring
    )
    out = {}
    runs = {}
    for label, cfg, lazy in (("eager", ecfg, False), ("lazy", lcfg, True)):
        batch = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
        t0 = time.perf_counter()
        state, n = _chunked_scan(batch, events, chunk, lazy)
        log(f"engine[lazy A/B {label}]: compile+first "
            f"{time.perf_counter() - t0:.1f}s")
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            state, n = _chunked_scan(batch, events, chunk, lazy)
            best = min(best, time.perf_counter() - t0)
        runs[label] = (batch, state, n, best)
    (eb, es, en, ebest), (lb, ls, ln, lbest) = runs["eager"], runs["lazy"]
    we, wl = eb.walk_counters(es), lb.walk_counters(ls)
    step_e = we["walk_hops"] + we["extract_hops"]
    step_l = wl["walk_hops"] + wl["extract_hops"]
    reduction = 1 - step_l / step_e if step_e else 0.0

    def rate(h):
        t = h["slab_hot_hits"] + h["slab_hot_misses"]
        return h["slab_hot_hits"] / t if t else 1.0

    # NOTE: the lazy hot counters include drain-pass hops; the step-phase
    # rate (drain excluded) is what the two-tier reduce-width model sees —
    # approximate it by removing the drain share proportionally is wrong,
    # so report both raw rates and the hop classes for offline analysis.
    ovf = lb.counters(ls)["handle_overflows"]
    out = {
        "eager_evps": round(K * T / ebest, 1),
        "lazy_evps": round(K * T / lbest, 1),
        "speedup": round(ebest / lbest, 3),
        "step_hop_reduction": round(reduction, 4),
        "drain_hops_conserved": wl["drain_hops"] == we["extract_hops"],
        "hot_hit_rate_eager": round(rate(eb.hot_counters(es)), 4),
        "hot_hit_rate_lazy": round(rate(lb.hot_counters(ls)), 4),
        "match_slots_eager": en,
        "match_slots_lazy": ln,
        "match_slot_parity": en == ln,
        "handle_overflows": ovf,
        "walk_counters_eager": we,
        "walk_counters_lazy": wl,
        "chunk": chunk,
        "handle_ring": ring,
    }
    log(
        f"engine[lazy A/B, chunk={chunk}]: eager {K * T / ebest / 1e3:.0f}K"
        f" ev/s vs lazy {K * T / lbest / 1e3:.0f}K ev/s "
        f"({ebest / lbest:.2f}x); step-hop reduction {reduction:.1%}, "
        f"match slots {en} vs {ln} (parity={en == ln}, "
        f"handle_overflows={ovf}), hot-hit rate "
        f"{out['hot_hit_rate_eager']:.3f} -> {out['hot_hit_rate_lazy']:.3f}"
    )
    return out


def bench_frontier(K, T, reps, events, base_cfg, spec):
    """(E, E_hot) frontier sweep: rerun the headline trace at each
    ``E:EH`` point of ``spec`` (comma-separated) with the two-tier walk
    kernels enabled — places the new frontier next to the round-5
    E-linear line (PERF.md) on chip."""
    import dataclasses

    pts = {}
    for pair in spec.split(","):
        e_s, eh_s = pair.strip().split(":")
        E, EH = int(e_s), int(eh_s)
        cfg = dataclasses.replace(
            base_cfg, slab_entries=E, slab_hot_entries=EH
        )
        batch = BatchMatcher(stock_demo.stock_pattern(), K, cfg)
        state0 = batch.init_state()
        state, out = batch.scan(state0, events)
        jax.block_until_ready(out.count)
        best = float("inf")
        for _ in range(max(reps - 2, 1)):
            t0 = time.perf_counter()
            state, out = batch.scan(state0, events)
            jax.block_until_ready(out.count)
            best = min(best, time.perf_counter() - t0)
        hot = batch.hot_counters(state)
        hops = hot["slab_hot_hits"] + hot["slab_hot_misses"]
        rate = hot["slab_hot_hits"] / hops if hops else 1.0
        pts[f"{E}:{EH}"] = {
            "evps": round(K * T / best, 1),
            "hot_hit_rate": round(rate, 4),
        }
        log(f"frontier[E={E},EH={EH}]: {K * T / best / 1e3:.0f}K ev/s, "
            f"hot-hit rate {rate:.3f}")
        del batch, state0, state, out
    return pts


def bench_tier():
    """``CEP_BENCH_TIER``: compiler-tiering A/B (ISSUE 7).

    Strict-prefix-dominated, match-sparse workload — the production-
    monitoring shape: a 3-strict-stage prefix + skip-till-next suffix
    over a 64-symbol alphabet, so the begin predicate rejects ~98% of
    events and full prefixes fire ~4e-6/event; a handful of complete
    occurrences are planted so match parity is non-vacuous.  Untiered
    vs tiered BatchMatcher at identical shapes and chunk cadence (the
    processor's batch granularity, where the tiered matcher's NFA skip
    gate operates).  Reports ev/s both ways, the screened-event
    fraction, the NFA dispatch fraction, and a match-parity flag; both
    sides must finish loss-free (all counters zero) for the speedup to
    count.
    """
    from kafkastreams_cep_tpu.parallel.tiered import TieredBatchMatcher

    K = int(os.environ.get("CEP_BENCH_TIER_K", "32"))
    T = int(os.environ.get("CEP_BENCH_TIER_T", "4096"))
    chunk = int(os.environ.get("CEP_BENCH_TIER_CHUNK", "128"))
    reps = int(os.environ.get("CEP_BENCH_TIER_REPS", "3"))
    pattern = (
        Query()
        .select("pa").where(lambda k, v, ts, st: v == 1)
        .then()
        .select("pb").where(lambda k, v, ts, st: v == 2)
        .then()
        .select("pc").where(lambda k, v, ts, st: v == 3)
        .then()
        .select("sd").skip_till_next_match()
        .where(lambda k, v, ts, st: v == 7)
        .build()
    )
    rng = np.random.default_rng(17)
    codes = rng.integers(8, 64, size=(K, T)).astype(np.int32)
    # Planted full occurrences, clustered into a few chunks: most batches
    # then skip the NFA dispatch entirely (the match-sparse production
    # shape), while the hit chunks keep match parity non-vacuous.
    n_chunks = max(T // chunk, 1)
    hot_chunks = sorted(
        rng.choice(n_chunks, size=min(3, n_chunks), replace=False)
    )
    for i in range(12):
        c = int(hot_chunks[i % len(hot_chunks)])
        k = int(rng.integers(0, K))
        t = c * chunk + int(rng.integers(0, max(chunk - 16, 1)))
        codes[k, t], codes[k, t + 1], codes[k, t + 2] = 1, 2, 3
        codes[k, t + 9] = 7
    cfg = EngineConfig(
        max_runs=32, slab_entries=64, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    tcfg = __import__("dataclasses").replace(cfg, tiering=True)
    events = EventBatch(
        key=jnp.zeros((K, T), jnp.int32),
        value=jnp.asarray(codes),
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T)),
        valid=jnp.ones((K, T), bool),
    )

    def _chunked_scan_tier(batch):
        # Same consumed-reduction contract as _chunked_scan: every chunk's
        # outputs materialize inside the span (int() pulls the reduction,
        # block_until_ready fences the final state).
        state = batch.init_state()
        n = 0
        hits = []
        for t0 in range(0, T, chunk):
            ev = jax.tree_util.tree_map(
                lambda x: x[:, t0:t0 + chunk], events
            )
            state, out = batch.scan(state, ev)
            n += int(jnp.sum(out.count > 0))  # consumed reduction
            ct = np.asarray(out.count)
            for k, t, r in zip(*np.nonzero(ct)):
                hits.append((int(k), t0 + int(t), int(ct[k, t, r])))
        jax.block_until_ready(
            state.slab.stage
            if not hasattr(state, "engine")
            else state.engine.slab.stage
        )
        return state, n, sorted(hits)

    runs = {}
    for label, b in (
        ("untiered", BatchMatcher(pattern, K, cfg)),
        ("tiered", TieredBatchMatcher(pattern, K, tcfg)),
    ):
        t0 = time.perf_counter()
        state, n, hits = _chunked_scan_tier(b)
        log(f"tier[{label}]: compile+first {time.perf_counter() - t0:.1f}s")
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            state, n, hits = _chunked_scan_tier(b)
            best = min(best, time.perf_counter() - t0)
        runs[label] = (b, state, n, hits, best)
    (ub, us, un, uh, ubest) = runs["untiered"]
    (tb, ts_, tn, th, tbest) = runs["tiered"]
    uc, tc = ub.counters(us), tb.counters(ts_)
    tier = tb.tier_counters(ts_)
    screened = tier["prefix_events_screened"]
    fires = tier["prefix_fires"]
    parity = uh == th and uc == tc
    zero = all(v == 0 for v in uc.values()) and all(
        v == 0 for v in tc.values()
    )
    # Denominator: under chunk-level gating (ISSUE 16) each scan offers
    # ceil(T'/gate_chunk) device-gated chunks, so the dispatched fraction
    # is per-chunk whenever the gate ran; pure-NFA plans and the
    # whole-scan kernel count whole batches (gate_chunks stays 0).
    gate_denom = tb.gate_chunks or tb.scan_calls
    dispatch_frac = tb.nfa_dispatches / gate_denom if gate_denom else 0.0
    out = {
        "k": K, "t": T, "chunk": chunk,
        "plan": tb.plan.describe(),
        "untiered_evps": round(K * T / ubest, 1),
        "tiered_evps": round(K * T / tbest, 1),
        "speedup": round(ubest / tbest, 3),
        "screened_fraction": (
            round(1.0 - fires / screened, 6) if screened else None
        ),
        "prefix_fires": fires,
        "tier_promotions": tier["tier_promotions"],
        "nfa_dispatch_fraction": round(dispatch_frac, 4),
        "match_slots": un,
        "match_parity": bool(parity),
        "counters_zero": bool(zero),
    }
    log(
        f"tier A/B ({K}x{T}, chunk={chunk}, {tb.plan.tier} "
        f"p={tb.plan.prefix_len}): untiered {K * T / ubest / 1e3:.0f}K "
        f"ev/s vs tiered {K * T / tbest / 1e3:.0f}K ev/s "
        f"({ubest / tbest:.2f}x); screened {out['screened_fraction']}, "
        f"NFA dispatched {dispatch_frac:.1%} of gated chunks, "
        f"{un} vs {tn} match slots (parity={parity}, zero={zero})"
    )
    return out


def bench_adapt():
    """``CEP_BENCH_ADAPT``: adaptive recompilation A/B (ISSUE 16).

    Two probes:

    1. *Hybrid sweep* — PROFILE_r09 §2's band re-run under the
       chunk-gated scan (the per-scan host gate is gone): 4-stage
       patterns with the first p of 4 stages strict, p = 1..3, untiered
       vs tiered at identical shapes/cadence.  Every point must sit at
       or above BENCH_r06's recorded 2.7-5.2x band, loss-free with
       match parity.
    2. *Drift A/B* — a two-conjunct workload whose accept mix inverts
       mid-stream, run twice on identical records: a supervised
       processor with ``AdaptPolicy`` (profiler-driven replans at
       checkpoint boundaries) vs the same supervisor with replanning
       off (the stale compile-time plan).  The adaptive side must fire
       >= 1 replan, stay bit-identical on matches and loss counters
       (exactly-once across the swap), and beat the stale declaration
       order on the lazy-chain objective — expected conjunct
       evaluation cost per event under the drifted mix (arxiv
       1612.05110's ranking quantity, computed from the measured
       marginal selectivities).  Wall-clock is reported for both sides
       but expected to tie: the array engine evaluates conjunct chains
       branch-free, so evaluation order is a host/short-circuit and
       future-gating lever, not a device-throughput one
       (PROFILE_r09 §3).

    ``CEP_BENCH_ADAPT_{K,T,CHUNK,REPS}`` size the sweep;
    ``CEP_BENCH_ADAPT_DRIFT_B`` sizes the drift stream (batches per
    phase).
    """
    import dataclasses
    import shutil
    import tempfile

    from kafkastreams_cep_tpu.parallel.tiered import TieredBatchMatcher
    from kafkastreams_cep_tpu.pattern.predicate import and_, hint
    from kafkastreams_cep_tpu.runtime import Record
    from kafkastreams_cep_tpu.runtime.supervisor import (
        AdaptPolicy,
        Supervisor,
    )

    K = int(os.environ.get("CEP_BENCH_ADAPT_K", "32"))
    T = int(os.environ.get("CEP_BENCH_ADAPT_T", "2048"))
    chunk = int(os.environ.get("CEP_BENCH_ADAPT_CHUNK", "128"))
    reps = int(os.environ.get("CEP_BENCH_ADAPT_REPS", "2"))

    # -- probe 1: hybrid sweep (strict-prefix length 1..3 of 4) ----------
    def sweep_pattern(p):
        q = Query()
        for i, (nm, code) in enumerate(
            zip(("pa", "pb", "pc", "sd"), (1, 2, 3, 7))
        ):
            q = q.select(nm) if i == 0 else q.then().select(nm)
            if i >= p:
                q = q.skip_till_next_match()
            q = q.where(lambda k, v, ts, st, c=code: v == c)
        return q.build()

    # dewey_depth 24: at 12 the seed-29 trace ticks ver_overflows (both
    # sides identically), and the loss contract here is all-zero.
    cfg = EngineConfig(
        max_runs=32, slab_entries=64, slab_preds=8, dewey_depth=24,
        max_walk=12,
    )
    tcfg = dataclasses.replace(cfg, tiering=True)
    rng = np.random.default_rng(29)
    codes = rng.integers(8, 64, size=(K, T)).astype(np.int32)
    n_chunks = max(T // chunk, 1)
    hot_chunks = sorted(
        rng.choice(n_chunks, size=min(3, n_chunks), replace=False)
    )
    for i in range(9):
        c = int(hot_chunks[i % len(hot_chunks)])
        k = int(rng.integers(0, K))
        t = c * chunk + int(rng.integers(0, max(chunk - 16, 1)))
        codes[k, t], codes[k, t + 1], codes[k, t + 2] = 1, 2, 3
        codes[k, t + 9] = 7
    events = EventBatch(
        key=jnp.zeros((K, T), jnp.int32),
        value=jnp.asarray(codes),
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T)),
        valid=jnp.ones((K, T), bool),
    )

    def _chunked_scan_adapt(batch):
        state = batch.init_state()
        n = 0
        hits = []
        for t0 in range(0, T, chunk):
            ev = jax.tree_util.tree_map(
                lambda x: x[:, t0:t0 + chunk], events
            )
            state, out = batch.scan(state, ev)
            n += int(jnp.sum(out.count > 0))
            ct = np.asarray(out.count)
            for k, t, r in zip(*np.nonzero(ct)):
                hits.append((int(k), t0 + int(t), int(ct[k, t, r])))
        jax.block_until_ready(
            state.slab.stage
            if not hasattr(state, "engine")
            else state.engine.slab.stage
        )
        return state, n, sorted(hits)

    sweep = {}
    sweep_parity = True
    sweep_zero = True
    for p in (1, 2, 3):
        pattern = sweep_pattern(p)
        runs = {}
        for label, b in (
            ("untiered", BatchMatcher(pattern, K, cfg)),
            ("tiered", TieredBatchMatcher(pattern, K, tcfg)),
        ):
            state, n, hits = _chunked_scan_adapt(b)  # compile + first
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                state, n, hits = _chunked_scan_adapt(b)
                best = min(best, time.perf_counter() - t0)
            runs[label] = (b, state, n, hits, best)
        ub, us, un, uh, ubest = runs["untiered"]
        tb, ts_, tn, th, tbest = runs["tiered"]
        uc, tc = ub.counters(us), tb.counters(ts_)
        parity = uh == th and uc == tc
        zero = all(v == 0 for v in uc.values()) and all(
            v == 0 for v in tc.values()
        )
        sweep_parity &= parity
        sweep_zero &= zero
        gate_denom = tb.gate_chunks or tb.scan_calls
        sweep[f"p{p}"] = {
            "plan": tb.plan.describe(),
            "untiered_evps": round(K * T / ubest, 1),
            "tiered_evps": round(K * T / tbest, 1),
            "speedup": round(ubest / tbest, 3),
            "nfa_dispatch_fraction": round(
                tb.nfa_dispatches / gate_denom if gate_denom else 0.0, 4
            ),
            "match_slots": un,
            "match_parity": bool(parity),
            "counters_zero": bool(zero),
        }
        log(
            f"adapt sweep p={p}: untiered {K * T / ubest / 1e3:.1f}K "
            f"ev/s vs tiered {K * T / tbest / 1e3:.1f}K ev/s "
            f"({ubest / tbest:.2f}x, parity={parity}, zero={zero})"
        )
        del runs, ub, tb, us, ts_

    # -- probe 2: drift A/B (replanning vs the stale plan) ---------------
    DK = 8
    n_phase = int(os.environ.get("CEP_BENCH_ADAPT_DRIFT_B", "16"))
    batch_sz = 64  # records per process() call, per key below

    def f_narrow(k, v, ts, st):
        return v < 8

    def g_mod(k, v, ts, st):
        return v % 4 == 0

    drift_pattern = (
        Query()
        .select("first")
        # Declared order (f, g): equal costs, so only measured
        # selectivity can flip the chain — exactly what the drift does.
        .where(and_(hint(f_narrow, cost=4.0), hint(g_mod, cost=4.0)))
        .then()
        .select("second").skip_till_next_match()
        .where(lambda k, v, ts, st: v == 0)
        .build()
    )
    dcfg = EngineConfig(
        max_runs=32, slab_entries=96, slab_preds=12, dewey_depth=48,
        max_walk=12, tiering=True, stage_attribution=True,
    )
    # Phase 1: {0,4,8,12} -> sel(f)=0.5, sel(g)=1.0 (declared order
    # already optimal).  Phase 2: {0,1,2,3,5,6,7} -> sel(f)=1.0,
    # sel(g)=1/7 — the cheap-reject conjunct is now g, so the measured
    # plan flips the chain.  Phase 2 keeps an occasional 0 so pending
    # skip-till runs can still complete: a 0-free phase leaves every
    # open run skipping all phase-2 events and overflows dewey versions.
    rng2 = np.random.default_rng(41)
    pools = [(0, 4, 8, 12), (0, 1, 2, 3, 5, 6, 7)]
    batches = []
    t_base = 0
    for phase, pool in enumerate(pools):
        for _ in range(n_phase):
            recs = []
            for i in range(batch_sz):
                k = int(rng2.integers(0, DK))
                v = int(rng2.choice(pool))
                recs.append(Record(k, v, 1000 + t_base + i))
            t_base += batch_sz
            batches.append(recs)

    def run_side(policy):
        d = tempfile.mkdtemp(prefix="cep_adapt_")
        try:
            sup = Supervisor(
                drift_pattern, DK, dcfg,
                checkpoint_path=os.path.join(d, "ckpt"),
                checkpoint_every=2,
                adapt_policy=policy,
                gc_interval=0,
            )
            matches = []
            # host-timed: end-to-end supervisor records/s — decode pulls
            # every match to host, and the replan rebuild cost is part
            # of what this A/B measures.
            t0 = time.perf_counter()  # host-timed
            for recs in batches:
                matches.extend(sup.process(recs))
            matches.extend(sup.drain_ingest())
            wall = time.perf_counter() - t0
            snap = sup.metrics_snapshot()
            order = [
                r["order"]
                for r in (sup.processor.batch.lazy_order or {}).values()
                if r.get("order")
            ]
            counters = sup.processor.counters()
            return matches, wall, snap, order, counters
        finally:
            shutil.rmtree(d, ignore_errors=True)

    policy = AdaptPolicy(
        drift_threshold=0.2, min_evals=64, replan_streak=1, cooldown=0
    )
    a_matches, a_wall, a_snap, a_order, a_counters = run_side(policy)
    s_matches, s_wall, s_snap, s_order, s_counters = run_side(None)

    def keyed(ms):
        return sorted(
            (k, tuple(
                (stg, tuple(e.offset for e in evs))
                for stg, evs in s.as_map().items()
            ))
            for k, s in ms
        )

    drift_parity = keyed(a_matches) == keyed(s_matches)
    loss_names = (
        "run_drops", "ver_overflows", "slab_full_drops",
        "slab_pred_drops", "slab_trunc", "walk_collisions",
        "handle_overflows",
    )
    drift_zero = all(
        c.get(n_, 0) == 0
        for c in (a_counters, s_counters)
        for n_ in loss_names
    )
    n_records = len(batches) * batch_sz

    # Lazy-chain objective under the drifted (phase 2) mix: expected
    # per-event evaluation cost of each side's live chain order, using
    # the true marginal selectivities of the drifted pool.  Short-
    # circuit cost of order (c1, c2) = c1 + sel1 * c2.
    pool2 = np.asarray(pools[1])
    sel2 = {
        "f_narrow": float(np.mean(pool2 < 8)),
        "g_mod": float(np.mean(pool2 % 4 == 0)),
    }
    cost = {"f_narrow": 4.0, "g_mod": 4.0}

    def chain_cost(order_labels):
        total, reach = 0.0, 1.0
        for lbl in order_labels:
            name = "f_narrow" if "f_narrow" in lbl else "g_mod"
            total += reach * cost[name]
            reach *= sel2[name]
        return total

    stale_first = next(
        (o for o in s_order if len(o) == 2), ["f_narrow", "g_mod"]
    )
    adapt_first = next(
        (o for o in a_order if len(o) == 2), stale_first
    )
    stale_cost = chain_cost(stale_first)
    adapt_cost = chain_cost(adapt_first)
    out = {
        "sweep": sweep,
        "sweep_speedup_min": min(s["speedup"] for s in sweep.values()),
        "band_r06": [2.7, 5.2],
        "drift": {
            "k": DK,
            "batches": len(batches),
            "records": n_records,
            "adaptive_rps": round(n_records / a_wall, 1),
            "stale_rps": round(n_records / s_wall, 1),
            "replans": a_snap.get("replans", 0),
            "replan_failures": a_snap.get("replan_failures", 0),
            "stale_order": stale_first,
            "replanned_order": adapt_first,
            "stale_cost_per_event": round(stale_cost, 3),
            "replanned_cost_per_event": round(adapt_cost, 3),
            "lazy_cost_ratio": round(stale_cost / adapt_cost, 3),
        },
        "match_parity": bool(sweep_parity and drift_parity),
        "counters_zero": bool(sweep_zero and drift_zero),
    }
    log(
        f"adapt drift (K={DK}, {n_records} records): adaptive "
        f"{n_records / a_wall / 1e3:.1f}K rec/s ({a_snap.get('replans', 0)} "
        f"replans) vs stale {n_records / s_wall / 1e3:.1f}K rec/s; "
        f"lazy-chain cost {stale_cost:.2f} -> {adapt_cost:.2f} "
        f"({stale_cost / adapt_cost:.2f}x better on the drifted mix); "
        f"parity={drift_parity}, zero={drift_zero}"
    )
    return out


def bench_stencil(total_events, reps):
    """BASELINE.json config 2: strict-contiguity 3-stage SEQ over ~1M
    synthetic StockEvents (stencil fast path; stderr-reported secondary)."""
    pattern = (
        Query()
        .select("rise").where(lambda k, v, ts, st: v["price"] > 110)
        .then()
        .select("surge").where(lambda k, v, ts, st: v["volume"] > 900)
        .then()
        .select("drop").where(lambda k, v, ts, st: v["price"] < 105)
        .build()
    )
    K = 128
    T = max(total_events // K, 1)
    m = StencilMatcher(pattern, K)
    rng = np.random.default_rng(7)
    events = make_batch(rng, K, T)
    # Amortize inside ONE dispatch: the rate is a kernel-only rate, not
    # an end-to-end one.
    inner = max(int(os.environ.get("CEP_BENCH_STENCIL_INNER", "10")), 1)

    @jax.jit
    def many(state):
        def body(s, _):
            s2, out = m.scan(s, events)
            return s2, jnp.sum(out.hit)
        return jax.lax.scan(body, state, None, length=inner)

    t0 = time.perf_counter()
    _, hits = many(m.init_state())
    jax.block_until_ready(hits)
    log(f"stencil: compile+first run {time.perf_counter() - t0:.1f}s")
    best = float("inf")
    for i in range(reps):
        t0 = time.perf_counter()
        _, hits = many(m.init_state())
        jax.block_until_ready(hits)
        best = min(best, time.perf_counter() - t0)
    n_hits = int(hits[0])
    total = K * T * inner
    log(
        f"stencil (strict 3-stage SEQ, {K}x{T} events x{inner} in-dispatch): "
        f"{total / best / 1e6:.1f}M ev/s, {n_hits} matches/scan"
    )
    return total / best


def bench_kleene(K, T, reps):
    """BASELINE.json config 2: skip_till_any_match + oneOrMore Kleene
    closure, vmapped over ~10K key lanes (stderr-reported secondary)."""
    pattern = (
        Query()
        .select("start").where(lambda k, v, ts, st: v["price"] > 120)
        .then()
        .select("run").one_or_more().skip_till_any_match()
        .where(lambda k, v, ts, st: v["volume"] > 900)
        .then()
        .select("end").where(lambda k, v, ts, st: v["price"] < 100)
        .build()
    )
    rng = np.random.default_rng(11)
    prices = rng.integers(80, 141, size=(K, T)).astype(np.int32)
    volumes = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    events = EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(volumes)},
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :] * 3, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )
    # Two capacity points make the throughput/fidelity tradeoff explicit:
    # the small shapes run ~2x faster but shed branches under this
    # branch-dense trace (counted); the second point's shapes are DERIVED
    # from a 128-lane probe of the same trace (engine/sizing.py) and run
    # with every capacity counter zero (slab_missing alone is semantic:
    # reference-NPE trace states, KVSharedVersionedBuffer.java:86-89).
    points = [
        ("small", EngineConfig(max_runs=16, slab_entries=32, slab_preds=6,
                               dewey_depth=10, max_walk=10)),
    ]
    if os.environ.get("CEP_BENCH_AUTOSIZE", "1") != "0":
        sK = min(K, 128)
        sample = jax.tree_util.tree_map(lambda x: x[:sK], events)
        derived = autosize(
            pattern, sample,
            start=EngineConfig(max_runs=24, slab_entries=64, slab_preds=8,
                               dewey_depth=12, max_walk=12),
            margin=1.4, sweep_every=T,
        )
        log(f"kleene: autosized config {derived}")
        points.append(("derived", derived))
    else:
        points.append(
            ("large", EngineConfig(max_runs=24, slab_entries=64,
                                   slab_preds=8, dewey_depth=12,
                                   max_walk=12)))
    rate = 0.0
    for label, cfg in points:
        batch = BatchMatcher(pattern, K, cfg)
        state0 = batch.init_state()
        t0 = time.perf_counter()
        state, out = batch.scan(state0, events)
        jax.block_until_ready(out.count)
        log(f"kleene[{label}]: compile+first scan {time.perf_counter() - t0:.1f}s")
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            state, out = batch.scan(state0, events)
            jax.block_until_ready(out.count)
            best = min(best, time.perf_counter() - t0)
        matches = int(jnp.sum(out.count > 0))
        counters = batch.counters(state)
        capacity_zero = not any(capacity_counters(counters).values())
        log(
            f"kleene[{label}] (skip_till_any + oneOrMore, {K} lanes x {T}): "
            f"{K * T / best / 1e3:.0f}K ev/s, {matches} match slots, "
            f"capacity_zero={capacity_zero}, counters {counters}"
        )
        rate = max(rate, K * T / best)
    return rate


def bench_bank(n_list, total_lanes, T, reps):
    """BASELINE.json config 3: multi-pattern NFA bank over ~100K total key
    lanes — N parameterized query variants over the same stream, serial
    (one dispatch per query, the reference's one-CEPProcessor-per-pattern
    composition) vs stacked (one dispatch for the whole bank,
    parallel/stacked.py), at each bank width in ``n_list``.  The
    auto-chooser (choose_bank) picks per width from a 128-lane sample;
    its pick is logged next to the full-size outcome."""
    from kafkastreams_cep_tpu.parallel.stacked import (
        StackedBankMatcher,
        choose_bank,
    )

    def q(i):
        lo, hi = 95 + i * 5, 120 - i * 3
        return (
            Query()
            .select("a").where(lambda k, v, ts, st, lo=lo: v["price"] < lo)
            .then()
            .select("b").skip_till_next_match()
            .where(lambda k, v, ts, st, hi=hi: v["price"] > hi)
            .build()
        )

    cfg = EngineConfig(
        max_runs=8, slab_entries=16, slab_preds=4, dewey_depth=6, max_walk=6
    )
    rng = np.random.default_rng(13)
    results = {}
    for N in n_list:
        K = max((total_lanes // N) // 128 * 128, 128)
        prices = rng.integers(80, 141, size=(K, T)).astype(np.int32)
        events = EventBatch(
            key=jnp.broadcast_to(
                jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
            value={"price": jnp.asarray(prices)},
            ts=jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
            off=jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
            valid=jnp.ones((K, T), bool),
        )
        patterns = [q(i) for i in range(N)]
        sample = jax.tree_util.tree_map(lambda x: x[:128], events)
        mode, det = choose_bank(patterns, cfg, sample, reps=1)

        t0 = time.perf_counter()
        matchers = [BatchMatcher(p, K, cfg) for p in patterns]
        states = [m.init_state() for m in matchers]
        outs = [m.scan(s, events) for m, s in zip(matchers, states)]
        jax.block_until_ready([o[1].count for o in outs])
        serial_compile = time.perf_counter() - t0
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            outs = [m.scan(s, events) for m, s in zip(matchers, states)]
            jax.block_until_ready([o[1].count for o in outs])
            best = min(best, time.perf_counter() - t0)
        total = N * K * T
        serial = total / best
        del matchers, states, outs  # free HBM before the fused compile

        t0 = time.perf_counter()
        bank = StackedBankMatcher(patterns, K, cfg)
        bstate0 = bank.init_state()
        bstate, bout = bank.scan(bstate0, events)
        jax.block_until_ready(bout.count)
        fused_compile = time.perf_counter() - t0
        bbest = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            bstate, bout = bank.scan(bstate0, events)
            jax.block_until_ready(bout.count)
            bbest = min(bbest, time.perf_counter() - t0)
        fused = total / bbest
        del bank, bstate0, bstate, bout

        winner = "fused" if bbest < best else "serial"
        agreed = (mode == "stacked") == (winner == "fused")
        log(
            f"bank[N={N}] ({N} queries x {K} lanes, {T} events): "
            f"serial {serial / 1e3:.0f}K q-ev/s (compile {serial_compile:.0f}s"
            f" for {N} programs), fused {fused / 1e3:.0f}K q-ev/s (compile "
            f"{fused_compile:.0f}s for 1), fused/serial {best / bbest:.2f}x; "
            f"chooser picked {mode} on the 128-lane sample "
            f"({'agrees' if agreed else 'DISAGREES'} with full size)"
        )
        results[N] = {
            "serial_qevps": serial,
            "fused_qevps": fused,
            "winner": winner,
            "chooser": mode,
        }
    return results


def bench_tenants():
    """``CEP_BENCH_TENANTS``: multi-tenant bank sweep (ISSUE 14).

    N strict-sequence queries drawn Zipf-style from a small template
    pool — the SaaS-monitoring shape: thousands of tenants install
    near-identical alert rules, so prefixes repeat heavily with a long
    tail of variants.  Every query is pure strict contiguity, so the
    tenant bank (``parallel/tenantbank.py``) runs the ENTIRE bank on the
    shared stencil screen: one deduplicated predicate matrix + one
    vmapped prefix recurrence, no NFA stepping at all.  The baseline is
    the naive-fused :class:`StackedBankMatcher` — one dispatch, but every
    query's full NFA machinery on every lane (measured up to
    ``CEP_BENCH_TENANTS_FUSED_MAX`` queries; beyond that its compile
    dominates and only the tenant side is recorded).  Matches must be
    bit-identical and both sides loss-free for the speedup to count —
    ``tenant_match_parity`` / ``tenant_loss_flags`` join the bench gate.
    """
    from kafkastreams_cep_tpu.parallel.stacked import StackedBankMatcher
    from kafkastreams_cep_tpu.parallel.tenantbank import TenantBankMatcher

    n_list = [
        int(x)
        for x in os.environ.get(
            "CEP_BENCH_TENANTS_N", "100,300,1000"
        ).split(",")
    ]
    K = int(os.environ.get("CEP_BENCH_TENANTS_K", "8"))
    T = int(os.environ.get("CEP_BENCH_TENANTS_T", "64"))
    reps = int(os.environ.get("CEP_BENCH_TENANTS_REPS", "3"))
    pool_n = int(os.environ.get("CEP_BENCH_TENANTS_POOL", "16"))
    fused_max = int(
        os.environ.get("CEP_BENCH_TENANTS_FUSED_MAX", "300")
    )
    cfg = EngineConfig(
        max_runs=4, slab_entries=16, slab_preds=4, dewey_depth=8,
        max_walk=4,
    )
    rng = np.random.default_rng(29)
    # Template pool over a 64-symbol alphabet (the bench_tier shape):
    # (a, b) prefix pairs; each query appends its own final symbol, so
    # queries differ while prefixes collapse onto the pool.
    pool = [
        (int(a), int(b))
        for a, b in rng.integers(1, 8, size=(pool_n, 2))
    ]

    def q(a, b, c):
        return (
            Query()
            .select("pa").where(lambda k, v, ts, st, a=a: v == a)
            .then()
            .select("pb").where(lambda k, v, ts, st, b=b: v == b)
            .then()
            .select("pc").where(lambda k, v, ts, st, c=c: v == c)
            .build()
        )

    # Match-sparse traffic with planted full occurrences so parity is
    # non-vacuous: codes outside the predicate range almost everywhere.
    codes = rng.integers(8, 64, size=(K, T)).astype(np.int32)
    planted = []
    for i in range(6):
        k = int(rng.integers(0, K))
        t = int(rng.integers(0, T - 3))
        planted.append((k, t))
    events = None  # built per N after the plants target real queries

    sweep = {}
    all_parity, all_zero = True, True
    for N in n_list:
        # Zipf-heavy template draw: a few templates carry most tenants.
        z = rng.zipf(1.5, size=N)
        params = []
        for i in range(N):
            a, b = pool[int(z[i] - 1) % pool_n]
            c = int(rng.integers(1, 8))
            params.append((a, b, c))
        ev_codes = codes.copy()
        for j, (k, t) in enumerate(planted):
            a, b, c = params[j % len(params)]
            ev_codes[k, t], ev_codes[k, t + 1], ev_codes[k, t + 2] = (
                a, b, c,
            )
        events = EventBatch(
            key=jnp.broadcast_to(
                jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
            value=jnp.asarray(ev_codes),
            ts=jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
            off=jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
            valid=jnp.ones((K, T), bool),
        )
        patterns = [q(*p) for p in params]

        t0 = time.perf_counter()
        bank = TenantBankMatcher(patterns, K, cfg)
        st0 = bank.init_state()
        st, out = bank.scan(st0, events)
        jax.block_until_ready(out.count)
        tb_compile = time.perf_counter() - t0
        tbest = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            st, out = bank.scan(st0, events)
            jax.block_until_ready(out.count)
            tbest = min(tbest, time.perf_counter() - t0)
        total = N * K * T
        tcount = np.asarray(out.count)
        tstage, toff = np.asarray(out.stage), np.asarray(out.off)
        tcounters = bank.counters(st)
        stats = bank.bank.stats
        del st0, st, out

        fused_qevps = None
        speedup = None
        parity = None
        zero = all(v == 0 for v in tcounters.values())
        if N <= fused_max:
            t0 = time.perf_counter()
            naive = StackedBankMatcher(patterns, K, cfg)
            ns0 = naive.init_state()
            ns, nout = naive.scan(ns0, events)
            jax.block_until_ready(nout.count)
            nv_compile = time.perf_counter() - t0
            nbest = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                ns, nout = naive.scan(ns0, events)
                jax.block_until_ready(nout.count)
                nbest = min(nbest, time.perf_counter() - t0)
            parity = (
                np.array_equal(tcount, np.asarray(nout.count))
                and np.array_equal(tstage, np.asarray(nout.stage))
                and np.array_equal(toff, np.asarray(nout.off))
            )
            ncounters = naive.counters(ns)
            zero = zero and all(v == 0 for v in ncounters.values())
            fused_qevps = total / nbest
            speedup = nbest / tbest
            all_parity &= bool(parity)
            del naive, ns0, ns, nout
        all_zero &= bool(zero)
        log(
            f"tenants[N={N}] ({N} queries x {K} lanes x {T} events, "
            f"{stats['prefix_columns_distinct']}/"
            f"{stats['prefix_columns_total']} distinct prefix columns, "
            f"dedup {stats['pred_dedup_ratio']:.1f}x): shared-screen "
            f"{total / tbest / 1e3:.0f}K q-ev/s (compile {tb_compile:.1f}s)"
            + (
                f", naive-fused {fused_qevps / 1e3:.0f}K q-ev/s, "
                f"speedup {speedup:.2f}x, parity={parity}, zero={zero}"
                if fused_qevps is not None
                else f", naive-fused skipped (N > {fused_max})"
            )
        )
        sweep[str(N)] = {
            "shared_qevps": round(total / tbest, 1),
            "fused_qevps": (
                round(fused_qevps, 1) if fused_qevps else None
            ),
            "speedup": round(speedup, 3) if speedup else None,
            "match_slots": int((tcount > 0).sum()),
            "match_parity": parity,
            "counters_zero": bool(zero),
            "prefix_columns_distinct": stats["prefix_columns_distinct"],
            "prefix_columns_total": stats["prefix_columns_total"],
            "prefix_shared_hit_rate": round(
                float(stats["prefix_shared_hit_rate"]), 4
            ),
            "pred_dedup_ratio": round(
                float(stats["pred_dedup_ratio"]), 3
            ),
        }
    return {
        "k": K, "t": T, "pool": pool_n,
        "sweep": sweep,
        # The gate flags: parity/loss over every N that ran the fused
        # baseline (bench_gate flattens these to tenant_*).
        "match_parity": bool(all_parity),
        "counters_zero": bool(all_zero),
    }


def bench_sharded_folds(K, T, reps):
    """BASELINE.json config 4: WITHIN window + fold(avg,volume) predicates
    over ~1M key lanes, sharded over the available mesh (one chip here;
    the sharding layer is the same shard_map program that lays lanes over
    a v5e-8 — stderr-reported secondary)."""
    from kafkastreams_cep_tpu.parallel import ShardedMatcher, key_mesh

    rng = np.random.default_rng(17)
    prices = rng.integers(90, 131, size=(K, T)).astype(np.int32)
    volumes = rng.integers(600, 1101, size=(K, T)).astype(np.int32)
    host_events = EventBatch(
        key=jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, T)),
        value={"price": jnp.asarray(prices), "volume": jnp.asarray(volumes)},
        ts=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :] * 2, (K, T)),
        off=jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (K, T)),
        valid=jnp.ones((K, T), bool),
    )
    # Round 4 ran this line with dewey_depth=8 and carried 222K
    # ver_overflows (straddling runs append a version digit per event,
    # NFA.java:185-188) plus assorted capacity drops.  The config is now
    # DERIVED from a 128-lane probe of the same trace so the measured
    # number is overflow- and capacity-drop-free.
    if os.environ.get("CEP_BENCH_AUTOSIZE", "1") != "0":
        # 512-lane sample: a 128-lane probe missed a rare pointer-width
        # peak at 32768 lanes (slab_pred_drops 2 in 524K events); rare
        # maxima need a sample big enough to contain them.
        sample = jax.tree_util.tree_map(lambda x: x[:min(K, 512)], host_events)
        cfg = autosize(
            stock_demo.stock_pattern(), sample,
            start=EngineConfig(max_runs=8, slab_entries=16, slab_preds=4,
                               dewey_depth=24, max_walk=8),
            margin=1.5, sweep_every=T,
        )
        log(f"sharded-folds: autosized config {cfg}")
    else:
        cfg = EngineConfig(
            max_runs=8, slab_entries=16, slab_preds=4, dewey_depth=24,
            max_walk=8,
        )
    mesh = key_mesh()
    m = ShardedMatcher(stock_demo.stock_pattern(), K, mesh, cfg)
    state0 = m.init_state()
    events = m.shard_events(host_events)
    t0 = time.perf_counter()
    state, out = m.scan(state0, events)
    jax.block_until_ready(out.count)
    log(f"sharded-folds: compile+first scan {time.perf_counter() - t0:.1f}s "
        f"on mesh {mesh.devices.shape}")
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        state, out = m.scan(state0, events)
        jax.block_until_ready(out.count)
        best = min(best, time.perf_counter() - t0)
    from kafkastreams_cep_tpu.utils.metrics import device_memory_stats

    stats = m.stats(state)
    capacity_zero = not any(capacity_counters(stats).values())
    log(
        f"sharded folds+window ({K} lanes x {T} events, "
        f"{mesh.devices.size} device(s)): {K * T / best / 1e3:.0f}K ev/s, "
        f"capacity_zero={capacity_zero}, stats {stats}, "
        f"hbm {device_memory_stats()}"
    )
    return K * T / best


def phase_latency_block(snap):
    """Per-phase p50/p99 milliseconds out of a ``metrics_snapshot()``'s
    ``phases`` histograms — the headline JSON's tail-behavior block (the
    BENCH trajectory previously captured throughput only)."""
    out = {}
    for name, h in sorted(snap.get("phases", {}).items()):
        if h["count"]:
            out[name] = {
                "count": h["count"],
                "p50_ms": round(h["p50"] * 1e3, 3),
                "p99_ms": round(h["p99"] * 1e3, 3),
            }
    return out


def bench_processor(K, T, n_batches):
    """Processor-level throughput at the headline config (SURVEY §2.2 PP
    row): columnar ingestion + pipelined dispatch + compacted decode.
    The gap to the engine-level rate is the host runtime's overhead —
    round 4 paid pack + full-grid pull + sync serially on every batch.
    Returns ``(events/s, per-phase p50/p99 block)``."""
    from kafkastreams_cep_tpu.runtime import CEPProcessor

    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    proc = CEPProcessor(
        stock_demo.stock_pattern(), K, cfg, epoch=0, pipeline=True,
        decode_budget=int(os.environ.get("CEP_BENCH_DECODE_BUDGET", "131072")),
    )
    rng = np.random.default_rng(23)
    N = K * T
    keys = np.tile(np.arange(K, dtype=np.int64), T)
    prices = rng.integers(90, 131, size=N).astype(np.int64)
    # Calibrated to ~1% match rate (0.5% begin spikes over a sub-
    # threshold base; the converging avg fold otherwise keeps every begun
    # lineage matching repeatedly — the headline trace's 139% match rate
    # measures Python match-object materialization, not the pipeline.
    # Every emitted match is a contractual host Sequence either way; this
    # line is about transport/packing/decode overlap, and the
    # engine-vs-oracle numbers cover matching cost).
    volumes = np.where(
        rng.random(N) < 0.005, 1100, rng.integers(700, 1000, size=N)
    ).astype(np.int64)

    def feed(b):
        ts = np.int64(b) * N + np.arange(N, dtype=np.int64)
        return proc.process_columns(
            keys, {"price": prices, "volume": volumes}, ts
        )

    t0 = time.perf_counter()  # host-timed (decode device_gets materialize)
    feed(0)
    proc.flush()
    log(f"processor: compile+first batch {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()  # host-timed (decode device_gets materialize)
    n_matches = 0
    for b in range(1, n_batches + 1):
        n_matches += len(feed(b))
    n_matches += len(proc.flush())
    dt = time.perf_counter() - t0
    snap = proc.metrics_snapshot(per_lane=False)
    phases = phase_latency_block(snap)
    log(
        f"processor (pipelined columnar, {K} lanes x {T} ev x "
        f"{n_batches} batches): {n_batches * N / dt / 1e3:.0f}K ev/s "
        f"end-to-end, {n_matches} matches, decode_fallbacks "
        f"{snap['decode_fallbacks']}, wall {dt:.2f}s (pipelined sections "
        f"overlap: device {snap['device_seconds']:.2f}s + decode "
        f"{snap['decode_seconds']:.2f}s measured independently)"
    )
    log(f"processor: per-phase latency {json.dumps(phases)}")
    return n_batches * N / dt, phases


def bench_metrics(K, T, n_batches, jsonl=None):
    """``CEP_BENCH_METRICS=1``: the headline stock config run under the
    full telemetry pipeline — JSONL trace sink + Reporter cadence +
    Prometheus rendering — printing the per-phase p50/p99 block.  Kept as
    a plain function over (K, T, n_batches) so the tier-1 smoke test
    (tests/test_telemetry.py) can drive it at tiny shapes — the extra
    cannot silently rot.  Returns ``(phase block, events written)``."""
    import io

    from kafkastreams_cep_tpu.runtime import CEPProcessor
    from kafkastreams_cep_tpu.utils.telemetry import (
        JsonlTraceSink,
        Reporter,
        render_prometheus,
    )

    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    buf = jsonl if jsonl is not None else io.StringIO()
    sink = JsonlTraceSink(buf)
    proc = CEPProcessor(
        stock_demo.stock_pattern(), K, cfg, epoch=0, trace_sink=sink,
    )
    reporter = Reporter(
        proc.metrics_snapshot, sink,
        every_batches=max(n_batches // 2, 1),
    )
    rng = np.random.default_rng(31)
    N = K * T
    keys = np.tile(np.arange(K, dtype=np.int64), T)
    for b in range(n_batches):
        prices = rng.integers(90, 131, size=N).astype(np.int64)
        volumes = rng.integers(600, 1101, size=N).astype(np.int64)
        ts = np.int64(b) * N + np.arange(N, dtype=np.int64)
        proc.process_columns(keys, {"price": prices, "volume": volumes}, ts)
        reporter.tick()
    snap = reporter.flush()
    block = phase_latency_block(snap)
    n_events = (
        buf.getvalue().count("\n") if isinstance(buf, io.StringIO) else None
    )
    log(
        f"metrics ({K} lanes x {T} ev x {n_batches} batches under the "
        f"Reporter): {reporter.flushes} snapshot flushes, "
        f"{n_events} JSONL events; per-phase latency {json.dumps(block)}"
    )
    prom = render_prometheus(snap)
    log(
        f"metrics: prometheus exposition {len(prom.splitlines())} lines "
        f"(e.g. {prom.splitlines()[0]!r})"
    )
    return block, n_events


def bench_resilience():
    """Supervisor fault-path latencies (ISSUE 2: track them across PRs).

    Three numbers, all wall-clock on this environment:

    * ``checkpoint_s`` — one full snapshot (state device_get + pickle);
    * ``recover_s``    — one restore-and-replay cycle (checkpoint restore,
      which recompiles the matcher, + journal-tail replay);
    * ``escalate_s``   — one capacity escalation end-to-end: rollback,
      live-state migration onto the wider config (another compile),
      post-escalation snapshot, and the re-processed batch.

    Both recovery and escalation are compile-dominated: each builds a
    fresh matcher, so the persistent compilation cache is the main lever
    (PROFILE_r06.md context).  Sizes kept small — these are latency
    probes, not throughput lines.
    """
    import shutil
    import tempfile

    from kafkastreams_cep_tpu.engine.sizing import EscalationPolicy
    from kafkastreams_cep_tpu.runtime import Record, Supervisor

    workdir = tempfile.mkdtemp(prefix="cep_bench_resil_")
    out = {}
    try:
        K = int(os.environ.get("CEP_BENCH_RESIL_K", "64"))
        n_batches = 4
        batch_records = int(os.environ.get("CEP_BENCH_RESIL_B", "512"))
        cfg = EngineConfig(
            max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
            max_walk=12,
        )
        rng = np.random.default_rng(5)

        def mk_batch(b, spike=0.005):
            n = batch_records
            keys = rng.integers(0, K, size=n)
            prices = rng.integers(90, 131, size=n)
            vols = np.where(
                rng.random(n) < spike, 1100, rng.integers(700, 1000, size=n)
            )
            return [
                Record(
                    int(keys[i]),
                    {"price": int(prices[i]), "volume": int(vols[i])},
                    b * n + i,
                )
                for i in range(n)
            ]

        sup = Supervisor(
            stock_demo.stock_pattern(), K, cfg, epoch=0,
            checkpoint_path=os.path.join(workdir, "r.ckpt"),
            journal_path=os.path.join(workdir, "r.jrnl"),
            checkpoint_every=10**6,
        )
        for b in range(n_batches):
            sup.process(mk_batch(b))
        t0 = time.perf_counter()  # host-timed (checkpoint device_gets)
        sup.checkpoint()
        out["checkpoint_s"] = round(time.perf_counter() - t0, 3)
        for b in range(n_batches, 2 * n_batches):
            sup.process(mk_batch(b))
        t0 = time.perf_counter()  # host-timed (restore + replay)
        sup._recover()  # restore + replay the n_batches journal tail
        out["recover_s"] = round(time.perf_counter() - t0, 3)

        tiny = EngineConfig(
            max_runs=8, slab_entries=32, slab_preds=4, dewey_depth=12,
            max_walk=12,
        )
        esc = Supervisor(
            stock_demo.stock_pattern(), K, tiny, epoch=0,
            checkpoint_path=os.path.join(workdir, "e.ckpt"),
            checkpoint_every=10**6,
            auto_escalate=EscalationPolicy(max_config=cfg),
        )
        # Match-dense trace (20% begin spikes): run counts overflow
        # max_runs=8 within a few batches.
        esc.process(mk_batch(100, spike=0.2))
        b = 101
        t0 = time.perf_counter()  # host-timed (escalation cycle)
        while esc.escalations == 0 and b < 120:
            t0 = time.perf_counter()  # host-timed (escalation cycle)
            esc.process(mk_batch(b, spike=0.2))
            b += 1
        if esc.escalations:
            out["escalate_s"] = round(time.perf_counter() - t0, 3)
        log(
            f"resilience (K={K}, {batch_records}-record batches): "
            f"checkpoint {out.get('checkpoint_s')}s, recovery "
            f"{out.get('recover_s')}s (restore + {n_batches}-batch "
            f"replay), escalation {out.get('escalate_s')}s (rollback + "
            f"migrate + snapshot + re-process; escalations="
            f"{esc.escalations})"
        )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return out


def bench_shard_fault():
    """``CEP_BENCH_SHARDF``: shard fault tolerance probes (ISSUE 13).

    Two supervisor-level scenarios on a 2-device sub-mesh, each compared
    for match parity against a fault-free single-device run of the same
    stream:

    * **kill one shard** — a ``ShardLost`` out of the meshed dispatch
      mid-stream.  ``evacuate_s`` is the wall-clock of the batch that
      absorbs the loss (rollback + journal replay + re-pin onto the
      surviving sub-mesh + the re-processed batch); ``post_evac_evps``
      is the degraded throughput afterwards.  ``evac_parity`` requires
      exactly-once emission vs the fault-free run.
    * **hot-key rebalance** — a skewed stream (two keys take ~all the
      work, both on shard 0) trips the heavy-hitter policy at a
      checkpoint boundary.  ``rebalance_lossfree`` is the loss
      contract: at least one move happened, zero dropped or duplicated
      matches, capacity counters clean.

    Both flags are guarded by bench_gate.py once recorded.  Returns
    ``{}`` (and the whole block is absent from the JSON) on a
    single-device host.
    """
    import shutil
    import tempfile

    from kafkastreams_cep_tpu.parallel import ShardLost, key_mesh
    from kafkastreams_cep_tpu.runtime import (
        CEPProcessor,
        Record,
        ShardPolicy,
        Supervisor,
    )
    from kafkastreams_cep_tpu.utils import failpoints as fp

    if jax.device_count() < 2:
        log("shard-fault: skipped (needs >= 2 devices)")
        return {}

    K = int(os.environ.get("CEP_BENCH_SHARDF_K", "16"))
    batch_records = int(os.environ.get("CEP_BENCH_SHARDF_B", "256"))
    n_batches = 6
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    rng = np.random.default_rng(7)

    def mk_batches(n, offs, skew=False):
        # Explicit per-key offsets: rollback + journal replay must dedup
        # re-presented records, and auto offsets would double-emit.
        # ``skew``: batch 0 touches every lane round-robin (pinning key i
        # to lane i, so keys 0/1 share shard 0), later batches hit only
        # keys 0 and 1.
        out_b = []
        for i in range(n):
            recs = []
            for j in range(batch_records):
                if skew:
                    k = int(rng.integers(2)) if i else (j % K)
                else:
                    k = int(rng.integers(K))
                vol = 1100 if rng.random() < 0.01 else int(
                    rng.integers(700, 1000)
                )
                recs.append(Record(
                    k,
                    {"price": int(rng.integers(90, 131)), "volume": vol},
                    1000 + batch_records * i + j,
                    offset=offs.setdefault(k, 0),
                ))
                offs[k] += 1
            out_b.append(recs)
        return out_b

    def canon(matches):
        return sorted(
            (k, tuple(sorted(
                (stage, tuple(e.offset for e in evs))
                for stage, evs in seq.as_map().items()
            )))
            for k, seq in matches
        )

    def oracle(batches):
        proc = CEPProcessor(
            stock_demo.stock_pattern(), K, cfg, gc_interval=0
        )
        out_m = []
        for b in batches:
            out_m += proc.process(b)
        return canon(out_m + proc.flush())

    out = {}
    workdir = tempfile.mkdtemp(prefix="cep_bench_shardf_")
    try:
        batches = mk_batches(n_batches, {})
        sup = Supervisor(
            stock_demo.stock_pattern(), K, cfg,
            checkpoint_path=os.path.join(workdir, "s.ckpt"),
            journal_path=os.path.join(workdir, "s.jrnl"),
            checkpoint_every=2, gc_interval=0,
            mesh=key_mesh(jax.devices()[:2]),
        )
        got = []
        for b in batches[:2]:
            got += sup.process(b)
        t0 = time.perf_counter()  # host-timed (evacuation + re-process)
        with fp.FAILPOINTS.session(
            {"shard.dispatch": [0]},
            exc=lambda: ShardLost("bench-injected device loss", shard=1),
        ):
            got += sup.process(batches[2])
        out["evacuate_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()  # host-timed (degraded throughput)
        for b in batches[3:]:
            got += sup.process(b)
        post_s = time.perf_counter() - t0
        got += sup.processor.flush()
        out["post_evac_evps"] = round(
            batch_records * (n_batches - 3) / post_s, 1
        )
        out["evac_parity"] = bool(
            sup.evacuations == 1 and canon(got) == oracle(batches)
        )

        skew = mk_batches(n_batches, {}, skew=True)
        sup2 = Supervisor(
            stock_demo.stock_pattern(), K, cfg,
            checkpoint_path=os.path.join(workdir, "r.ckpt"),
            journal_path=os.path.join(workdir, "r.jrnl"),
            checkpoint_every=2, gc_interval=0,
            mesh=key_mesh(jax.devices()[:2]),
            shard_policy=ShardPolicy(
                rebalance_skew=1.2, rebalance_min_hops=8,
                rebalance_streak=1, rebalance_cooldown=0,
            ),
        )
        got2 = []
        for b in skew:
            got2 += sup2.process(b)
        got2 += sup2.processor.flush()
        out["rebalance_moves"] = int(sup2.rebalances)
        out["rebalance_lanes_moved"] = int(sup2.lanes_moved)
        ph = sup2.metrics_snapshot(per_lane=False)["phases"].get(
            "rebalance"
        )
        if ph and ph.get("count"):
            out["rebalance_s"] = round(float(ph["p50"]), 3)
        out["rebalance_lossfree"] = bool(
            sup2.rebalances >= 1
            and not any(sup2.processor.counters().values())
            and canon(got2) == oracle(skew)
        )
        log(
            f"shard-fault (K={K}, {batch_records}-record batches): "
            f"evacuate {out['evacuate_s']}s (parity="
            f"{out['evac_parity']}), post-evacuation "
            f"{out['post_evac_evps']} events/s, rebalance moves="
            f"{out['rebalance_moves']} lanes={out['rebalance_lanes_moved']}"
            f" (lossfree={out['rebalance_lossfree']})"
        )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return out


def bench_tenant_iso():
    """``CEP_BENCH_TENANT_ISO``: per-tenant isolation probes (ISSUE 17).

    One tenant floods the bank — a promote-every-pair prefix whose
    suffix never closes, the run-queue-exhausting worst case — while
    compliant tenants run a normal workload with the flooder's quota
    enforced (``match_rate_budget=0``: every one of its prefix fires is
    shed at the shared screen).

    * ``clean_evps`` / ``flooded_evps`` — compliant-workload record
      throughput without and with the quota-limited flooding tenant;
    * ``shed_fires`` — the flooder's screen sheds (must be > 0 or the
      scenario was vacuous);
    * ``quarantine_s`` — quarantine-entry latency: the enforcement
      rebuild (column gating + fresh screen jit) plus the first batch
      dispatched with the tenant dark;
    * ``parity`` — compliant tenants' matches bit-equal to a bank that
      never contained the flooder (the blast-radius contract,
      guarded by bench_gate once recorded);
    * ``compliant_lossfree`` — compliant tenants shed nothing: zero
      ``quota_shed`` and zero capacity-loss counters.
    """
    from kafkastreams_cep_tpu import Query
    from kafkastreams_cep_tpu.compiler.multitenant import TenantQuota
    from kafkastreams_cep_tpu.runtime import Record
    from kafkastreams_cep_tpu.runtime.tenant import TenantCEP

    K = int(os.environ.get("CEP_BENCH_TENANT_ISO_K", "64"))
    n_batches = int(os.environ.get("CEP_BENCH_TENANT_ISO_BATCHES", "6"))
    batch_records = int(os.environ.get("CEP_BENCH_TENANT_ISO_B", "2048"))
    # Sized so the COMPLIANT workload is loss-free (the lossfree flag is
    # about isolation, not capacity): the flooder never reaches the
    # engine — its pressure lands on the shared screen and is shed there.
    cfg = EngineConfig(
        max_runs=16, slab_entries=64, slab_preds=8, dewey_depth=128,
        max_walk=8,
    )

    def _ge(th):
        return lambda k, v, ts, st, th=th: v["x"] >= th

    def _lt(th):
        return lambda k, v, ts, st, th=th: v["x"] < th

    def q3(a, b, c):
        return (
            Query()
            .select("a").where(_ge(a)).then()
            .select("b").where(_lt(b)).then()
            .select("c").where(_ge(c)).build()
        )

    def qh(a, b, z):
        return (
            Query()
            .select("a").where(_ge(a)).then()
            .select("b").where(_lt(b)).then()
            .select("z").skip_till_next_match().where(_ge(z)).build()
        )

    def compliant_patterns():
        return {"spike": q3(8, 3, 7), "dip": qh(8, 3, 9)}

    def flooded_patterns():
        out = compliant_patterns()
        out["flood"] = qh(0, 10, 99)  # fires every pair, never closes
        return out

    rng = np.random.default_rng(17)
    per_lane = max(batch_records // K, 2)
    ts = 0
    bs = []
    for _ in range(n_batches + 1):  # +1: the quarantine-entry batch
        recs = []
        for i in range(per_lane * K):
            ts += 1
            recs.append(
                Record(i % K, {"x": int(rng.integers(0, 10))}, ts)
            )
        bs.append(recs)

    def canon(matches):
        return [
            (qn, k, tuple(sorted(
                (st, e.partition, e.offset)
                for st, evs in seq.as_map().items()
                for e in evs
            )))
            for qn, k, seq in matches
        ]

    out = {}
    clean = TenantCEP(compliant_patterns(), K, cfg)
    clean.process(bs[0])  # warm the compile before timing
    t0 = time.perf_counter()  # host-timed (compliant-only throughput)
    clean_m = [canon(clean.process(b)) for b in bs[1:n_batches]]
    dt = time.perf_counter() - t0
    out["clean_evps"] = round(per_lane * K * (n_batches - 1) / dt, 1)

    flooded = TenantCEP(
        flooded_patterns(), K, cfg,
        quotas={"flood": TenantQuota(match_rate_budget=0.0)},
    )
    flooded.process(bs[0])
    t0 = time.perf_counter()  # host-timed (1 flooding tenant, quotaed)
    fl_m = [canon(flooded.process(b)) for b in bs[1:n_batches]]
    dt = time.perf_counter() - t0
    out["flooded_evps"] = round(per_lane * K * (n_batches - 1) / dt, 1)

    pq = flooded.per_query_counters()
    out["shed_fires"] = pq["flood"]["quota_shed"]

    t0 = time.perf_counter()  # host-timed (rebuild + first dark batch)
    flooded.quarantine("flood", "bench")
    q_m = canon(flooded.process(bs[n_batches]))
    out["quarantine_s"] = round(time.perf_counter() - t0, 3)
    clean_q = canon(clean.process(bs[n_batches]))

    compliant = lambda ms: [m for m in ms if m[0] != "flood"]
    out["parity"] = bool(
        [compliant(m) for m in fl_m] == clean_m
        and compliant(q_m) == clean_q
    )
    out["compliant_lossfree"] = bool(
        out["shed_fires"] > 0
        and all(
            pq[n]["quota_shed"] == 0
            and all(pq[n][c] == 0 for c in (
                "run_drops", "ver_overflows", "slab_full_drops",
                "slab_pred_drops", "slab_trunc", "handle_overflows",
            ))
            for n in ("spike", "dip")
        )
    )
    log(
        f"tenant-iso (K={K}, {per_lane * K}-record batches): compliant "
        f"{out['clean_evps']} ev/s clean vs {out['flooded_evps']} ev/s "
        f"with a quota-limited flooder ({out['shed_fires']} fires shed), "
        f"quarantine entry {out['quarantine_s']}s, parity="
        f"{out['parity']}, compliant_lossfree={out['compliant_lossfree']}"
    )
    return out


def bench_latency():
    """``CEP_BENCH_LATENCY``: end-to-end latency attribution (ISSUE 18).

    The segment ledger on the record-path processor, three ways:

    * **Ledger A/B** — the same in-order stream with the ledger off vs
      on: matches and loss counters must stay bit-identical
      (``parity``, guarded by bench_gate once recorded) and the
      host-side stamping cost is reported (``ledger_overhead_pct``);
    * **Drain-cadence A/B** — ``drain_interval`` 1 vs ``D`` under lazy
      extraction: deferral trades emit latency (the ``drain_defer``
      segment) for fewer device_get round-trips, and the ledger makes
      the trade visible per segment instead of folded into e2e;
    * **Reorder-grace A/B** — watermark guard with grace 0 vs ``G`` ms
      on the same in-order stream: the grace window surfaces as
      ``reorder_hold`` p99, the latency price of skew tolerance.

    ``e2e_p99_s`` (the ledgered baseline's end-to-end p99) joins
    bench_gate as a lower-is-better ceiling.  Record-path rates are
    host-bound (µs/record Python), so the overhead number is relative,
    like bench_ooo's.  ``CEP_BENCH_LATENCY_{K,B,BATCHES,GRACE,DRAIN,RING}``
    size it.
    """
    from kafkastreams_cep_tpu.runtime import CEPProcessor, IngestPolicy, Record
    from kafkastreams_cep_tpu.utils.latency import LatencyLedger

    K = int(os.environ.get("CEP_BENCH_LATENCY_K", "64"))
    n_batches = int(os.environ.get("CEP_BENCH_LATENCY_BATCHES", "8"))
    batch_records = int(os.environ.get("CEP_BENCH_LATENCY_B", "2048"))
    grace = int(os.environ.get("CEP_BENCH_LATENCY_GRACE", "64"))
    drain = int(os.environ.get("CEP_BENCH_LATENCY_DRAIN", "8"))
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    # The cadence A/B runs BOTH sides on this config so only
    # drain_interval differs: deferral parks completed chains and match
    # handles until the drain, so it needs slab headroom (2x, like the
    # lazy A/B's default) and a ring sized for `drain` batches of
    # handles — otherwise the comparison measures drop policy, not
    # scheduling, and parity stops meaning "cadence is pure scheduling".
    lazy_cfg = EngineConfig(
        max_runs=24, slab_entries=96, slab_preds=8, dewey_depth=12,
        max_walk=12, lazy_extraction=True,
        handle_ring=int(os.environ.get("CEP_BENCH_LATENCY_RING", "512")),
    )
    rng = np.random.default_rng(18)
    N = n_batches * batch_records
    keys = rng.integers(0, K, size=N)
    prices = rng.integers(90, 131, size=N)
    vols = np.where(
        rng.random(N) < 0.005, 1100, rng.integers(700, 1000, size=N)
    )
    ts = np.arange(N, dtype=np.int64) * 2  # distinct event times
    recs = [
        Record(
            int(keys[i]),
            {"price": int(prices[i]), "volume": int(vols[i])},
            int(ts[i]),
            offset=i,
        )
        for i in range(N)
    ]

    def canon(matches):
        # Emission order differs across drain cadences (deferred matches
        # flush late) and the ingest guard renumbers offsets per lane, so
        # parity compares the sorted canonical set keyed by event time —
        # globally distinct in this stream by construction.
        return sorted(
            (k, tuple(sorted(
                (st, e.timestamp)
                for st, evs in seq.as_map().items()
                for e in evs
            )))
            for k, seq in matches
        )

    def run(policy, drain_interval, config, ledger):
        proc = CEPProcessor(
            stock_demo.stock_pattern(), K, config, epoch=0, ingest=policy,
            drain_interval=drain_interval, latency=ledger,
        )
        warm = min(2, n_batches - 1)
        matches = []
        for b in range(warm):
            matches += proc.process(
                recs[b * batch_records:(b + 1) * batch_records]
            )
        t0 = time.perf_counter()  # host-timed (record path is host-bound)
        for b in range(warm, n_batches):
            matches += proc.process(
                recs[b * batch_records:(b + 1) * batch_records]
            )
        matches += proc.drain_ingest()
        matches += proc.flush()
        dt = time.perf_counter() - t0
        return proc, canon(matches), (n_batches - warm) * batch_records / dt

    def segs(proc):
        snap = proc.ledger.snapshot()["segments"]
        return {
            name: {
                "count": s["count"],
                "p50_s": round(s["p50"], 6),
                "p99_s": round(s["p99"], 6),
            }
            for name, s in snap.items() if s["count"]
        }

    out = {"records": N, "grace_ms": grace, "drain_interval": drain}
    p_off, m_off, evps_off = run(None, 1, cfg, None)
    p_on, m_on, evps_on = run(None, 1, cfg, LatencyLedger())
    out["parity"] = bool(
        m_off == m_on and p_off.counters() == p_on.counters()
    )
    out["matches"] = len(m_on)
    out["evps_ledger_off"] = round(evps_off, 1)
    out["evps_ledger_on"] = round(evps_on, 1)
    out["ledger_overhead_pct"] = round(100 * (1 - evps_on / evps_off), 1)
    base = segs(p_on)
    out["segments"] = base
    out["e2e_p99_s"] = base["e2e_total"]["p99_s"]

    p_d1, m_d1, _ = run(None, 1, lazy_cfg, LatencyLedger())
    p_dn, m_dn, _ = run(None, drain, lazy_cfg, LatencyLedger())
    out["drain_ab"] = {
        "interval_1": segs(p_d1),
        f"interval_{drain}": segs(p_dn),
    }
    p_g0, m_g0, _ = run(IngestPolicy(grace_ms=0), 1, cfg, LatencyLedger())
    p_gg, m_gg, _ = run(
        IngestPolicy(grace_ms=grace), 1, cfg, LatencyLedger()
    )
    out["grace_ab"] = {
        "grace_0": segs(p_g0),
        f"grace_{grace}": segs(p_gg),
    }
    # Within one engine config, cadence and grace change batching and
    # timing, never the match set: the guard releases the sorted stream
    # and the final flush drains every deferral.  (Across configs the
    # slab headroom itself shifts the drop policy, so the eager and lazy
    # sides are not compared to each other.)
    out["ab_match_parity"] = bool(
        m_d1 == m_dn and m_on == m_g0 == m_gg
    )
    log(
        f"latency ({N} records, {K} lanes): ledger overhead "
        f"{out['ledger_overhead_pct']}% ({out['evps_ledger_off']} -> "
        f"{out['evps_ledger_on']} ev/s), parity={out['parity']}, e2e p99 "
        f"{out['e2e_p99_s']}s; drain 1 vs {drain} defer p99 "
        f"{segs(p_dn).get('drain_defer', {}).get('p99_s')}s; grace 0 vs "
        f"{grace} ms hold p99 "
        f"{segs(p_gg).get('reorder_hold', {}).get('p99_s')}s; "
        f"ab_match_parity={out['ab_match_parity']}"
    )
    return out


def bench_overload():
    """``CEP_BENCH_OVERLOAD``: brownout ladder under flood (ISSUE 20).

    A dense flood (every record held by the watermark guard: the
    event-time pressure signal saturates) drives the ladder L1→L4, then
    a sparse subside tail lets it recover.  The same stream runs twice
    through the supervised record path:

    * **controller OFF** — the unprotected baseline: everything is
      admitted, the reorder buffer evicts past its depth (order loss),
      and the batch-time tail stretches with the backlog;
    * **controller ON** — the ladder sheds at the door as typed
      ``overload_shed`` dead letters; reported: admitted-goodput, the
      shed fraction, the batch-time p99 while browned out, and how many
      subside batches the ladder needs to step back to L0.

    Flags for bench_gate: ``ledger_reconciles`` (``offered == admitted
    + overload_shed + late_dropped + quarantined``, exactly — auditable
    shedding, nothing silent) and ``recovers`` (final level 0, zero
    failed transitions).  Record-path rates are host-bound
    (µs/record Python), so the off/on comparison is relative, like
    bench_ooo's.  ``CEP_BENCH_OVERLOAD_{K,B,BATCHES,SUB,DEPTH}`` size it.
    """
    import shutil
    import tempfile

    from kafkastreams_cep_tpu.runtime import Record, Supervisor
    from kafkastreams_cep_tpu.runtime.ingest import IngestPolicy
    from kafkastreams_cep_tpu.runtime.overload import OverloadPolicy

    K = int(os.environ.get("CEP_BENCH_OVERLOAD_K", "64"))
    n_batches = int(os.environ.get("CEP_BENCH_OVERLOAD_BATCHES", "8"))
    batch_records = int(os.environ.get("CEP_BENCH_OVERLOAD_B", "2048"))
    subside = int(os.environ.get("CEP_BENCH_OVERLOAD_SUB", "24"))
    depth = int(os.environ.get("CEP_BENCH_OVERLOAD_DEPTH", "4096"))
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    # Event-time-driven policy (the wall-clock signals are neutralized):
    # pressure = reorder-hold occupancy / hold_ref, so the ladder
    # trajectory is a pure function of the record stream — the same
    # deterministic setup the overload test suite proves against.
    policy = OverloadPolicy(
        burn_ref=1e9, queue_ref=1e9, ring_ref=1e9, hold_age_ref=1e9,
        hold_ref=0.05, enter_streak=1, exit_streak=2,
    )
    rng = np.random.default_rng(20)
    N = n_batches * batch_records
    grace = N  # ts advance +1/record: the whole flood sits in the window
    keys = rng.integers(0, K, size=N)
    prices = rng.integers(90, 131, size=N)
    vols = np.where(
        rng.random(N) < 0.005, 1100, rng.integers(700, 1000, size=N)
    )
    recs = [
        Record(
            int(keys[i]),
            {"price": int(prices[i]), "volume": int(vols[i])},
            i + 1,
            offset=i,
        )
        for i in range(N)
    ]
    sub_recs = [
        Record(
            int(rng.integers(0, K)), {"price": 100, "volume": 800},
            N + 1 + (j + 1) * 2 * grace, offset=N + j,
        )
        for j in range(subside)
    ]

    def run(overload):
        tmp = tempfile.mkdtemp(prefix="cep-bench-ovl-")
        try:
            kw = dict(
                checkpoint_path=os.path.join(tmp, "b.ckpt"),
                journal_path=os.path.join(tmp, "b.jrnl"),
                checkpoint_every=100, gc_interval=0,
                ingest=IngestPolicy(grace_ms=grace, reorder_depth=depth),
            )
            if overload:
                kw["overload_policy"] = policy
            sup = Supervisor(stock_demo.stock_pattern(), K, cfg, **kw)
            batch_s = []
            levels = []
            t0 = time.perf_counter()  # host-timed (record path)
            for b in range(n_batches):
                tb = time.perf_counter()  # host-timed (supervised batch)
                sup.process(
                    recs[b * batch_records:(b + 1) * batch_records]
                )
                batch_s.append(time.perf_counter() - tb)
                if overload:
                    levels.append(sup._overload.level)
            flood_dt = time.perf_counter() - t0
            recovery = None
            for j, r in enumerate(sub_recs):
                sup.process([r])
                if overload and recovery is None \
                        and sup._overload.level == 0:
                    recovery = j + 1
            sup.processor.drain_ingest()
            sup.processor.flush()
            g = sup.processor._guard
            lc = g.loss_counters()
            offered = N + subside
            return {
                "flood_dt": flood_dt,
                "batch_p99_s": float(np.percentile(batch_s, 99)),
                "levels": levels,
                "recovery": recovery,
                "admitted": g.admitted,
                "loss": lc,
                "reconciles": offered == g.admitted
                + lc["overload_shed"] + lc["late_dropped"]
                + lc["quarantined"],
                "transitions": (
                    sup._overload.transitions if overload else 0
                ),
                "transition_failures": (
                    sup._overload.transition_failures if overload else 0
                ),
                "final_level": sup._overload.level if overload else 0,
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    off = run(False)
    on = run(True)
    out = {
        "records": N + subside,
        "reorder_depth": depth,
        "evps_controller_off": round(N / off["flood_dt"], 1),
        "goodput_controller_on": round(
            (on["admitted"] - subside) / on["flood_dt"], 1
        ),
        "shed": on["loss"]["overload_shed"],
        "shed_pct": round(
            100 * on["loss"]["overload_shed"] / (N + subside), 1
        ),
        "evictions_off": off["loss"]["reorder_evictions"],
        "evictions_on": on["loss"]["reorder_evictions"],
        "batch_p99_off_s": round(off["batch_p99_s"], 4),
        "batch_p99_on_s": round(on["batch_p99_s"], 4),
        "max_level": max(on["levels"]),
        "recovery_batches": on["recovery"],
        "transitions": on["transitions"],
        "ledger_reconciles": bool(on["reconciles"] and off["reconciles"]),
        "recovers": bool(
            on["final_level"] == 0
            and on["recovery"] is not None
            and on["transition_failures"] == 0
        ),
    }
    log(
        f"overload (K={K}, {N} flood records, depth {depth}): "
        f"{out['evps_controller_off']} ev/s unprotected "
        f"({out['evictions_off']} order-loss evictions) vs "
        f"{out['goodput_controller_on']} admitted-ev/s browned out "
        f"({out['shed']} shed = {out['shed_pct']}%, "
        f"{out['evictions_on']} evictions), batch p99 "
        f"{out['batch_p99_off_s']}s -> {out['batch_p99_on_s']}s, "
        f"max level {out['max_level']}, L0 after "
        f"{out['recovery_batches']} subside batches; ledger_reconciles="
        f"{out['ledger_reconciles']}, recovers={out['recovers']}"
    )
    return out


def bench_ooo():
    """``CEP_BENCH_OOO``: graceful-ingestion A/B (ISSUE 5).

    The same record stream three ways through the per-record processor
    path: (a) no guard, in-order — the historical front door; (b) the
    watermark reorder buffer, in-order — the guard's bookkeeping
    overhead; (c) the guard with a bounded-skew (<= grace) shuffled
    arrival — the production case the buffer exists for.  Reports ev/s
    for each, the reorder overhead, match-count parity (all three must
    agree: the release stream is the sorted stream), and the loss
    counters (all-zero ⇒ the shuffle was fully absorbed).

    ``CEP_BENCH_OOO_{K,B,BATCHES,GRACE}`` size it.  Record-path rates are
    host-bound (µs/record Python), so this measures the guard's relative
    cost, not engine throughput — the columnar numbers stay the
    throughput story.
    """
    from kafkastreams_cep_tpu.runtime import CEPProcessor, IngestPolicy, Record

    K = int(os.environ.get("CEP_BENCH_OOO_K", "64"))
    n_batches = int(os.environ.get("CEP_BENCH_OOO_BATCHES", "8"))
    batch_records = int(os.environ.get("CEP_BENCH_OOO_B", "2048"))
    grace = int(os.environ.get("CEP_BENCH_OOO_GRACE", "64"))
    cfg = EngineConfig(
        max_runs=24, slab_entries=48, slab_preds=8, dewey_depth=12,
        max_walk=12,
    )
    rng = np.random.default_rng(17)
    N = n_batches * batch_records
    keys = rng.integers(0, K, size=N)
    prices = rng.integers(90, 131, size=N)
    vols = np.where(
        rng.random(N) < 0.005, 1100, rng.integers(700, 1000, size=N)
    )
    ts = np.arange(N, dtype=np.int64) * 2  # distinct event times
    recs = [
        Record(
            int(keys[i]),
            {"price": int(prices[i]), "volume": int(vols[i])},
            int(ts[i]),
        )
        for i in range(N)
    ]
    skew_key = ts + rng.uniform(0, grace, size=N)
    shuffled = [recs[i] for i in np.argsort(skew_key, kind="stable")]

    def run(records, policy):
        proc = CEPProcessor(
            stock_demo.stock_pattern(), K, cfg, epoch=0, ingest=policy,
        )
        # Two warmup batches: the guard's watermark hold shifts released
        # batch sizes onto different T buckets than the raw path, and the
        # resulting recompiles belong to warmup, not the timed window.
        warm = min(2, n_batches - 1)
        n_matches = 0
        for b in range(warm):
            n_matches += len(
                proc.process(
                    records[b * batch_records:(b + 1) * batch_records]
                )
            )
        t0 = time.perf_counter()  # host-timed (record path is host-bound)
        for b in range(warm, n_batches):
            n_matches += len(
                proc.process(
                    records[b * batch_records:(b + 1) * batch_records]
                )
            )
        n_matches += len(proc.drain_ingest())
        n_matches += len(proc.flush())
        dt = time.perf_counter() - t0
        return proc, (n_batches - warm) * batch_records / dt, n_matches

    _, base_evps, base_m = run(recs, None)
    _, in_evps, in_m = run(recs, IngestPolicy(grace_ms=grace))
    p_sh, sh_evps, sh_m = run(shuffled, IngestPolicy(grace_ms=grace))
    loss = p_sh._guard.loss_counters()
    out = {
        "grace_ms": grace,
        "records": N,
        "evps_no_guard": round(base_evps, 1),
        "evps_guard_inorder": round(in_evps, 1),
        "evps_guard_shuffled": round(sh_evps, 1),
        "reorder_overhead_pct": round(100 * (1 - in_evps / base_evps), 1),
        "shuffled_overhead_pct": round(100 * (1 - sh_evps / base_evps), 1),
        "matches": base_m,
        "match_parity": bool(base_m == in_m == sh_m),
        "loss_counters": loss,
        "loss_free": not any(loss.values()),
    }
    log(
        f"ooo ({N} records, {K} lanes, grace {grace} ms): no-guard "
        f"{base_evps / 1e3:.0f}K ev/s, guard in-order {in_evps / 1e3:.0f}K "
        f"ev/s ({out['reorder_overhead_pct']}% overhead), guard shuffled "
        f"{sh_evps / 1e3:.0f}K ev/s ({out['shuffled_overhead_pct']}% "
        f"overhead); match parity {out['match_parity']} "
        f"({base_m}/{in_m}/{sh_m}), loss counters {loss}"
    )
    return out


def bench_oracle(n_events):
    rng = np.random.default_rng(42)
    prices = rng.integers(90, 131, size=n_events)
    volumes = rng.integers(600, 1101, size=n_events)
    oracle = OracleNFA.from_pattern(stock_demo.stock_pattern())
    t0 = time.perf_counter()  # host-timed (pure-Python oracle loop)
    n_matches = 0
    early_dt = None
    for i in range(n_events):
        n_matches += len(
            oracle.match(
                None,
                {"price": int(prices[i]), "volume": int(volumes[i])},
                2 * i,
                offset=i,
            )
        )
        if i == 499:
            early_dt = time.perf_counter() - t0
    dt = time.perf_counter() - t0
    early = f", first 500 at {500 / early_dt:.0f} ev/s" if early_dt else ""
    log(
        f"oracle: {n_events} events in {dt:.2f}s ({n_events / dt:.0f} ev/s"
        f"{early}; unbounded state grows per event, like the reference), "
        f"{n_matches} matches"
    )
    return n_events / dt


def main():
    t_start = time.perf_counter()  # host-timed (wall budget)
    K = int(os.environ.get("CEP_BENCH_K", "4096"))
    T = int(os.environ.get("CEP_BENCH_T", "256"))
    reps = int(os.environ.get("CEP_BENCH_REPS", "5"))
    # The oracle is faithful to the reference's unbounded-state design, so
    # its per-event cost GROWS on this match-dense trace (measured: 500
    # events in ~1s, 2000 in ~120s cumulative); 1000 events keeps the
    # comparison honest without dominating bench wall time.
    oracle_n = int(os.environ.get("CEP_BENCH_ORACLE_N", "1000"))

    parity_gate()
    bench_stencil(int(os.environ.get("CEP_BENCH_STENCIL_N", "1048576")), reps)
    (engine_evps, engine_spread, engine_counters, recall, precision,
     hot_metrics, lazy_metrics, attr_metrics) = bench_engine(K, T, reps)
    if os.environ.get("CEP_BENCH_LOSSFREE", "1") != "0":
        lf_evps, lf_zero, lf_parity = bench_lossfree(
            int(os.environ.get("CEP_BENCH_LOSSFREE_K", "1024")),
            int(os.environ.get("CEP_BENCH_LOSSFREE_CYCLES", "32")),
            reps,
        )
    else:
        lf_evps, lf_zero, lf_parity = 0.0, None, None
        log("lossfree: skipped (CEP_BENCH_LOSSFREE=0)")
    oracle_evps = bench_oracle(oracle_n)
    # BASELINE.json configs 2-4, stderr-reported; sized via env knobs so
    # smoke runs stay fast (CEP_BENCH_EXTRAS=0 skips them entirely).  Each
    # extra is skipped once the wall budget is spent — the headline JSON
    # must always be printed.
    resilience = {}
    proc_phases = {}
    ooo = {}
    tier = {}
    tenants = {}
    adapt = {}
    latency = {}
    overload = {}

    def _shard_fault_block():
        # Nested under ``resilience`` so the JSON groups every
        # fault-path number; absent entirely when skipped (single
        # device or CEP_BENCH_SHARDF=0), which bench_gate treats as a
        # missing metric, not a regression.
        if os.environ.get("CEP_BENCH_SHARDF", "1") != "1":
            log("shard-fault: skipped (CEP_BENCH_SHARDF=0)")
            return {}
        shard = bench_shard_fault()
        return {"shard": shard} if shard else {}

    def _tenant_iso_block():
        # Nested under ``resilience`` like the shard-fault probes:
        # absent entirely when skipped, which bench_gate treats as a
        # missing metric, not a regression.
        if os.environ.get("CEP_BENCH_TENANT_ISO", "1") != "1":
            log("tenant-iso: skipped (CEP_BENCH_TENANT_ISO=0)")
            return {}
        block = bench_tenant_iso()
        return {"tenant": block} if block else {}

    if os.environ.get("CEP_BENCH_EXTRAS", "1") != "0":
        budget = float(os.environ.get("CEP_BENCH_BUDGET_S", "1200"))
        extras = [
            (
                "tier",
                lambda: tier.update(
                    bench_tier()
                    if os.environ.get("CEP_BENCH_TIER", "1") == "1"
                    else {}
                ),
            ),
            (
                "adapt",
                lambda: adapt.update(
                    bench_adapt()
                    if os.environ.get("CEP_BENCH_ADAPT", "1") == "1"
                    else {}
                ),
            ),
            (
                "tenants",
                lambda: tenants.update(
                    bench_tenants()
                    if os.environ.get("CEP_BENCH_TENANTS", "1") == "1"
                    else {}
                ),
            ),
            (
                "ooo",
                lambda: ooo.update(
                    bench_ooo()
                    if os.environ.get("CEP_BENCH_OOO", "1") == "1"
                    else {}
                ),
            ),
            (
                "resilience",
                lambda: resilience.update(bench_resilience()),
            ),
            (
                "shard-fault",
                lambda: resilience.update(_shard_fault_block()),
            ),
            (
                "tenant-iso",
                lambda: resilience.update(_tenant_iso_block()),
            ),
            (
                "latency",
                lambda: latency.update(
                    bench_latency()
                    if os.environ.get("CEP_BENCH_LATENCY", "1") == "1"
                    else {}
                ),
            ),
            (
                "overload",
                lambda: overload.update(
                    bench_overload()
                    if os.environ.get("CEP_BENCH_OVERLOAD", "1") == "1"
                    else {}
                ),
            ),
            (
                "processor",
                # 128 events/lane/batch: two in-flight [K,T,R,W] outputs
                # at 256 exceed HBM.
                lambda: proc_phases.update(
                    bench_processor(
                        int(os.environ.get("CEP_BENCH_PROC_K", str(K))),
                        int(os.environ.get("CEP_BENCH_PROC_T", "128")),
                        int(os.environ.get("CEP_BENCH_PROC_BATCHES", "4")),
                    )[1]
                ),
            ),
            (
                "bank",
                lambda: bench_bank(
                    [
                        int(x) for x in os.environ.get(
                            "CEP_BENCH_BANK_N", "2,8,16"
                        ).split(",")
                    ],
                    int(os.environ.get("CEP_BENCH_BANK_K", "102400")),
                    int(os.environ.get("CEP_BENCH_BANK_T", "64")),
                    max(reps - 1, 1),
                ),
            ),
            (
                "sharded-folds",
                lambda: bench_sharded_folds(
                    # 262144 lanes fit the round-4 hand config; the derived
                    # loss-free config is larger per lane (D=24, MP=16 from
                    # the probe — 65536 lanes still RESOURCE_EXHAUSTED on a
                    # v5e chip shared with earlier extras), so the default
                    # drops to 32768.  Throughput is per-event, not
                    # per-lane-count.
                    int(os.environ.get("CEP_BENCH_SHARD_K", "32768")),
                    int(os.environ.get("CEP_BENCH_SHARD_T", "16")),
                    max(reps - 1, 1),
                ),
            ),
            (
                "kleene",
                lambda: bench_kleene(
                    int(os.environ.get("CEP_BENCH_KLEENE_K", "10240")),
                    int(os.environ.get("CEP_BENCH_KLEENE_T", "64")),
                    max(reps - 1, 1),
                ),
            ),
        ]
        if os.environ.get("CEP_BENCH_METRICS", "0") == "1":
            # Telemetry-pipeline extra (tier-1 smoke-tested at tiny
            # shapes): first so the wall budget can't starve it out when
            # explicitly requested.
            extras.insert(0, (
                "metrics",
                lambda: bench_metrics(
                    int(os.environ.get("CEP_BENCH_METRICS_K", "256")),
                    int(os.environ.get("CEP_BENCH_METRICS_T", "64")),
                    int(os.environ.get("CEP_BENCH_METRICS_BATCHES", "4")),
                ),
            ))
        import gc

        for name, fn in extras:
            if time.perf_counter() - t_start > budget:
                log(f"{name}: skipped (past {budget:.0f}s bench budget)")
                continue
            try:
                fn()
            except Exception as e:  # extras never break the headline line
                log(f"{name} bench failed: {type(e).__name__}: {e}")
            # Drop the extra's device arrays before the next one compiles
            # (a prior extra's live buffers have caused RESOURCE_EXHAUSTED
            # cascades on the shared chip).
            gc.collect()

    print(
        json.dumps(
            {
                # "capacity-bounded": the measured trace sheds state past
                # the configured shapes (counted below + recall measured);
                # the lossfree_* keys carry the zero-counters line.
                "metric": (
                    "events/sec/chip, SASE stock pattern, "
                    f"{K} key lanes x {T}-event scan, capacity-bounded "
                    "(see recall_sampled + counters)"
                ),
                "value": round(engine_evps, 1),
                "unit": "events/s",
                # vs this repo's host oracle — a faithful reimplementation
                # of the reference engine's per-event loop, in the same
                # store-bound throughput class as the Java original
                # (BASELINE.md "derived cost notes"); the reference itself
                # publishes no numbers.
                "vs_baseline": round(engine_evps / oracle_evps, 2),
                "spread_pct": round(engine_spread, 1),
                # Match-space effect of the counted drops, vs the oracle
                # on sampled lanes (None when CEP_BENCH_RECALL_LANES=0).
                "recall_sampled": (
                    round(recall, 4) if recall is not None else None
                ),
                "precision_sampled": (
                    round(precision, 4) if precision is not None else None
                ),
                "counters": engine_counters,
                # Two-tier hot-window run on the same trace/shapes (None
                # when CEP_BENCH_HOT_ENTRIES=0 or the run failed).
                "hot_tier": hot_metrics,
                # Lazy-extraction A/B on the same trace/shapes (ISSUE 4;
                # None when CEP_BENCH_LAZY=0 or the run failed).
                "lazy": lazy_metrics,
                # Per-stage attribution A/B (ISSUE 6): measured overhead
                # of stage_attribution on this headline + the per-stage
                # selectivity/cost table (None when CEP_BENCH_ATTR=0 or
                # the run failed).
                "attribution": attr_metrics,
                "lossfree_evps": round(lf_evps, 1),
                "lossfree_counters_zero": bool(lf_zero),
                "lossfree_oracle_parity": bool(lf_parity),
                # Supervisor fault-path latencies (bench_resilience; None
                # when extras are skipped) — ISSUE 2 asks later PRs to
                # track recovery/escalation cost.
                "resilience": resilience or None,
                # Per-phase p50/p99 end-to-end latency from the processor
                # extra's telemetry histograms (ISSUE 3) — tail behavior,
                # not just throughput (None when extras are skipped).
                "phase_latency": proc_phases or None,
                # Graceful-ingestion A/B (ISSUE 5): in-order vs bounded-
                # skew shuffled arrival through the watermark reorder
                # buffer — reorder overhead, match parity, loss counters
                # (None when extras are skipped or CEP_BENCH_OOO=0).
                "ooo": ooo or None,
                # Compiler-tiering A/B (ISSUE 7): untiered vs tiered on a
                # strict-prefix-dominated match-sparse trace — speedup,
                # screened-event fraction, NFA dispatch fraction, match
                # parity (None when extras skipped or CEP_BENCH_TIER=0).
                "tier": tier or None,
                # Multi-tenant bank sweep (ISSUE 14): N Zipf-overlapping
                # queries, shared stencil screen + deduplicated predicate
                # matrix vs the naive-fused stacked bank — per-N q-ev/s,
                # speedup, match parity, loss flags (None when extras
                # skipped or CEP_BENCH_TENANTS=0).
                "tenants": tenants or None,
                # Adaptive recompilation (ISSUE 16): hybrid sweep under
                # the chunk-gated scan vs BENCH_r06's 2.7-5.2x band +
                # drift A/B (AdaptPolicy replans vs the stale plan) —
                # parity, loss flags, replan count, lazy-chain cost win
                # (None when extras skipped or CEP_BENCH_ADAPT=0).
                "adapt": adapt or None,
                # End-to-end latency attribution (ISSUE 18): per-segment
                # p50/p99 from the ingest->emit ledger, ledger on/off
                # match parity + overhead, drain-cadence and
                # reorder-grace A/Bs (None when extras skipped or
                # CEP_BENCH_LATENCY=0).
                "latency": latency or None,
                # Overload control (ISSUE 20): brownout ladder under
                # flood — goodput with/without the controller, typed
                # shed accounting (ledger_reconciles), brownout
                # batch-time tail, recovery-to-L0 (None when extras
                # skipped or CEP_BENCH_OVERLOAD=0).
                "overload": overload or None,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
