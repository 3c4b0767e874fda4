"""Capacity estimation — derive an ``EngineConfig`` instead of hand-tuning.

The reference needs no sizing: its run queue, shared buffer, and versions
are heap-backed and unbounded (``NFA.java:75``, ``CEPProcessor.java:
144-149``).  The array engine's shapes are static, so every dimension is a
capacity knob with an overflow counter.  This module closes the gap the
way a profiler would: run the real pattern over a *sample* of the real
traffic with instrumented occupancy maxima, then derive a config with
headroom — growing any dimension whose counter fires and tightening the
rest.

``probe``    — one instrumented run: counters + occupancy maxima.
``suggest``  — a config from a probe report (structural floors from the
               compiled tables + measured maxima x margin).
``autosize`` — the closed loop: probe, grow what overflowed, re-probe,
               then tighten.  The returned config is verified loss-free
               on the sample (capacity counters zero; ``slab_missing``
               is excluded — with every capacity counter zero it marks
               reference-NPE trace states, a pattern property the
               reference would crash on, not a sizing defect).
``escalate`` — the *online* analog of one autosize growth step: given
               the capacity counters a live batch tripped, the next
               strictly-wider config under an :class:`EscalationPolicy`
               (growth factor, per-dim ceiling).  The supervisor pairs
               it with live-state migration (``runtime/migrate.py``) so
               a production overflow becomes a transparent capacity
               escalation instead of a loss warning.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kafkastreams_cep_tpu.engine.matcher import (
    EngineConfig,
    EventBatch,
)
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("engine.sizing")

# Counters that indicate a capacity knob is too small, with the knob they
# grow.  slab_missing is deliberately absent (see module docstring);
# walk_collisions is a semantics flag, not a capacity.
_COUNTER_KNOB = {
    "run_drops": "max_runs",
    "ver_overflows": "dewey_depth",
    "slab_full_drops": "slab_entries",
    "slab_pred_drops": "slab_preds",
    "slab_trunc": "max_walk",
    "handle_overflows": "handle_ring",
}

# Ingestion-guard loss counters and the IngestPolicy knob each one grows
# (runtime/ingest.py) — the host-side twin of _COUNTER_KNOB: a late drop
# means the grace window under-covered the stream's skew, an eviction
# means the reorder buffer was too shallow for the in-flight disorder.
# ``quarantined`` is deliberately absent: a schema/lane defect is a data
# defect, not a capacity defect — no knob makes a malformed record valid.
_INGEST_COUNTER_KNOB = {
    "late_dropped": "grace_ms",
    "reorder_evictions": "reorder_depth",
}

# Additive growth floors for knobs whose current value may be 0 (a pure
# multiplier would never move grace_ms off zero).
_INGEST_KNOB_FLOOR = {"grace_ms": 1000, "reorder_depth": 64}


class ProbeReport(NamedTuple):
    """What one instrumented sample run observed."""

    counters: Dict[str, int]
    max_alive_runs: int  # per lane, max over chunk boundaries
    max_live_entries: int  # slab entries in use, per lane
    max_npreds: int  # pointer-list width in use
    max_vlen: int  # deepest Dewey version (runs and pointers)
    max_match_len: int  # longest extracted match
    max_matches_chunk: int  # matches completed per lane per chunk — the
    #   handle-ring working set under lazy extraction (drain runs at scan
    #   cadence, so one chunk's completions must fit the ring)
    config: EngineConfig


def _chunked(events: EventBatch, chunk: int):
    T = int(events.ts.shape[1])
    for t0 in range(0, T, chunk):
        yield jax.tree_util.tree_map(
            lambda x: x[:, t0:t0 + chunk], events
        )


def probe(
    pattern,
    events: EventBatch,
    config: EngineConfig,
    sweep_every: int = 16,
) -> ProbeReport:
    """Run ``pattern`` over ``events [K, T]`` under ``config``, sweeping
    every ``sweep_every`` events (match the deployment's cadence: the
    processor sweeps every ``gc_interval`` micro-batches), and record
    occupancy maxima.

    Maxima are sampled at chunk boundaries; within-chunk peaks are covered
    by the growth loop in :func:`autosize` (a dimension that only peaks
    intra-chunk still fires its counter and grows).
    """
    from kafkastreams_cep_tpu.parallel.batch import BatchMatcher

    K = int(events.ts.shape[0])
    batch = BatchMatcher(pattern, K, config)
    state = batch.init_state()
    chunk = max(int(sweep_every), 1)
    mx = dict(alive=0, entries=0, npreds=0, vlen=0, mlen=0, mchunk=0)
    for ev in _chunked(events, chunk):
        state, out = batch.scan(state, ev)
        mx["alive"] = max(mx["alive"], int(jnp.max(jnp.sum(state.alive, -1))))
        mx["entries"] = max(
            mx["entries"], int(jnp.max(jnp.sum(state.slab.stage >= 0, -1)))
        )
        mx["npreds"] = max(mx["npreds"], int(jnp.max(state.slab.npreds)))
        mx["vlen"] = max(
            mx["vlen"],
            int(jnp.max(state.vlen)),
            int(jnp.max(state.slab.pvlen)),
        )
        if config.lazy_extraction:
            # Lazy configs emit through the drain pass: drain at chunk
            # cadence (the processor's) and measure there instead.
            state, dout = batch.drain(state)
            mx["mlen"] = max(mx["mlen"], int(jnp.max(dout.count)))
            mx["mchunk"] = max(
                mx["mchunk"],
                int(jnp.max(jnp.sum(dout.count > 0, axis=-1))),
            )
        else:
            mx["mlen"] = max(mx["mlen"], int(jnp.max(out.count)))
            # Completions per lane over this chunk — sum of completed
            # match slots across the chunk's (t, r) grid, max over lanes:
            # the lazy handle ring must hold one drain interval's worth.
            mx["mchunk"] = max(
                mx["mchunk"],
                int(jnp.max(jnp.sum(out.count > 0, axis=(-2, -1)))),
            )
        state = batch.sweep(state)
    return ProbeReport(
        counters=batch.counters(state),
        max_alive_runs=mx["alive"],
        max_live_entries=mx["entries"],
        max_npreds=mx["npreds"],
        max_vlen=mx["vlen"],
        max_match_len=mx["mlen"],
        max_matches_chunk=mx["mchunk"],
        config=config,
    )


def _round8(x: int) -> int:
    return max(8, int(math.ceil(x / 8)) * 8)


def suggest(tables, report: ProbeReport, margin: float = 1.5) -> EngineConfig:
    """An ``EngineConfig`` from a probe report.

    Structural floors come from the compiled tables: a run chain can hold
    ``max_hops`` frames, every stage can hold a run; measured maxima get
    ``margin`` on top.  Shapes round to multiples of 8 (TPU sublane tile)
    except the walk bound, which is exact work, not storage.  Intra-chunk
    peaks the boundary sampling missed are handled by :func:`autosize`'s
    verify step, not by padding every dimension here — a 2x "branchy"
    multiplier on runs was measured costing the loss-free bench 4.5x
    throughput for capacity the verify pass proves unnecessary.
    """
    S = tables.num_stages
    floor_runs = S + 2
    cfg = report.config
    slab_entries = _round8(max(8, int(report.max_live_entries * margin)))
    return dataclasses.replace(
        cfg,
        max_runs=_round8(
            max(floor_runs, int(report.max_alive_runs * margin))
        ),
        slab_entries=slab_entries,
        slab_hot_entries=suggest_hot_entries(
            slab_entries, report.max_alive_runs
        ),
        slab_preds=_round8(max(2, int(report.max_npreds * margin))),
        dewey_depth=_round8(
            max(tables.max_hops + 2, int(report.max_vlen * margin))
        ),
        max_walk=max(
            tables.max_hops + 2, int(report.max_match_len * margin) + 2
        ),
        handle_ring=suggest_handle_ring(report.max_matches_chunk, margin),
    )


def suggest_hot_entries(slab_entries: int, max_alive_runs: int) -> int:
    """E_hot for a derived ``slab_entries``.

    The hot tier is a perf knob, not a capacity knob (drops are identical at
    any E_hot — ops/slab.py "Two-tier layout"), so sizing targets the walk
    access pattern: walks start at run pointer events and the current event, so
    the per-step *fresh* working set is bounded by the live run count, and the
    round-5 E-sweep (PERF.md, walk-pass cost model) puts the sweet spot for the
    hot window at ~16-24 rows.  Below E=32 a two-tier split buys nothing (the
    full reduce is already hot-sized) and 0 keeps the legacy single tier.
    """
    if slab_entries < 32:
        return 0
    e_hot = _round8(max(8, min(24, 2 * max_alive_runs)))
    return min(e_hot, slab_entries - 8)


def suggest_handle_ring(max_matches_chunk: int, margin: float = 1.5) -> int:
    """HB for a probed per-chunk completion maximum.

    The ring holds every match completed between drains; the probe's
    chunk cadence matches the processor's scan cadence (drain runs after
    every scan), so the measured per-lane per-chunk completion maximum x
    margin, rounded to the sublane tile, is the loss-free capacity.
    Derived even for eager configs — the knob is inert there and a later
    ``lazy_extraction=True`` flip inherits a sized ring.
    """
    return _round8(max(8, int(max_matches_chunk * margin)))


def capacity_counters(counters: Dict[str, int]) -> Dict[str, int]:
    """The capacity-relevant subset of an engine counters dict."""
    return {k: counters[k] for k in _COUNTER_KNOB if k in counters}


def ingest_capacity_counters(stats: Dict[str, int]) -> Dict[str, int]:
    """The knob-growable subset of an ingestion-guard stats dict."""
    return {k: stats[k] for k in _INGEST_COUNTER_KNOB if k in stats}


def escalate_ingest(
    policy,
    tripped: Dict[str, int],
    growth: float = 2.0,
    max_policy=None,
):
    """The next wider :class:`~kafkastreams_cep_tpu.runtime.ingest.
    IngestPolicy` for the loss counters in ``tripped`` (counter-name ->
    positive-delta, names per ``_INGEST_COUNTER_KNOB``).

    Unlike engine escalation this is *forward-only*: the supervisor does
    not roll back and re-process (the dropped records are already in the
    dead-letter queue, recoverable by the caller) — widening stops the
    bleeding for the rest of the stream.  Returns None when nothing can
    grow (at the ``max_policy`` ceiling, or no knob-mapped counter
    tripped).
    """
    grown = {}
    for counter, delta in tripped.items():
        knob = _INGEST_COUNTER_KNOB.get(counter)
        if knob is None or not delta:
            continue
        cur = getattr(policy, knob)
        new = max(int(math.ceil(cur * growth)), cur + _INGEST_KNOB_FLOOR[knob])
        if max_policy is not None:
            new = min(new, getattr(max_policy, knob))
        if new > cur:
            grown[knob] = new
    if not grown:
        return None
    return dataclasses.replace(policy, **grown)


class EscalationPolicy(NamedTuple):
    """How a live supervisor grows capacity when a batch trips a loss
    counter (``Supervisor(auto_escalate=...)``).

    ``growth``      — multiplier applied to each tripped dimension (shape
                      dims re-round to the TPU sublane tile of 8).
    ``hysteresis``  — consecutive tripping batches required before an
                      escalation actually fires.  1 (default) escalates
                      on the first trip, which is the only setting under
                      which *nothing is ever lost* (the tripping batch is
                      rolled back and re-processed wide); >1 tolerates
                      transient spikes at the cost of warned-not-recovered
                      loss on the tolerated batches — the classic
                      stability-vs-loss hysteresis tradeoff, made
                      explicit.
    ``max_config``  — per-dimension ceiling; a dimension at its ceiling
                      stops growing (None = unbounded).  When *every*
                      tripped dimension is at its ceiling, escalation is
                      exhausted and the supervisor degrades to the
                      warn-and-count behavior.
    ``max_rounds``  — growth rounds attempted per batch (a batch whose
                      re-run still trips grows again, up to this bound).
    """

    growth: float = 2.0
    hysteresis: int = 1
    max_config: Optional[EngineConfig] = None
    max_rounds: int = 4


def escalate(
    config: EngineConfig,
    tripped: Dict[str, int],
    policy: EscalationPolicy = EscalationPolicy(),
) -> Optional[EngineConfig]:
    """The next strictly-wider config for the counters in ``tripped``
    (a counter-name -> positive-delta dict; names map to dims via the
    same ``_COUNTER_KNOB`` table autosize uses).  Returns None when every
    tripped dimension is already at its ceiling — escalation exhausted.
    """
    grown = {}
    for counter, delta in tripped.items():
        knob = _COUNTER_KNOB.get(counter)
        if knob is None or not delta:
            continue
        cur = getattr(config, knob)
        new = int(math.ceil(cur * policy.growth))
        if knob != "max_walk":  # walk bound is exact work, not storage
            new = _round8(new)
        if policy.max_config is not None:
            new = min(new, getattr(policy.max_config, knob))
        if new > cur:
            grown[knob] = new
    if not grown:
        return None
    new_cfg = dataclasses.replace(config, **grown)
    # Keep the hot-tier split valid (a perf knob — ops/slab.py proves
    # drops identical at any E_hot, so deriving it fresh is safe) and
    # sized for the grown run count.
    if new_cfg.slab_hot_entries:
        new_cfg = dataclasses.replace(
            new_cfg,
            slab_hot_entries=suggest_hot_entries(
                new_cfg.slab_entries, new_cfg.max_runs // 2
            ),
        )
    return new_cfg


def autosize(
    pattern,
    events: EventBatch,
    start: Optional[EngineConfig] = None,
    margin: float = 1.5,
    sweep_every: int = 16,
    max_iters: int = 6,
) -> EngineConfig:
    """Probe -> grow what overflowed -> re-probe -> tighten -> verify.

    Returns a config whose capacity counters are all zero on ``events``
    (the sample); raises if ``max_iters`` doublings cannot get there.
    The sample should be representative traffic — like sizing a JVM heap
    from a load test, a heavier production trace can still overflow, and
    the counters remain the runtime signal for that.
    """
    from kafkastreams_cep_tpu.compiler.tables import lower

    cfg = start or EngineConfig(
        max_runs=16, slab_entries=64, slab_preds=8, dewey_depth=16,
        max_walk=16,
    )
    tables = lower(pattern)
    report = probe(pattern, events, cfg, sweep_every)
    for it in range(max_iters):
        hot = {
            k: v for k, v in capacity_counters(report.counters).items() if v
        }
        if not hot:
            break
        grown = {}
        for counter in hot:
            knob = _COUNTER_KNOB[counter]
            grown[knob] = getattr(cfg, knob) * 2
        logger.info("autosize iter %d: grew %s (counters %s)", it, grown, hot)
        cfg = dataclasses.replace(cfg, **grown)
        report = probe(pattern, events, cfg, sweep_every)
    hot = {k: v for k, v in capacity_counters(report.counters).items() if v}
    if hot:
        raise RuntimeError(
            f"autosize: counters still nonzero after {max_iters} growth "
            f"iterations: {hot}"
        )

    tight = suggest(tables, report, margin)
    verify = probe(pattern, events, tight, sweep_every)
    if any(capacity_counters(verify.counters).values()):
        # The margin under-covered an intra-chunk peak; keep the loose
        # (verified-clean) config rather than iterate forever.
        logger.info(
            "autosize: tightened config overflowed (%s); keeping probe "
            "config", capacity_counters(verify.counters),
        )
        return report.config
    return tight
