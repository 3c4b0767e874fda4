"""Overload control (ISSUE 20): the SLO-burn-driven brownout ladder.

Three layers of proof:

1. *Controller unit tests* — ladder mechanics (streaks, hysteresis,
   one-step moves), the begin/commit/abort transition protocol, the
   Bresenham shed stride, admission-pressure math, and state round-trip.
2. *Supervisor integration* — a flood escalates L1→L4 with exact loss
   accounting (``offered == admitted + shed + dead_lettered``), recovery
   is symmetric back to L0, a crash at any brownout level resumes in the
   same level with the actuators re-applied, and a fault injected
   mid-transition leaves the previous level authoritative.
3. *Differential proof* — on the jnp and walk-kernel paths, the
   survivor stream of a browned-out run is bit-equal to an
   unloaded run over the same admitted subset (determined post hoc from
   the typed ``overload_shed`` dead letters).

Pressure in every scenario is driven by the *event-time* reorder-hold
signal (the wall-clock signals — burn rate, queue p99 — are disabled via
huge references), so the ladder trajectory is deterministic: same
records, same levels, same sheds, on every machine.
"""

import collections
import json
import os
import pathlib

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import EngineConfig
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record, Supervisor
from kafkastreams_cep_tpu.runtime.ingest import (
    AdmissionLimiter,
    IngestPolicy,
    REASON_OVERLOAD_SHED,
)
from kafkastreams_cep_tpu.runtime.overload import (
    MAX_LEVEL,
    OverloadController,
    OverloadPolicy,
    ladder_table_markdown,
    shed_keep,
)
from kafkastreams_cep_tpu.utils import failpoints as fp
from kafkastreams_cep_tpu.utils.telemetry import render_prometheus

CFG = EngineConfig(
    max_runs=16, slab_entries=48, slab_preds=8, dewey_depth=16, max_walk=12
)

# Event-time-driven policy: wall-clock signals neutralized (refs ~1e9),
# pressure comes from reorder-buffer occupancy only — deterministic for a
# given record stream.  enter_streak=1 moves one level per flood batch;
# exit_streak=2 keeps recovery deliberate but short enough to test.
POLICY = OverloadPolicy(
    burn_ref=1e9, queue_ref=1e9, ring_ref=1e9, hold_age_ref=1e9,
    hold_ref=0.05, enter_streak=1, exit_streak=2,
)
INGEST = IngestPolicy(grace_ms=1000, reorder_depth=64)


def flood_batches(n_batches, per_batch, n_keys=4, t0=0, val_mod=5,
                  offs=None):
    """Monotone-timestamp flood: +1 ms per record, so with a 1000 ms
    grace everything is held and hold pressure rises immediately."""
    offs = offs if offs is not None else collections.defaultdict(int)
    batches, t = [], t0
    for _ in range(n_batches):
        recs = []
        for i in range(per_batch):
            t += 1
            k = f"k{i % n_keys}"
            recs.append(Record(k, i % val_mod, t, offset=offs[k]))
            offs[k] += 1
        batches.append(recs)
    return batches, t, offs


def subside_batches(n, t0, offs, key="k0", step=5000):
    """Sparse trailing traffic with big timestamp jumps: the watermark
    races ahead, the held backlog drains, pressure subsides."""
    batches, t = [], t0
    for _ in range(n):
        t += step
        batches.append([Record(key, 4, t, offset=offs[key])])
        offs[key] += 1
    return batches, t


def reconciles(guard, offered):
    """The loss-accounting contract: every offered record is admitted,
    shed (typed), or dead-lettered (typed) — nothing silent.  Reorder
    evictions are an ORDER loss, not a record loss (the record was
    admitted, then force-released), so they don't enter this sum."""
    lc = guard.loss_counters()
    return offered == guard.admitted + lc["overload_shed"] + lc[
        "late_dropped"
    ] + lc["quarantined"]


# ---------------------------------------------------------------------------
# controller unit tests
# ---------------------------------------------------------------------------


def test_policy_validation():
    with pytest.raises(ValueError):
        OverloadPolicy(enter_at=(1.0, 2.0))  # wrong arity
    with pytest.raises(ValueError):
        OverloadPolicy(exit_at=(1.0, 2.0, 4.0, 8.0))  # no hysteresis
    with pytest.raises(ValueError):
        OverloadPolicy(drain_widen=(1, 2, 3))  # needs L0..L4
    with pytest.raises(ValueError):
        OverloadPolicy(enter_streak=0)


def test_pressure_is_max_of_normalized_signals():
    ctl = OverloadController(OverloadPolicy(
        burn_ref=2.0, hold_ref=0.5, hold_age_ref=4.0, queue_ref=1.0,
        ring_ref=16.0,
    ))
    assert ctl.pressure({}) == 0.0
    assert ctl.pressure({"burn_rate": 1.0}) == pytest.approx(0.5)
    # hold_frac 0.75 / 0.5 = 1.5 dominates burn 0.5.
    assert ctl.pressure(
        {"burn_rate": 1.0, "hold_frac": 0.75}
    ) == pytest.approx(1.5)
    assert ctl.pressure({"ring_depth": 32}) == pytest.approx(2.0)
    assert ctl.pressure({"queue_p99_s": None}) == 0.0  # missing -> 0


def step(ctl, pressure):
    """One tick + full transition protocol at a synthetic pressure."""
    prop = ctl.tick({"hold_frac": pressure * ctl.policy.hold_ref})
    if prop is not None:
        ctl.begin(prop[1])
        ctl.commit()
    return prop


def test_ladder_requires_streaks_and_moves_one_step():
    ctl = OverloadController(OverloadPolicy(
        burn_ref=1e9, queue_ref=1e9, ring_ref=1e9, hold_age_ref=1e9,
        hold_ref=0.5, enter_streak=2, exit_streak=3,
    ))
    # Huge pressure: still only one step per enter_streak ticks.
    assert step(ctl, 100.0) is None  # streak 1 of 2
    assert step(ctl, 100.0) == (0, 1)
    assert ctl.level == 1
    assert step(ctl, 100.0) is None  # streak resets after a commit
    assert step(ctl, 100.0) == (1, 2)
    # Exit needs exit_streak consecutive quiet ticks; a pressure blip
    # resets the streak.
    assert step(ctl, 0.0) is None
    assert step(ctl, 0.0) is None
    assert step(ctl, 100.0) is None  # blip: exit streak resets (enter 1/2)
    assert step(ctl, 0.0) is None
    assert step(ctl, 0.0) is None
    assert step(ctl, 0.0) == (2, 1)
    assert ctl.level == 1


def test_hysteresis_band_holds_the_level():
    """Pressure between exit_at and enter_at moves nothing, forever."""
    ctl = OverloadController(OverloadPolicy(
        burn_ref=1e9, queue_ref=1e9, ring_ref=1e9, hold_age_ref=1e9,
        hold_ref=0.5, enter_streak=1, exit_streak=1,
    ))
    assert step(ctl, 1.5) == (0, 1)
    for _ in range(20):  # enter_at[1]=2.0, exit_at[0]=0.5: 1.5 is inert
        assert step(ctl, 1.5) is None
    assert ctl.level == 1


def test_abort_keeps_previous_level_and_retains_streaks():
    ctl = OverloadController(OverloadPolicy(
        burn_ref=1e9, queue_ref=1e9, ring_ref=1e9, hold_age_ref=1e9,
        hold_ref=0.5, enter_streak=2, exit_streak=3,
    ))
    ctl.admission_pressure = (1.0, {})
    assert ctl.tick({"hold_frac": 50.0}) is None
    prop = ctl.tick({"hold_frac": 50.0})
    assert prop == (0, 1)
    ctl.begin(1)
    ctl.admission_pressure = (0.5, {"t": 1.0})  # transition side effect
    ctl.abort()
    assert ctl.level == 0
    assert ctl.admission_pressure == (1.0, {})  # side effect reverted
    assert ctl.transition_failures == 1
    assert ctl.transitions == 0
    # Streaks were retained at threshold: the very next tick re-proposes.
    assert ctl.tick({"hold_frac": 50.0}) == (0, 1)
    ctl.begin(1)
    ctl.commit()
    assert ctl.level == 1 and ctl.transitions == 1


@pytest.mark.parametrize("frac", [0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0])
def test_shed_keep_bresenham_is_exact_and_deterministic(frac):
    n = 1000
    kept = [shed_keep(i, frac) for i in range(n)]
    assert sum(kept) == int(np.floor(n * frac))  # exact, not approximate
    assert kept == [shed_keep(i, frac) for i in range(n)]  # pure
    if 0.0 < frac < 1.0:
        # Evenly spread: the longest kept-gap is bounded by the stride.
        gaps, last = [], -1
        for i, k in enumerate(kept):
            if k:
                gaps.append(i - last)
                last = i
        assert max(gaps) <= int(np.ceil(1.0 / frac)) + 1


def test_state_roundtrip_is_json_safe_and_exact():
    ctl = OverloadController(POLICY)
    ctl.begin(3)
    ctl.commit()
    ctl.base_drain = 2
    ctl.shed_total = 17
    ctl.admission_pressure = (0.25, {"t0": 0.6, "t1": 0.2})
    ctl._enter_streak = 1
    state = json.loads(json.dumps(ctl.to_state()))  # header-safe
    back = OverloadController.from_state(state, POLICY)
    assert back.to_state() == ctl.to_state()
    assert back.level == 3 and back.base_drain == 2
    assert back.admit_fraction() == pytest.approx(0.5)
    assert back.metrics()["overload_level"] == 3


def test_admission_limiter_pressure_squeezes_by_cost_share():
    lim = AdmissionLimiter(rate_per_batch=1.0, burst=4.0)
    for t in ("hog", "light", "zero"):
        assert lim.admit(t)  # buckets exist
    lim.tokens = {t: 0.0 for t in lim.tokens}
    lim.set_pressure(0.5, {"hog": 0.6, "light": 0.2, "zero": 0.0})
    lim.refill()
    # Heaviest share gets the full squeeze; lighter shares
    # proportionally less; zero share untouched; refill = rate * factor.
    assert lim.tokens["hog"] == pytest.approx(0.5)
    assert lim.tokens["light"] == pytest.approx(1 - 0.5 * (0.2 / 0.6))
    assert lim.tokens["zero"] == pytest.approx(1.0)
    # A tenant first seen under pressure starts with a squeezed burst —
    # unmeasured, so it gets the conservative full squeeze.
    assert lim.admit("newcomer")
    assert lim.tokens["newcomer"] == pytest.approx(4.0 * 0.5 - 1.0)
    # Pressure rides the state round-trip (replayed crash admits the
    # same records).
    back = AdmissionLimiter.from_state(
        json.loads(json.dumps(lim.to_state()))
    )
    assert back.pressure_scale == lim.pressure_scale
    assert back.pressure_shares == lim.pressure_shares
    # scale=1.0 clears the squeeze entirely.
    lim.set_pressure(1.0, {})
    lim.tokens = {t: 0.0 for t in lim.tokens}
    lim.refill()
    assert all(v == pytest.approx(1.0) for v in lim.tokens.values())


def test_ladder_table_is_pinned_in_readme():
    """The README "Overload & backpressure" ladder table embeds
    ``ladder_table_markdown()`` verbatim — doc drift fails here."""
    readme = (
        pathlib.Path(__file__).parent.parent / "README.md"
    ).read_text()
    assert ladder_table_markdown() in readme


# ---------------------------------------------------------------------------
# supervisor integration (jnp path)
# ---------------------------------------------------------------------------


def make_sup(tmp_path, tag, resume=False, **kw):
    args = (sc.strict3(), 4, CFG)
    base = dict(
        checkpoint_path=str(tmp_path / f"{tag}.ckpt"),
        journal_path=str(tmp_path / f"{tag}.jrnl"),
        checkpoint_every=100, gc_interval=0, overload_policy=POLICY,
        ingest=INGEST,
    )
    base.update(kw)
    if resume:
        return Supervisor.resume(*args, **base)
    return Supervisor(*args, **base)


def test_flood_escalates_sheds_recovers_and_reconciles(tmp_path):
    sup = make_sup(tmp_path, "flood")
    flood, t, offs = flood_batches(12, 40)
    offered = sum(len(b) for b in flood)
    levels = []
    for b in flood:
        sup.process(b)
        levels.append(sup._overload.level)
    assert levels[:4] == [1, 2, 3, 4]  # one deliberate step per batch
    assert max(levels) == MAX_LEVEL
    g = sup.processor._guard
    assert g.overload_shed > 0  # L3 stride + L4 refusal both fired
    assert reconciles(g, offered)
    # Every shed is a typed dead letter, not a silent drop.
    shed_dl = [
        d for d in g.dead_letters if d.reason == REASON_OVERLOAD_SHED
    ]
    assert len(shed_dl) == g.overload_shed
    # Actuators live while browned out.
    assert sup.processor.overload_admit_fraction == 0.0  # L4 door shut
    assert sup.processor.telemetry_defer
    assert sup.processor.drain_interval == POLICY.drain_widen[4]
    # Recovery is symmetric: pressure subsides, the ladder steps all the
    # way down, and the actuators come back to their base settings.
    sub, t = subside_batches(30, t, offs)
    offered += len(sub)
    for b in sub:
        sup.process(b)
    assert sup._overload.level == 0
    assert sup.processor.overload_admit_fraction is None
    assert not sup.processor.telemetry_defer
    assert sup.processor.drain_interval == 1
    assert reconciles(g, offered)
    # 4 up + 4 down, all committed, none failed.
    assert sup._overload.transitions == 8
    assert sup._overload.transition_failures == 0
    # Telemetry: gauges in the snapshot and the Prometheus rendering.
    snap = sup.metrics_snapshot(per_lane=False)
    assert snap["overload_level"] == 0
    assert snap["overload_transitions"] == 8
    assert snap["overload_shed"] == g.overload_shed
    txt = render_prometheus(snap)
    assert "# TYPE cep_overload_level gauge" in txt
    assert "cep_overload_transitions 8" in txt


@pytest.mark.parametrize("level", [1, 2, 3, 4])
def test_crash_at_any_level_resumes_in_that_level(tmp_path, level):
    """Transitions pin a checkpoint, so a crash at ANY brownout level
    resumes in exactly that level with the actuators re-applied — and
    recovery proceeds as if the crash never happened."""
    sup = make_sup(tmp_path, f"lvl{level}")
    flood, t, offs = flood_batches(level, 40)
    offered = sum(len(b) for b in flood)
    for b in flood:
        sup.process(b)
    assert sup._overload.level == level
    pre_shed = sup.processor._guard.overload_shed
    del sup  # crash
    sup2 = make_sup(tmp_path, f"lvl{level}", resume=True)
    ctl = sup2._overload
    assert ctl.level == level  # pinned level is authoritative
    # Actuators were re-wired from the restored controller state.
    assert sup2.processor.drain_interval == POLICY.drain_widen[level]
    assert sup2.processor.telemetry_defer
    assert sup2.processor.overload_admit_fraction == ctl.admit_fraction()
    assert sup2.processor._guard.overload_shed == pre_shed
    # The resumed ladder recovers symmetrically.
    sub, t = subside_batches(30, t, offs)
    offered += len(sub)
    for b in sub:
        sup2.process(b)
    assert sup2._overload.level == 0
    assert reconciles(sup2.processor._guard, offered)


def test_enter_fault_leaves_previous_level_authoritative(tmp_path):
    """Satellite 1: a fault at the "overload.enter" site (crash
    mid-transition, pin-checkpoint failure) defers the transition — the
    previous level stays live, the failure is counted, and the streak
    retention re-proposes on the next tick.  A crash right after the
    fault resumes in the PREVIOUS level (nothing was pinned)."""
    sup = make_sup(tmp_path, "efault")
    flood, t, offs = flood_batches(3, 40)
    fp.FAILPOINTS.arm("overload.enter", times=1)
    try:
        sup.process(flood[0])
    finally:
        fp.FAILPOINTS.clear()
    assert sup._overload.level == 0  # transition deferred, not taken
    assert sup._overload.transition_failures == 1
    assert sup.processor.overload_admit_fraction is None
    del sup  # crash after the failed transition
    sup2 = make_sup(tmp_path, "efault", resume=True)
    assert sup2._overload.level == 0  # previous level was authoritative
    # With the fault gone the ladder proceeds normally.
    sup2.process(flood[1])
    assert sup2._overload.level == 1
    assert sup2._overload.transitions == 1


def test_exit_fault_defers_recovery_one_tick(tmp_path):
    sup = make_sup(tmp_path, "xfault")
    flood, t, offs = flood_batches(1, 40)
    sup.process(flood[0])
    assert sup._overload.level == 1
    sub, t = subside_batches(4, t, offs)
    sup.process(sub[0])  # exit streak 1 of 2
    fp.FAILPOINTS.arm("overload.exit", times=1)
    try:
        sup.process(sub[1])  # proposes L1 -> L0; the failpoint kills it
    finally:
        fp.FAILPOINTS.clear()
    assert sup._overload.level == 1
    assert sup._overload.transition_failures == 1
    sup.process(sub[2])  # streak retained: re-proposes and commits
    assert sup._overload.level == 0


def test_shed_fault_recovers_to_exactly_once(tmp_path):
    """A fault at the "overload.shed" site mid-ingest is absorbed by the
    supervisor's recovery (restore + replay), and the retried batch
    sheds the identical subset — loss accounting still reconciles."""
    sup = make_sup(tmp_path, "sfault", checkpoint_every=1)
    flood, t, offs = flood_batches(5, 40)
    offered = sum(len(b) for b in flood)
    for b in flood[:4]:  # reach L4 (door shut; every record sheds)
        sup.process(b)
    assert sup._overload.level == 4
    fp.FAILPOINTS.arm("overload.shed", times=1)
    try:
        sup.process(flood[4])
    finally:
        fp.FAILPOINTS.clear()
    assert sup.recoveries == 1
    assert sup._overload.level == 4
    assert reconciles(sup.processor._guard, offered)


def test_every_transition_emits_a_trace_span_and_flight_dump(tmp_path):
    """L3+ entry is the incident boundary: the flight recorder dumps,
    and every transition (either direction) carries a trace span."""
    from kafkastreams_cep_tpu.runtime import FlightRecorder
    from kafkastreams_cep_tpu.utils.telemetry import InMemoryTraceSink

    sink = InMemoryTraceSink()
    flight = FlightRecorder(capacity=64, path=str(tmp_path / "fr"))
    sup = make_sup(
        tmp_path, "span", trace_sink=sink, flight=flight,
    )
    flood, t, offs = flood_batches(4, 40)
    for b in flood:
        sup.process(b)
    assert sup._overload.level == 4
    spans = sink.spans("overload.transition")
    assert [(s["from_level"], s["to_level"]) for s in spans] == [
        (0, 1), (1, 2), (2, 3), (3, 4),
    ]
    assert flight.dumps >= 2  # L3 entry and L4 entry each dump
    assert any("overload" in p for p in flight.dump_paths)


# ---------------------------------------------------------------------------
# differential proof: survivor stream == unloaded run of the admitted
# subset, on all three execution paths
# ---------------------------------------------------------------------------

# Compact flood for the kernel paths (interpret mode scales with T):
# values cycle 0..2, so each key's released stream is A,B,C repeating —
# strict3 matches keep the differential non-vacuous.  Depth 64 keeps the
# steady-state subside pressure (one in-flight hold, 1/64/hold_ref ~= 0.3)
# below exit_at[0]=0.5 so the ladder can step all the way back to L0; a
# tighter buffer would floor the pressure above an exit threshold and
# pin the ladder mid-descent.
DIFF_INGEST = IngestPolicy(grace_ms=1000, reorder_depth=64)


def run_brownout(num_lanes, tmp_path, tag):
    sup = Supervisor(
        sc.strict3(), num_lanes, CFG,
        checkpoint_path=str(tmp_path / f"{tag}.ckpt"),
        checkpoint_every=100, gc_interval=0, overload_policy=POLICY,
        ingest=DIFF_INGEST,
    )
    flood, t, offs = flood_batches(6, 16, val_mod=3)
    sub, t = subside_batches(20, t, offs)
    batches = flood + sub
    matches = []
    levels = []
    for b in batches:
        matches.extend(sup.process(b))
        levels.append(sup._overload.level)
    matches.extend(sup.processor.drain_ingest())
    matches.extend(sup.processor.flush())
    return sup, batches, matches, levels


def run_admitted_oracle(num_lanes, batches, dead):
    """The unloaded oracle: the same batches minus the records the
    browned-out run shed or dead-lettered (identified post hoc by
    (key, offset) from the typed dead letters)."""
    proc = CEPProcessor(
        sc.strict3(), num_lanes, CFG, gc_interval=0, ingest=DIFF_INGEST,
    )
    matches = []
    for b in batches:
        keep = [r for r in b if (r.key, r.offset) not in dead]
        if keep:
            matches.extend(proc.process(keep))
    matches.extend(proc.drain_ingest())
    matches.extend(proc.flush())
    return proc, matches


def canon_stream(matches):
    return [
        (k, tuple(sorted(
            (stage, tuple(sorted(e.offset for e in events)))
            for stage, events in seq.as_map().items()
        )))
        for k, seq in matches
    ]


def assert_survivor_differential(num_lanes, tmp_path, tag):
    sup, batches, got, levels = run_brownout(num_lanes, tmp_path, tag)
    assert max(levels) >= 3, levels  # shedding actually engaged
    assert levels[-1] == 0, levels  # and fully recovered
    g = sup.processor._guard
    offered = sum(len(b) for b in batches)
    assert reconciles(g, offered)
    dead = {(d.record.key, d.record.offset) for d in g.dead_letters}
    assert dead  # non-vacuous: some records were shed
    oracle_proc, want = run_admitted_oracle(num_lanes, batches, dead)
    assert canon_stream(got) == canon_stream(want)  # bit-equal, in order
    assert want, "vacuous differential: the admitted subset must match"
    # Engine-level loss counters agree (and are all zero) on both runs.
    assert not any(sup.processor.counters().values())
    assert not any(oracle_proc.counters().values())


def test_survivor_stream_differential_jnp(tmp_path):
    assert_survivor_differential(4, tmp_path, "diffjnp")


@pytest.mark.parametrize("env,mode", [("CEP_WALK_KERNEL", "interpret")])
def test_survivor_stream_differential_kernels(tmp_path, env, mode):
    """The same proof through the Pallas walk kernel (interpret mode;
    the 128-lane floor is the kernel's LANE_BLOCK).  Shedding is a
    host-side door decision, so the kernel path must reproduce the jnp
    survivor stream record-for-record."""
    os.environ[env] = mode
    try:
        assert_survivor_differential(128, tmp_path, f"diff{env[-11:]}")
    finally:
        os.environ[env] = "0"
