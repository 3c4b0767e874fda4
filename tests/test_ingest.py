"""Ingestion guard (runtime/ingest.py) — ISSUE 5 tentpole suites.

Contracts under test:

1. *Bounded-skew absorption*: any shuffle of a trace whose timestamp
   inversions are bounded by the grace drains **bit-identical matches,
   emission order, and loss counters** to the in-order run — on the jnp
   and fused walk-kernel paths (interpret mode; CPU CI checks parity,
   not perf).
2. *Per-record quarantine*: schema/lane/time defects and too-late events
   are diverted to the dead-letter queue with typed reasons — never a
   batch-level exception in the default mode; ``on_bad_record="raise"``
   preserves the strict behavior with record index + key in the message.
3. *Loss counters*: ``late_dropped`` / ``quarantined`` /
   ``reorder_evictions`` all zero ⇒ loss-free; depth-cap evictions are
   counted, never silent.
4. *Durability*: the reorder buffer is first-class state — it survives
   checkpoint/restore and live migration with records held, and chaos
   schedules that crash with a non-empty buffer (including the new
   ``ingest.admit`` / ``ingest.release`` failpoints) still converge to
   the fault-free oracle with exactly-once emission.
"""

import collections
import dataclasses
import os

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.engine import EngineConfig
from kafkastreams_cep_tpu.engine import sizing
from kafkastreams_cep_tpu.runtime import (
    CEPProcessor,
    IngestPolicy,
    InputRejected,
    Record,
    Supervisor,
    restore_processor,
    save_checkpoint,
)
from kafkastreams_cep_tpu.runtime.ingest import (
    REASON_LANE_OVERFLOW,
    REASON_LATE,
    REASON_SCHEMA,
    REASON_TIME_RANGE,
    IngestGuard,
)
from kafkastreams_cep_tpu.runtime.migrate import (
    canonical_state,
    migrate_processor,
)
from kafkastreams_cep_tpu.utils import failpoints as fp

GRACE = 8


def trace(pattern_vals, keys=("k0", "k1"), ts0=1000, step=2):
    """Every key sees the full value sequence (so per-key patterns can
    match), interleaved with globally distinct, strictly increasing
    timestamps (ties would make 'the in-order run' ambiguous; the guard
    breaks ties by arrival)."""
    recs, t = [], 0
    for v in pattern_vals:
        for k in keys:
            recs.append(Record(k, v, ts0 + step * t))
            t += 1
    return recs


def bounded_shuffle(records, skew, seed):
    """Arrival order whose timestamp inversions are <= ``skew`` ms: sort
    by ts + U(0, skew) — if y precedes x with ts(y) > ts(x) then
    ts(y) - ts(x) <= skew (the classic bounded-disorder model)."""
    rng = np.random.default_rng(seed)
    key = [r.timestamp + rng.uniform(0, skew) for r in records]
    return [records[i] for i in np.argsort(key, kind="stable")]


def run_guarded(pattern, records, num_lanes=2, batch=5, grace=GRACE,
                config=None, **pol):
    proc = CEPProcessor(
        pattern, num_lanes, config or sc.default_config(), epoch=0,
        gc_interval=0, ingest=IngestPolicy(grace_ms=grace, **pol),
    )
    out = []
    for i in range(0, len(records), batch):
        out += proc.process(records[i:i + batch])
    out += proc.drain_ingest()
    out += proc.flush()
    return proc, [(k, sc.canon(s)) for k, s in out]


VALS = [sc.A, sc.B, sc.C, sc.X, sc.A, sc.B, sc.D, sc.C, sc.A, sc.B,
        sc.C, sc.X, sc.A, sc.D, sc.B, sc.C, sc.X, sc.A, sc.B, sc.C]


@pytest.mark.parametrize(
    "pattern,seed",
    [(sc.strict3, 0), (sc.strict3, 1), (sc.skip_till_any, 0),
     (sc.skip_till_any, 2)],
)
def test_bounded_skew_shuffle_is_bit_identical_jnp(pattern, seed):
    recs = trace(VALS)
    p_ref, m_ref = run_guarded(pattern(), recs)
    assert m_ref  # a vacuous (matchless) parity proves nothing
    p_sh, m_sh = run_guarded(
        pattern(), bounded_shuffle(recs, GRACE, seed)
    )
    assert m_sh == m_ref  # content AND emission order
    assert p_sh.batch.counters(p_sh.state) == p_ref.batch.counters(
        p_ref.state
    )
    assert not any(p_sh._guard.loss_counters().values())
    assert not any(p_ref._guard.loss_counters().values())


@pytest.mark.parametrize("env,mode", [("CEP_WALK_KERNEL", "interpret")])
def test_bounded_skew_shuffle_is_bit_identical_kernel(env, mode):
    """The same parity through the Pallas walk kernel (128-lane floor is
    the kernel's LANE_BLOCK).  The in-order reference runs on the jnp
    path — jnp↔kernel parity is pinned by the kernel suite, so this
    closes the triangle: shuffled-through-kernel ≡ in-order-jnp.
    Trace kept small: interpret-mode cost scales with T."""
    recs = trace([sc.A, sc.B, sc.C, sc.X, sc.A, sc.B, sc.C],
                 keys=("k0", "k1"))
    p_ref, m_ref = run_guarded(sc.strict3(), recs, num_lanes=128, batch=7)
    assert m_ref
    assert not p_ref.batch.uses_walk_kernel
    os.environ[env] = mode
    try:
        p_sh, m_sh = run_guarded(
            sc.strict3(), bounded_shuffle(recs, GRACE, 5), num_lanes=128,
            batch=7,
        )
        assert p_sh.batch.uses_walk_kernel
    finally:
        os.environ[env] = "0"
    assert m_sh == m_ref
    assert p_sh.batch.counters(p_sh.state) == p_ref.batch.counters(
        p_ref.state
    )
    assert not any(p_sh._guard.loss_counters().values())


def test_release_batching_matches_watermark_not_arrival():
    """Records stay held until the watermark (max seen - grace) passes
    them; a later batch's newer timestamps release them."""
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=10),
    )
    assert proc.process([Record("k", sc.A, 1000)]) == []
    assert proc._guard.held == 1
    proc.process([Record("k", sc.B, 1005)])
    assert proc._guard.held == 2  # watermark 995 < 1000
    proc.process([Record("k", sc.C, 1020)])  # watermark 1010: A,B release
    assert proc._guard.held == 1
    out = proc.drain_ingest()
    assert len(out) == 1  # A,B,C in timestamp order
    assert proc._guard.held == 0


# -- quarantine / dead-letter -------------------------------------------------


def test_quarantine_typed_reasons_never_batch_exception():
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=2),
    )
    out = proc.process([
        Record("k0", sc.A, 1000),
        Record("k0", {"nested": 1}, 1001),       # schema: structure
        Record("k0", 2.5, 1002),                 # schema: float-in-int
        Record("k1", sc.X, 1003),                # lane overflow (1 lane)
        Record("k0", sc.B, 10**14, None),        # time range
        Record("k0", sc.B, 1004),
        Record("k0", sc.C, 1005),
    ])
    out += proc.drain_ingest()
    g = proc._guard
    assert g.reason_counts == {
        REASON_SCHEMA: 2, REASON_LANE_OVERFLOW: 1, REASON_TIME_RANGE: 1,
    }
    reasons = [d.reason for d in g.dead_letters]
    assert reasons == [
        REASON_SCHEMA, REASON_SCHEMA, REASON_LANE_OVERFLOW,
        REASON_TIME_RANGE,
    ]
    assert all(d.corr == "stream-1" for d in g.dead_letters)
    # The healthy remainder of the batch still matched.
    assert [(k, sc.canon(s)) for k, s in out] == [
        ("k0", {"first": [0], "second": [1], "latest": [2]})
    ]


def test_late_records_are_dead_lettered_not_raised():
    recs = [
        Record("k", sc.A, 1000),
        Record("k", sc.B, 1050),
        Record("k", sc.C, 1001),  # 41 ms behind watermark 1042
    ]
    proc, _ = run_guarded(sc.strict3(), recs, num_lanes=1, batch=1)
    g = proc._guard
    assert g.late_dropped == 1
    assert g.dead_letters[-1].reason == REASON_LATE
    assert "behind the watermark" in g.dead_letters[-1].detail


def test_strict_mode_raises_with_record_index_and_key():
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=2, on_bad_record="raise"),
    )
    with pytest.raises(InputRejected) as ei:
        proc.process([
            Record("k0", sc.A, 1000),
            Record("k0", {"bad": 1}, 1001),
        ])
    msg = str(ei.value)
    assert "record 1" in msg and "'k0'" in msg and "schema" in msg


def test_dead_letter_cap_drops_oldest_and_counts():
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=0, dead_letter_cap=2),
    )
    proc.process(
        [Record("k", sc.A, 1000)]
        + [Record("k", {"bad": i}, 1001 + i) for i in range(4)]
    )
    g = proc._guard
    assert len(g.dead_letters) == 2
    assert g.dead_letter_dropped == 2
    assert g.quarantined == 4  # the counter never forgets


def test_reorder_depth_eviction_is_counted_never_silent():
    recs = bounded_shuffle(trace(VALS, keys=("k",)), GRACE, 9)
    proc, _ = run_guarded(
        sc.strict3(), recs, num_lanes=1, grace=10**6, reorder_depth=4,
    )
    g = proc._guard
    assert g.reorder_evictions > 0
    # Nothing lost to the engine: every admitted record was released.
    assert g.admitted == g.released
    assert proc.metrics.records_in == g.admitted


def test_admission_dedup_absorbs_source_offset_replay():
    recs = [
        Record("k", v, 1000 + 2 * i, offset=i)
        for i, v in enumerate([sc.A, sc.B, sc.C])
    ]
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=2),
    )
    out = proc.process(recs)
    out += proc.process(recs)  # at-least-once re-delivery
    out += proc.drain_ingest()
    assert proc.metrics.duplicates_dropped == 3
    assert len(out) == 1  # matched exactly once


def test_guard_rejects_columnar_path():
    proc = CEPProcessor(
        sc.strict3(), 1, sc.default_config(), epoch=0,
        ingest=IngestPolicy(),
    )
    with pytest.raises(ValueError, match="per-record path"):
        proc.process_columns(
            np.zeros(1, np.int64), np.zeros(1, np.int64),
            np.zeros(1, np.int64),
        )


# -- durability ---------------------------------------------------------------


def test_checkpoint_restore_with_held_records(tmp_path):
    recs = bounded_shuffle(trace(VALS, keys=("k0", "k1")), GRACE, 3)
    p_ref, m_ref = run_guarded(sc.strict3(), recs)

    proc = CEPProcessor(
        sc.strict3(), 2, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=GRACE),
    )
    out = []
    for i in range(0, 10, 5):
        out += proc.process(recs[i:i + 5])
    assert proc._guard.held > 0
    path = str(tmp_path / "held.ckpt")
    save_checkpoint(proc, path)

    res = restore_processor(sc.strict3(), path)
    assert res._guard.held == proc._guard.held
    assert res._guard.policy == proc._guard.policy
    for i in range(10, len(recs), 5):
        out += res.process(recs[i:i + 5])
    out += res.drain_ingest()
    assert [(k, sc.canon(s)) for k, s in out] == m_ref
    assert not any(res._guard.loss_counters().values())


def test_migration_carries_guard_with_held_records():
    recs = bounded_shuffle(trace(VALS, keys=("k0", "k1")), GRACE, 4)
    _, m_ref = run_guarded(sc.strict3(), recs)

    proc = CEPProcessor(
        sc.strict3(), 2, sc.default_config(), epoch=0, gc_interval=0,
        ingest=IngestPolicy(grace_ms=GRACE),
    )
    out = []
    for i in range(0, 10, 5):
        out += proc.process(recs[i:i + 5])
    held = proc._guard.held
    assert held > 0
    wide = dataclasses.replace(
        sc.default_config(), max_runs=32, slab_entries=64
    )
    proc = migrate_processor(sc.strict3(), proc, wide)
    assert proc._guard.held == held
    for i in range(10, len(recs), 5):
        out += proc.process(recs[i:i + 5])
    out += proc.drain_ingest()
    assert [(k, sc.canon(s)) for k, s in out] == m_ref


# -- supervisor integration ---------------------------------------------------


def test_supervisor_ingest_escalation_widens_grace(tmp_path):
    """A disordered stream against grace=0: late drops trip the
    sizing rows (late_dropped -> grace_ms) and the supervisor widens the
    live policy forward-only, pinning it with a snapshot."""
    recs = bounded_shuffle(trace(VALS, keys=("k",)), 6, 11)
    sup = Supervisor(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "esc.ckpt"),
        checkpoint_every=100, gc_interval=0, epoch=0,
        auto_escalate=True, ingest=IngestPolicy(grace_ms=0),
    )
    for i in range(0, len(recs), 4):
        sup.process(recs[i:i + 4])
    guard = sup.processor._guard
    assert guard.late_dropped > 0
    assert sup.ingest_escalations >= 1
    assert guard.policy.grace_ms >= 1000
    assert sup.checkpoints >= 1  # the widened policy is pinned

    # The pinned policy survives a resume.
    del sup
    res = Supervisor.resume(
        sc.strict3(), 1, sc.default_config(),
        checkpoint_path=str(tmp_path / "esc.ckpt"), gc_interval=0,
        epoch=0, ingest=IngestPolicy(grace_ms=0),
    )
    assert res.processor._guard.policy.grace_ms >= 1000


def test_escalate_ingest_rows():
    pol = IngestPolicy(grace_ms=0, reorder_depth=64)
    wider = sizing.escalate_ingest(pol, {"late_dropped": 3})
    assert wider.grace_ms >= 1000 and wider.reorder_depth == 64
    wider2 = sizing.escalate_ingest(pol, {"reorder_evictions": 1})
    assert wider2.reorder_depth > 64 and wider2.grace_ms == 0
    capped = sizing.escalate_ingest(
        pol, {"late_dropped": 1}, max_policy=IngestPolicy(grace_ms=0)
    )
    assert capped is None  # at the ceiling: nothing can grow


def test_guard_state_roundtrip_is_exact():
    g = IngestGuard(IngestPolicy(grace_ms=5, reorder_depth=8))
    for i, r in enumerate(trace(VALS[:8], keys=("k",))):
        g.push(r._replace(offset=i))
        g.source_hw[0] = i + 1
    g.quarantine(Record("k", 99, 1), REASON_SCHEMA, "detail", "corr-1")
    g.release()
    h = IngestGuard.from_state(g.to_state())
    assert h.to_state() == g.to_state()
    assert h.held == g.held and h.watermark == g.watermark
    assert h.drain() == g.drain()


# -- chaos: crashes with a non-empty reorder buffer ---------------------------

CHAOS_CFG = EngineConfig(
    max_runs=16, slab_entries=48, slab_preds=8, dewey_depth=16, max_walk=12
)
CHAOS_FAULTS = (
    ("ingest.admit", 0.12, 1),
    ("ingest.release", 0.12, 1),
    ("device.dispatch", 0.08, 1),
    ("device.result", 0.08, 1),
    ("checkpoint.save", 0.08, 1),
    ("journal.append", 0.08, 1),
)


def chaos_batches(seed, grace=6):
    """A seeded 2-key stream, bounded-skew shuffled, with explicit
    source offsets in ARRIVAL order (the Kafka model: offsets are log
    positions; event time is what's disordered)."""
    rng = np.random.default_rng(seed)
    vals = [int(rng.integers(0, 5)) for _ in range(12)]
    recs = trace(vals, keys=("k0", "k1"))
    shuffled = bounded_shuffle(recs, grace, seed + 77)
    offs = collections.defaultdict(int)
    withoff = []
    for r in shuffled:
        withoff.append(r._replace(offset=offs[r.key]))
        offs[r.key] += 1
    return [withoff[i:i + 4] for i in range(0, len(withoff), 4)]


def mk_guarded_sup(ck, jr, resume=False, grace=6):
    args = (sc.skip_till_any(), 2, CHAOS_CFG)
    kw = dict(
        checkpoint_path=ck, journal_path=jr, checkpoint_every=2,
        gc_interval=0, epoch=0, ingest=IngestPolicy(grace_ms=grace),
    )
    if resume:
        return Supervisor.resume(*args, **kw)
    return Supervisor(*args, **kw)


def canon_match(key, seq):
    return (key, tuple(sorted(
        (stage, tuple(sorted(e.offset for e in events)))
        for stage, events in seq.as_map().items()
    )))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ingest_chaos_crash_with_held_records(seed, tmp_path):
    batches = chaos_batches(seed)
    # Fault-free oracle, same batching, same guard.
    oracle = mk_guarded_sup(
        str(tmp_path / "o.ckpt"), str(tmp_path / "o.jrnl")
    )
    want = collections.Counter()
    for b in batches:
        for k, s in oracle.process(b):
            want[canon_match(k, s)] += 1
    for k, s in oracle.drain_ingest():
        want[canon_match(k, s)] += 1

    ck, jr = str(tmp_path / f"c{seed}.ckpt"), str(tmp_path / f"c{seed}.jrnl")
    sup = mk_guarded_sup(ck, jr)
    sup._sleep = lambda s: None  # no real backoff waits in CI
    rng = np.random.default_rng(seed + 500)
    emitted = collections.Counter()
    crashes_with_held = 0
    i, guard_iter = 0, 0
    while i < len(batches):
        guard_iter += 1
        assert guard_iter < 200, "chaos made no progress"
        for site, p, times in CHAOS_FAULTS:
            if rng.random() < p:
                fp.FAILPOINTS.arm(site, times=times)
        crash_after = rng.random() < 0.22
        try:
            for k, s in sup.process(batches[i]):
                emitted[canon_match(k, s)] += 1
            i += 1
        except (fp.InjectedFault, fp.InjectedIOError):
            crash_after = True
        finally:
            fp.FAILPOINTS.clear()
        if crash_after:
            if sup.processor._guard.held > 0:
                crashes_with_held += 1
            del sup
            sup = mk_guarded_sup(ck, jr, resume=True)
            sup._sleep = lambda s: None
            i = 0  # at-least-once source re-submits; dedup absorbs
    for k, s in sup.drain_ingest():
        emitted[canon_match(k, s)] += 1

    assert emitted == want, f"seed {seed}: exactly-once violated"
    import jax

    ca = canonical_state(sup.processor.state)
    cb = canonical_state(oracle.processor.state)
    for n, (x, y) in enumerate(
        zip(jax.tree_util.tree_leaves(ca), jax.tree_util.tree_leaves(cb))
    ):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"seed {seed}: state leaf {n} diverged",
        )
    assert not any(sup.processor.counters().values())
    # The suite as a whole must see crashes with records in the buffer;
    # per-seed it is stochastic, so stash the observation for the
    # aggregate assertion below.
    _HELD_CRASHES.append(crashes_with_held)


_HELD_CRASHES = []


def test_ingest_chaos_observed_crashes_with_held_records():
    """Aggregate over the seeds above: at least one crash landed while
    the reorder buffer was non-empty (the adversarial window the
    snapshot+journal protocol must cover)."""
    assert sum(_HELD_CRASHES) > 0


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(40, 80))
def test_ingest_chaos_sweep(seed, tmp_path):
    test_ingest_chaos_crash_with_held_records(seed, tmp_path)
