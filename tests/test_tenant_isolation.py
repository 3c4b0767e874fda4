"""Per-tenant isolation: quotas, admission shedding, quarantine.

The contract under test (``compiler/multitenant.py: TenantQuota`` +
``parallel/tenantbank.py: TenantIsolation`` + ``runtime/tenant.py``):

- **Quotas** mask an over-budget tenant's prefix fires in the shared
  screen; sheds are counted per tenant (``quota_shed``) and every other
  tenant's emissions stay bit-identical to an unquotaed bank.
- **Admission shedding** drops a flooding tenant's records at the front
  door with a typed ``tenant_quota`` dead letter, atomically per batch
  (a raise rolls the ledger back, so replay meets identical buckets).
- **Quarantine** circuit-breaks one query out of the bank — its columns
  go dark, its state freezes — and the rest of the bank is bit-identical
  to a bank that never contained it (the differential blast-radius
  proof, on the jnp path and the Pallas walk kernel).
- **Isolated escalation** attributes capacity trips per tenant and
  refuses bank-wide widening charged to an over-quota tenant.

Fixture idioms (CFG, traces, record batches) come from
test_multitenant — the loss-free precondition scoping serial parity is
the same.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from test_multitenant import (
    CFG,
    MIXED,
    batches,
    canon,
    ge,
    lt,
    make_patterns,
    q_hybrid,
    q_stencil,
    trace,
)

from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.compiler.multitenant import TenantQuota
from kafkastreams_cep_tpu.engine.sizing import EscalationPolicy
from kafkastreams_cep_tpu.parallel.tenantbank import TenantBankMatcher
from kafkastreams_cep_tpu.runtime.ingest import (
    REASON_DOCS,
    REASON_TENANT_QUOTA,
    REASONS,
    policy_table_markdown,
)
from kafkastreams_cep_tpu.runtime.processor import Record
from kafkastreams_cep_tpu.runtime.tenant import (
    AdmissionPolicy,
    QuarantinePolicy,
    TenantCEP,
    TenantMisbehave,
    TenantSupervisor,
    restore_tenant,
    save_tenant_checkpoint,
)
from kafkastreams_cep_tpu.utils.failpoints import (
    FAILPOINTS,
    InjectedIOError,
    random_schedule,
)
from kafkastreams_cep_tpu.utils.telemetry import render_prometheus


# -- quota enforcement at the shared screen -----------------------------------


def test_match_rate_zero_sheds_and_isolates(monkeypatch):
    """A zero match-rate budget sheds a tenant's every prefix fire from
    the first batch; the other tenants' emissions are bit-identical to
    an unquotaed bank's and the sheds are ledgered per tenant."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    K, T = 4, 16
    names = ["free", "capped", "other"]
    patterns = [MIXED[0], MIXED[1], MIXED[2]]
    bank = TenantBankMatcher(
        patterns, K, CFG, names=names,
        quotas={"capped": TenantQuota(match_rate_budget=0.0)},
    )
    ref = TenantBankMatcher(patterns, K, CFG, names=names)
    st, sr = bank.init_state(), ref.init_state()
    for b in range(3):
        ev = trace(K, T, 201 + b)
        st, out = bank.scan(st, ev)
        sr, outr = ref.scan(sr, ev)
        assert not np.asarray(out.count)[1].any(), "capped tenant emitted"
        for f in ("count", "stage", "off"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f))[[0, 2]],
                np.asarray(getattr(outr, f))[[0, 2]],
                err_msg=f"batch {b} field {f}",
            )
    pq = bank.per_query_counters(st)
    assert pq["capped"]["quota_shed"] > 0
    assert pq["capped"]["quota_throttled"] == 1
    assert pq["free"]["quota_shed"] == 0 and pq["other"]["quota_shed"] == 0
    # Screen-level reconciliation: every offered fire was shed.
    assert bank.iso.offered_fires[1] == bank.iso.quota_shed[1] > 0
    snap = bank.metrics_snapshot(st)
    assert snap["quota_shed_total"] == int(bank.iso.quota_shed.sum())
    assert snap["quota_throttled_queries"] == 1
    text = render_prometheus(snap)
    assert 'cep_quota_shed{query="capped"}' in text


def test_pred_eval_budget_masks_offending_batch_itself(monkeypatch):
    """``pred_eval_budget`` is pre-dispatch (usage = K*T*p is known
    before the scan), so it masks the offending batch itself — no
    one-batch verdict lag, no throttle latch."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    K, T = 4, 16
    names = ["free", "tiny"]
    patterns = [MIXED[0], MIXED[1]]
    # K*T*p = 4*16*2 = 128 > 100: every batch of this shape is masked.
    bank = TenantBankMatcher(
        patterns, K, CFG, names=names,
        quotas={"tiny": TenantQuota(pred_eval_budget=100)},
    )
    ref = TenantBankMatcher(patterns, K, CFG, names=names)
    st, sr = bank.init_state(), ref.init_state()
    for b in range(3):
        ev = trace(K, T, 71 + b)
        st, out = bank.scan(st, ev)
        sr, _ = ref.scan(sr, ev)
        assert not np.asarray(out.count)[1].any()
    # The mask is stateless per batch: sheds equal the unquotaed bank's
    # raw fire count exactly, and no throttle verdict is latched.
    assert bank.iso.quota_shed[1] == ref.iso.offered_fires[1] > 0
    assert bank.per_query_counters(st)["tiny"]["quota_throttled"] == 0


def test_live_lane_quota_throttles_with_one_batch_lag(monkeypatch):
    """``max_live_lanes``: the batch that first exceeds the quota
    completes (its usage rides the gate readback), the next is masked
    and its fires shed."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    K, T = 4, 16
    sticky = q_hybrid(8, 3, 99)  # suffix never satisfied: runs stay live
    bank = TenantBankMatcher(
        [MIXED[0], sticky], K, CFG, names=["free", "sticky"],
        quotas={"sticky": TenantQuota(max_live_lanes=0)},
    )
    st = bank.init_state()
    # Batch 1 promotes runs; the usage bundle rides the gate readback,
    # so batch 2's scan is the first to SEE them live and latch the
    # verdict; batch 3 is the first masked one.
    st, _ = bank.scan(st, trace(K, T, 301))
    assert bank.iso.quota_shed[1] == 0
    st, _ = bank.scan(st, trace(K, T, 302))
    assert bank.iso.live_lanes[1] > 0, "fixture must leave live runs"
    assert bank.iso.throttled[1]
    assert bank.iso.over[1] == ("max_live_lanes",)
    assert bank.iso.quota_shed[1] == 0, "verdict batches complete unmasked"
    st, _ = bank.scan(st, trace(K, T, 303))
    assert bank.iso.quota_shed[1] > 0, "post-verdict fires must shed"


# -- quarantine: differential blast-radius proof ------------------------------


def _assert_quarantine_blast_radius(patterns, victim, K, T, n_batches,
                                    seed0, cfg=CFG):
    """Quarantine ``victim`` mid-stream and prove containment: every
    surviving tenant's emissions and counters are bit-identical, batch
    by batch, to a bank that NEVER contained the victim; the victim
    emits nothing once dark."""
    names = [f"q{i}" for i in range(len(patterns))]
    full = TenantBankMatcher(patterns, K, cfg, names=names)
    keep = [i for i in range(len(patterns)) if i != victim]
    ref = TenantBankMatcher([patterns[i] for i in keep], K, cfg)
    sf, sr = full.init_state(), ref.init_state()
    cut = n_batches // 2
    for b in range(n_batches):
        if b == cut:
            full.quarantine(victim)
        ev = trace(K, T, seed0 + b)
        sf, outf = full.scan(sf, ev)
        sr, outr = ref.scan(sr, ev)
        for f in ("count", "stage", "off"):
            np.testing.assert_array_equal(
                np.asarray(getattr(outf, f))[keep],
                np.asarray(getattr(outr, f)),
                err_msg=f"batch {b} field {f}",
            )
        if b >= cut:
            assert not np.asarray(outf.count)[victim].any(), (
                f"quarantined tenant emitted in batch {b}"
            )
    assert full.quarantined_qids == [victim]
    pf, pr = full.per_query_counters(sf), ref.per_query_counters(sr)
    iso_keys = ("quota_shed", "quota_throttled", "quarantined")
    for ri, qi in enumerate(keep):
        a = {k: v for k, v in pf[f"q{qi}"].items() if k not in iso_keys}
        b_ = {k: v for k, v in pr[f"q{ri}"].items() if k not in iso_keys}
        assert a == b_, f"survivor q{qi} counters diverged"
    return full, sf


@pytest.mark.parametrize("victim", [1, 3], ids=["shared-prefix", "private"])
def test_quarantine_blast_radius_jnp(monkeypatch, victim):
    """jnp path.  victim=1 shares its full prefix with query 0 (the
    shared columns must keep evaluating — the live tenant paid for
    them); victim=3 has a private prefix (its columns go dark)."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    _assert_quarantine_blast_radius(
        MIXED, victim, K=5, T=20, n_batches=4, seed0=501
    )


def test_quarantine_blast_radius_walk_kernel(monkeypatch):
    from kafkastreams_cep_tpu.parallel.batch import _select_walk_kernel

    monkeypatch.setenv("CEP_WALK_KERNEL", "interpret")
    patterns = [q_hybrid(8, 3, 9), q_hybrid(9, 1, 7)]
    assert _select_walk_kernel(CFG, 2 * 64) == (True, True)
    _assert_quarantine_blast_radius(
        patterns, 0, K=64, T=12, n_batches=2, seed0=5
    )


def test_quarantine_checkpoint_restore_and_reinstate(tmp_path):
    """Quarantine state (flags + reasons + shed ledgers) rides the
    checkpoint header; restore rebuilds enforcement WITHOUT re-entering
    the ``quarantine.enter`` failpoint, continuations are identical,
    and reinstate resumes the frozen tenant."""
    bs = batches(6, seed=7)
    t = TenantCEP(make_patterns(), 3, CFG)
    for b in bs[:2]:
        t.process(b)
    t.quarantine("crash", "manual")
    for b in bs[2:4]:
        t.process(b)
    assert t.quarantined_names() == ["crash"]
    path = str(tmp_path / "iso.ckpt")
    save_tenant_checkpoint(t, path)
    with FAILPOINTS.session():
        t2 = restore_tenant(make_patterns(), path)
        assert FAILPOINTS.hits("quarantine.enter") == 0, (
            "restore must rebuild quarantine state, not re-enter it"
        )
    assert t2.quarantined_names() == ["crash"]
    assert t2.quarantine_reasons == {"crash": "manual"}
    # Satellite: per-query counters and plan stats survive round-trip.
    assert t2.per_query_counters() == t.per_query_counters()
    assert t2.batch.bank.stats == t.batch.bank.stats
    m1 = [canon(t.process(b)) for b in bs[4:]]
    m2 = [canon(t2.process(b)) for b in bs[4:]]
    assert m1 == m2
    assert all(qn != "crash" for batch in m1 for qn, _, _ in batch)
    t.reinstate("crash")
    assert t.quarantined_names() == []
    assert t.quarantine_reasons == {}
    t.process(batches(1, seed=99)[0])  # reinstated bank stays live


def test_widen_with_quarantined_tenant(tmp_path, monkeypatch):
    """Capacity widening with a quarantined tenant present: the iso
    state (including the dark columns) migrates with the bank, the
    widened incarnation is pinned with a checkpoint, and emissions stay
    identical to an un-widened twin."""
    monkeypatch.setenv("CEP_WALK_KERNEL", "0")
    wide_cfg = dataclasses.replace(
        CFG, max_runs=16, slab_entries=48, max_walk=12
    )
    bs = batches(5, seed=41)
    ref = TenantCEP(make_patterns(), 3, CFG)
    sup = TenantSupervisor(
        make_patterns(), 3, CFG,
        checkpoint_path=str(tmp_path / "w.ckpt"), retry_backoff_ms=0.0,
    )
    for b in bs[:2]:
        assert canon(sup.process(b)) == canon(ref.process(b))
    ref.quarantine("crash", "capacity")
    sup._quarantine_for("crash", "capacity")
    sup._widen(wide_cfg)
    assert sup.tenant.batch.config.max_runs == 16
    assert sup.tenant.quarantined_names() == ["crash"]
    assert sup.checkpoints >= 1, "widening must pin a checkpoint"
    for b in bs[2:]:
        assert canon(sup.process(b)) == canon(ref.process(b))


# -- admission shedding at the front door -------------------------------------


def test_admission_shedding_ledger_and_atomic_rollback():
    """Token-bucket admission sheds a flooding tenant's records with a
    typed ``tenant_quota`` dead letter; per tenant
    ``offered == admitted + shed + quarantined_dropped``; an injected
    ``"quota.shed"`` fault rolls the whole batch's ledger back so the
    retried batch meets identical buckets."""
    t = TenantCEP(
        make_patterns(), 3, CFG,
        admission=AdmissionPolicy(rate_per_batch=2.0, burst=2.0),
    )
    bs = batches(4, per_batch=12, seed=7)
    for b in bs[:2]:
        t.process(b)
    led = t.admission_ledger()
    assert set(led) == {"alpha", "beta", "gamma"}
    for row in led.values():
        assert row["offered"] == (
            row["admitted"] + row["shed"] + row["quarantined_dropped"]
        )
    total_shed = sum(r["shed"] for r in led.values())
    assert total_shed > 0, "fixture must actually shed"
    snap = t.metrics_snapshot()
    assert snap["dead_letters"] == {REASON_TENANT_QUOTA: total_shed}
    assert snap["dead_letter_depth"] == total_shed
    assert snap["admission_shed_total"] == total_shed
    text = render_prometheus(snap)
    assert 'dead_letters_total{reason="tenant_quota"}' in text

    before = t.admission_ledger()
    with FAILPOINTS.session({"quota.shed": [0]}):
        with pytest.raises(InjectedIOError):
            t.process(bs[2])
        assert t.admission_ledger() == before, (
            "a failed batch must not half-count admission"
        )
        t.process(bs[2])  # retry replays against identical buckets
    after = t.admission_ledger()
    for k in after:
        assert after[k]["offered"] == (
            before[k]["offered"]
            + sum(1 for r in bs[2] if r.key == k)
        )


# -- supervisor: attribution, containment, recovery ---------------------------


def test_misbehave_quarantines_offender_and_defers_on_enter_fault(tmp_path):
    """A ``"tenant.misbehave"`` fault quarantines exactly the named
    tenant; a ``"quarantine.enter"`` fault during that quarantine
    leaves the bank live and un-quarantined, and the recorded decision
    is re-applied on recovery.  Compliant tenants' matches equal the
    fault-free oracle's throughout."""
    bs = batches(4, seed=19)
    ref = TenantCEP(make_patterns(), 3, CFG)
    ref_m = [canon(ref.process(b)) for b in bs]
    assert sum(len(m) for m in ref_m) > 0
    sup = TenantSupervisor(
        make_patterns(), 3, CFG,
        checkpoint_path=str(tmp_path / "q.ckpt"),
        checkpoint_every=100, max_retries=3, retry_backoff_ms=0.0,
    )
    with FAILPOINTS.session({"quarantine.enter": [0]}):
        FAILPOINTS.arm(
            "tenant.misbehave", hits=[1],
            exc=lambda: TenantMisbehave("crash"),
        )
        got = [canon(sup.process(b)) for b in bs]
        # First entry attempt faulted (deferred), recovery re-applied it.
        assert FAILPOINTS.hits("quarantine.enter") == 2
    assert sup.quarantines == {"crash": "misbehave"}
    assert sup.tenant.quarantined_names() == ["crash"]
    assert sup.tenant_quarantines == 1
    assert sup.recoveries == 1
    compliant = lambda ms: [m for m in ms if m[0] != "crash"]
    assert got[0] == ref_m[0]  # pre-quarantine batch fully intact
    assert [compliant(g) for g in got[1:]] == [
        compliant(r) for r in ref_m[1:]
    ]
    assert all(m[0] != "crash" for g in got[1:] for m in g)


def test_poisoned_predicate_attributed_and_quarantined(tmp_path):
    """A tenant predicate that starts raising at (re)trace time is
    attributed by ``find_poison`` host probing, its owner quarantined
    (columns dark — the poisoned predicate is never called again), and
    the compliant tenant's matches are unaffected even while the
    predicate keeps raising."""
    flag = {"on": False}

    def poison(th):
        def pred(k, v, ts, st, th=th):
            if flag["on"]:
                raise RuntimeError("tenant predicate corrupted")
            return v["x"] >= th

        return pred

    def make():
        return {
            "spike": q_stencil(8, 3, 7),
            "toxic": (
                Query()
                .select("a").where(ge(8)).then()
                .select("b").where(lt(3)).then()
                .select("c").where(poison(7)).build()
            ),
        }

    def kv(key, x, ts):
        return Record(key=key, value={"x": x}, timestamp=ts)

    xs1, xs2, xs3 = (
        [9, 2, 8],
        [9, 1, 7, 8, 0, 9, 9, 2, 8],  # 9 records: a bigger T bucket
        [8, 2, 7],
    )
    ts = iter(range(1, 100))
    b1 = [kv("alpha", x, next(ts)) for x in xs1]
    b2 = [kv("alpha", x, next(ts)) for x in xs2]
    b3 = [kv("alpha", x, next(ts)) for x in xs3]

    sup = TenantSupervisor(
        make(), 2, CFG,
        checkpoint_path=str(tmp_path / "p.ckpt"),
        checkpoint_every=10, max_retries=2, retry_backoff_ms=0.0,
    )
    got = [canon(sup.process(b1))]
    flag["on"] = True  # the retrace forced by b2's batch shape raises
    got.append(canon(sup.process(b2)))
    got.append(canon(sup.process(b3)))  # still poisoned, still contained
    assert sup.quarantines == {"toxic": "predicate_raise"}
    assert sup.tenant.quarantined_names() == ["toxic"]
    assert sup.recoveries >= 1

    flag["on"] = False
    oracle = TenantCEP(make(), 2, CFG)
    ref_m = [canon(oracle.process(b)) for b in (b1, b2, b3)]
    spikes = lambda ms: [m for m in ms if m[0] == "spike"]
    assert [spikes(g) for g in got] == [spikes(r) for r in ref_m]
    assert sum(len(spikes(r)) for r in ref_m) > 0


def test_escalation_denied_for_over_quota_tenant(tmp_path):
    """Capacity trips attributed to a tenant that is over its declared
    quota refuse the bank-wide widening (``tenant_escalation_denied``)
    and, at the denial streak, quarantine the offender — one tenant
    cannot grow everyone's engine."""
    patterns = {
        "spike": q_stencil(8, 3, 7),
        "flood": q_hybrid(0, 10, 99),  # every pair promotes, never closes
    }
    sup = TenantSupervisor(
        patterns, 3, CFG,
        checkpoint_path=str(tmp_path / "d.ckpt"), retry_backoff_ms=0.0,
        auto_escalate=EscalationPolicy(),
        quarantine_policy=QuarantinePolicy(trip_streak=1),
        quotas={"flood": TenantQuota(max_live_lanes=1)},
    )
    # Batch 1 stays under max_runs (no trip while the live-lane verdict
    # is still unlatched — usage rides the readback with a one-batch
    # lag); batch 2's promotions overflow the run queue WITH the quota
    # violation latched, so the trip is denied, not escalated.
    for b in batches(3, per_batch=16, seed=13):
        sup.process(b)
    assert sup.tenant_escalation_denied >= 1
    assert sup.quarantines.get("flood") == "capacity"
    assert sup.escalations == 0
    assert sup.tenant.batch.config.max_runs == CFG.max_runs, (
        "a denied escalation must leave the bank config untouched"
    )
    pq = sup.per_query_counters()
    assert pq["flood"]["run_drops"] > 0, "fixture must actually trip"
    assert pq["spike"]["run_drops"] == 0
    snap = sup.metrics_snapshot()
    assert snap["tenant_escalation_denied"] == sup.tenant_escalation_denied
    assert snap["tenant_quarantines"] == 1


def test_escalation_widens_for_compliant_trips(tmp_path):
    """The same trip pattern WITHOUT a violated quota escalates: the
    bank widens live (state migrated, checkpoint pinned) and keeps
    processing."""
    patterns = {
        "spike": q_stencil(8, 3, 7),
        "greedy": q_hybrid(0, 10, 99),
    }
    sup = TenantSupervisor(
        patterns, 3, CFG,
        checkpoint_path=str(tmp_path / "e.ckpt"), retry_backoff_ms=0.0,
        auto_escalate=EscalationPolicy(),
    )
    bs = batches(2, per_batch=45, seed=7)
    sup.process(bs[0])
    assert sup.escalations >= 1
    assert sup.tenant_escalation_denied == 0
    assert sup.quarantines == {}
    assert sup.tenant.batch.config.max_runs > CFG.max_runs
    assert sup.checkpoints >= 1, "widening must pin a checkpoint"
    sup.process(bs[1])  # the widened bank keeps processing


def test_retry_backoff_deterministic(tmp_path):
    """Retry and recovery-loop backoff follow the supervisor discipline:
    exponential in attempt, capped, jitter seeded by (batches, attempt)
    — two identical runs wait identically; 0 disables."""

    def run(tag):
        sup = TenantSupervisor(
            make_patterns(), 3, CFG,
            checkpoint_path=str(tmp_path / f"b{tag}.ckpt"),
            max_retries=3, retry_backoff_ms=100.0,
            retry_backoff_cap_ms=400.0,
        )
        sleeps = []
        sup._sleep = sleeps.append
        bs = batches(2, seed=19)
        sup.process(bs[0])
        with FAILPOINTS.session({"device.dispatch": [0, 1]}):
            sup.process(bs[1])
        return sup, sleeps

    sup1, s1 = run("x")
    sup2, s2 = run("y")
    assert s1 == s2, "backoff schedule must be deterministic"
    # One retry backoff plus one recovery-loop backoff (the journal
    # replay faulted once mid-recovery).
    assert len(s1) == 2
    rng = np.random.default_rng((2, 0))
    expected = 100.0 * (0.5 + 0.5 * float(rng.random())) / 1000.0
    assert s1[0] == pytest.approx(expected)
    assert 0.05 <= s1[0] < 0.1
    assert sup1.retry_backoff_ms_total == pytest.approx(sum(s1) * 1000.0)
    assert sup1.recoveries >= 1

    sup3 = TenantSupervisor(
        make_patterns(), 3, CFG,
        checkpoint_path=str(tmp_path / "bz.ckpt"),
        max_retries=2, retry_backoff_ms=0.0,
    )
    sleeps3 = []
    sup3._sleep = sleeps3.append
    with FAILPOINTS.session({"device.dispatch": [0]}):
        sup3.process(batches(1, seed=19)[0])
    assert sleeps3 == [], "retry_backoff_ms=0 must not sleep"


def test_chaos_flood_and_misbehave_exactly_once_for_compliant(tmp_path):
    """Seeded chaos (device + checkpoint faults) plus a misbehaving
    tenant, with quotas and admission limiting live: every compliant
    tenant's matches are emitted exactly once in oracle order, the
    admission ledger reconciles bit-identically with the fault-free
    run's, and the quarantine survives crash/restore."""
    pol = AdmissionPolicy(rate_per_batch=5.0, burst=6.0)
    quotas = {"crash": TenantQuota(match_rate_budget=2.0)}
    kwargs = dict(admission=pol, quotas=quotas)
    bs = batches(8, seed=19)
    ref = TenantCEP(make_patterns(), 3, CFG, **kwargs)
    ref_m = [canon(ref.process(b)) for b in bs]
    assert sum(len(m) for m in ref_m) > 0

    schedule = random_schedule(
        seed=3, horizon=8, rate=0.3,
        sites=("device.dispatch", "device.result", "checkpoint.save"),
    )
    assert schedule, "seed produced an empty schedule; pick another"
    with FAILPOINTS.session(schedule):
        sup = TenantSupervisor(
            make_patterns(), 3, CFG,
            checkpoint_path=str(tmp_path / "c.ckpt"),
            checkpoint_every=2, max_retries=8, retry_backoff_ms=0.0,
            **kwargs,
        )
        got = []
        for i, b in enumerate(bs):
            if i == 5:
                # Arm at the CURRENT hit count so the very next fire is
                # batch 5's top-level attempt (the site also fires on
                # recovery replays, where misbehave is swallowed).
                FAILPOINTS.arm(
                    "tenant.misbehave",
                    hits=[FAILPOINTS.hits("tenant.misbehave")],
                    exc=lambda: TenantMisbehave("crash"),
                )
            got.append(canon(sup.process(b)))
    assert sup.recoveries > 0, "schedule never faulted; chaos was vacuous"
    assert sup.quarantines == {"crash": "misbehave"}, (
        "the misbehave injection must land on a live batch attempt"
    )
    compliant = lambda ms: [m for m in ms if m[0] != "crash"]
    assert [compliant(g) for g in got] == [compliant(r) for r in ref_m]
    # Exactly-once admission accounting across crash/replay: the ledger
    # equals the fault-free oracle's, and reconciles per tenant.
    assert sup.admission_ledger() == ref.admission_ledger()
    for row in sup.admission_ledger().values():
        assert row["offered"] == (
            row["admitted"] + row["shed"] + row["quarantined_dropped"]
        )
    # Compliant tenants' per-query counters also survive exactly-once.
    pq_s, pq_r = sup.per_query_counters(), ref.per_query_counters()
    assert pq_s["spike"] == pq_r["spike"]
    assert pq_s["dip"] == pq_r["dip"]
    snap = sup.metrics_snapshot()
    assert snap["tenant_quarantines"] == 1
    assert snap["quarantined_queries"] == 1


# -- the dead-letter policy contract ------------------------------------------


def test_dead_letter_reason_policy_single_source_of_truth():
    """The typed reason enum, its docs, and the README policy table are
    one artifact: README embeds ``policy_table_markdown()`` verbatim."""
    assert set(REASON_DOCS) == set(REASONS)
    assert REASON_TENANT_QUOTA in REASONS
    table = policy_table_markdown()
    for reason in REASONS:
        assert f"`{reason}`" in table
    readme = os.path.join(os.path.dirname(__file__), "..", "README.md")
    with open(readme, encoding="utf-8") as fh:
        text = fh.read()
    assert table in text, (
        "README dead-letter policy table has drifted from "
        "runtime/ingest.py: REASON_DOCS; regenerate it with "
        "policy_table_markdown()"
    )
