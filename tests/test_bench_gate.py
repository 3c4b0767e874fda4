"""Bench regression gate + profiler CLI — tier-1 smoke (ISSUE 6 satellite).

The gate must accept the repo's real BENCH_r01→r05 trajectory replayed
against itself unchanged, pass on a fixture equal to its baseline, and
reject a fixture with an injected 2× slowdown.  The profiler CLI must
emit one parseable PROFILE JSON object with the per-stage selectivity
table on a tiny synthetic trace.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)

import bench_gate

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _fx(name):
    return os.path.join(FIXTURES, name)


def test_gate_passes_on_equal_input():
    ok, report = bench_gate.gate_paths(
        _fx("bench_equal.json"), [_fx("bench_base.json")]
    )
    assert ok, report
    metrics = {c["metric"] for c in report["checks"]}
    assert {"value", "lossfree_evps", "lossfree_counters_zero"} <= metrics
    assert all(c["ok"] for c in report["checks"])


def test_gate_rejects_injected_2x_slowdown():
    ok, report = bench_gate.gate_paths(
        _fx("bench_slow_2x.json"), [_fx("bench_base.json")]
    )
    assert not ok
    bad = {c["metric"] for c in report["checks"] if not c["ok"]}
    assert {"value", "lossfree_evps"} <= bad


def test_gate_rejects_loss_flag_regression(tmp_path):
    doc = json.load(open(_fx("bench_equal.json")))
    doc["parsed"]["lossfree_counters_zero"] = False
    p = tmp_path / "lossy.json"
    p.write_text(json.dumps(doc))
    ok, report = bench_gate.gate_paths(str(p), [_fx("bench_base.json")])
    assert not ok
    assert any(
        c["metric"] == "lossfree_counters_zero" and not c["ok"]
        for c in report["checks"]
    )


def test_gate_accepts_real_trajectory_unchanged():
    """Each round gated against all earlier rounds must pass — the gate
    would have accepted the project's own history."""
    paths = sorted(glob.glob(os.path.join(_ROOT, "BENCH_r0*.json")))
    assert len(paths) >= 5
    docs = [bench_gate.load_doc(p) for p in paths]
    for k in range(1, len(docs)):
        ok, report = bench_gate.gate(docs[k], docs[:k])
        assert ok, (paths[k], report)


def test_gate_tolerates_noise_within_spread():
    base = bench_gate.load_doc(_fx("bench_base.json"))
    noisy = json.loads(json.dumps(base))
    noisy["parsed"]["value"] *= 0.95  # inside the 10% default tolerance
    ok, _ = bench_gate.gate(noisy, [base])
    assert ok
    worse = json.loads(json.dumps(base))
    worse["parsed"]["value"] *= 0.80  # outside it
    ok, _ = bench_gate.gate(worse, [base])
    assert not ok


def test_gate_cli_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_gate.py"),
         _fx("bench_equal.json"), _fx("bench_base.json")],
        capture_output=True, text=True, env=env,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    json.loads(ok.stdout)  # the verdict is machine-readable
    bad = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_gate.py"),
         _fx("bench_slow_2x.json"), "--trajectory",
         os.path.join(FIXTURES, "bench_base.json")],
        capture_output=True, text=True, env=env,
    )
    assert bad.returncode == 1


def test_profiler_cli_selectivity_smoke():
    """``python -m kafkastreams_cep_tpu.profile selectivity`` on a tiny
    synthetic trace: one JSON object on stdout with the per-stage
    selectivity table and the attribution-overhead A/B."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "kafkastreams_cep_tpu.profile",
         "selectivity", "--k", "8", "--t", "16", "--reps", "1"],
        capture_output=True, text=True, cwd=_ROOT, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["profile"] == "selectivity"
    assert doc["evps_attr_on"] > 0 and doc["evps_attr_off"] > 0
    per_stage = doc["per_stage"]
    assert per_stage, "per-stage table must not be empty"
    row = next(iter(per_stage.values()))
    for key in ("stage_evals", "stage_accepts", "stage_ignores",
                "stage_rejects", "stage_walk_hops", "selectivity"):
        assert key in row
    assert "top" in doc["per_key"]


def test_gate_guards_tier_parity_flags():
    """From BENCH_r06 on, the nested ``tier`` block's match-parity and
    counters-zero flags flatten into guarded ``tier_*`` flags: a later
    round may not regress them (ISSUE 7 satellite)."""
    r06 = bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r06.json"))
    m = bench_gate.extract_metrics(r06)
    assert m["tier_match_parity"] is True
    assert m["tier_counters_zero"] is True
    bad = json.loads(json.dumps(r06))
    bad["parsed"]["tier"]["match_parity"] = False
    ok, report = bench_gate.gate(bad, [r06])
    assert not ok
    assert any(
        c["metric"] == "tier_match_parity" and not c["ok"]
        for c in report["checks"]
    )
    # Earlier rounds without a tier block are simply unguarded, so the
    # historical trajectory still replays clean (covered above).
    assert "tier_match_parity" not in (
        bench_gate.extract_metrics(
            bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r05.json"))
        ) or {}
    )


def test_gate_guards_tenant_bank_flags():
    """From BENCH_r07 on, the nested ``tenants`` block's bit-exactness
    and all-counters-zero flags flatten into guarded ``tenant_*`` flags:
    the shared-screen bank may never silently diverge from the
    naive-fused oracle (ISSUE 14 satellite)."""
    r07 = bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r07.json"))
    m = bench_gate.extract_metrics(r07)
    assert m["tenant_match_parity"] is True
    assert m["tenant_loss_flags"] is True
    bad = json.loads(json.dumps(r07))
    bad["parsed"]["tenants"]["match_parity"] = False
    ok, report = bench_gate.gate(bad, [r07])
    assert not ok
    assert any(
        c["metric"] == "tenant_match_parity" and not c["ok"]
        for c in report["checks"]
    )
    lossy = json.loads(json.dumps(r07))
    lossy["parsed"]["tenants"]["counters_zero"] = False
    ok, report = bench_gate.gate(lossy, [r07])
    assert not ok
    assert any(
        c["metric"] == "tenant_loss_flags" and not c["ok"]
        for c in report["checks"]
    )
    # Rounds predating the tenants block stay unguarded on these flags,
    # so the historical trajectory replays clean (covered above).
    assert "tenant_match_parity" not in (
        bench_gate.extract_metrics(
            bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r06.json"))
        ) or {}
    )


def test_gate_guards_tenant_iso_flags():
    """From BENCH_r09 on, the nested ``resilience.tenant`` block's
    isolation flags flatten into guarded ``tenant_iso_*`` flags: with one
    tenant flooding past its quota, the compliant tenants' matches must
    stay bit-equal to the unquotaed clean bank's (parity) and lose
    nothing to shedding (compliant_lossfree) — a later round may not
    regress either (ISSUE 17 satellite)."""
    r09 = bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r09.json"))
    m = bench_gate.extract_metrics(r09)
    assert m["tenant_iso_parity"] is True
    assert m["tenant_iso_compliant_lossfree"] is True
    bad = json.loads(json.dumps(r09))
    bad["parsed"]["resilience"]["tenant"]["parity"] = False
    ok, report = bench_gate.gate(bad, [r09])
    assert not ok
    assert any(
        c["metric"] == "tenant_iso_parity" and not c["ok"]
        for c in report["checks"]
    )
    lossy = json.loads(json.dumps(r09))
    lossy["parsed"]["resilience"]["tenant"]["compliant_lossfree"] = False
    ok, report = bench_gate.gate(lossy, [r09])
    assert not ok
    assert any(
        c["metric"] == "tenant_iso_compliant_lossfree" and not c["ok"]
        for c in report["checks"]
    )
    # Rounds predating the resilience.tenant block stay unguarded on
    # these flags, so the historical trajectory replays clean.
    assert "tenant_iso_parity" not in (
        bench_gate.extract_metrics(
            bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r08.json"))
        ) or {}
    )


def test_gate_guards_latency_flags_and_p99_ceiling():
    """From BENCH_r10 on, the nested ``latency`` block flattens into the
    guarded ``latency_*`` flags (ledger on/off match+counter parity,
    within-config cadence/grace scheduling parity) and the
    ``latency_e2e_p99_s`` lower-is-better ceiling: observability may
    never change what the engine computes, and the end-to-end p99 may
    not silently blow past the trajectory's best (ISSUE 18 satellite)."""
    r10 = bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r10.json"))
    m = bench_gate.extract_metrics(r10)
    assert m["latency_parity"] is True
    assert m["latency_ab_parity"] is True
    assert m["latency_e2e_p99_s"] > 0
    for key, metric in (
        ("parity", "latency_parity"),
        ("ab_match_parity", "latency_ab_parity"),
    ):
        bad = json.loads(json.dumps(r10))
        bad["parsed"]["latency"][key] = False
        ok, report = bench_gate.gate(bad, [r10])
        assert not ok
        assert any(
            c["metric"] == metric and not c["ok"]
            for c in report["checks"]
        )
    slow = json.loads(json.dumps(r10))
    # The ceiling's latency-specific tolerance is wide (tail latency is
    # log-bucket quantized); 5x p99 must still trip it.
    slow["parsed"]["latency"]["e2e_p99_s"] *= 5
    ok, report = bench_gate.gate(slow, [r10])
    assert not ok
    assert any(
        c["metric"] == "latency_e2e_p99_s" and not c["ok"]
        for c in report["checks"]
    )
    # Rounds predating the latency block stay unguarded on these
    # metrics, so the historical trajectory replays clean.
    assert "latency_parity" not in (
        bench_gate.extract_metrics(
            bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r09.json"))
        ) or {}
    )


def test_gate_guards_overload_flags():
    """From BENCH_r11 on, the nested ``overload`` block flattens into
    the guarded ``overload_*`` flags: the brownout loss ledger must keep
    reconciling exactly (``offered == admitted + shed + dead_lettered``)
    and the ladder must keep recovering to L0 once the flood subsides —
    a later round may not regress either (ISSUE 20 satellite)."""
    r11 = bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r11.json"))
    m = bench_gate.extract_metrics(r11)
    assert m["overload_ledger_reconciles"] is True
    assert m["overload_recovers"] is True
    # The new round itself gates clean against the full history.
    history = [
        bench_gate.load_doc(p)
        for p in sorted(glob.glob(os.path.join(_ROOT, "BENCH_r*.json")))
        if not p.endswith("BENCH_r11.json")
    ]
    ok, report = bench_gate.gate(r11, history)
    assert ok, report
    for key, metric in (
        ("ledger_reconciles", "overload_ledger_reconciles"),
        ("recovers", "overload_recovers"),
    ):
        bad = json.loads(json.dumps(r11))
        bad["parsed"]["overload"][key] = False
        ok, report = bench_gate.gate(bad, [r11])
        assert not ok
        assert any(
            c["metric"] == metric and not c["ok"]
            for c in report["checks"]
        )
    # Rounds predating the overload block stay unguarded on these flags,
    # so the historical trajectory replays clean.
    assert "overload_ledger_reconciles" not in (
        bench_gate.extract_metrics(
            bench_gate.load_doc(os.path.join(_ROOT, "BENCH_r10.json"))
        ) or {}
    )
