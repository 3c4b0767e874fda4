"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (``tests/test_tpu_compile.py`` compiles
the kernels for a described v5e topology, and ``chip_smoke.py`` runs the
served path on the real chip).  Set ``CEP_TEST_TPU=1`` to run the suite on
whatever platform the environment provides instead (the sharding tests
then skip if fewer than 8 devices are present).
"""

import os
import tempfile

if not os.environ.get("CEP_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    # In case a plugin imported jax before the variable was set.
    jax.config.update("jax_platforms", "cpu")
    # Persistent compilation cache: the suite compiles the same engine
    # programs (identical HLO, distinct Python closures) dozens of times;
    # caching them cuts suite wall time substantially across and within
    # runs.  JAX_COMPILATION_CACHE_DIR, when set, places it (JAX reads the
    # variable itself); otherwise CEP_TEST_CACHE_DIR ('' disables), then a
    # temp-dir default.
    _cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.environ.get(
        "CEP_TEST_CACHE_DIR",
        os.path.join(tempfile.gettempdir(), "cep_tpu_jax_cache"),
    )
    if _cache:
        jax.config.update("jax_compilation_cache_dir", _cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1
        )


def pytest_sessionfinish(session, exitstatus):
    """Remember the session's exit status for the fast exit below."""
    global _EXITSTATUS
    _EXITSTATUS = int(exitstatus)


_EXITSTATUS = None


def pytest_unconfigure(config):
    """Skip interpreter teardown: after a full suite run the final GC of
    accumulated JAX state (hundreds of jitted executables, interpret-mode
    Pallas traces, the process-level trace cache) takes 40 s+ — dead time
    that counts against the tier-1 wall budget after the last test has
    already passed.  The terminal summary is printed by the time
    ``pytest_unconfigure`` runs, so flush and exit with pytest's own
    status.  ``CEP_TEST_NO_FAST_EXIT=1`` restores the normal exit path
    (e.g. for plugins that need atexit hooks, like coverage)."""
    if _EXITSTATUS is None or os.environ.get("CEP_TEST_NO_FAST_EXIT"):
        return
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_EXITSTATUS)


def pytest_collection_modifyitems(config, items):
    """Run the newest (and compile-heaviest) suites last.

    Tier-1 runs under a fixed wall budget; ordering the newest suites
    after the long-standing ones means a budget truncation cuts the
    newest coverage first instead of displacing established tests —
    the no-worse-than-baseline dot count stays monotone as suites grow.
    Newest last: the PR 8 shard-fault suites follow the PR 7 tiering
    suite, which follows everything else in collection order.
    """
    def _age(it):
        nid = it.nodeid
        if "test_overload" in nid:
            return 6  # PR 13: overload control (incl. chaos section)
        if "test_latency" in nid or "test_metrics_guard" in nid:
            return 5  # PR 18: latency attribution
        if "test_tenant_isolation" in nid:
            return 4  # PR 11: per-tenant isolation
        if "test_multitenant" in nid:
            return 3  # PR 9: multi-tenant query bank
        if (
            "test_shard_fault" in nid
            or "test_shard_chaos" in nid
            or "test_chaos_schedule_tiered" in nid
            or "test_resume_on_shrunk_mesh" in nid
        ):
            return 2  # PR 8: shard fault tolerance
        if "test_tiering" in nid:
            return 1  # PR 7: compiler tiering
        return 0

    items.sort(key=_age)  # stable: collection order kept within a tier
