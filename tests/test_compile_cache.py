"""The compile-cache helper every entry point calls, and the HBM gauge."""

from pathlib import Path

import jax
import pytest

from kafkastreams_cep_tpu.utils import compile_cache, metrics

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    seen = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: seen.append((name, value))
    )
    return seen


def test_environment_placed_cache_sets_nothing(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None
    assert updates == []


def test_default_cache_is_fixed_inside_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == str(REPO / ".jax_cache")
    assert compile_cache.compile_cache_dir() == first
    assert compile_cache.enable_compile_cache() == first
    assert compile_cache.enable_compile_cache() == first
    assert updates == [("jax_compilation_cache_dir", first)] * 2


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_hbm_gauge_reports_the_fullest_device(monkeypatch):
    devs = [
        _Dev({"bytes_in_use": 10, "bytes_limit": 100, "num_allocs": 3}),
        _Dev({"bytes_in_use": 70, "bytes_limit": 100}),
        _Dev(None),
    ]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    assert metrics.device_memory_stats() == {
        "bytes_in_use": 70, "bytes_limit": 100,
    }
