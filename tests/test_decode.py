"""Device-side match compaction (``ops/decode.py``) against a host model.

The compaction moves the hit rows (``count > 0``) of a match grid to the
front of a fixed budget in scan order and zeroes the rest; the processor's
decode trusts it for every emitted match, so it is pinned here against a
plain NumPy statement of that contract, over and under the budget.
"""

from collections import namedtuple

import jax.numpy as jnp
import numpy as np
import pytest

from kafkastreams_cep_tpu.engine.matcher import StepOutput
from kafkastreams_cep_tpu.ops.decode import compact_drained, compact_matches

Drained = namedtuple("Drained", "stage off count seq row")


def _model(count, fields, meta, budget):
    """Hit rows first, in flat order; zeros past the hits."""
    hits = np.flatnonzero(count.reshape(-1) > 0)
    n = len(hits)
    G = min(budget, count.size)
    rows = hits[:G]
    out = []
    for f in fields:
        flat = f.reshape((count.size,) + f.shape[count.ndim:])
        z = np.zeros((G,) + flat.shape[1:], flat.dtype)
        z[:len(rows)] = flat[rows]
        out.append(z)
    for m in meta:
        z = np.zeros(G, np.int32)
        z[:len(rows)] = m(rows)
        out.append(z)
    return out + [n, n > G]


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("budget", [1, 5, 4096])
def test_compact_matches_keeps_hits_in_scan_order(seed, budget):
    rng = np.random.default_rng(seed)
    K, T, R, W = (int(x) for x in rng.integers(1, 7, size=4))
    density = [0.0, 0.1, 0.6, 1.0][seed % 4]
    count = np.where(
        rng.random((K, T, R)) < density, rng.integers(1, 5, (K, T, R)), 0
    ).astype(np.int32)
    stage = rng.integers(-1, 6, (K, T, R, W)).astype(np.int32)
    off = rng.integers(0, 1 << 20, (K, T, R, W)).astype(np.int32)
    got = compact_matches(
        StepOutput(stage=jnp.asarray(stage), off=jnp.asarray(off),
                   count=jnp.asarray(count)),
        budget,
    )
    want = _model(
        count, (stage, off, count),
        (lambda n: n // (T * R), lambda n: (n // R) % T, lambda n: n % R),
        budget,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("budget", [1, 3, 512])
def test_compact_drained_keeps_hits_in_ring_order(seed, budget):
    rng = np.random.default_rng(100 + seed)
    K, HB, W = (int(x) for x in rng.integers(1, 7, size=3))
    count = np.where(
        rng.random((K, HB)) < [0.0, 0.3, 0.8, 1.0][seed], 1, 0
    ).astype(np.int32)
    stage = rng.integers(-1, 6, (K, HB, W)).astype(np.int32)
    off = rng.integers(0, 99, (K, HB, W)).astype(np.int32)
    seq = rng.integers(0, 99, (K, HB)).astype(np.int32)
    row = rng.integers(0, 9, (K, HB)).astype(np.int32)
    got = compact_drained(
        Drained(*(jnp.asarray(a) for a in (stage, off, count, seq, row))),
        budget,
    )
    want = _model(
        count, (stage, off, count, seq, row), (lambda n: n // HB,), budget
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
