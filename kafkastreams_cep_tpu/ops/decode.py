"""Device-side match compaction — shrink the decode transfer.

The processor's decode pulls the scan's match outputs to the host.  Raw
``StepOutput`` arrays are ``[K, T, R, W]`` — at the headline shape that is
gigabytes per batch, nearly all of it zeros (match density is a fraction
of a slot per lane-step), and the host pull dominates the processor's
critical path (SURVEY §2.2 PP row; the reference's per-record loop never
materializes a grid, ``CEPProcessor.java:154-163``).

``compact_matches`` reduces the transfer on-device: the hit rows
(``count > 0``) move to the front of a fixed ``budget`` of rows, in
(k, t, r) scan order, found by a prefix sum and gathered, plus the
(k, t, r, count) metadata the host decode needs for arrival-order
emission.  A one-shot batched gather is fine on TPU — the 4x-slower-gather
finding in PROFILE_r04 applies to gathers inside while-loop bodies, not to
a single post-scan op.  More hits than ``budget`` are flagged; the
processor falls back to the full pull for that batch (correctness never
depends on the budget).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _hit_rows(count, budget: int):
    """Source rows of the first ``G = min(budget, N)`` hits of a flat
    ``count [N]``, in order: ``(src [G], valid [G], n_hits [], overflow [])``
    with ``src`` 0 where ``valid`` is false.

    A gather, not a scatter: the ``g``-th hit is the first row whose
    inclusive hit prefix sum reaches ``g + 1`` (a binary search over the
    monotone prefix sum).  A masked scatter of all ``N`` rows into the
    budget computed the same rows but took 240 s to compile for a v5e at
    ``N`` = 4096 x 128 x 40 (PR 21); the search is ``log2 N`` gathers of
    ``G`` rows.
    """
    N = count.shape[0]
    G = min(budget, N)
    csum = jnp.cumsum(jnp.where(count > 0, 1, 0))
    n_hits = csum[-1]
    g = jnp.arange(G, dtype=jnp.int32)
    valid = g < n_hits
    src = jnp.searchsorted(csum, g + 1, side="left").astype(jnp.int32)
    return jnp.where(valid, src, 0), valid, n_hits, n_hits > G


def _take(rows, valid):
    """Gathered ``rows`` with those past the hit count zeroed."""
    mask = valid.reshape(valid.shape + (1,) * (rows.ndim - 1))
    return jnp.where(mask, rows, jnp.zeros_like(rows))


@functools.partial(jax.jit, static_argnames=("budget",))
def compact_matches(out, budget: int):
    """``StepOutput [K, T, R, ...]`` -> globally compacted match rows.

    Returns ``(stage [G, W], off [G, W], count [G], k [G], t [G], r [G],
    n_hits [], overflow [] bool)`` with the hit rows first in (k, t, r)
    order and ``count == 0`` rows past the total hit count.  Compaction
    is global across lanes (one prefix sum over the flattened grid): the
    host pull is then proportional to the match *budget*, not ``lanes x
    budget`` — a per-lane layout was measured pulling ~200 MB/batch for
    ~18K actual matches.  ``n_hits`` lets the caller slice the rows to the
    actual match count before pulling (two-phase pull: one scalar, then
    ``rows[:n]``).
    """
    K, T, R = out.count.shape
    src, valid, n_hits, overflow = _hit_rows(out.count.reshape(-1), budget)
    # src is 0 past the hits, so k = t = r = 0 there.  Gathering through
    # (k, t, r) keeps the [K, T, R, W] grids in their own layout: a flat
    # [N, W] view of them is a relayout copy the size of the grid.
    k, t, r = src // (T * R), (src // R) % T, src % R
    take = lambda grid: _take(grid[k, t, r], valid)
    return (
        take(out.stage),
        take(out.off),
        take(out.count),
        k,
        t,
        r,
        n_hits,
        overflow,
    )


@functools.partial(jax.jit, static_argnames=("budget",))
def compact_drained(dout, budget: int):
    """``DrainOutput [K, HB, ...]`` -> globally compacted match rows.

    The lazy-extraction analog of :func:`compact_matches`: the drain
    pass's raw outputs are ``[K, HB, W]`` — ~100 MB per drain at
    production lane counts, nearly all empty ring slots — so the hit
    rows compact on-device into ``budget`` rows in (lane, ring) order
    before the host pull.  Returns ``(stage [G, W], off [G, W],
    count [G], seq [G], row [G], k [G], n_hits [], overflow [] bool)``;
    same two-phase-pull contract as :func:`compact_matches` (overflow ⇒
    the caller falls back to the full pull — correctness never depends
    on the budget).
    """
    K, HB = dout.count.shape
    src, valid, n_hits, overflow = _hit_rows(dout.count.reshape(-1), budget)
    k, h = src // HB, src % HB
    take = lambda grid: _take(grid[k, h], valid)
    return (
        take(dout.stage),
        take(dout.off),
        take(dout.count),
        take(dout.seq),
        take(dout.row),
        k,
        n_hits,
        overflow,
    )
