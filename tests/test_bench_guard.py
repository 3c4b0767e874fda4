"""Tier-1 guard: every bench timing path forces materialization.

JAX dispatch is asynchronous, so a
``perf_counter`` span that never forces its outputs measures enqueue
time, not device time — lazy outputs once made ``block_until_ready``-free
timings physically impossible to trust, and a future edit could
reintroduce that silently.  This guard statically scans ``bench.py``:
every ``t = time.perf_counter()`` … ``time.perf_counter() - t`` span must
either force device work inside the span (``block_until_ready``,
``device_get``, or a helper that documents a consumed reduction) or be
explicitly annotated ``# host-timed`` at the start-of-span assignment —
so un-materialized device timings can't regress into fiction.
"""

import os
import re

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")

# Evidence that a span forces device results to exist before the clock
# stops: an explicit barrier, a host pull, or the chunked-scan helper
# whose contract is a consumed reduction per chunk (see bench.py
# ``_chunked_scan`` docstring).
_FORCERS = ("block_until_ready", "device_get", "_chunked_scan")

_ASSIGN = re.compile(r"^(\s*)(\w+)\s*=\s*time\.perf_counter\(\)\s*(#.*)?$")
_USE = re.compile(r"time\.perf_counter\(\)\s*-\s*(\w+)")


def _spans(lines):
    """Yield (var, assign_line_idx, use_line_idx, assign_comment) for each
    timing span: a use matched to the nearest preceding assignment of the
    same variable."""
    assigns = {}
    for i, line in enumerate(lines):
        m = _ASSIGN.match(line)
        if m:
            assigns[m.group(2)] = (i, m.group(3) or "")
            continue
        for m in _USE.finditer(line):
            var = m.group(1)
            if var in assigns:
                a_i, comment = assigns[var]
                yield var, a_i, i, comment


def test_every_bench_timing_span_materializes():
    with open(BENCH) as f:
        lines = f.read().splitlines()
    offenders = []
    for var, a_i, u_i, comment in _spans(lines):
        if "host-timed" in comment:
            continue
        body = "\n".join(lines[a_i:u_i + 1])
        if not any(f in body for f in _FORCERS):
            offenders.append(
                f"bench.py:{a_i + 1}-{u_i + 1} times {var!r} without "
                "forcing materialization (add block_until_ready/"
                "device_get inside the span, or annotate the assignment "
                "'# host-timed' if it intentionally measures host work)"
            )
    assert not offenders, "\n".join(offenders)


def test_guard_sees_the_real_spans():
    """The guard itself must not silently go blind: bench.py has many
    timing spans and at least one annotated host-timed span."""
    with open(BENCH) as f:
        lines = f.read().splitlines()
    spans = list(_spans(lines))
    assert len(spans) >= 20, len(spans)
    assert any("host-timed" in c for _, _, _, c in spans)


def test_lazy_bench_block_forces_drained_outputs():
    """The lazy A/B block's timing helper must consume the DRAIN outputs
    (the lazy engine's only emissions) — not just the eager grid."""
    with open(BENCH) as f:
        src = f.read()
    m = re.search(
        r"def _chunked_scan\(.*?\n(?:.*\n)*?    return state, n", src
    )
    assert m, "_chunked_scan missing from bench.py"
    body = m.group(0)
    assert "drained.count" in body and "int(" in body
    assert "block_until_ready" in body
