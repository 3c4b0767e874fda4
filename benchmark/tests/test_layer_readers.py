"""The sweep's own device time, read on a hand-built context: a part of
the matcher's, and nothing where the trace has no sweep program."""

import pytest

from harness import spec

MS = 1e-3


def ctx(programs, events=2_000_000):
    return {"window_s": 1.0, "events": events,
            "phases": {"decode": 0.5}, "trace": {"programs": programs}}


@pytest.mark.parametrize("name", ["sweep_device_ms_per_mevent",
                                  "sweep_device_ms_per_mevent.live"])
def test_sweep_reads_the_sweep_program_alone(name):
    read = spec.reader(name)
    c = ctx({"jit_scan": 500 * MS, "jit__lambda": 200 * MS,
             "jit_compact_matches": 90 * MS})
    # 200 ms over 2 Mevents.
    assert read(c) == pytest.approx(100.0)
    assert read(c) <= spec.reader("matcher_device_ms_per_mevent")(c)


@pytest.mark.parametrize("c", [
    None,
    ctx({"jit_scan": 500 * MS}),
    ctx({"jit__lambda": 200 * MS}, events=0),
    {"window_s": 1.0, "events": 10, "phases": {}, "trace": None},
])
def test_sweep_reads_nothing_without_a_sweep(c):
    assert spec.reader("sweep_device_ms_per_mevent")(c) is None
