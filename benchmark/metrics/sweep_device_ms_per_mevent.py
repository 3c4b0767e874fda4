"""Device milliseconds of the maintenance sweep alone (parallel/batch.py sweep, a jitted lambda: ``jit__lambda``) per million events, from the profiler trace; a part of matcher_device_ms_per_mevent."""

import os, sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace import program_seconds  # noqa: E402

SWEEP_PROGRAM = r"^jit__lambda$"


def read(ctx):
    tr = ctx and ctx.get("trace")
    if not tr or ctx["events"] <= 0:
        return None
    s = program_seconds(tr, SWEEP_PROGRAM)
    if s is None:
        return None
    return s * 1e3 / (ctx["events"] / 1e6)
