"""Device milliseconds of the sharded matcher's programs (parallel/sharding.py: the walk-kernel scan ``jit_sharded_scan`` and the sweep ``jit_sharded_sweep``) per million events of the whole host, from the profiler trace; device seconds are the mean over the mesh's devices."""

import os, sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace import program_seconds  # noqa: E402

SHARDED_MATCHER_PROGRAMS = r"^jit_sharded_scan$|^jit_sharded_sweep$"


def read(ctx):
    tr = ctx and ctx.get("trace")
    if not tr or ctx["events"] <= 0:
        return None
    s = program_seconds(tr, SHARDED_MATCHER_PROGRAMS)
    if s is None:
        return None
    return s * 1e3 / (ctx["events"] / 1e6)
