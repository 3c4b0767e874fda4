"""Device milliseconds of the tiered matcher's chunk-gated NFA suffix scan with its promotion step (parallel/tiered.py, a jitted ``tiered_suffix_scan``: ``jit_tiered_suffix_scan``) per million events, from the profiler trace."""

import os, sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace import program_seconds  # noqa: E402

SUFFIX_PROGRAM = r"^jit_tiered_suffix_scan$"


def read(ctx):
    tr = ctx and ctx.get("trace")
    if not tr or ctx["events"] <= 0:
        return None
    s = program_seconds(tr, SUFFIX_PROGRAM)
    if s is None:
        return None
    return s * 1e3 / (ctx["events"] / 1e6)
