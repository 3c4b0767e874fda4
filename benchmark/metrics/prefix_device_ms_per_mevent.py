"""Device milliseconds of the tiered matcher's stencil prefix (engine/stencil.py StencilPrefix, a jitted ``stencil_prefix_scan``: ``jit_stencil_prefix_scan``) per million events, from the profiler trace."""

import os, sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace import program_seconds  # noqa: E402

PREFIX_PROGRAM = r"^jit_stencil_prefix_scan$"


def read(ctx):
    tr = ctx and ctx.get("trace")
    if not tr or ctx["events"] <= 0:
        return None
    s = program_seconds(tr, PREFIX_PROGRAM)
    if s is None:
        return None
    return s * 1e3 / (ctx["events"] / 1e6)
