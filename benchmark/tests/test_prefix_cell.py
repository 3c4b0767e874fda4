"""``prefix-sparse-backlog`` cut to a CPU test's size: the tiered
processor runs the cell through the harness as on the chip (bar its look
for a TPU) and is correct, and the configuration's control fails the
comparison on every seed."""

import contextlib
import io
import json

import pytest

from harness import spec
from harness.check import compare, control_pattern, reference_matches, \
    sample_lanes
from harness.traffic import generate

CELL = "prefix-sparse-backlog"
SMALL = {"config": {"lanes": 128},
         "traffic": {"check": {"sample_lanes": 32, "min_matches": 32}}}


def test_cell_runs_correct_on_the_tiered_matcher():
    import run

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", "2147483711",
                       "--seconds", "3", "--trace", "0"],
                      require_tpu=False, overrides=SMALL)
    assert rc == 0, err.getvalue()[-3000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"], err.getvalue()[-3000:]
    assert set(res["metrics"]) == {"events_per_s", "setup_s"}
    assert "matcher TieredBatchMatcher" in err.getvalue()


@pytest.mark.parametrize("seed", [5, 2147483648, 77777])
def test_control_fails(seed):
    c = spec.cell(CELL)
    conf, traffic = c["config"], c["traffic"]
    stream = generate(traffic, 64, seed)
    lanes = sample_lanes(stream, dict(traffic["check"], sample_lanes=8), seed)
    want = {int(l): reference_matches(conf["pattern"], False, stream, int(l),
                                      600) for l in lanes}
    ctl = {int(l): reference_matches(control_pattern(conf), False, stream,
                                     int(l), 600) for l in lanes}
    assert sum(map(len, want.values())) >= 8 * 4
    assert compare(ctl, want)["lanes_mismatched"] > 0
