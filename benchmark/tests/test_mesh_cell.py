"""``stock-131072-mesh4`` cut to a CPU test's size: the configuration's
``"mesh": 4`` builds a four-device processor, which runs the cell through
the harness as on the chip (bar its look for a TPU), on four forced CPU
devices and the walk kernel in interpret mode, 128 lanes a device.  The
run is correct and compiles nothing in the window.  The two sharded
device-time readers read the sharded programs alone."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "stock-131072-mesh4"
SMALL = {"config": {"lanes": 4 * 128},
         "traffic": {"check": {"sample_lanes": 16, "min_matches": 4}}}
MS = 1e-3


def test_cell_runs_correct_on_four_devices():
    code = (
        "import sys; sys.path.insert(0, 'benchmark'); import run; "
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        "'2147483713', '--seconds', '2'], require_tpu=False, "
        f"overrides={SMALL!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", CEP_WALK_KERNEL="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], p.stderr[-3000:]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"events_per_s", "setup_s"}
    assert "matcher ShardedMatcher, walk kernel True" in p.stderr
    assert "compiles in the window: 0\n" in p.stderr


def ctx(programs, events=2_000_000):
    return {"window_s": 1.0, "events": events,
            "phases": {"decode": 0.5}, "trace": {"programs": programs}}


def test_sharded_readers_read_the_sharded_programs():
    c = ctx({"jit_sharded_scan": 500 * MS, "jit_sharded_sweep": 200 * MS,
             "jit_scan": 900 * MS, "jit__lambda": 900 * MS,
             "jit_sharded_compact": 90 * MS})
    # 700 and 200 ms over 2 Mevents.
    assert spec.reader("sharded_matcher_device_ms_per_mevent")(c) == \
        pytest.approx(350.0)
    assert spec.reader("sharded_sweep_device_ms_per_mevent")(c) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("name", ["sharded_matcher_device_ms_per_mevent",
                                  "sharded_sweep_device_ms_per_mevent"])
@pytest.mark.parametrize("c", [
    None,
    ctx({"jit_scan": 500 * MS, "jit__lambda": 200 * MS}),
    ctx({"jit_sharded_sweep": 200 * MS}, events=0),
])
def test_sharded_readers_read_nothing_off_the_mesh(name, c):
    assert spec.reader(name)(c) is None
