"""Chip smoke: the served CEP path once, on the chip, as a user drives it.

    python chip_smoke.py [--seed N]             # one chip, phases 1-4
    python chip_smoke.py --chips 4 [--seed N]   # the key-sharded mesh only

One chip runs, in order:

1. device — refuse anything but a TPU (no CPU fallback);
2. README parity — the 8-event stock trace through ``CEPProcessor``
   reproduces the reference README's four match sequences;
3. stream — ``CEPProcessor(stock_pattern(), 4096, CONFIG)`` (one Kafka
   partition's key space, ~160 MiB of engine state) on the compiled Pallas
   walk kernel, 4 columnar batches of 128 events per lane (2.1M events)
   from the seeded loss-free staircase stream; after batch 2 flush, checkpoint
   and restore, and the emissions must equal an uninterrupted processor's;
   every loss counter stays 0;
4. oracle parity — sampled lanes replayed through ``OracleNFA`` give the
   device's emissions exactly.

``--chips 4`` runs only the mesh phase: 16384 lanes sharded over four
chips against a single-device processor on the same stream, with a
checkpoint taken on the mesh and restored onto it.

Every failure raises and exits non-zero.  Earlier stdout lines are smoke
timings and counts, not benchmark numbers; the last line is the contract
JSON, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples"))

import jax
import numpy as np

import stock_demo
from kafkastreams_cep_tpu import OracleNFA, native
from kafkastreams_cep_tpu.engine import EngineConfig
from kafkastreams_cep_tpu.parallel import key_mesh
from kafkastreams_cep_tpu.runtime import CEPProcessor
from kafkastreams_cep_tpu.runtime.checkpoint import (
    restore_processor,
    save_checkpoint,
)
from kafkastreams_cep_tpu.utils.compile_cache import enable_compile_cache

LANES = 4096  # one partition's key space
MESH_LANES = 16384  # 4096 lanes on each of four chips
BATCHES = 4
STEPS = 128  # events per lane per batch
ORACLE_LANES = 16
CYCLE = 24  # events per staircase cycle
# Loss-free capacity point for this stream, derived once on the CPU with
# engine/sizing.autosize (margin 1.5, one sweep per batch, a 128-lane
# sample covering every phase) and kept as literals so the chip run
# compiles one known shape.
CONFIG = EngineConfig(
    max_runs=40, slab_entries=104, slab_hot_entries=24, slab_preds=8,
    dewey_depth=8, max_walk=8,
)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def stream(seed: int, lanes: int):
    """``BATCHES`` planes of ``(price, volume)``, each ``[STEPS, lanes]``:
    the loss-free staircase stream of ``bench.py`` (``staircase_trace``)
    with a seeded phase, price offset and filler per lane.

    Every 24 events a lane sees one cycle ``c``: a begin (volume 1200),
    two takes at a price above it, and a completion whose volume is below
    this cycle's threshold but not an older one's; prices fall from cycle
    to cycle, so no run takes outside its own cycle and a finite config is
    loss-free.  Filler events (price under the lane's offset + 1000,
    volume 300-1000) can neither begin, take nor complete.  The spike generator of
    ``bench.py``'s processor line cannot serve here: runs begun at the top
    price straddle forever, so Dewey width grows with the stream (PERF.md).
    """
    rng = np.random.default_rng(seed)
    n = BATCHES * STEPS
    lead = rng.integers(0, CYCLE, size=lanes)
    price = rng.integers(0, 1000, size=(n, lanes))
    volume = rng.integers(300, 1001, size=(n, lanes))
    lane = np.arange(lanes)
    for c in range(n // CYCLE + 1):
        top = 2000 - 20 * c
        cycle = ((top, 1200), (top + 2, 100 + 10 * c), (top + 2, 100 + 10 * c),
                 (top - 5, 79 + 8 * c))
        for j, (p, v) in enumerate(cycle):
            t = lead + c * CYCLE + j
            ok = t < n
            price[t[ok], lane[ok]] = p
            volume[t[ok], lane[ok]] = v
    price += rng.integers(0, 1000, size=lanes)
    return [(price[b * STEPS:(b + 1) * STEPS], volume[b * STEPS:(b + 1) * STEPS])
            for b in range(BATCHES)]


def columns(planes, b: int):
    """Batch ``b`` as the columns ``process_columns`` takes: time-major
    records, key = lane index, one global timestamp per record."""
    price, volume = planes[b]
    n = price.size
    keys = np.tile(np.arange(price.shape[1]), price.shape[0])
    ts = np.arange(b * n, (b + 1) * n)
    return keys, {"price": price.reshape(-1), "volume": volume.reshape(-1)}, ts


def fmt(matches):
    """Emissions as comparable data: (key, {stage: [per-lane offsets]})."""
    return [
        (int(key), {name: [e.offset for e in evs]
                    for name, evs in seq.as_map().items()})
        for key, seq in matches
    ]


def loss_counters(proc) -> dict:
    counters = proc.counters()
    require(not any(counters.values()), f"loss counters nonzero: {counters}")
    return counters


def interpret() -> bool:
    """Pallas interpret mode: never on the chip; only when a CPU rehearsal
    calls the phases directly (``main`` refuses anything but a TPU)."""
    return jax.default_backend() != "tpu"


def require_walk_kernel(proc) -> None:
    require(proc.batch.uses_walk_kernel, "walk kernel not selected")
    require(
        proc.batch._kernel_interpret == interpret(),
        "walk kernel runs in interpret mode",
    )


def run_stream(proc, planes, b0: int, b1: int, label: str):
    """Feed batches ``[b0, b1)`` and flush; print per-batch smoke times."""
    out = []
    for b in range(b0, b1):
        t0 = time.perf_counter()
        out += proc.process_columns(*columns(planes, b))
        note = " (first call: includes compiles)" if b == b0 else ""
        say(f"{label} batch {b}: {time.perf_counter() - t0:.3f} s{note}")
    out += proc.flush()
    return out


def checkpointed(make, planes, mesh=None):
    """Batches 0-1, flush, checkpoint, restore (onto ``mesh``), then
    batches 2-3 on the restored processor."""
    proc = make()
    got = run_stream(proc, planes, 0, BATCHES // 2, "checkpointed")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "smoke.ckpt")
        t0 = time.perf_counter()
        save_checkpoint(proc, path)
        del proc
        proc = restore_processor(stock_demo.stock_pattern(), path, mesh=mesh)
        say(f"checkpoint save+restore: {time.perf_counter() - t0:.3f} s")
    got += run_stream(proc, planes, BATCHES // 2, BATCHES, "restored")
    return proc, got


def phase_device(chips: int):
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip smoke: needs a TPU, found platform {platform!r} "
            f"({devs[0].device_kind}); there is no CPU fallback"
        )
    require(len(devs) >= chips, f"needs {chips} chips, found {len(devs)}")
    say(f"device {devs[0].device_kind}, {len(devs)} device(s), native "
        f"ingest library {'active' if native.available() else 'ABSENT'}")
    return devs


def phase_readme() -> None:
    t0 = time.perf_counter()
    lines = stock_demo.run()
    require(lines == stock_demo.EXPECTED, f"README parity: {lines}")
    say(f"README parity OK ({len(lines)} sequences, "
        f"{time.perf_counter() - t0:.3f} s)")


def phase_stream(planes, lanes: int):
    make = lambda: CEPProcessor(
        stock_demo.stock_pattern(), lanes, CONFIG, gc_interval=1,
    )
    ref = make()
    require_walk_kernel(ref)
    want = run_stream(ref, planes, 0, BATCHES, "uninterrupted")
    proc, got = checkpointed(make, planes)
    require_walk_kernel(proc)
    require(fmt(got) == fmt(want), "checkpoint/restore emissions differ")
    counters = loss_counters(ref)
    require(loss_counters(proc) == counters, "restored counters differ")
    events = lanes * STEPS * BATCHES
    say(f"stream: {events} events, {len(want)} matches, checkpoint/restore "
        f"parity OK, loss counters {counters}")
    say(f"stream: hot {ref.hot_counters()} walk {ref.walk_counters()}")
    phases = ref.metrics.phases()
    say("stream: per-batch host phase p50 (s) " + ", ".join(
        f"{name} {h['p50']:.4f}" for name, h in sorted(phases.items())
        if h["count"]))
    return want


def phase_oracle(planes, want, seed: int, lanes: int) -> None:
    t0 = time.perf_counter()
    # Half the sample from lanes that emitted, half from all lanes.
    device = fmt(want)
    rng = np.random.default_rng(seed)
    emitted = sorted({key for key, _ in device})
    half = min(ORACLE_LANES // 2, len(emitted))
    sample = set(rng.choice(emitted, half, replace=False).tolist())
    rest = [k for k in range(lanes) if k not in sample]
    sample |= set(rng.choice(rest, ORACLE_LANES - half, replace=False).tolist())
    n = 0
    for lane in sorted(sample):
        oracle = OracleNFA.from_pattern(stock_demo.stock_pattern())
        expect = []
        for b, (price, volume) in enumerate(planes):
            for t in range(STEPS):
                off = b * STEPS + t
                for m in oracle.match(
                    lane,
                    {"price": int(price[t, lane]),
                     "volume": int(volume[t, lane])},
                    off, offset=off,
                ):
                    expect.append({name: [e.offset for e in evs]
                                   for name, evs in m.as_map().items()})
        got = [m for key, m in device if key == lane]
        require(got == expect, f"oracle parity, lane {lane}")
        n += len(expect)
    say(f"oracle parity OK on {ORACLE_LANES} lanes ({n} matches, "
        f"{time.perf_counter() - t0:.3f} s)")


def phase_mesh(planes, devs) -> None:
    mesh = key_mesh(devs[:4])
    single = CEPProcessor(
        stock_demo.stock_pattern(), MESH_LANES, CONFIG, gc_interval=1,
    )
    want = run_stream(single, planes, 0, BATCHES, "single-device")
    sharded, got = checkpointed(
        lambda: CEPProcessor(
            stock_demo.stock_pattern(), MESH_LANES, CONFIG, gc_interval=1,
            mesh=mesh,
        ),
        planes, mesh=mesh,
    )
    require(sharded.batch.uses_walk_kernel, "mesh walk kernel not selected")
    for path, leaf in jax.tree_util.tree_leaves_with_path(sharded.state):
        require(len(leaf.sharding.device_set) == 4,
                f"state leaf {jax.tree_util.keystr(path)} on "
                f"{len(leaf.sharding.device_set)} device(s)")
    require(fmt(got) == fmt(want), "mesh emissions differ from one device")
    stats = lambda p: (p.counters(), p.hot_counters(), p.walk_counters())
    require(stats(sharded) == stats(single), "mesh stats differ")
    say(f"mesh: {MESH_LANES} lanes over 4 chips, state on 4 devices, "
        f"{len(want)} matches equal to one device, stats {stats(single)}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)
    enable_compile_cache()
    devs = phase_device(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(stream(args.seed, MESH_LANES), devs)
    else:
        phase_readme()
        planes = stream(args.seed, LANES)
        want = phase_stream(planes, LANES)
        phase_oracle(planes, want, args.seed, LANES)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
