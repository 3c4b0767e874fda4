"""The host event GC (``CEPProcessor._gc_events``) over whole arrays,
against the per-lane pass it replaced, written out below.

The processor's device state is drawn from the seed (slab, run queue and,
tiered, the stencil carry) over events fed through its host packing
alone: records into the mirror, columns into lazy column batches.  No
device scan runs.  Each pass must leave the same mirror (key sets and
events per lane, mirror entries kept as the same objects), count the
same ``gc_carry_pinned``, and count its own work in
``gc_events_materialized`` and ``gc_lanes_swept``.  A ``process_columns``
stream then emits the same matches whether the GC runs after every call
or every 8th."""

import dataclasses

import jax
import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.engine.stencil import partial_prefix_mask
from kafkastreams_cep_tpu.engine.tiered import engine_view
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record
from kafkastreams_cep_tpu.utils.events import Event

K = 16
FED = K - 3  # lanes K-3 .. K-1 are never fed: empty lanes


def screen():
    """Three strict stages (the stencil prefix when tiered), then a
    skip-till-next-match stage."""
    return (
        Query()
        .select("first").where(sc.value_is(sc.A))
        .then().select("second").where(sc.value_is(sc.B))
        .then().select("third").where(sc.value_is(sc.C))
        .then().select("latest").skip_till_next_match()
        .where(sc.value_is(sc.D))
        .build()
    )


def config(tiered):
    return dataclasses.replace(sc.default_config(), tiering=tiered)


def reference_gc(proc):
    """The per-lane pass: a set of live offsets per lane, every column
    batch walked for each, then the lane's dead mirror keys dropped.
    Returns ``(pinned, materialized, swept)``."""
    eng = engine_view(proc.state)
    carry = getattr(proc.state, "carry", None)
    slab_stage = np.asarray(jax.device_get(eng.slab.stage))
    slab_off = np.asarray(jax.device_get(eng.slab.off))
    run_alive = np.asarray(jax.device_get(eng.alive))
    run_off = np.asarray(jax.device_get(eng.event_off))
    pending, pinned, built = {}, 0, 0
    swept = sum(1 for d in proc._events if d)
    if carry is not None:
        c_bools, c_offs = (np.asarray(a) for a in jax.device_get(
            (carry.bools, carry.offs)))
        mask = partial_prefix_mask(c_bools, c_offs)
        for k in np.flatnonzero(mask.any(axis=1)).tolist():
            pending[k] = c_offs[k][mask[k]].tolist()
    _, treedef = jax.tree_util.tree_flatten(proc._value_proto)
    for k in range(proc.num_lanes):
        live = set(slab_off[k][slab_stage[k] >= 0].tolist())
        live.update(run_off[k][run_alive[k]].tolist())
        held = pending and pending.get(k)
        if held:
            pinned += len(set(held) - live)
            live.update(held)
        for start, cnt, abs_ts, leaves in proc._col_batches:
            s = int(start[k])
            if s < 0:
                continue
            hi = s + int(cnt[k])
            for o in live:
                if s <= o < hi and o not in proc._events[k]:
                    t = o - s
                    value = jax.tree_util.tree_unflatten(
                        treedef, [leaf[k, t].item() for leaf in leaves]
                    )
                    proc._events[k][o] = Event(
                        proc._key_of[k], value, int(abs_ts[k, t]),
                        proc.topic, k, o + int(proc._off_base[k]),
                    )
                    built += 1
        store = proc._events[k]
        for o in [o for o in store if o not in live]:
            del store[o]
    proc._col_batches.clear()
    return pinned, built, swept


def typed(x):
    return jax.tree_util.tree_map(lambda v: (type(v), v), x)


def event_view(e):
    return (typed(e.key), typed(e.value), typed(e.timestamp), e.topic,
            typed(e.partition), typed(e.offset))


def mirror_view(proc):
    return [{off: event_view(e) for off, e in d.items()} for d in proc._events]


def feed(proc, sources, rng, ts0):
    """Pack one batch per source: ``"records"`` into the mirror,
    ``"columns"`` into a lazy column batch; keys on the fed lanes only,
    so a lane a batch skips has ``start == -1`` there."""
    ts = ts0
    for src in sources:
        n = 24
        keys = rng.integers(0, FED, size=n)
        codes = rng.integers(0, 5, size=n).astype(np.int32)
        stamps = ts + np.arange(n)
        ts += n
        if src == "records":
            proc._pack_records([
                Record(int(keys[i]), int(codes[i]), int(stamps[i]))
                for i in range(n)
            ])
        else:
            proc._pack_columns(keys, codes, stamps)
    return ts


def fed_offsets(proc):
    return np.where(proc._off_base >= 0, proc._next_offset - proc._off_base, 0)


def draw_state(proc, rng):
    """Liveness drawn from the seed: offsets over every row fed so far
    and a few past the end (in no batch and not in the mirror), slots
    marked dead or holding ``-1`` among them."""
    eng = engine_view(proc.state)
    k, e = eng.slab.stage.shape
    r = eng.alive.shape[1]
    top = fed_offsets(proc)[:, None] + 3

    def offs(n):
        o = (rng.random((k, n)) * top).astype(np.int32)
        return np.where(rng.random((k, n)) < 0.1, -1, o)

    eng = eng._replace(
        slab=eng.slab._replace(
            stage=np.where(rng.random((k, e)) < 0.3, 0, -1).astype(np.int32),
            off=offs(e),
        ),
        alive=rng.random((k, r)) < 0.3,
        event_off=offs(r),
    )
    if not hasattr(proc.state, "carry"):
        proc.state = eng
        return
    carry = proc.state.carry
    p1, p = carry.bools.shape[1:]
    carry = carry._replace(
        bools=rng.random((k, p1, p)) < 0.6, offs=offs(p1),
    )
    proc.state = proc.state._replace(engine=eng, carry=carry)


def build(tiered, seed):
    """Two processors in the same state: one for the array pass, one for
    the reference."""
    out = []
    for _ in range(2):
        rng = np.random.default_rng(seed)
        proc = CEPProcessor(screen(), K, config(tiered), epoch=0,
                            gc_events=False)
        out.append((proc, rng))
    return out


@pytest.mark.parametrize("seed", [7, 2147483990])
@pytest.mark.parametrize("tiered", [False, True])
def test_gc_equals_per_lane_pass(tiered, seed):
    pair = build(tiered, seed)
    (proc, _), (ref, _) = pair
    ts = 0
    schedule = [["columns", "records", "columns", "columns"],
                ["records", "columns", "columns"], []]
    for sources in schedule:
        for p, rng in pair:
            end = feed(p, sources, rng, ts)
            if p._col_batches:
                # Live rows already in the mirror stay as they are.
                start = p._col_batches[-1][0]
                lanes = np.flatnonzero(start >= 0)[:4]
                p._materialize_slots(lanes, start[lanes])
            draw_state(p, rng)
        ts = end
        before = [dict(d) for d in proc._events]
        pinned0 = proc.metrics.gc_carry_pinned
        built0 = proc.metrics.gc_events_materialized
        swept0 = proc.metrics.gc_lanes_swept

        proc._gc_events()
        pinned, built, swept = reference_gc(ref)

        assert mirror_view(proc) == mirror_view(ref)
        assert not proc._col_batches
        assert proc.metrics.gc_carry_pinned - pinned0 == pinned
        assert proc.metrics.gc_events_materialized - built0 == built
        assert proc.metrics.gc_lanes_swept - swept0 == swept
        for d, old in zip(proc._events, before):
            for off, ev in d.items():
                if off in old:
                    assert ev is old[off]
        assert not any(proc._events[FED:])
    # The draws reached every case the pass tells apart.
    assert proc.metrics.gc_events_materialized > 0
    assert proc.metrics.gc_lanes_swept > 0
    assert (proc.metrics.gc_carry_pinned > 0) == tiered


def test_gc_skips_a_live_offset_no_batch_holds():
    """A live offset past every row fed, on a lane whose batch starts at
    0, is kept out of the mirror and raises nothing."""
    (proc, rng), _ = build(False, 3)
    feed(proc, ["columns"], rng, 0)
    eng = proc.state
    k, e = eng.slab.stage.shape
    stage = np.full((k, e), -1, np.int32)
    off = np.full((k, e), -1, np.int32)
    stage[0, :2] = 0
    off[0, :2] = (0, fed_offsets(proc)[0] + 5)
    proc.state = eng._replace(
        slab=eng.slab._replace(stage=stage, off=off),
        alive=np.zeros(eng.alive.shape, bool),
    )
    proc._gc_events()
    assert list(proc._events[0]) == [0]
    assert not any(proc._events[1:])
    assert proc.metrics.gc_events_materialized == 1
    assert proc.metrics.gc_lanes_swept == 0
    snap = proc.metrics_snapshot(per_lane=False)
    assert snap["gc_events_materialized"] == 1
    assert snap["gc_lanes_swept"] == 0


def stream(proc, codes, call):
    """``codes [K, S]`` through ``process_columns`` in calls of ``call``
    steps (time-major, key = lane); every match, as offsets per stage."""
    lanes, steps = codes.shape
    out = []
    for s0 in range(0, steps, call):
        n = min(call, steps - s0)
        keys = np.tile(np.arange(lanes, dtype=np.int32), n)
        vals = codes[:, s0:s0 + n].T.reshape(-1)
        ts = np.repeat(np.arange(s0, s0 + n, dtype=np.int64), lanes) * 1000
        out += [
            (key, [(stage, [(e.offset, e.value, e.timestamp) for e in evs])
                   for stage, evs in seq.as_map().items()])
            for key, seq in proc.process_columns(keys, vals, ts)
        ]
    return out


@pytest.mark.parametrize("tiered", [False, True])
def test_gc_interval_keeps_emissions(tiered):
    """Sparse random codes over filler, plus the occurrence ``A, B, C, X,
    D`` every 16 steps from a lane's own phase, so prefixes straddle the
    4-step calls."""
    rng = np.random.default_rng(11)
    codes = rng.choice(5, size=(K, 96), p=[0.04] * 4 + [0.84])
    for k in range(K):
        for s in range(k % 5, 91, 16):
            codes[k, s:s + 5] = (sc.A, sc.B, sc.C, sc.X, sc.D)
    codes = codes.astype(np.int32)
    got, procs = {}, {}
    for every in (1, 8):
        proc = procs[every] = CEPProcessor(
            screen(), K, config(tiered), epoch=0, gc_events_interval=every
        )
        got[every] = stream(proc, codes, 4)
        assert not any(proc.counters().values())
    assert len(got[1]) >= K * 6
    assert got[1] == got[8]
    assert procs[1].metrics.gc_events_materialized > 0
    assert (procs[1].metrics.gc_carry_pinned > 0) == tiered
