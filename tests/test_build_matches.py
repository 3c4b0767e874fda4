"""The decode's batched match build (``CEPProcessor._build_matches``)
against a straightforward per-row build: the same matches, stage order,
event fields and types, the same mirror afterwards, and the mirror's own
Event objects shared wherever a slot repeats.

The processor's state is set up through its host packing alone (records
into the mirror, columns into lazy column batches); no device scan runs,
so the hit-row blocks are drawn from the seed."""

import os
import sys

import jax
import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record
from kafkastreams_cep_tpu.utils.events import Event, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

K, W = 8, 6


def reference_build(proc, ks, cnts, stages, offs):
    """One row, one event at a time: the mirror, else the newest column
    batch holding the slot, cached in the mirror on first use."""
    names = proc.batch.names
    _, treedef = jax.tree_util.tree_flatten(proc._value_proto)
    out = []
    for i in range(len(ks)):
        lane = int(ks[i])
        seq = Sequence()
        for w in range(int(cnts[i])):
            off = int(offs[i, w])
            ev = proc._events[lane].get(off)
            if ev is None:
                for start, cnt, abs_ts, leaves in reversed(proc._col_batches):
                    s, t = int(start[lane]), off - int(start[lane])
                    if s >= 0 and 0 <= t < int(cnt[lane]):
                        value = jax.tree_util.tree_unflatten(
                            treedef, [leaf[lane, t].item() for leaf in leaves]
                        )
                        ev = Event(
                            proc._key_of[lane], value, int(abs_ts[lane, t]),
                            proc.topic, lane, off + int(proc._off_base[lane]),
                        )
                        proc._events[lane][off] = ev
                        break
                else:
                    raise KeyError(
                        f"lane {lane} has no event at device offset {off}"
                    )
            seq.add(names[int(stages[i, w])], ev)
        out.append((proc._key_of[lane], seq))
    return out


def typed(x):
    return jax.tree_util.tree_map(lambda v: (type(v), v), x)


def event_view(e):
    return (typed(e.key), typed(e.value), typed(e.timestamp), e.topic,
            typed(e.partition), typed(e.offset))


def match_view(matches):
    return [
        (typed(key), [(stage, [event_view(e) for e in evs])
                      for stage, evs in seq.as_map().items()])
        for key, seq in matches
    ]


def mirror_view(proc):
    return [[(off, event_view(e)) for off, e in d.items()]
            for d in proc._events]


VALUES = {
    # The stock demo's schema: a flat dict of ints.
    "int": lambda rng, n: {
        "price": rng.integers(90, 131, size=n),
        "volume": rng.integers(600, 1101, size=n),
    },
    # A float leaf inside a nested pytree.
    "nested_float": lambda rng, n: {
        "a": {"x": rng.normal(size=n).astype(np.float32)},
        "b": rng.integers(-5, 5, size=n),
    },
}


def fed_processor(sources, values, rng):
    """A processor whose events sit where ``sources`` says: ``"records"``
    packs a record batch into the mirror, ``"columns"`` one column batch.
    Returns it with every lane's device offsets fed so far."""
    proc = CEPProcessor(sc.strict3(), K, sc.default_config(), epoch=0,
                        gc_events=False)
    ts = 0
    for src in sources:
        n = 40
        keys = rng.integers(0, K, size=n)
        cols = VALUES[values](rng, n)
        stamps = ts + np.arange(n)
        ts += n
        if src == "records":
            recs = [
                Record(int(keys[i]),
                       jax.tree_util.tree_map(lambda c: c[i].item(), cols),
                       int(stamps[i]))
                for i in range(n)
            ]
            proc._pack_records(recs)
        else:
            proc._pack_columns(keys, cols, stamps)
    fed = [np.arange(int(proc._next_offset[l] - proc._off_base[l]))
           if proc._off_base[l] >= 0 else np.arange(0) for l in range(K)]
    return proc, fed


def hit_block(rng, fed, n, pool=3):
    """``n`` hit rows over lanes with events: each slot drawn from a few
    offsets a lane, so slots repeat across and within rows; counts from 1
    to W (both ends present), padding slots left as junk."""
    lanes = [l for l in range(K) if fed[l].size]
    ks = rng.choice(lanes, size=n).astype(np.int32)
    cnts = rng.integers(1, W + 1, size=n).astype(np.int32)
    cnts[0], cnts[-1] = 1, W
    offs = rng.integers(-7, 1 << 20, size=(n, W)).astype(np.int32)
    stages = rng.integers(0, 3, size=(n, W)).astype(np.int32)
    picks = {l: rng.choice(fed[l], size=min(pool, fed[l].size), replace=False)
             for l in lanes}
    for i in range(n):
        offs[i, : cnts[i]] = rng.choice(picks[int(ks[i])], size=cnts[i])
    return ks, cnts, stages, offs


SOURCES = {
    "mirror_only": ["records"],
    "one_batch": ["columns"],
    "many_batches": ["columns", "columns", "columns"],
    "mirror_and_batches": ["records", "columns", "records", "columns"],
}


@pytest.mark.parametrize("values", sorted(VALUES))
@pytest.mark.parametrize("source", sorted(SOURCES))
def test_batched_build_equals_per_row_build(source, values):
    rng = np.random.default_rng(sorted(SOURCES).index(source) * 10
                                + sorted(VALUES).index(values))
    proc, fed = fed_processor(SOURCES[source], values, rng)
    for n in (1, 24, 24):  # the second 24 reuses what the first built
        ks, cnts, stages, offs = hit_block(rng, fed, n)
        before = [dict(d) for d in proc._events]
        fresh = {(int(ks[i]), int(offs[i, w]))
                 for i in range(n) for w in range(cnts[i])
                 if int(offs[i, w]) not in before[int(ks[i])]}
        want = reference_build(proc, ks, cnts, stages, offs)
        want_mirror = mirror_view(proc)
        proc._events = before
        built0 = proc.metrics.decode_events_built
        reused0 = proc.metrics.decode_events_reused
        got = proc._build_matches(ks, cnts, stages, offs)
        assert match_view(got) == match_view(want)
        assert mirror_view(proc) == want_mirror
        # Every slot is the mirror's own object: one Event per (lane,
        # offset), shared by every row that holds it.
        for (key, seq), lane in zip(got, ks.tolist()):
            for evs in seq.as_map().values():
                for e in evs:
                    dev = e.offset - int(proc._off_base[lane])
                    assert proc._events[lane][dev] is e
        assert proc.metrics.decode_events_built - built0 == len(fresh)
        assert (proc.metrics.decode_events_reused - reused0
                == int(cnts.sum()) - len(fresh))


def test_batched_build_of_an_empty_block():
    proc, _ = fed_processor(["columns"], "int", np.random.default_rng(7))
    empty = np.zeros((0, W), np.int32)
    got = proc._build_matches(np.zeros(0, np.int32), np.zeros(0, np.int32),
                              empty, empty)
    assert got == []
    assert proc.metrics.decode_events_built == 0
    assert proc.metrics.decode_events_reused == 0
    assert proc.metrics.phases()["decode_build"]["count"] == 1


def test_batched_build_raises_for_an_offset_no_batch_holds():
    rng = np.random.default_rng(8)
    proc, fed = fed_processor(["records", "columns"], "int", rng)
    ks, cnts, stages, offs = hit_block(rng, fed, 4)
    lane = int(ks[2])
    offs[2, cnts[2] - 1] = fed[lane].size + 5
    with pytest.raises(KeyError, match=(
        f"lane {lane} has no event at device offset {fed[lane].size + 5}"
    )):
        proc._build_matches(ks, cnts, stages, offs)


def test_event_of_is_event():
    """``Event._of`` builds the same frozen, hashable, picklable Event."""
    import dataclasses
    import pickle

    a = Event("k", {"p": 1.5}, 7, "t", 3, 11)
    b = Event._of("k", {"p": 1.5}, 7, "t", 3, 11)
    assert dataclasses.astuple(a) == dataclasses.astuple(b)
    assert a == b and hash(a) == hash(b)
    assert pickle.dumps(a) == pickle.dumps(b)
    assert event_view(pickle.loads(pickle.dumps(b))) == event_view(a)
    with pytest.raises(dataclasses.FrozenInstanceError):
        b.offset = 12


def test_decode_event_counters_sum_to_emitted_events():
    """Over a process_columns stream, built + reused is every event slot
    of every emitted match, and no slot is built twice."""
    rng = np.random.default_rng(31)
    N, KEYS = 240, 8
    keys = rng.integers(0, KEYS, size=N).astype(np.int64)
    prices = rng.integers(90, 131, size=N).astype(np.int64)
    volumes = rng.integers(600, 1101, size=N).astype(np.int64)
    ts = 1000 + np.arange(N, dtype=np.int64)
    proc = CEPProcessor(stock_demo.stock_pattern(), KEYS, sc.default_config(
        max_runs=32, slab_entries=64, slab_preds=8, dewey_depth=16,
        max_walk=16,
    ), gc_events_interval=3)
    out = []
    for i in range(0, N, 48):
        sl = slice(i, i + 48)
        out += proc.process_columns(
            keys[sl], {"price": prices[sl], "volume": volumes[sl]}, ts[sl]
        )
    slots = [e for _, seq in out for evs in seq.as_map().values() for e in evs]
    assert len(out) > 0
    snap = proc.metrics_snapshot(per_lane=False)
    assert (snap["decode_events_built"] + snap["decode_events_reused"]
            == len(slots))
    assert 0 < snap["decode_events_built"] <= len(
        {(e.partition, e.offset) for e in slots}
    )
