"""The columnar path's sorted key index (runtime/processor.py
``_column_lanes``): every call's lanes, lane-assignment order, rejections
and key codes are those of the plain mapping it replaces — ``np.unique``
over the column and the key->lane dict — across new keys mid-stream,
other writers of the dict (``lane()``, ``process()``, checkpoint restore,
``move_lanes``) and the column dtypes the index does not serve."""

import os
import sys

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu.runtime import (
    CEPProcessor,
    Record,
    restore_processor,
    save_checkpoint,
)
from kafkastreams_cep_tpu.runtime.migrate import move_lanes
from kafkastreams_cep_tpu.runtime.processor import InputRejected
from kafkastreams_cep_tpu.utils.telemetry import InMemoryTraceSink

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
import stock_demo

from test_runtime import _fmt_all, stock_cfg


def ref_lanes(lane_of, key_of, keys, num_lanes):
    """The lane mapping as it was before the index: the column's distinct
    keys in first-appearance order, new ones committed after the overflow
    check, then every row looked up in the whole dict."""
    if keys.dtype == object:
        uniq = list(dict.fromkeys(keys.tolist()))
    else:
        vals, first = np.unique(keys, return_index=True)
        uniq = [v.item() for v in vals[np.argsort(first)]]
    new = [k for k in uniq if k not in lane_of]
    if len(lane_of) + len(new) > num_lanes:
        raise InputRejected(
            f"first overflowing key: {new[num_lanes - len(lane_of)]!r}"
        )
    for k in new:
        key_of[len(lane_of)] = k
        lane_of[k] = len(lane_of)
    return np.fromiter(
        (lane_of[k] for k in keys.tolist()), dtype=np.int32, count=len(keys)
    )


def index_state(proc):
    keys, lanes, n = proc._int_key_index()
    return keys.copy(), lanes.copy(), n


def _proc(num_lanes, **kw):
    return CEPProcessor(sc.strict3(), num_lanes, sc.default_config(), **kw)


@pytest.mark.parametrize(
    "dtype,lo,hi,seed",
    [
        (np.int32, -(2**31), 2**31 - 1, 1),
        (np.int32, 0, 64, 2),
        (np.int64, -(2**62), 2**62, 3),
        (np.int64, 2**31 - 8, 2**31 + 8, 4),
        (np.uint32, 0, 2**32 - 1, 5),
        (np.int16, -300, 300, 6),
    ],
)
def test_index_lanes_match_the_dict_mapping(dtype, lo, hi, seed):
    """Over calls whose key pool grows mid-stream, with keys repeated
    within a call: the same lanes, the same dicts, the same first-appearance
    lane order as the plain mapping."""
    rng = np.random.default_rng(seed)
    K = 256
    pool = np.unique(rng.integers(lo, hi, size=400, endpoint=True))
    pool = rng.permutation(pool)[:200].astype(dtype)
    proc = _proc(K)
    lane_of, key_of = {}, {}
    missed = 0
    for size in (8, 40, 40, 120, 200, 200, 64):
        keys = rng.choice(pool[:size], size=3 * size)
        fresh = ~np.isin(keys, np.asarray(list(lane_of), dtype=dtype))
        want = ref_lanes(lane_of, key_of, keys, K)
        got = proc._column_lanes(keys)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
        assert proc._lane_of == lane_of and proc._key_of == key_of
        assert list(proc._lane_of) == list(lane_of)  # assignment order
        missed += int(fresh.sum())
        assert proc.metrics.pack_lane_misses == missed
    ik, il, n = proc._int_key_index()
    assert n == len(lane_of) and np.all(np.diff(ik) > 0)
    assert dict(zip(ik.tolist(), il.tolist())) == lane_of


def test_overflow_after_index_leaves_dict_index_and_counter():
    proc = _proc(8)
    proc._column_lanes(np.arange(6, dtype=np.int32))
    before = (dict(proc._lane_of), dict(proc._key_of), index_state(proc),
              proc.metrics.pack_lane_misses)
    with pytest.raises(InputRejected, match="first overflowing key: 8"):
        proc._column_lanes(np.asarray([0, 6, 3, 7, 8, 6, 9], np.int32))
    after = (dict(proc._lane_of), dict(proc._key_of), index_state(proc),
             proc.metrics.pack_lane_misses)
    assert after[:2] == before[:2] and after[3] == before[3]
    for a, b in zip(after[2], before[2]):
        np.testing.assert_array_equal(a, b)
    # Through process_columns too: nothing half-ingested, and a batch
    # that fits still goes in afterwards.
    with pytest.raises(InputRejected, match="num_lanes"):
        proc.process_columns(np.asarray([6, 7, 8], np.int32),
                             np.zeros(3, np.int32), [1, 1, 1])
    assert proc._lane_of == before[0] and proc.metrics.records_in == 0
    np.testing.assert_array_equal(
        proc._column_lanes(np.asarray([7, 0, 6], np.int32)), [6, 0, 7]
    )


def test_replaced_lane_map_is_never_served_stale():
    """A whole new map (what restore and migration assign) of the same
    length drops the index; keys it serves resolve to the new lanes."""
    proc = _proc(4)
    proc._column_lanes(np.asarray([10, 11, 12, 13], np.int64))
    proc._lane_of = {13: 0, 12: 1, 11: 2, 10: 3}
    proc._key_of = {v: k for k, v in proc._lane_of.items()}
    np.testing.assert_array_equal(
        proc._column_lanes(np.asarray([10, 13, 11], np.int64)), [3, 0, 2]
    )


def _stock_columns(seed, n, keys):
    rng = np.random.default_rng(seed)
    return (
        rng.choice(np.asarray(keys, np.int64), size=n),
        rng.integers(90, 131, size=n).astype(np.int64),
        rng.integers(600, 1101, size=n).astype(np.int64),
    )


def _records(k, p, v, ts):
    return [Record(int(k[j]), {"price": int(p[j]), "volume": int(v[j])},
                   int(ts[j])) for j in range(len(k))]


def _columns(proc, k, p, v, ts):
    return proc.process_columns(k, {"price": p, "volume": v}, ts)


@pytest.mark.parametrize("writer", ["lane", "process", "restore", "move"])
def test_other_lane_writers_between_columnar_calls(writer, tmp_path):
    """Keys assigned between columnar calls by ``lane()`` or the record
    path, or a lane map rewritten by a checkpoint restore or a lane move,
    resolve to their lanes: the matches equal the per-record path's."""
    keys = [40, 7, 19, 3, 28, 11]
    ref = CEPProcessor(stock_demo.stock_pattern(), 8, stock_cfg())
    col = CEPProcessor(stock_demo.stock_pattern(), 8, stock_cfg())
    want, got = [], []
    t = 1000
    # Keys 3.. arrive at call 2; the map is rewritten at call 3, once every
    # key holds a lane (a lane move hands out lanes by permutation, so a
    # key that arrived after it would not take the next free one).
    at = 2 if writer in ("lane", "process") else 3
    for call, pool in enumerate((keys[:3], keys[:3], keys, keys, keys)):
        k, p, v = _stock_columns(call, 48, pool)
        ts = t + np.arange(48, dtype=np.int64)
        t += 48
        if call == at:
            extra = keys[3:]
            if writer == "lane":
                for key in extra:
                    ref.lane(key)
                    col.lane(key)
            elif writer == "process":
                head = _records(np.asarray(extra), [100] * 3, [1000] * 3,
                                [t - 1] * 3)
                want.append(ref.process(head))
                got.append(col.process(head))
            elif writer == "restore":
                path = str(tmp_path / "col.ckpt")
                save_checkpoint(col, path)
                col = restore_processor(stock_demo.stock_pattern(), path)
            else:
                col = move_lanes(stock_demo.stock_pattern(), col,
                                 [5, 2, 7, 0, 1, 3, 6, 4])
        want.append(ref.process(_records(k, p, v, ts)))
        missed = col.metrics.pack_lane_misses
        got.append(_columns(col, k, p, v, ts))
        if call >= at:
            # Every key already holds a lane: the refreshed index has all.
            assert col.metrics.pack_lane_misses == missed
        for key in set(k.tolist()):
            assert col._column_lanes(np.asarray([key])) == col._lane_of[key]
    assert sum(map(len, want)) > 0
    assert _fmt_all(got) == _fmt_all(want)


@pytest.mark.parametrize(
    "first,second",
    [
        (np.asarray([5, "x", 2**40, -3], dtype=object),
         np.asarray([-3, 5, 9, "y"], dtype=object)),
        (np.asarray([5.0, 7.5, -1.0]), np.asarray([7.5, 2.0, 5.0])),
        (np.asarray([5.0, 7.5]), np.asarray([5, 9, 5], np.int32)),
        (np.asarray(["a", 3], dtype=object), np.asarray([3, 4, 3], np.int64)),
        (np.asarray([True, False]), np.asarray([1, 0, 2], np.int8)),
        (np.asarray([2**63 + 1, 4], np.uint64),
         np.asarray([4, 2**64 - 1], np.uint64)),
    ],
    ids=["object", "float", "float-then-int", "str-then-int", "bool-then-int",
         "uint64"],
)
def test_columns_the_index_does_not_serve(first, second):
    """Object, float, unsigned-64 and mixed-type key columns give the
    plain mapping's lanes, and every packed row the key code of its
    column's rule: an integer column's in-range values pass through, an
    object column's elements take ``_key_code``, any other column codes
    its lanes."""
    proc = _proc(8)
    lane_of, key_of = {}, {}
    for keys in (first, second):
        want = ref_lanes(lane_of, key_of, keys, 8)
        events, _, n = proc._pack_columns(
            keys, np.zeros(len(keys), np.int32),
            np.arange(len(keys), dtype=np.int64),
        )
        assert proc._lane_of == lane_of and proc._key_of == key_of
        key_arr = np.asarray(events.key)
        pos = {}
        for key, lane in zip(keys.tolist(), want.tolist()):
            j = pos.get(lane, 0)
            pos[lane] = j + 1
            if keys.dtype == object:
                code = proc._key_code(key, lane)
            elif np.issubdtype(keys.dtype, np.integer):
                code = key if -(2**31) <= key < 2**31 else lane
            else:
                code = lane
            assert key_arr[lane, j] == code, key


def test_pack_lane_misses_and_the_pack_lanes_span():
    """``pack_lane_misses`` counts exactly the rows whose key the index
    did not hold, and ``phase.pack_lanes`` nests inside ``phase.pack``."""
    sink = InMemoryTraceSink()
    proc = _proc(16, trace_sink=sink)
    calls = [
        (np.asarray([1, 2, 1, 3], np.int32), 4),  # all new
        (np.asarray([3, 1, 1, 2, 2], np.int32), 0),  # all held
        (np.asarray([2, 9, 9, 4, 1], np.int32), 3),  # 9, 9, 4 new
        (np.asarray([4, 9], np.int32), 0),
    ]
    for keys, misses in calls:
        before = proc.metrics.pack_lane_misses
        proc.process_columns(keys, np.zeros(len(keys), np.int32),
                             np.arange(len(keys)))
        assert proc.metrics.pack_lane_misses - before == misses
    # A float column is not served by the index: every row misses.
    proc.process_columns(np.asarray([1.5, 2.5]), np.zeros(2, np.int32),
                         [9, 9])
    assert proc.metrics.pack_lane_misses == 9
    packs = sink.spans("phase.pack")
    lanes = sink.spans("phase.pack_lanes")
    assert len(packs) == len(lanes) == len(calls) + 1
    for pack, sub in zip(packs, lanes):
        assert sub["parent_id"] == pack["span_id"]
    m = proc.metrics
    assert 0 < m.pack_lanes_seconds <= m.pack_seconds
    assert m.phases()["pack_lanes"]["count"] == len(calls) + 1
    snap = proc.metrics_snapshot(per_lane=False)
    assert snap["pack_lane_misses"] == 9 and "pack_lanes_seconds" in snap
