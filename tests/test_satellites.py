"""Regression tests for columnar-ingestion validation and key-code parity
(runtime/processor.py)."""

import numpy as np
import pytest

import engine_scenarios as sc
from kafkastreams_cep_tpu import Query
from kafkastreams_cep_tpu.runtime import CEPProcessor, Record


def key_pair_pattern():
    """Two-stage pattern whose SECOND stage also needs the key code — a
    mixed record/column ingestion only matches if both paths encode the
    same key identically."""
    return (
        Query()
        .select("a").where(lambda k, v, ts, st: (k == 5) & (v == 0))
        .then()
        .select("b").where(lambda k, v, ts, st: (k == 5) & (v == 1))
        .build()
    )


# ---------------------------------------------------------------------------
# processor.py:408 — column length validation before the native pack
# ---------------------------------------------------------------------------


def test_process_columns_rejects_short_timestamps():
    proc = CEPProcessor(sc.strict3(), 2, sc.default_config())
    with pytest.raises(ValueError, match="timestamps"):
        proc.process_columns(
            np.array([1, 2]), np.array([0, 0], dtype=np.int32), [1]
        )


def test_process_columns_rejects_scalar_timestamps():
    proc = CEPProcessor(sc.strict3(), 2, sc.default_config())
    with pytest.raises(ValueError, match="timestamps"):
        proc.process_columns(
            np.array([1, 2]), np.array([0, 0], dtype=np.int32), 7
        )


def test_process_columns_rejects_2d_keys():
    proc = CEPProcessor(sc.strict3(), 2, sc.default_config())
    with pytest.raises(ValueError, match="keys"):
        proc.process_columns(
            np.zeros((2, 2), dtype=np.int32),
            np.array([0, 0], dtype=np.int32),
            [1, 2],
        )


def test_process_columns_rejection_is_atomic():
    """A rejected batch must not consume lane slots or advance offsets."""
    proc = CEPProcessor(sc.strict3(), 2, sc.default_config())
    with pytest.raises(ValueError):
        proc.process_columns(
            np.array([1, 2]), np.array([0, 0], dtype=np.int32), [1]
        )
    assert proc._lane_of == {}
    # A well-formed batch afterwards works normally.
    out = proc.process_columns(
        np.array([1, 2]), np.array([sc.A, sc.A], dtype=np.int32), [1, 1]
    )
    assert out == []


# ---------------------------------------------------------------------------
# processor.py:502 — object-dtype key columns keep per-element key codes
# ---------------------------------------------------------------------------


def test_object_keys_mixed_paths_same_key_codes():
    """An int key ingested via records and via an object-dtype column must
    present the same ``key`` value to predicates (the record path's
    _key_code rule, per element)."""
    proc = CEPProcessor(key_pair_pattern(), 4, sc.default_config())
    # Stage a: record path, key 5 (int -> code 5).
    assert proc.process([Record(5, 0, 1)]) == []
    # Stage b: columnar path; the object dtype (mixed with a string key)
    # must NOT degrade key 5's code to its lane index.
    out = proc.process_columns(
        np.array([5, "other"], dtype=object),
        np.array([1, 1], dtype=np.int32),
        [2, 2],
    )
    assert len(out) == 1 and out[0][0] == 5


def test_object_keys_column_only_match():
    """Same-key pair entirely through the columnar path with object keys."""
    proc = CEPProcessor(key_pair_pattern(), 4, sc.default_config())
    out = proc.process_columns(
        np.array([5, "other", 5], dtype=object),
        np.array([0, 0, 1], dtype=np.int32),
        [1, 1, 2],
    )
    assert len(out) == 1 and out[0][0] == 5


def test_object_keys_out_of_range_int_still_lane_coded():
    """An int key outside int32 keeps the lane-code rule, matching the
    record path for the same key."""
    proc = CEPProcessor(sc.strict3(), 2, sc.default_config())
    big = 2**40
    out = proc.process_columns(
        np.array([big, "x"], dtype=object),
        np.array([sc.A, sc.A], dtype=np.int32),
        [1, 1],
    )
    assert out == []
    assert proc._lane_of[big] == 0  # assigned; no crash, lane-coded
