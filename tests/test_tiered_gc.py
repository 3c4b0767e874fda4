"""The host event GC under compiler tiering.

A strict prefix split across two calls waits in the stencil carry
(``PrefixCarry``) between them: it owns no slab entry and no run until it
completes and is promoted.  The GC (``CEPProcessor._gc_events``) must keep
those events alive, or the decode of the promoted run's match finds no
event at the carried offsets.  Checked at 128 lanes on the columnar path
with a GC after every call, against the host oracle, the untiered
processor and the benchmark's frozen reference.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

from kafkastreams_cep_tpu import OracleNFA, Query
from kafkastreams_cep_tpu.runtime import CEPProcessor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402
from harness.traffic import generate  # noqa: E402
from reference.nfa import ReferenceNFA  # noqa: E402

CONF = spec.load_json(os.path.join(BENCH, "configs", "prefix-131072.json"))
TRAFFIC = spec.load_json(os.path.join(BENCH, "traffic", "prefix-sparse.json"))
TCFG = spec.engine_config(CONF)
UCFG = dataclasses.replace(TCFG, tiering=False)
K = 128
EPOCH = 1_700_000_000_000


def screen():
    """The configuration's pattern: three strict stages, then a
    skip-till-next-match stage."""
    return (
        Query()
        .select("first").where(lambda k, v, ts, st: v["code"] == 1)
        .then().select("second").where(lambda k, v, ts, st: v["code"] == 2)
        .then().select("third").where(lambda k, v, ts, st: v["code"] == 3)
        .then().select("latest").skip_till_next_match()
        .where(lambda k, v, ts, st: v["code"] == 7)
        .build()
    )


def feed(proc, codes, call):
    """``codes [K, S]`` in calls of ``call`` steps through
    ``process_columns`` (time-major, key = lane); the matches in order."""
    lanes, steps = codes.shape
    out = []
    for s0 in range(0, steps, call):
        n = min(call, steps - s0)
        keys = np.tile(np.arange(lanes, dtype=np.int32), n)
        vals = codes[:, s0:s0 + n].T.reshape(-1)
        ts = EPOCH + np.repeat(np.arange(s0, s0 + n, dtype=np.int64), lanes) * 1000
        out += proc.process_columns(keys, {"code": vals}, ts)
    return out


def canon(matches):
    """Per key, each match as stages of (offset, value, timestamp)."""
    per = {}
    for key, seq in matches:
        per.setdefault(int(key), []).append(tuple(
            (stage, tuple((e.offset, tuple(sorted(e.value.items())),
                           e.timestamp) for e in evs))
            for stage, evs in seq.as_map().items()
        ))
    return per


def oracle(codes):
    lanes, steps = codes.shape
    out = []
    for k in range(lanes):
        nfa = OracleNFA.from_pattern(screen())
        for s in range(steps):
            out += [(k, m) for m in nfa.match(
                k, {"code": int(codes[k, s])}, EPOCH + s * 1000, offset=s)]
    return canon(out)


def reference(codes):
    """The benchmark's frozen reference over the configuration's pattern."""
    lanes, steps = codes.shape
    per = {}
    for k in range(lanes):
        nfa = ReferenceNFA(CONF["pattern"])
        for s in range(steps):
            for m in nfa.match(k, {"code": int(codes[k, s])},
                               EPOCH + s * 1000, s):
                stages = {}
                for stage, (off, value, ts) in m:
                    stages.setdefault(stage, []).append(
                        (off, tuple(sorted(value.items())), ts))
                per.setdefault(k, []).append(
                    tuple((st, tuple(evs)) for st, evs in stages.items()))
    return per


def split_prefixes(lanes=K, steps=48, call=4):
    """Filler 0 (no stage takes it), and on each lane the occurrence
    ``1, 2, 3, 40, 7`` every 16 steps, starting where lane ``k % 4``
    puts it against the call boundary: ``1 | 2, 3``, ``1, 2 | 3``,
    ``1, 2, 3 | 40, 7`` or inside one call."""
    codes = np.zeros((lanes, steps), np.int32)
    for k in range(lanes):
        lead = (call - 1 - k % 4) % call + call  # boundary after 1, 2, 3, 0 events
        for s in range(lead, steps - 5, 16):
            codes[k, s:s + 5] = (1, 2, 3, 40, 7)
    return codes


def test_gc_keeps_carried_prefix_events():
    codes = split_prefixes()
    tiered = CEPProcessor(screen(), K, TCFG, gc_events_interval=1)
    got = canon(feed(tiered, codes, 4))
    untiered = CEPProcessor(screen(), K, UCFG, gc_events_interval=1)
    want = oracle(codes)
    assert sum(map(len, want.values())) == K * 3
    assert got == want
    assert canon(feed(untiered, codes, 4)) == want
    assert tiered.metrics.gc_carry_pinned > 0
    assert untiered.metrics.gc_carry_pinned == 0
    assert not any(tiered.counters().values())
    snap = tiered.metrics_snapshot(per_lane=False)
    assert snap["gc_carry_pinned"] == tiered.metrics.gc_carry_pinned
    assert snap["prefix_fires"] == snap["tier_promotions"] == K * 3


@pytest.mark.parametrize("call", [1, 3, 8])
@pytest.mark.parametrize("seed", [3, 2147483999, 90210])
def test_tiered_processor_matches_reference(seed, call):
    codes = generate(TRAFFIC, K, seed).period["code"]  # [P, K]
    codes = np.concatenate([codes, codes[:72]]).T  # 200 steps, wrap joined
    proc = CEPProcessor(spec.build_query(CONF["pattern"]), K, TCFG,
                        gc_events_interval=1, **CONF["processor"])
    got = canon(feed(proc, codes, call))
    want = reference(codes)
    assert sum(map(len, want.values())) >= K
    assert got == want
    assert got == oracle(codes)
    assert not any(proc.counters().values())
