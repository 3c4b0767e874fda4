"""Checkpoint / restore of processor state — the changelog-store analog.

The reference persists its entire engine state in Kafka Streams changelog
stores: the run queue re-serialized every record (``CEPProcessor.java:
158-160``) and the buffer/aggregate stores mutated through the store API.
Critically, **code is never serialized** — runs reference stages by *name*
and are rehydrated from the compiled topology on restore
(``ComputationStageSerDe.java:40-46,66-78``).

The TPU analog: all canonical state already lives in fixed-shape device
arrays (:class:`EngineState`), so a checkpoint is a host-side snapshot of
those arrays plus the host bookkeeping (key→lane map, per-lane event store,
offsets).  The manifest records the compiled stage *names*; restore
compiles the pattern fresh from user code and refuses a topology whose
names differ — exactly the reference's lookup-by-name contract.

Format: one ``.npz`` for the arrays + a pickled header for host metadata
(events and keys are user objects — pickle is the Kryo analog; predicates
and fold functions are never written).
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import pickle
from typing import Any, Dict, Optional

import jax
import numpy as np

from kafkastreams_cep_tpu.engine.matcher import EngineConfig, EngineState
from kafkastreams_cep_tpu.runtime.processor import CEPProcessor

from kafkastreams_cep_tpu.utils.failpoints import fire as _failpoint
from kafkastreams_cep_tpu.utils.logging import get_logger

logger = get_logger("runtime.checkpoint")

# v2: EngineState.agg became typed-encoded int32 (float32 fold states as
# bit patterns) — v1 checkpoints' float32 agg arrays are not translatable
# without the old dtype convention, so they are refused rather than
# silently cast.
# v3: headers record state_dtypes so a fold dtype flip between save and
# restore is refused; v2 files lack the record and cannot be checked, so
# they are refused too (same no-silent-reinterpretation rule).  v3's
# decode_budget header field is the processor's GLOBAL compacted-row
# budget (runtime/processor.py) — no earlier released format carried a
# per-lane meaning.
FORMAT_VERSION = 3


class CheckpointCorrupt(ValueError):
    """The checkpoint file's payload does not match its recorded sha256
    digest (bit rot, torn write, truncation).  The supervisor's resume
    path falls back to the previous-good snapshot + journal-chain replay
    instead of crashing (``runtime/supervisor.py``)."""


def _flatten_state(state: EngineState) -> Dict[str, np.ndarray]:
    """EngineState -> flat ``{path: ndarray}`` with stable names."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    out = {}
    for path, leaf in flat:
        name = "/".join(
            p.name if hasattr(p, "name") else str(p.idx) for p in path
        )
        out[name] = np.asarray(jax.device_get(leaf))
    return out


def _unflatten_state(template: EngineState, arrays: Dict[str, np.ndarray]) -> EngineState:
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        name = "/".join(
            p.name if hasattr(p, "name") else str(p.idx) for p in path
        )
        if name not in arrays:
            raise ValueError(f"checkpoint missing state array {name!r}")
        arr = arrays[name]
        if arr.shape != leaf.shape:
            raise ValueError(
                f"checkpoint array {name!r} has shape {arr.shape}, "
                f"engine expects {leaf.shape} (EngineConfig mismatch?)"
            )
        # No silent reinterpretation — the array-level twin of the header
        # ``state_dtypes`` rule: agg stores float32 fold states as int32
        # bit patterns, so a cast here could flip bits-as-values without
        # any shape mismatch to catch it.
        if np.dtype(arr.dtype) != np.dtype(leaf.dtype):
            raise ValueError(
                f"checkpoint array {name!r} has dtype {arr.dtype}, engine "
                f"expects {np.dtype(leaf.dtype)} — refusing the silent "
                "cast (dtype changes are not translatable)"
            )
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(
    processor: CEPProcessor, path: str, extra: Optional[Dict[str, Any]] = None
) -> None:
    """Snapshot a processor's full state to ``path`` (a single file).

    ``extra`` rides along in the header for the caller's own bookkeeping
    (e.g. the supervisor's journal sequence number)."""
    _failpoint("checkpoint.save")
    if getattr(processor, "_pending", None) is not None:
        raise ValueError(
            "pipelined processor holds an undecoded batch; call flush() "
            "before checkpointing (a snapshot cannot carry device outputs)"
        )
    if processor._col_batches:
        # Lazy columnar batches (process_columns) materialize their live
        # rows into the picklable mirror; dead rows are dropped.
        processor._gc_events()
    arrays = _flatten_state(processor.state)
    header = {
        "format_version": FORMAT_VERSION,
        "extra": dict(extra or {}),
        # Stage names only — the lookup-by-name restore contract.
        "stage_names": list(processor.batch.names),
        "state_names": list(processor.batch.matcher.tables.state_names),
        # Dtypes travel with the names: agg stores float32 states as int32
        # bit patterns, so a dtype flip between save and restore would
        # silently reinterpret bits — refused like a name mismatch.
        "state_dtypes": list(processor.batch.matcher.tables.state_dtypes),
        "config": dataclasses.asdict(processor.batch.matcher.config),
        "num_lanes": processor.num_lanes,
        "topic": processor.topic,
        "epoch": processor.epoch,
        "gc_events": processor.gc_events,
        "dedup": processor.dedup,
        "gc_interval": processor.gc_interval,
        "gc_events_interval": processor.gc_events_interval,
        "decode_budget": processor.decode_budget,
        "pipeline": processor.pipeline,
        "drain_interval": processor.drain_interval,
        "lane_of": dict(processor._lane_of),
        # Which mesh wrote this snapshot (None/absent: single device).
        # Lane rows are stored in LOGICAL lane order — mesh-agnostic — so
        # these are provenance, not placement: a restore onto a different
        # device count re-places the same rows through repartition_state
        # (see restore_processor) and logs the assignment change.
        "mesh_size": (
            int(processor.mesh.devices.size)
            if processor.mesh is not None
            else None
        ),
        "lane_shards": processor.lane_shards(),
        "next_offset": processor._next_offset.copy(),
        "off_base": processor._off_base.copy(),
        "events": [dict(d) for d in processor._events],
        "value_proto": processor._value_proto,
        # Ingestion-guard state (runtime/ingest.py): records still held in
        # the reorder buffer, watermark/frontier, dead letters, and loss
        # counters — first-class durable state, restored verbatim so a
        # resume releases exactly what the crashed process would have.
        "ingest": (
            processor._guard.to_state()
            if processor._guard is not None
            else None
        ),
        # Latency-ledger state (utils/latency.py): committed segment
        # histograms plus in-flight deferred bundles — additive key
        # (readers default to None when absent, so format_version stays
        # put), same durability discipline as the guard state above.
        "latency": (
            processor.ledger.to_state()
            if getattr(processor, "ledger", None) is not None
            else None
        ),
    }
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    # Payload integrity: a digest over the flattened state arrays, checked
    # on load — a corrupt snapshot must fail loudly (and recoverably, via
    # the supervisor's previous-good fallback), never restore flipped bits.
    header["arrays_sha256"] = hashlib.sha256(buf.getvalue()).hexdigest()
    with open(path, "wb") as f:
        pickle.dump({"header": header, "arrays": buf.getvalue()}, f)
    logger.info(
        "checkpoint saved to %s: %d lanes, stages %s",
        path, header["num_lanes"], header["stage_names"],
    )


def load_checkpoint(path: str) -> Dict[str, Any]:
    """Read a checkpoint file into ``{header, arrays}``.

    Raises :class:`CheckpointCorrupt` when the file cannot be parsed or
    its array payload fails the header's sha256 digest."""
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        header = blob["header"]
    except (OSError, FileNotFoundError):
        raise
    except Exception as e:
        raise CheckpointCorrupt(
            f"checkpoint {path} is unreadable ({type(e).__name__}: {e})"
        ) from e
    if header["format_version"] != FORMAT_VERSION:
        raise ValueError(
            f"checkpoint format {header['format_version']} unsupported"
        )
    want = header.get("arrays_sha256")
    if want is not None:
        got = hashlib.sha256(blob["arrays"]).hexdigest()
        if got != want:
            raise CheckpointCorrupt(
                f"checkpoint {path} failed integrity check: array payload "
                f"sha256 {got} != header digest {want}"
            )
    try:
        with np.load(io.BytesIO(blob["arrays"])) as z:
            arrays = {k: z[k] for k in z.files}
    except Exception as e:
        raise CheckpointCorrupt(
            f"checkpoint {path} array payload is unreadable "
            f"({type(e).__name__}: {e})"
        ) from e
    return {"header": header, "arrays": arrays}


def restore_processor(
    pattern, path: str, ckpt: Optional[Dict[str, Any]] = None, mesh=None
) -> CEPProcessor:
    """Rebuild a processor from user code + a checkpoint.

    ``pattern`` is compiled fresh (predicates/folds come from code, exactly
    like ``ComputationStageSerDe`` rehydrating stages from the topology);
    the checkpoint supplies only state.  A topology whose stage names don't
    match the checkpoint is refused.  Pass ``ckpt`` (a
    :func:`load_checkpoint` result) to reuse an already-loaded file.

    Checkpoints are mesh-agnostic host arrays, so ``mesh`` may differ from
    the mesh (or single device) that wrote the snapshot — the rebalance
    analog: lanes re-place onto the new device set, exactly like Kafka
    Streams restoring changelogged partitions onto a resized consumer
    group.  ``mesh`` may be a device count, as for :class:`CEPProcessor`.
    The mesh size must divide ``num_lanes`` (refused with a clear
    error, not a shard_map internality); a device-count change routes the
    state through ``runtime.migrate.repartition_state`` — identity, by
    the relabeling invariant below — and is logged as an explicit
    assignment change.
    """
    if ckpt is None:
        ckpt = load_checkpoint(path)
    header = ckpt["header"]
    config = EngineConfig(**header["config"])
    from kafkastreams_cep_tpu.parallel.sharding import as_mesh

    mesh = as_mesh(mesh)
    target_devs = int(mesh.devices.size) if mesh is not None else 1
    if int(header["num_lanes"]) % target_devs:
        raise ValueError(
            f"checkpoint holds {header['num_lanes']} lanes, not divisible "
            f"by the {target_devs}-device restore mesh — pick a mesh whose "
            "size divides the lane count (parallel/sharding.py contract)"
        )
    proc = CEPProcessor(
        pattern,
        header["num_lanes"],
        config,
        topic=header["topic"],
        epoch=header["epoch"],
        gc_events=header.get("gc_events", True),
        dedup=header.get("dedup", True),
        gc_interval=header.get("gc_interval", 0),
        gc_events_interval=header.get("gc_events_interval", 8),
        decode_budget=header.get("decode_budget", 131072),
        pipeline=header.get("pipeline", False),
        drain_interval=header.get("drain_interval", 1),
        mesh=mesh,
    )
    if list(proc.batch.names) != list(header["stage_names"]):
        raise ValueError(
            "pattern topology does not match checkpoint: stages "
            f"{proc.batch.names} vs checkpoint {header['stage_names']}"
        )
    if list(proc.batch.matcher.tables.state_names) != list(header["state_names"]):
        raise ValueError("fold-state names do not match checkpoint")
    proc_dtypes = list(proc.batch.matcher.tables.state_dtypes)
    if list(header["state_dtypes"]) != proc_dtypes:
        raise ValueError(
            "fold-state dtypes do not match checkpoint: "
            f"{proc_dtypes} vs checkpoint {header['state_dtypes']} "
            "(typed agg bit patterns are not translatable across dtypes)"
        )
    state = _unflatten_state(proc.state, ckpt["arrays"])
    written_devs = int(header.get("mesh_size") or 1)
    if written_devs != target_devs:
        # Snapshot rows are logical lanes and every lane→shard assignment
        # this runtime produces (evacuation, rebalance — runtime/migrate.py
        # move_lanes) RELABELS lanes so the live assignment is always the
        # contiguous identity.  Restoring onto a different device count is
        # therefore the identity repartition re-placed in new-sized blocks;
        # routing it through repartition_state keeps one audited
        # re-assignment point (shape/permutation validation, host
        # normalization) instead of a silent device_put.
        from kafkastreams_cep_tpu.runtime import migrate as migrate_mod

        state = migrate_mod.repartition_state(
            state, np.arange(int(header["num_lanes"]))
        )
        logger.info(
            "checkpoint written on %d device(s) restored onto %d: lanes "
            "re-placed in %d-lane shard blocks",
            written_devs, target_devs,
            int(header["num_lanes"]) // target_devs,
        )
    proc.state = proc.place(state)
    # The drained-handle ordering base is derivable from device state:
    # step_seq is the per-lane step counter (identical across lanes — all
    # lanes step together), and a restore resumes exactly at it.  Tiered
    # processors nest the engine state (engine/tiered.py: TieredState),
    # so the flattened array name carries the ``engine/`` prefix.
    step_seq = ckpt["arrays"].get(
        "step_seq", ckpt["arrays"].get("engine/step_seq")
    )
    proc._step_base = int(np.max(np.asarray(step_seq)))
    proc._lane_of = dict(header["lane_of"])
    proc._key_of = {v: k for k, v in proc._lane_of.items()}
    proc._next_offset = np.asarray(header["next_offset"]).copy()
    if "off_base" in header:
        proc._off_base = np.asarray(header["off_base"]).copy()
    else:
        # Pre-rebasing checkpoint: lanes that already saw records hold
        # absolute (unrebased) device offsets, so their base must stay 0;
        # untouched lanes stay unset so their first record fixes a base.
        proc._off_base = np.where(proc._next_offset > 0, 0, -1).astype(np.int64)
    proc._events = [dict(d) for d in header["events"]]
    proc._value_proto = header["value_proto"]
    if header.get("ingest") is not None:
        from kafkastreams_cep_tpu.runtime.ingest import IngestGuard

        proc._guard = IngestGuard.from_state(header["ingest"])
        # Flight-recorder burst detection diffs against the cumulative
        # dead-letter total; re-base it so a restore never reads the
        # whole history as one burst.
        proc._dlq_base = int(sum(proc._guard.reason_counts.values()))
    if header.get("latency") is not None:
        from kafkastreams_cep_tpu.utils.latency import LatencyLedger

        # The clock is not durable (pickling a callable would be a lie
        # across hosts): the restored ledger runs on wall clock; callers
        # with a pinned clock re-inject it via ``proc.set_clock(...)``.
        proc.ledger = LatencyLedger.from_state(header["latency"])
    logger.info(
        "restored processor from %s: %d keys assigned, offsets %s",
        path, len(proc._lane_of), proc._next_offset.tolist(),
    )
    return proc
