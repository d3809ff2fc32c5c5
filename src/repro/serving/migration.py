"""Live in-flight request migration: move per-request KV state between
engines instead of draining.

The paper's online-reconfiguration story only fully lands when a *stateful*
request can leave its engine mid-generation: retirement latency is
otherwise bounded below by the longest in-flight decode. This module is
the state-transfer primitive (FlexPipe-style inflight refactoring):

    export   `ServingEngine.export_slot(rid)` snapshots everything one
             request owns — the KV slices of its decode slot (sliced out
             of the (n_slots, s_max) pool along the per-leaf batch axis),
             its decode position, generated tokens, and metric stamps —
             and frees the slot. Queued requests export as lightweight
             ``phase="queued"`` snapshots (no KV yet).
    reshard  `fit_single` reshapes the snapshot onto the target pool's
             single-sequence layout (differing ``s_max`` pads/truncates);
             `place_like` `jax.device_put`s the snapshot onto the target
             pool's sharding in one transfer call (specs that do not
             divide the slice shape degrade to replication on that dim).
    import   `ServingEngine.import_slot(snapshot)` writes the KV into a
             free slot and resumes decode at the snapshot position — no
             recompilation (decode is shape-static) and no re-run of
             prefill.
    resume   the request decodes on the target; the generated-token
             stream is bitwise identical to an unmigrated run (the KV
             prefix is copied verbatim and decode is deterministic
             per batch row).

Fail-closed rules (enforced at import, before any state is dropped):

  * the request's remaining token budget must fit the target pool's
    sequence capacity — migrating into a smaller ``s_max`` that cannot
    hold the rest of the generation raises `MigrationError`;
  * `export_slot` clamps ``max_new_tokens`` to what the SOURCE pool could
    have produced, so a larger target can never extend a stream beyond
    what the unmigrated run would have emitted;
  * a failed import restores the snapshot onto the source (the caller —
    `ServingCluster.migrate_requests` — re-imports on the source engine,
    which always fits its own snapshot).

Route-constraint compliance is the cluster's job (`migrate_requests`
checks the destination with the same fail-closed predicate the router
uses); this module only moves state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs import events as obs_events

if TYPE_CHECKING:                      # no runtime import: engine.py imports us
    from repro.serving.engine import Request

PyTree = Any


def _record_migration(record: "MigrationRecord") -> None:
    """Flight-recorder hook: one ``migration.pause`` event + a span whose
    duration is EXACTLY ``record.pause_s`` (the span is synthesized from
    the measured pause, so trace totals match `MigrationRecord` sums to
    the millisecond by construction)."""
    rec = obs_events.RECORDER
    if rec is None:
        return
    end = obs_events.now()
    rec.emit("migration.pause", engine=record.src, rid=record.rid,
             pause_s=record.pause_s, dst=record.dst, phase=record.phase,
             bytes_moved=record.bytes_moved, batch=record.batch,
             reason=record.reason)
    rec.span_at("migration.pause", end - record.pause_s, record.pause_s,
                track=record.src or "migration", cat="migration",
                rid=record.rid, dst=record.dst, reason=record.reason)


class MigrationError(RuntimeError):
    """A snapshot cannot be imported (capacity/slot/layout mismatch) —
    the request stays on (or is restored to) its source engine."""


@dataclasses.dataclass
class SlotSnapshot:
    """Everything one in-flight request owns, detached from its engine.

    Attributes:
        rid: the request id (lookup key for export).
        request: the live `Request` object — tokens generated so far and
            the metric stamps travel with it; nothing is re-stamped.
        phase: ``"decoding"`` (was resident in a slot; ``kv`` holds its
            cache slices) or ``"queued"`` (not yet prefilled; no KV).
        pos: the decode write position (``slot_pos``) for a decoding
            snapshot; the prompt length for a queued one.
        kv: single-sequence cache pytree sliced from the source pool
            (batch dim == 1, seq dims == the source ``s_max``); ``None``
            for queued snapshots.
        src_s_max: the source pool's sequence capacity (import refits
            seq dims from this to the target's).
        src_engine: source engine name (telemetry only).
        t_export: wall-clock stamp when the snapshot was taken.
    """

    rid: int
    request: "Request"
    phase: str
    pos: int
    kv: Optional[PyTree]
    src_s_max: int
    src_engine: str = ""
    t_export: float = dataclasses.field(default_factory=time.time)

    @property
    def nbytes(self) -> int:
        """Bytes of KV state carried by this snapshot (0 when queued)."""
        if self.kv is None:
            return 0
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.kv))

    def remaining_tokens(self) -> int:
        """Decode budget left after the tokens already generated."""
        return max(self.request.max_new_tokens - len(self.request.tokens_out), 0)


@dataclasses.dataclass(frozen=True)
class MigrationRecord:
    """Telemetry for one migrated request (the per-request pause is the
    paper's <50 ms budget; benchmarks assert it).

    Attributes:
        rid: the migrated request.
        src / dst: engine names.
        phase: ``"decoding"`` or ``"queued"`` at export time.
        pause_s: the request's blocking window — export + reshard +
            import, measured wall-clock (the request makes no progress
            inside it). Under a batched transfer (`migrate_many`) the
            shared device_put window is amortized: each request's pause
            is its own export + import plus a ``1/batch`` share of the
            one transfer.
        bytes_moved: KV bytes transferred (0 for queued requests).
        batch: decoding requests that shared this record's device_put
            (1 == an unbatched transfer).
        reason: why the request moved — ``""`` for an operator-initiated
            migration/retirement, ``"handoff"`` for the cluster's
            first-token prefill→decode handoff (the SLO ledger buckets
            pause time by this).
    """

    rid: int
    src: str
    dst: str
    phase: str
    pause_s: float
    bytes_moved: int
    batch: int = 1
    reason: str = ""


# ---------------------------------------------------------------------------
# pool surgery (shape-driven, architecture-agnostic)
# ---------------------------------------------------------------------------


def batch_axis_tree(model, s_max: int) -> PyTree:
    """Per-leaf batch-axis index of a model's KV cache layout.

    Probes `Model.cache_shapes` (eval_shape — no device work) at two batch
    sizes; the axis that tracks the probe is the batch axis. ``-1`` marks
    leaves with no batch dim (replicated state)."""
    one = model.cache_shapes(1, s_max)
    three = model.cache_shapes(3, s_max)

    def find(a, b):
        for ax in range(a.ndim):
            if a.shape[ax] == 1 and b.shape[ax] == 3:
                return ax
        return -1

    return jax.tree.map(find, one, three)


def slice_slot(pool: PyTree, axes: PyTree, slot: int) -> PyTree:
    """Slice one batch slot out of a KV pool, keeping the batch dim at
    size 1 (the single-sequence layout `ServingEngine._admit` also uses)."""

    def one(p, ax):
        if ax < 0:
            return p
        idx = [slice(None)] * p.ndim
        idx[ax] = slice(slot, slot + 1)
        return p[tuple(idx)]

    return jax.tree.map(one, pool, axes)


def fit_single(kv: PyTree, dst_single: PyTree) -> PyTree:
    """Refit a single-sequence cache onto a target single-sequence layout:
    longer dims are truncated (valid entries live in the prefix — decode
    masks by position), shorter ones zero-padded; dtypes follow the target.

    Raises:
        MigrationError: if the pytrees are not congruent (different
            architectures cannot exchange KV state).
    """

    def one(k, d):
        for ax in range(k.ndim):
            if k.shape[ax] > d.shape[ax]:
                k = jax.lax.slice_in_dim(k, 0, d.shape[ax], axis=ax)
            elif k.shape[ax] < d.shape[ax]:
                pad = [(0, 0)] * k.ndim
                pad[ax] = (0, d.shape[ax] - k.shape[ax])
                k = jnp.pad(k, pad)
        return k.astype(d.dtype)

    try:
        return jax.tree.map(one, kv, dst_single)
    except ValueError as e:
        raise MigrationError(
            f"snapshot cache layout is not congruent with the target "
            f"engine's (different model architecture?): {e}") from e


def place_like(kv: PyTree, pool: PyTree) -> PyTree:
    """`jax.device_put` a snapshot onto the target pool's sharding, in
    ONE transfer call for the whole tree (``kv`` and ``pool`` may be
    lists of congruent trees — a migration cohort).

    The pool's `NamedSharding` specs are re-derived for the slice shape:
    a spec entry whose mesh-axis extent does not divide the slice dim
    (e.g. a sharded batch dim collapsed to 1) degrades to replication on
    that dim, so the transfer is always expressible. No device program
    runs, so placing never compiles."""
    from jax.sharding import NamedSharding, PartitionSpec

    def sharding_for(k, p):
        sh = p.sharding
        if not isinstance(sh, NamedSharding):
            return sh
        parts = []
        for ax in range(k.ndim):
            entry = sh.spec[ax] if ax < len(sh.spec) else None
            names = entry if isinstance(entry, (tuple, list)) else (
                (entry,) if entry is not None else ())
            size = 1
            for nm in names:
                size *= sh.mesh.shape[nm]
            parts.append(entry if k.shape[ax] % size == 0 else None)
        return NamedSharding(sh.mesh, PartitionSpec(*parts))

    return jax.device_put(kv, jax.tree.map(sharding_for, kv, pool))


def write_single(pool: PyTree, single: PyTree, axes: PyTree,
                 slot: int) -> PyTree:
    """Write a single-sequence cache into batch slot ``slot`` of a pool
    (the inverse of `slice_slot`; trailing dims already fit the pool)."""

    def one(p, c, ax):
        if ax < 0:
            return p
        idx = [slice(None)] * p.ndim
        idx[ax] = slice(slot, slot + 1)
        return p.at[tuple(idx)].set(c.astype(p.dtype))

    return jax.tree.map(one, pool, single, axes)


def needed_capacity(request: "Request", phase: str, pos: int,
                    src_s_max: int) -> int:
    """The minimum target ``s_max`` that can finish this request's
    generation without ever hitting the pool's sequence cap — computable
    BEFORE export (it applies the same source-pool budget clamp
    `ServingEngine.export_slot` will).

    For a decoding request the remaining tokens write positions
    ``pos .. pos+rem-1`` and the engine stops when ``slot_pos >=
    s_max - 1``; a queued request additionally gets its first token from
    prefill. Importing below this capacity would truncate the stream, so
    `ServingEngine.import_slot` fails closed instead."""
    if phase == "queued":
        # prefill emits token 1 at pos=len(prompt); rem-1 decode steps follow
        rem = min(max(request.max_new_tokens - len(request.tokens_out), 0),
                  src_s_max - len(request.prompt))
        return len(request.prompt) + max(rem, 1)
    rem = min(max(request.max_new_tokens - len(request.tokens_out), 0),
              src_s_max - 1 - pos)
    return pos + rem + 1


def required_capacity(snapshot: SlotSnapshot) -> int:
    """`needed_capacity` of an already-exported snapshot."""
    return needed_capacity(snapshot.request, snapshot.phase, snapshot.pos,
                           snapshot.src_s_max)


def migrate_one(src_engine, dst_engine, rid: int, *,
                src: str = "", dst: str = "",
                reason: str = "") -> MigrationRecord:
    """Export `rid` from ``src_engine`` and import it into ``dst_engine``,
    restoring it to the source if the import fails closed.

    This is the primitive `ServingCluster.migrate_requests` loops over;
    eligibility (labels, route constraints, free slots) is the caller's
    responsibility — state transfer and honest pause accounting are ours.

    Returns:
        The `MigrationRecord` (pause measured export→import, blocking).

    Raises:
        KeyError: ``rid`` is not on the source engine.
        MigrationError: the destination cannot hold the request (it has
            been restored to the source, unchanged).
    """
    t0 = time.perf_counter()
    snap = src_engine.export_slot(rid)
    if src:
        snap.src_engine = src
    try:
        moved = dst_engine.import_slot(snap)
    except MigrationError:
        src_engine.import_slot(snap)   # the source always fits its own state
        raise
    record = MigrationRecord(rid=rid, src=src, dst=dst, phase=snap.phase,
                             pause_s=time.perf_counter() - t0,
                             bytes_moved=moved, reason=reason)
    _record_migration(record)
    return record


def migrate_many(src_engine, dst_engine, rids: Sequence[int], *,
                 src: str = "", dst: str = "",
                 reason: str = "") -> List[MigrationRecord]:
    """Move a batch of requests between one engine pair with ONE
    `jax.device_put` for all of their KV state, instead of one per
    request (`ServingCluster.migrate_requests` calls this).

    Pipeline: export every snapshot, fit each decoding snapshot onto the
    destination's single-sequence layout, place the whole cohort on the
    destination's sharding in one batched transfer, then import each
    request. The per-request ``pause_s`` is honest under batching: each
    request's own export +
    import window plus a ``1/batch`` share of the shared transfer (the
    batching is exactly what makes the shared window small).

    Fail-closed: if any import fails, that request AND every
    not-yet-imported one are restored to the source (which always fits
    its own state) before the error propagates — nothing is ever lost
    mid-batch. Requests imported before the failure remain moved.

    Returns:
        One `MigrationRecord` per request, in ``rids`` order, with
        ``batch`` set to the number of decoding requests that shared
        the transfer.

    Raises:
        KeyError: a ``rid`` is not on the source engine (raised during
            export; earlier exports are restored).
        MigrationError: an import failed closed (see above).
    """
    # Empty cohort (every candidate filtered out upstream, e.g. by route
    # predicates): nothing pauses, nothing moves — return before any
    # telemetry so no degenerate batch record or pause span is ever
    # emitted for a migration that did not happen.
    if not rids:
        return []
    # the destination layout lookup (eval_shape) happens BEFORE the
    # first export, while the requests are still live and serving
    layout = dst_engine.single_layout()

    snaps: List[SlotSnapshot] = []
    t_export: Dict[int, float] = {}
    for rid in rids:
        t0 = time.perf_counter()
        try:
            snap = src_engine.export_slot(rid)
        except KeyError:
            for s in snaps:            # unwind: nothing moved
                src_engine.import_slot(s)
            raise
        if src:
            snap.src_engine = src
        t_export[rid] = time.perf_counter() - t0
        snaps.append(snap)

    decoding = [s for s in snaps if s.phase == "decoding"]
    fitted: Dict[int, PyTree] = {}
    t_share = 0.0
    if decoding:
        t0 = time.perf_counter()
        fits = [fit_single(s.kv, layout) for s in decoding]
        # ONE device_put for the whole cohort (a list of per-request
        # trees): a batched transfer that runs no device program, so the
        # pause window stays compile-free for ANY cohort size and the KV
        # never round-trips through the host
        placed = place_like(fits, [dst_engine.cache] * len(fits))
        jax.block_until_ready(jax.tree.leaves(placed))
        for s, kv in zip(decoding, placed):
            fitted[s.rid] = kv
        t_share = (time.perf_counter() - t0) / len(decoding)

    records: List[MigrationRecord] = []
    for k, snap in enumerate(snaps):
        t0 = time.perf_counter()
        try:
            moved = dst_engine.import_slot(snap,
                                           kv_fitted=fitted.get(snap.rid))
        except MigrationError:
            for s in snaps[k:]:        # this one + every not-yet-imported
                src_engine.import_slot(s)
            raise
        decode_share = t_share if snap.phase == "decoding" else 0.0
        record = MigrationRecord(
            rid=snap.rid, src=src, dst=dst, phase=snap.phase,
            pause_s=t_export[snap.rid] + decode_share
            + (time.perf_counter() - t0),
            bytes_moved=moved,
            batch=len(decoding) if snap.phase == "decoding" else 1,
            reason=reason)
        _record_migration(record)
        records.append(record)
    return records
