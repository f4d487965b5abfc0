"""The Snapshot orchestrator: take / restore / read_object.

TPU-native redesign of the reference's Snapshot (torchsnapshot/snapshot.py).
An *app state* is a ``Dict[str, Stateful]`` — model params, optimizer state,
step counters, PRNG keys — where the canonical unit of state is a pytree
(wrap raw pytrees in ``StateDict``).

Entry semantics (reference: snapshot.py:112-155):

- **per-rank**: the default. The entry is saved by one process and restorable
  only by that process index.
- **replicated** (via ``replicated=[globs]`` or auto-detected multi-host
  fully-replicated jax.Arrays): logically identical across processes. Saved
  once — chunks are greedily striped across processes so the save
  parallelizes — and restorable by any process, including new processes after
  a world-size change.
- **sharded**: jax.Arrays whose sharding partitions data across devices.
  Each process saves the shards it owns (deduplicated deterministically when
  a mesh replicates shards across processes); on restore, all shards are
  available to all processes and are resharded to the destination sharding
  via overlap-region reads.

A snapshot is world-size- and sharding-layout-independent iff all entries are
replicated or sharded (reference: snapshot.py:150-154).

Commit protocol: ``.snapshot_metadata`` (YAML) is written by rank 0 *after*
all ranks' storage I/O completes — a snapshot without metadata is invisible,
so partial failures never produce a readable-but-corrupt snapshot
(reference: snapshot.py:230-237).

Unlike the reference, *all* coordination here (key gather, replication
verification, chunk striping, barriers) runs over the out-of-band KV store —
never over device collectives — so every phase is background-thread-safe and
checkpoint traffic stays off ICI (see pg_wrapper.py).
"""

from __future__ import annotations

import asyncio
import fnmatch
import json
import logging
import os
import sys
import threading
import uuid
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from . import faultinject, telemetry

from .batcher import batch_read_requests, batch_write_requests, batching_enabled
from .dist_store import DEFAULT_BARRIER_TIMEOUT_S, LinearBarrier
from .flatten import flatten, inflate
from .io_types import ReadIO, ReadReq, StoragePlugin, WriteIO, WriteReq
from .io_preparers import (
    ChunkedArrayIOPreparer,
    ObjectIOPreparer,
    PrimitivePreparer,
    get_storage_path,
    is_partitionable_array,
    is_sharded_jax_array,
    prepare_read,
)
from .io_preparers.array import zero_copy_staging
from .io_preparers.prepare import is_jax_array
from .manifest import (
    ChunkedArrayEntry,
    CorruptSnapshotError,
    Entry,
    Manifest,
    PrimitiveEntry,
    ShardedArrayEntry,
    SnapshotMetadata,
    get_manifest_for_rank,
    is_container_entry,
)
from .pg_wrapper import PGWrapper, ProcessGroup, ensure_default_pg
from .rng_state import RNGState
from .scheduler import (
    PendingIOWork,
    execute_write_reqs,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from .serialization import array_size_bytes, dtype_to_string
from .stateful import AppState, Stateful
from .storage_plugin import url_to_storage_plugin_in_event_loop
from .tenancy import admission as tenancy_admission
from .version import __version__

logger = logging.getLogger(__name__)


class _PhaseTimer:
    """One-line phase-duration summary per take/restore.

    Complements the scheduler's periodic pipeline tables (scheduler.py)
    with the snapshot-level view: where did the wall time go — state_dict
    materialization, write planning, staging, storage I/O, commit?
    (Reference observability is the scheduler progress table only,
    scheduler.py:96-175; this is the layer above it.)
    """

    def __init__(self, op: str) -> None:
        self.op = op
        self.phases: List[Tuple[str, float]] = []
        self._t = telemetry.monotonic()

    def mark(self, name: str) -> None:
        now = telemetry.monotonic()
        self.phases.append((name, now - self._t))
        # Phase boundaries double as trace markers: the exported Chrome
        # trace shows where materialize/plan/stage/commit begin and end.
        telemetry.event(f"phase:{name}", cat="phase", op=self.op, dur_s=now - self._t)
        # ...and as the flight recorder's phase-transition events (what
        # an abort dump anchors on) and the live heartbeat's phase field
        # (what `watch` renders as "where is this rank").
        telemetry.flightrec.record(
            "phase", name=name, op=self.op, dur_s=round(now - self._t, 6)
        )
        telemetry.health.update(phase=name)
        self._t = now

    def log(self) -> None:
        total = sum(dt for _, dt in self.phases)
        logger.info(
            "%s completed in %.3fs (%s)",
            self.op,
            total,
            ", ".join(f"{n}={dt:.3f}s" for n, dt in self.phases),
        )

SNAPSHOT_METADATA_FNAME = ".snapshot_metadata"
# Commit fence: written by rank 0 BEFORE any payload I/O with this take's
# generation token, re-read at the commit point, deleted after a
# successful commit. A resurrected straggler (an async commit thread that
# outlived its world, a hung rank resuming after a restart re-took the
# step) finds a foreign or missing token and aborts instead of committing
# stale metadata over a newer snapshot. Committed snapshots carry no
# fence; a fence without metadata marks an in-flight or abandoned take
# (fsck's partial-commit signal).
SNAPSHOT_FENCE_FNAME = ".snapshot_fence"


class StaleCommitError(RuntimeError):
    """The commit fence no longer carries this take's generation token —
    a newer take claimed (or garbage-collection reclaimed) the snapshot
    path while this take was in flight. Nothing was committed; the newer
    snapshot, if any, is untouched."""

    def __init__(self, path: str, expected: str, found: Optional[str]) -> None:
        super().__init__(
            f"Refusing to commit snapshot metadata at {path!r}: the commit "
            f"fence holds {found!r}, not this take's generation "
            f"{expected!r}. A newer take has claimed this path (or its "
            "partial directory was garbage-collected); committing would "
            "splice this take's manifest over the newer snapshot's "
            "payloads. This take is aborted; nothing was committed."
        )
        self.path = path
        self.expected = expected
        self.found = found


def _drain_background_storage(
    storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
) -> None:
    """Drain plugin-internal background work (e.g. mirror replication)
    before the commit barrier — see StoragePlugin.drain_background."""
    event_loop.run_until_complete(storage.drain_background())


class Snapshot:
    """A handle to a snapshot at ``path`` (fs://, s3://, gs:// or bare path)."""

    def __init__(
        self,
        path: str,
        pg: Optional[ProcessGroup] = None,
        storage_options: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.path = path
        # No explicit group: bootstrap the default one from the
        # environment (TORCHSNAPSHOT_TPU_STORE_ADDR + _STORE_REPLICAS,
        # jax.distributed identity) — the bootstrap carries the store's
        # replica set, so restores opened from a bare path get the same
        # leader-failover coverage as launcher-managed worlds. Returns
        # None (single-process) when the env is not configured.
        self.pg = pg if pg is not None else ensure_default_pg()
        self._storage_options = storage_options
        self._metadata: Optional[SnapshotMetadata] = None

    # ------------------------------------------------------------------ take

    @classmethod
    def take(
        cls,
        path: str,
        app_state: AppState,
        pg: Optional[ProcessGroup] = None,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        incremental_base: Optional[str] = None,
        record_digests: bool = False,
        compression: Optional[str] = None,
        save_dtype: Optional[Dict[str, str]] = None,
        device_digests: Optional[bool] = None,
        layout: Optional[Any] = None,
    ) -> "Snapshot":
        """Persist ``app_state`` at ``path``.

        ``save_dtype`` maps logical-path globs to storage dtypes (e.g.
        ``{"model/**": "bfloat16", "optim/**": "bfloat16"}``): matching
        float arrays are downcast ON DEVICE before staging, halving DtoH
        and storage bytes for fp32 states; restore casts back into the
        destination's dtype (see :meth:`restore`). Casts apply only within
        one dtype class (float->float incl. bf16/fp8, int->int) and only
        when ``same_kind``-safe, so int/bool/object leaves under a broad
        float glob — optax step counts, PRNG keys — are left alone and the
        snapshot always restores into the original state.

        ``incremental_base`` names a previous snapshot: payloads whose
        content is unchanged since it are not rewritten — their entries
        reference the base's bytes instead (see dedup.py; the base must
        have been taken with ``record_digests=True`` or be incremental
        itself). ``record_digests`` records content digests so a FUTURE
        take can use this snapshot as its base; implied by
        ``incremental_base``.

        ``device_digests`` (default: the
        ``TORCHSNAPSHOT_TPU_DEVICE_DIGESTS`` env var) additionally
        fingerprints device arrays ON DEVICE (device_digest.py): an
        incremental take whose base recorded matching fingerprints skips
        the DtoH transfer for unchanged payloads entirely — on TPU the
        dominant cost — instead of staging them to hash. Opt-in because
        the fingerprint is strong but not cryptographic.

        ``compression`` enables payload compression ("zstd", "zstd:<lvl>",
        "zlib", "zlib:<lvl>"); default is the
        ``TORCHSNAPSHOT_TPU_COMPRESSION`` env var, else off. The codec is
        recorded per entry, so mixed-codec snapshots/chains restore
        transparently (see compression.py for the full design rules).

        ``layout`` declares the partition-rule layout this state was
        built under (a :class:`layout.LayoutSpec` or its ``to_dict()``
        form): the rule set is recorded in the snapshot metadata as the
        snapshot's SOURCE layout, so ``tstpu plan`` can dry-run a
        reshard into a destination rule set and operators can see what
        layout a checkpoint was written from. Descriptive only — shard
        geometry always comes from the arrays' real shardings.
        """
        cls._validate_app_state(app_state)
        cls._validate_save_dtype(save_dtype)
        event_loop = asyncio.new_event_loop()
        pg_wrapper = PGWrapper(pg if pg is not None else ensure_default_pg())
        path = cls._coalesce_path(path, pg_wrapper)
        storage = url_to_storage_plugin_in_event_loop(
            path, event_loop, storage_options
        )
        timer = _PhaseTimer("Snapshot.take")
        recorder = telemetry.begin_op("take", pg_wrapper.get_rank())
        telemetry.flightrec.record(
            "op.begin", op="take", rank=pg_wrapper.get_rank(), path=path
        )
        heartbeat = telemetry.health.maybe_start(pg_wrapper, "take", path)
        # The stall-forensics watchdog, armed alongside the heartbeat:
        # self-dumps stacks on overdue collectives / slow storage ops /
        # frozen progress, and answers `watch --dump` requests.
        watchdog = telemetry.forensics.arm(pg_wrapper, "take", path)
        # Tenancy admission: registers this op's bandwidth share and
        # rides `storage` to the scheduler's I/O-slot acquisition. None
        # (one env check) without a tenant.
        admission = tenancy_admission.maybe_arm("take", storage, pg_wrapper)
        # Live /metrics endpoint (TORCHSNAPSHOT_TPU_METRICS_PORT): armed
        # once per process at the first op; a no-op with the env unset.
        telemetry.promexp.maybe_start(rank=pg_wrapper.get_rank())
        body_ok = False
        try:
            # Synchronous take blocks the caller until I/O drains, so staged
            # buffers may alias caller memory — halves host memory traffic
            # vs async_take's consistency copy — and large plain entries may
            # STREAM: sub-chunks write while the next stages, collapsing a
            # big entry's critical path to ~max(stage, write). async_take
            # keeps both off: its early return is the consistency point.
            with zero_copy_staging():
                pending_io_work, metadata = cls._take_impl(
                    path=path,
                    app_state=app_state,
                    replicated=replicated or [],
                    pg_wrapper=pg_wrapper,
                    storage=storage,
                    event_loop=event_loop,
                    timer=timer,
                    incremental_base=incremental_base,
                    record_digests=record_digests,
                    storage_options=storage_options,
                    compression=compression,
                    save_dtype=save_dtype,
                    device_digests=device_digests,
                    layout=layout,
                    streaming=True,
                )
            # Drain + commit, with the cross-rank error channel armed:
            # staging errors ride the manifest gather inside _take_impl,
            # but a storage write can also fail HERE — in the post-gather
            # drain (an io task that was still in flight when the gather
            # ran) or at the fenced metadata write. Without report_error,
            # one rank raising in this phase deserts its peers at the
            # commit barrier until the barrier timeout (the 1800 s hang
            # class); with it, every blocked collective of this wrapper
            # raises immediately. (async_take's LinearBarrier has its own
            # error channel for the same phase.)
            try:
                pending_io_work.sync_complete(event_loop)
                _drain_background_storage(storage, event_loop)
                timer.mark("io_drain")
                pg_wrapper.barrier()
                if pg_wrapper.get_rank() == 0:
                    cls._write_snapshot_metadata(metadata, storage, event_loop)
                pg_wrapper.barrier()
            except BaseException as e:  # noqa: B036
                try:
                    pg_wrapper.report_error(e)
                except Exception:
                    pass
                raise
            timer.mark("commit")
            timer.log()
            # AFTER the commit barrier: a telemetry failure can degrade
            # observability but never un-commit a snapshot. The gather
            # inside is unconditional (disabled ranks contribute None) so
            # env skew can never desync the collective order.
            cls._publish_telemetry(
                "take", recorder, timer, pg_wrapper, storage, event_loop,
                persist=True, path=path,
            )
            body_ok = True
        except BaseException as e:  # noqa: B036
            # The flight recorder's moment: record the abort and dump the
            # ring next to the snapshot BEFORE the exception propagates —
            # StaleCommitError, a barrier timeout, a peer desertion, and
            # plain storage failures all unwind through here. The dump
            # never raises (it must not mask the abort).
            telemetry.flightrec.record(
                "op.abort", op="take", error=repr(e), kind=type(e).__name__
            )
            telemetry.flightrec.dump(
                path, pg_wrapper.get_rank(),
                f"take aborted: {type(e).__name__}",
            )
            # The recorder never reaches finish() on this path; release
            # it so it stops pinning the telemetry event buffer (the
            # abort's traceback cycle can outlive this frame by a lot).
            recorder.abandon()
            raise
        finally:
            if heartbeat is not None:
                heartbeat.stop()
            if watchdog is not None:
                watchdog.stop()
            tenancy_admission.disarm(storage, admission)
            # A success flag, NOT sys.exc_info(): in a finally block
            # exc_info also reports an AMBIENT exception the caller is
            # currently handling (take() inside an except block), which
            # would wrongly swallow close-time errors below.
            # Retire on failure too (a pure non-blocking write): a training
            # loop that catches failed takes must not leak store keys.
            try:
                pg_wrapper.retire()
            except Exception:
                pass
            try:
                storage.sync_close(event_loop)
            except Exception:
                # Close-time errors (e.g. a strict mirror failure) matter —
                # but never at the cost of masking an in-flight take error,
                # and never leaking the event loop.
                if body_ok:
                    raise
                logger.exception(
                    "storage close also failed while handling a take "
                    "failure; the original take error propagates."
                )
            finally:
                event_loop.close()
        snapshot = cls(path, pg, storage_options)
        snapshot._metadata = metadata
        return snapshot

    @classmethod
    def async_take(
        cls,
        path: str,
        app_state: AppState,
        pg: Optional[ProcessGroup] = None,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        incremental_base: Optional[str] = None,
        record_digests: bool = False,
        compression: Optional[str] = None,
        save_dtype: Optional[Dict[str, str]] = None,
        device_digests: Optional[bool] = None,
        layout: Optional[Any] = None,
    ) -> "PendingSnapshot":
        """Non-blocking take. Returns once *staging* (DtoH copy + serialize)
        completes — after that, mutations to the app state do not affect the
        snapshot. Storage I/O and the metadata commit continue on a
        background thread; call ``.wait()`` on the returned handle
        (reference: snapshot.py:245-313). ``incremental_base`` /
        ``record_digests`` / ``save_dtype`` / ``device_digests`` /
        ``layout`` as in :meth:`take`."""
        cls._validate_app_state(app_state)
        cls._validate_save_dtype(save_dtype)
        event_loop = asyncio.new_event_loop()
        pg_wrapper = PGWrapper(pg if pg is not None else ensure_default_pg())
        path = cls._coalesce_path(path, pg_wrapper)
        storage = url_to_storage_plugin_in_event_loop(
            path, event_loop, storage_options
        )
        timer = _PhaseTimer("Snapshot.async_take")
        recorder = telemetry.begin_op("take", pg_wrapper.get_rank())
        telemetry.flightrec.record(
            "op.begin", op="take", rank=pg_wrapper.get_rank(), path=path
        )
        heartbeat = telemetry.health.maybe_start(pg_wrapper, "take", path)
        watchdog = telemetry.forensics.arm(pg_wrapper, "take", path)
        admission = tenancy_admission.maybe_arm("take", storage, pg_wrapper)
        telemetry.promexp.maybe_start(rank=pg_wrapper.get_rank())
        try:
            pending_io_work, metadata = cls._take_impl(
                path=path,
                app_state=app_state,
                replicated=replicated or [],
                pg_wrapper=pg_wrapper,
                storage=storage,
                event_loop=event_loop,
                timer=timer,
                incremental_base=incremental_base,
                record_digests=record_digests,
                storage_options=storage_options,
                compression=compression,
                save_dtype=save_dtype,
                device_digests=device_digests,
                layout=layout,
            )
        except BaseException as e:  # noqa: B036
            telemetry.flightrec.record(
                "op.abort", op="take", error=repr(e), kind=type(e).__name__
            )
            telemetry.flightrec.dump(
                path, pg_wrapper.get_rank(),
                f"async_take staging aborted: {type(e).__name__}",
            )
            recorder.abandon()
            if heartbeat is not None:
                heartbeat.stop()
            if watchdog is not None:
                watchdog.stop()
            tenancy_admission.disarm(storage, admission)
            raise
        # All mutations from this point on do not affect the snapshot.
        return PendingSnapshot(
            path=path,
            pending_io_work=pending_io_work,
            pg_wrapper=pg_wrapper,
            metadata=metadata,
            storage=storage,
            event_loop=event_loop,
            storage_options=storage_options,
            timer=timer,
            recorder=recorder,
            heartbeat=heartbeat,
            watchdog=watchdog,
            admission=admission,
        )

    @classmethod
    def _take_impl(
        cls,
        path: str,
        app_state: AppState,
        replicated: List[str],
        pg_wrapper: PGWrapper,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        timer: Optional[_PhaseTimer] = None,
        incremental_base: Optional[str] = None,
        record_digests: bool = False,
        storage_options: Optional[Dict[str, Any]] = None,
        compression: Optional[str] = None,
        save_dtype: Optional[Dict[str, str]] = None,
        device_digests: Optional[bool] = None,
        layout: Optional[Any] = None,
        streaming: bool = False,
    ) -> Tuple[PendingIOWork, SnapshotMetadata]:
        timer = timer or _PhaseTimer("Snapshot.take")  # unlogged unless the caller logs
        rank = pg_wrapper.get_rank()
        world_size = pg_wrapper.get_world_size()
        # Validate/serialize the declared layout BEFORE any staging: a
        # malformed rule set must fail the take here, not a later plan
        # or restore that reads the metadata back.
        from .layout import resolve_layout

        layout_dict = resolve_layout(layout)
        app_state = dict(app_state)

        from .compression import compression_staging, env_codec, resolve_codec
        from .dedup import DedupContext, canonical_base_url, dedup_staging
        from .device_digest import enabled_by_env as device_digests_env

        if device_digests is None:
            device_digests = device_digests_env()

        # Validate the codec spec before any I/O happens; the explicit
        # argument wins over TORCHSNAPSHOT_TPU_COMPRESSION.
        codec = (
            resolve_codec(compression) if compression is not None else env_codec()
        )

        if incremental_base is not None:
            # Recorded origins must resolve from any working directory /
            # via symlinks later (restores, CLI deps/verify), so pin the
            # canonical URL before anything references it.
            incremental_base = canonical_base_url(incremental_base)

        dedup_ctx: Optional[DedupContext] = None
        if (
            incremental_base is not None or record_digests or device_digests
        ) and batching_enabled():
            # Slab packing rewrites small-write locations to batched/<uuid>
            # before staging, which can never match a base's ref index, and
            # byte-ranged slab sub-entries are excluded from future indexes
            # — batched payloads silently opt out of dedup. Say so.
            logger.warning(
                "Write batching (%s) is enabled: batched (small) payloads "
                "will not be deduplicated against the incremental base and "
                "their digests will not serve future incremental takes. "
                "Disable batching for snapshots used in incremental chains.",
                "TORCHSNAPSHOT_TPU_ENABLE_BATCHING",
            )
        # This snapshot's own mirror, recorded in its metadata so future
        # incrementals can point origin reads at the durable tier too.
        own_mirror: Optional[str] = None
        if storage_options and storage_options.get("mirror_url"):
            own_mirror = canonical_base_url(storage_options["mirror_url"])
        origin_mirrors: Dict[str, str] = {}
        if incremental_base is not None:
            from .storage_plugin import strip_mirror_options

            base_meta = cls(
                incremental_base,
                storage_options=strip_mirror_options(storage_options),
            ).metadata
            dedup_ctx = DedupContext.from_base(
                incremental_base, base_meta, device_digests=device_digests
            )
            if not dedup_ctx.refs:
                logger.warning(
                    "incremental_base %s has no content digests (take it with "
                    "record_digests=True); every payload will be rewritten.",
                    incremental_base,
                )
            # Origin mirrors propagate transitively: payloads this snapshot
            # borrows may physically live in any ancestor, so carry every
            # ancestor's mirror mapping forward alongside the base's own.
            origin_mirrors.update(base_meta.origin_mirrors or {})
            if base_meta.mirror_url and (
                canonical_base_url(base_meta.mirror_url) != incremental_base
            ):
                # Self-reference guard: when the base IS a mirror tier
                # (the natural rebase after losing a primary), wrapping it
                # with itself as fallback would be a pointless double open.
                origin_mirrors[incremental_base] = base_meta.mirror_url
        elif record_digests or device_digests:
            # device_digests alone still needs a recording context: the
            # fingerprints must land in THIS snapshot's manifest for the
            # next take to match against.
            dedup_ctx = DedupContext.recording_only(device_digests=device_digests)

        # RNG invariant (reference: snapshot.py:329-373): RNG state is
        # captured at entry and re-applied after take, so the snapshot
        # reflects entry state and taking it never perturbs the RNG stream.
        rng_captured: Dict[str, Dict[str, Any]] = {
            key: stateful.state_dict()
            for key, stateful in app_state.items()
            if isinstance(stateful, RNGState)
        }
        try:
            keys = cls._gather_keys(pg_wrapper, sorted(app_state.keys()))

            manifest: Manifest = {}
            flattened: Dict[str, Any] = {}
            # Materialize statefuls in cross-rank lockstep: one barrier per
            # key so a state_dict() that internally runs collectives (e.g. a
            # device_get of a non-addressable array) can never interleave
            # with a DIFFERENT stateful's collectives on another rank
            # (reference: snapshot.py:361-367). On failure, the rank still
            # *invokes* every remaining stateful's state_dict() (discarding
            # the result) and still barriers per key: skipping the calls
            # would desert any collectives inside them and hang healthy
            # peers mid-state_dict, where no error channel can reach them.
            # The first error rides the manifest gather's error channel
            # below, so every rank aborts and no rank commits.
            materialize_exc: Optional[BaseException] = None
            for key in keys:
                if key in app_state:
                    try:
                        sd = (
                            rng_captured[key]
                            if key in rng_captured
                            else app_state[key].state_dict()
                        )
                        if materialize_exc is None:
                            key_manifest, key_flattened = flatten(sd, prefix=key)
                            manifest.update(key_manifest)
                            flattened.update(key_flattened)
                    except BaseException as e:  # noqa: B036
                        if materialize_exc is None:
                            materialize_exc = e
                pg_wrapper.barrier()
            timer.mark("materialize")

            if save_dtype and materialize_exc is None:
                elided = cls._convert_save_dtypes(flattened, save_dtype)
                if elided:
                    logger.info(
                        "save_dtype downcast elided %.1f MB before staging",
                        elided / 1e6,
                    )
                timer.mark("convert")

            replicated_paths = cls._calculate_replicated_paths(
                flattened, replicated, pg_wrapper
            )

            write_reqs: List[WriteReq] = []
            chunk_assignments, owned_objects = _partition_write_units(
                flattened, replicated_paths, rank, world_size
            )

            # Stagers capture the dedup context and active codec at
            # construction (prepare time) and consult them at stage time —
            # digest recording / unchanged-payload write elision for
            # incremental snapshots, payload compression when enabled.
            with dedup_staging(dedup_ctx), compression_staging(codec):
                for logical_path in sorted(flattened.keys()):
                    obj = flattened[logical_path]
                    is_repl = logical_path in replicated_paths
                    if is_partitionable_array(obj):
                        prefix = get_storage_path(
                            logical_path, rank, replicated=is_repl
                        )
                        entry, reqs = _prepare_chunked_array_write(
                            prefix,
                            obj,
                            local_chunks=chunk_assignments[logical_path],
                            replicated=is_repl,
                        )
                        manifest[logical_path] = entry
                        write_reqs.extend(reqs)
                    elif is_sharded_jax_array(obj):
                        from .io_preparers.sharded import ShardedArrayIOPreparer

                        storage_prefix = get_storage_path(
                            logical_path, rank, sharded=True
                        )
                        entry, reqs = ShardedArrayIOPreparer.prepare_write(
                            storage_prefix, obj
                        )
                        manifest[logical_path] = entry
                        write_reqs.extend(reqs)
                    elif PrimitivePreparer.should_inline(obj):
                        manifest[logical_path] = PrimitivePreparer.prepare_write(
                            obj, replicated=is_repl
                        )
                    else:
                        storage_path = get_storage_path(
                            logical_path, rank, replicated=is_repl
                        )
                        entry, reqs = ObjectIOPreparer.prepare_write(
                            storage_path, obj, replicated=is_repl
                        )
                        manifest[logical_path] = entry
                        if not is_repl or logical_path in owned_objects:
                            write_reqs.extend(reqs)

            if batching_enabled():
                # Pack small per-rank/sharded writes into slabs; rewrites the
                # manifest entries' locations/byte-ranges in place, so this
                # must run before the manifest gather.
                _, write_reqs = batch_write_requests(
                    list(manifest.values()), write_reqs
                )

            memory_budget = get_process_memory_budget_bytes(
                pg_wrapper if world_size > 1 else None
            )
            # Claim the snapshot path BEFORE any payload I/O: rank 0
            # plants this take's generation token as the commit fence.
            # The commit point re-reads it — see SNAPSHOT_FENCE_FNAME.
            # Async takes plant here too, NOT in the background commit
            # thread: a fence planted after async_take returns would be
            # self-satisfying — a straggler suspended before its own
            # plant, reclaimed by the manager's fenced GC and re-taken,
            # would resume, plant its own token over the newer snapshot,
            # pass its own commit check, and splice stale metadata. Only
            # plant-before-return makes "its fence is gone" (the GC's
            # safety argument) actually final. One small fence write on
            # the staging path buys that; a storage failure here fails
            # the take fast, before any staging work — captured, not
            # raised: on a multi-rank take an immediate raise would
            # desert the peers at the manifest gather below until the
            # barrier timeout, so the failure rides the collective like
            # every other stage-time error.
            commit_gen = uuid.uuid4().hex
            fence_exc: Optional[BaseException] = None
            if rank == 0:
                try:
                    cls._write_fence(commit_gen, storage, event_loop)
                except BaseException as e:  # noqa: B036
                    fence_exc = e
            timer.mark("plan")
            # Gather AFTER execute_write_reqs returns: staging (the
            # consistency point) is complete by then, so stage-time entry
            # mutations — notably integrity checksums — are present in the
            # manifests the ranks exchange. Storage I/O continues in the
            # background; only metadata rides the collective. A local
            # staging failure must still reach the collective (a deserted
            # all-gather hangs every peer), so the error rides it too and
            # is raised on every rank afterwards — no rank commits.
            stage_exc: Optional[BaseException] = materialize_exc or fence_exc
            pending_io_work = None
            if stage_exc is None:
                try:
                    pending_io_work = event_loop.run_until_complete(
                        execute_write_reqs(
                            write_reqs,
                            storage,
                            memory_budget,
                            rank,
                            allow_streaming=streaming,
                        )
                    )
                except BaseException as e:  # noqa: B036
                    stage_exc = e
            timer.mark("stage")
            global_manifest, peer_errors = cls._gather_manifest(
                manifest, pg_wrapper, local_error=repr(stage_exc) if stage_exc else None
            )
            if stage_exc is not None:
                raise stage_exc
            failed = [f"rank {i}: {e}" for i, e in enumerate(peer_errors) if e]
            if failed:
                # Cancel/drain local in-flight storage writes before raising:
                # the abort path must leave no orphaned I/O behind.
                if pending_io_work is not None:
                    pending_io_work.sync_abort(event_loop)
                raise RuntimeError(
                    "snapshot aborted — staging failed on peer rank(s): "
                    + "; ".join(failed)
                )
            timer.mark("gather")
            metadata = SnapshotMetadata(
                version=__version__,
                world_size=world_size,
                manifest=global_manifest,
                mirror_url=own_mirror,
                origin_mirrors=origin_mirrors or None,
                layout=layout_dict,
            )
            # Runtime-only commit context (never serialized — to_yaml
            # walks declared fields only): the fence token the commit
            # point must still find, and the path for error reporting.
            metadata._commit_gen = commit_gen
            metadata._commit_path = path
            return pending_io_work, metadata
        finally:
            # Undo any RNG perturbation caused by state_dict materialization.
            for key, sd in rng_captured.items():
                app_state[key].load_state_dict(sd)

    # --------------------------------------------------------------- restore

    def restore(
        self,
        app_state: AppState,
        device_digests: Optional[bool] = None,
        hot: Optional[Sequence[Any]] = None,
    ) -> "Optional[PageInSession]":
        """Restore the app state in place. Arrays are restored into the
        shapes/dtypes/shardings of the *current* state (memory-efficient and
        sharding-aware; reference rationale: snapshot.py:693-700).

        The destination is the spec: a checkpoint saved in a different
        dtype is cast to the destination's on restore (``same_kind`` casts
        only — float<->float incl. bf16/fp8, int<->int; mirroring the
        reference's ``dst.copy_(src)``, io_preparer.py:426-427). For jax
        destinations the cast runs on device AFTER the transfer, so the
        host->device wire carries the checkpoint's (often narrower) bytes.

        ``device_digests`` (default: the ``TORCHSNAPSHOT_TPU_DEVICE_DIGESTS``
        env var): device destinations that ALREADY hold a payload's content
        — fingerprinted on device against the snapshot's recorded
        fingerprint (device_digest.py) — skip the storage read and the
        HtoD transfer and keep their current array. Wins whenever a
        process re-restores mostly-unchanged state: reloading the next
        snapshot of an incremental chain, retrying a partial restore.

        ``hot``: lazy-restore hot set — regex strings or ``layout.Rule``
        objects naming the leaves that must be resident before this call
        returns. Consulted only under ``TORCHSNAPSHOT_TPU_LAZY_RESTORE``
        (default ``never``: one env check, eager semantics unchanged,
        return value ``None``). When the lazy election engages, deferred
        leaves come back as ``pagein.LeafFuture`` proxies in the loaded
        state and the returned :class:`pagein.PageInSession` pages them
        in — ``session.wait()`` is the eager restore's return point.
        """
        self._validate_app_state(app_state)
        return self._restore_impl(
            app_state, PGWrapper(self.pg), device_digests=device_digests,
            hot=hot,
        )

    def async_restore(
        self, app_state: AppState, device_digests: Optional[bool] = None
    ) -> "PendingRestore":
        """Restore on a background thread; returns a handle immediately.

        Lets a resuming program overlap the restore (storage reads, HtoD
        transfers) with other startup work — typically jit compilation of
        the train step, which needs only shapes, not values. The app state
        must not be read, mutated, or checkpointed until ``.wait()``
        returns; the KV-store collectives used for cross-rank lockstep are
        background-thread-safe, but do not start OTHER snapshot operations
        (take/restore) on any rank before waiting — collective ordering
        across ranks must stay consistent. No reference analogue (its
        restore is synchronous only).
        """
        self._validate_app_state(app_state)
        pg_wrapper = PGWrapper(self.pg)
        # Entry barrier on the CALLING thread: synchronizes all ranks into
        # the restore and — critically — performs the wrapper's namespace
        # handshake in foreground construction order, so the background
        # thread's collectives can never desynchronize against other
        # wrappers created later on the main thread.
        pg_wrapper.barrier()
        return PendingRestore(
            self, app_state, pg_wrapper, device_digests=device_digests
        )

    def _restore_impl(
        self,
        app_state: AppState,
        pg_wrapper: PGWrapper,
        device_digests: Optional[bool] = None,
        hot: Optional[Sequence[Any]] = None,
    ) -> "Optional[PageInSession]":
        # An explicit device_digests=True is a direct instruction to
        # verify; only the ambient (env-enabled) default is subject to
        # the governor's hash-vs-read economics below.
        explicit_digests = device_digests is not None
        if device_digests is None:
            from .device_digest import enabled_by_env

            device_digests = enabled_by_env()
        # Lazy page-in election (pagein.py): local decision here; made
        # collective below by riding the ONE election all-gather as a
        # fifth tuple element. Default-off costs exactly one env check.
        from . import pagein as _pagein

        lazy_token = ""
        lazy_hot = None
        lazy_learned: List[str] = []
        lazy_mode = _pagein.lazy_restore_mode()
        if lazy_mode != "never":
            lazy_hot = _pagein.HotSet(_pagein.compile_hot_set(hot))
            lazy_learned = _pagein.learned_order(self.path)
            # `auto` engages only when there is something to serve early
            # (declared hot set or a learned first-touch order); both
            # modes stand down when committed delta-journal epochs exist
            # — replay folds NEWER values onto restored leaves, and a
            # page landing after it would silently roll a leaf back.
            engage_local = (
                lazy_mode == "always"
                or bool(lazy_hot.rules)
                or bool(lazy_learned)
            ) and not _pagein.journal_blocks_lazy(self.path)
            lazy_token = _pagein.vote_token(engage_local, lazy_hot)
        event_loop = asyncio.new_event_loop()
        rank = pg_wrapper.get_rank()
        storage = url_to_storage_plugin_in_event_loop(
            self.path, event_loop, self._storage_options
        )
        # Fleet seeding tier (distrib.py, TORCHSNAPSHOT_TPU_SEED_RESTORE):
        # shareable buffered reads source from peers that already hold the
        # chunk before touching storage, and chunks this restore obtains
        # keep seeding later restorers. Default-off is one env check; the
        # election is per-replica (no collective) because every seed miss
        # independently falls back to a direct read.
        from . import distrib as _distrib

        storage, seed_tier = _distrib.maybe_wrap_restore(
            storage, self.path, pg_wrapper
        )
        timer = _PhaseTimer("Snapshot.restore")
        recorder = telemetry.begin_op("restore", rank)
        telemetry.flightrec.record(
            "op.begin", op="restore", rank=rank, path=self.path
        )
        heartbeat = telemetry.health.maybe_start(pg_wrapper, "restore", self.path)
        watchdog = telemetry.forensics.arm(pg_wrapper, "restore", self.path)
        admission = tenancy_admission.maybe_arm("restore", storage, pg_wrapper)
        telemetry.promexp.maybe_start(rank=rank)
        coop_session = None
        pagein_session = None
        pagein_handoff = False
        try:
            metadata = self._read_metadata(storage, event_loop)
            available = get_manifest_for_rank(metadata, rank)
            timer.mark("metadata")
            memory_budget = get_process_memory_budget_bytes(
                pg_wrapper if pg_wrapper.get_world_size() > 1 else None
            )
            keys = self._gather_keys(pg_wrapper, sorted(app_state.keys()))
            # RNG states restore last so earlier load side effects can't
            # perturb them (reference: snapshot.py:489-500). Which keys are
            # RNG is agreed globally (union across ranks): an order derived
            # from local types alone could pair DIFFERENT keys at the same
            # lockstep slot on different ranks, which would let two
            # statefuls' internal collectives interleave — the exact hazard
            # the per-key barrier exists to prevent.
            rng_local = sorted(
                k for k in keys if isinstance(app_state.get(k), RNGState)
            )
            rng_keys = set(self._gather_keys(pg_wrapper, rng_local))
            ordered = [k for k in keys if k not in rng_keys]
            ordered += [k for k in keys if k in rng_keys]
            # Load statefuls in cross-rank lockstep: one barrier per key so
            # a load_state_dict()/state_dict() that internally runs
            # collectives can't interleave with a different stateful's on
            # another rank (reference restore: snapshot.py:477-487). After a
            # failure (e.g. a per-rank entry missing after a world-size
            # change) the rank still *invokes* the remaining keys' loads and
            # still barriers — skipping them would desert any collectives
            # inside and hang healthy peers — then raises the first error
            # after the last key.
            exc: Optional[BaseException] = None
            # Distributed digest verification is COLLECTIVE (one object
            # all-gather per key), so when active every rank participates
            # at every key slot — including ranks whose app_state lacks
            # the key (they contribute nothing) — or peers would hang.
            # The state flatten happens here, before the gather, and is
            # reused by the load. Gated on the MANIFEST actually holding
            # digest-bearing sharded entries (identical on every rank:
            # sharded entries are merged globally), so restores with
            # nothing to verify pay no extra round trips.
            #
            # BOTH flags are AGREED COLLECTIVELY before the key loop:
            # each rank resolves device_digests from its own env/args
            # and its own measured hash-vs-read economics (io_governor),
            # so skew — a rank with TORCHSNAPSHOT_TPU_DEVICE_DIGESTS
            # unset, or one whose measured rates favor reading —
            # previously meant one rank skipping the per-key gather
            # while peers entered it, hanging the restore until the
            # 1800 s store timeout. One up-front all-gather ANDs the
            # local flags: any divergence degrades to
            # no-verification/direct-reads everywhere, never a hang.
            # The cooperative fan-out election (fanout.py —
            # TORCHSNAPSHOT_TPU_COOP_RESTORE + the governor's bandwidth
            # gate) RIDES THE SAME all-gather: a multi-rank restore pays
            # one flag round trip, not two. Each rank's peer-channel
            # address travels with its opt-in; cooperation engages only
            # when every rank offered one. The planned-reshard election
            # (reshard.py — TORCHSNAPSHOT_TPU_RESHARD + the governor's
            # should_planned_reshard gate) rides it as well: its vote is
            # one more element of the SAME gathered tuple, never a
            # second round trip (pinned by tests — the tuple is
            # (preverify, addr, coop, reshard, lazy_token); the lazy
            # page-in vote (pagein.py) is the fifth slot, a hot-set
            # signature string that must be unanimous). The peer
            # listener and
            # session are a shared transport: either subsystem opting in
            # binds it, and each engages only on its own unanimous vote,
            # so env skew in one knob cannot half-enable the other.
            manifest_verifiable = any(
                isinstance(e, ShardedArrayEntry)
                and e.shards
                and all(s.array.device_digest is not None for s in e.shards)
                for e in available.values()
            )
            dist_verify = False
            use_coop = False
            reshard_min_req = 0
            if pg_wrapper.get_world_size() > 1:
                from . import reshard as reshard_mod
                from .fanout import CoopRestoreSession

                local_pre = False
                if manifest_verifiable:
                    local_pre = bool(
                        device_digests
                    ) and self._preverify_worthwhile(
                        storage, explicit=explicit_digests
                    )
                # Reshard vote: 0 = opted out, else this rank's
                # min-requesters knob (the fleet negotiates max() so a
                # skewed env still yields ONE deterministic plan).
                local_reshard = (
                    reshard_mod.reshard_min_requesters()
                    if reshard_mod.local_opt_in(
                        type(storage).__name__, pg_wrapper
                    )
                    else 0
                )
                offer = CoopRestoreSession.local_offer(
                    type(storage).__name__,
                    pg_wrapper,
                    extra_opt_in=local_reshard > 0,
                )
                gathered_flags = pg_wrapper.all_gather_object(
                    (
                        bool(local_pre),
                        offer.addr,
                        offer.coop_in,
                        local_reshard,
                        lazy_token,
                    )
                )
                # Lazy page-in engages only on a unanimous identical
                # token (same mode AND same hot set): divergence — one
                # rank lazy, one not, or differing hot rules — degrades
                # to the eager restore everywhere, never a half-lazy
                # fleet whose deferred sets skew the coop plan gather.
                if lazy_token and not all(
                    f[4] == lazy_token for f in gathered_flags
                ):
                    logger.info(
                        "lazy page-in disabled for this restore: not "
                        "every rank voted the same mode/hot set (env "
                        "skew); restoring eagerly everywhere"
                    )
                    lazy_token = ""
                if manifest_verifiable:
                    dist_verify = all(f[0] for f in gathered_flags)
                    if local_pre and not dist_verify:
                        logger.info(
                            "distributed digest verification disabled for "
                            "this restore: not every rank opted in (env "
                            "skew or rate-gate divergence); reading normally"
                        )
                coop_session = offer.engage(
                    [f[1] for f in gathered_flags], rank, event_loop
                )
                if coop_session is not None:
                    use_coop = all(f[2] for f in gathered_flags)
                    if all(f[3] > 0 for f in gathered_flags):
                        reshard_min_req = max(f[3] for f in gathered_flags)
            if lazy_token:
                layout_spec = None
                if getattr(metadata, "layout", None):
                    from .layout import LayoutSpec

                    try:
                        layout_spec = LayoutSpec.from_dict(metadata.layout)
                    except Exception:  # noqa: BLE001 - ordering is advisory
                        layout_spec = None
                pagein_session = _pagein.PageInSession(
                    self.path,
                    rank,
                    lazy_hot,
                    memory_budget,
                    world_size=pg_wrapper.get_world_size(),
                    layout_spec=layout_spec,
                    learned=lazy_learned,
                    storage_options=self._storage_options,
                )
            for key in ordered:
                prepared = None
                if key in app_state:
                    try:
                        sd = app_state[key].state_dict()
                        prepared = (sd, flatten(sd, prefix=key)[1])
                    except BaseException as e:  # noqa: B036
                        if exc is None:
                            exc = e
                preverified: set = set()
                if dist_verify:
                    preverified = self._distributed_preverify(
                        prepared[1] if prepared is not None else {},
                        available,
                        pg_wrapper,
                    )
                # Read planning is hoisted ahead of execution so the
                # cooperative plan collective can run between the two on
                # EVERY rank — with an empty request list when this rank
                # has nothing (missing key, planning failure): the
                # gather is by slot, and a deserted one would hang
                # peers. A rank contributing nothing simply isn't a
                # requester; its would-be units stay direct elsewhere.
                # Planned-reshard context for this key: the plan is a
                # pure function of (manifest, destination shardings,
                # world size) — devices_indices_map is global — so every
                # rank computes identical roles with no communication. A
                # rank that never plans (missing key, planning failure)
                # simply never forwards; its subscribers time out into
                # counted storage fallbacks, trading speed, never
                # correctness.
                reshard_ctx = None
                if reshard_min_req > 0 and coop_session is not None:
                    from . import reshard as reshard_mod

                    reshard_ctx = reshard_mod.ReshardContext(
                        coop_session,
                        rank,
                        pg_wrapper.get_world_size(),
                        min_requesters=reshard_min_req,
                    )
                groups = None
                flattened = None
                if prepared is not None:
                    try:
                        read_reqs, flattened = self._plan_stateful_reads(
                            rank=rank,
                            key=key,
                            available=available,
                            metadata=metadata,
                            device_digests=device_digests,
                            prepared=prepared,
                            preverified=preverified,
                            reshard=reshard_ctx,
                            # RNG states restore last BECAUSE order
                            # matters; deferring one would reorder its
                            # load arbitrarily — they stay eager.
                            pagein=(
                                pagein_session
                                if key not in rng_keys
                                else None
                            ),
                        )
                        groups = self._group_read_reqs(read_reqs)
                    except BaseException as e:  # noqa: B036
                        if exc is None:
                            exc = e
                        groups = None
                coop_plan = None
                if coop_session is not None and use_coop:
                    # Reshard-claimed requests stay OUT of the coop unit
                    # gather: their roles are already assigned by the
                    # (identical-on-every-rank) plan, so the filter is
                    # symmetric and the two subsystems can never hand
                    # one request conflicting roles.
                    coop_plan = coop_session.plan_for_key(
                        [
                            rr
                            for _, reqs in (groups or [])
                            for rr in reqs
                            if reshard_ctx is None
                            or not reshard_mod.is_reshard_claimed(rr)
                        ],
                        pg_wrapper,
                    )
                if reshard_ctx is not None:
                    coop_plan = reshard_mod.ComposedRestorePlan(
                        reshard_ctx, coop_plan
                    )
                if groups is not None:
                    try:
                        try:
                            self._execute_grouped(
                                groups,
                                storage,
                                memory_budget,
                                rank,
                                event_loop,
                                origin_mirrors=metadata.origin_mirrors,
                                coop=coop_plan,
                            )
                        finally:
                            if coop_plan is not None:
                                # Owned units never forwarded (an error
                                # aborted this key's execution) must not
                                # leave subscribers waiting out the coop
                                # timeout: abort them promptly.
                                coop_plan.abort_incomplete()
                        self._finish_stateful_load(
                            stateful=app_state[key],
                            key=key,
                            metadata=metadata,
                            rank=rank,
                            flattened=flattened,
                        )
                    except BaseException as e:  # noqa: B036
                        if exc is None:
                            exc = e
                elif coop_plan is not None:
                    coop_plan.abort_incomplete()
                pg_wrapper.barrier()
            timer.mark("load")
            # Delta-journal replay: fold committed journal epochs onto the
            # just-restored base (journal.py). Fixed symmetric point —
            # every rank reaches it (per-key failures are captured, the
            # loop always completes), so its cross-rank verdict gather
            # cannot desync; a rank whose base restore failed participates
            # with base_ok=False and every rank falls back together.
            # Never raises.
            from . import journal as _journal

            _journal.maybe_replay(
                self.path, app_state, pg_wrapper=pg_wrapper,
                base_ok=exc is None,
            )
            # DR provenance: a replication cursor in the directory means
            # this restore ran against the REMOTE tier's copy (base +
            # applied epochs) — the fleet is recovering from a region
            # loss, which the operator log and counters should say.
            from . import georep as _georep
            from .storage_plugin import local_fs_root as _lfr

            _local = _lfr(self.path)
            if _local is not None and os.path.isfile(
                os.path.join(_local, _georep.CURSOR_FNAME)
            ):
                telemetry.counter_add("dr_replica_restores", 1)
                logger.info(
                    "restored from a geo-replicated copy (%s present in %s)",
                    _georep.CURSOR_FNAME,
                    self.path,
                )
            # BEFORE the raise: every rank reaches this point (per-key
            # failures are captured, the loop always completes), so the
            # unconditional telemetry gather stays symmetric even when
            # this rank is about to raise. Restores never write into the
            # snapshot directory — the fleet view is logged and exposed
            # via telemetry.last_fleet() only.
            self._publish_telemetry(
                "restore", recorder, timer, pg_wrapper, storage, event_loop,
                persist=False,
            )
            if exc is not None:
                raise exc
            # Lazy handoff: the restore returns HERE — hot set resident,
            # deferred leaves held as futures — and the page-in engine
            # adopts this restore's storage plugin and event loop (the
            # finally block below skips closing them). Failure paths
            # never reach this, so an aborted restore still closes its
            # own I/O and the session's futures raise PageInAborted.
            if pagein_session is not None:
                if pagein_session.has_deferred:
                    pagein_session.handoff(storage, event_loop, heartbeat)
                    pagein_handoff = True
                else:
                    pagein_session.finish_empty()
            timer.log()
            return pagein_session
        except BaseException as e:  # noqa: B036
            telemetry.flightrec.record(
                "op.abort", op="restore", error=repr(e), kind=type(e).__name__
            )
            telemetry.flightrec.dump(
                self.path, rank, f"restore aborted: {type(e).__name__}"
            )
            recorder.abandon()
            if pagein_session is not None and not pagein_handoff:
                try:
                    # Partial page-in state must be unreferencable: every
                    # unresolved leaf future raises PageInAborted.
                    pagein_session.abort()
                except Exception:
                    pass
            if seed_tier is not None:
                try:
                    # Retract THIS restore's seed registrations: an
                    # aborted replica must not advertise chunks it may
                    # be about to throw away.
                    seed_tier.abort()
                except Exception:
                    pass
            raise
        finally:
            # After a lazy handoff the page-in engine owns the storage
            # plugin, the event loop, and the health heartbeat (it stops
            # and closes them when the last page lands); everything else
            # — watchdog, admission, coop transport, wrapper — belongs
            # to the restore and shuts down here as before.
            if heartbeat is not None and not pagein_handoff:
                heartbeat.stop()
            if watchdog is not None:
                watchdog.stop()
            tenancy_admission.disarm(storage, admission)
            if coop_session is not None:
                try:
                    # Clean shutdown (bye frames) so this rank's exit is
                    # never mistaken for a mid-restore death by peers.
                    coop_session.close()
                except Exception:
                    pass
            try:
                pg_wrapper.retire()
            except Exception:
                pass
            if not pagein_handoff:
                storage.sync_close(event_loop)
                event_loop.close()

    def _distributed_preverify(
        self,
        flattened: Dict[str, Any],
        available: Manifest,
        pg_wrapper: PGWrapper,
    ) -> set:
        """Zero-byte verification of sharded destinations ACROSS process
        boundaries: fingerprint lanes are additive over disjoint word
        covers (device_digest.py), so each process computes 16-byte
        partial lanes over the destination regions it was elected for,
        one object all-gather moves the partials over the coordination
        plane, and every rank sums them against the manifest's recorded
        piece fingerprints. A piece no single process fully holds —
        which the local verification paths of
        ShardedArrayIOPreparer._dst_already_matches must fall back on —
        is verified here without moving a payload byte.

        Returns the logical paths whose entries are fully verified AND
        locally eligible on THIS rank (verdicts are identical everywhere
        — computed from identical gathered data — but they only apply
        where the rank's own destination passed the eligibility checks:
        a rank whose local object is e.g. a numpy array or has a shape
        mismatch must go through the normal read path and raise its
        normal errors). Collective: EVERY rank must call this at the
        same key slot, with an empty ``flattened`` when it has nothing,
        and the local-contribution phase NEVER raises — an unexpected
        per-entry failure just withholds that entry's contribution (its
        coverage then falls short and it reads normally) — because an
        asymmetric exception before the all-gather would desert peers
        mid-collective."""
        from .device_digest import combine_partials
        from .io_preparers.sharded import ShardedArrayIOPreparer

        local: Dict[str, Any] = {}
        eligible: set = set()
        for lp, obj in flattened.items():
            try:
                entry = available.get(lp)
                if not isinstance(entry, ShardedArrayEntry):
                    continue
                if not is_jax_array(obj) or getattr(
                    obj, "is_fully_addressable", True
                ):
                    # Fully-addressable destinations verify locally
                    # (global slices) — cheaper, and no exchange needed.
                    continue
                if list(obj.shape) != list(entry.shape):
                    continue
                if dtype_to_string(obj.dtype) != entry.dtype:
                    continue
                if not entry.shards or any(
                    s.array.device_digest is None for s in entry.shards
                ):
                    continue
                contribs = (
                    ShardedArrayIOPreparer.partial_digest_contributions(
                        entry, obj
                    )
                )
                # None (unfingerprintable region) is published as-is:
                # peers must see this rank failed, not "no overlap".
                local[lp] = contribs
                if contribs is not None:
                    eligible.add(lp)
            except Exception:  # noqa: BLE001 - lockstep safety
                logger.exception(
                    "distributed digest verification: contribution for "
                    "%r failed; it will read normally",
                    lp,
                )
                local[lp] = None

        gathered = pg_wrapper.all_gather_object(local)

        verified: set = set()
        try:
            candidate_lps = sorted(set().union(*(set(g) for g in gathered)))
            for lp in candidate_lps:
                entry = available.get(lp)
                if not isinstance(entry, ShardedArrayEntry):  # pragma: no cover
                    continue
                merged: Dict[int, Dict[str, Any]] = {}
                failed = False
                for g in gathered:
                    if lp not in g:
                        continue
                    contribs = g[lp]
                    if contribs is None:
                        failed = True
                        break
                    for i, regions in contribs.items():
                        bucket = merged.setdefault(int(i), {})
                        for box_key, n_elems, lanes in regions:
                            # Replicated boxes are elected to ONE owner,
                            # so a duplicate (piece, box) means equal
                            # values; keep the first.
                            bucket.setdefault(box_key, (n_elems, lanes))
                if failed:
                    continue
                ok = True
                for i, shard in enumerate(entry.shards):
                    piece_elems = 1
                    for s in shard.sizes:
                        piece_elems *= s
                    regions = merged.get(i, {})
                    covered = sum(n for n, _ in regions.values())
                    if covered != piece_elems:
                        ok = False  # a rank missing, or boxes didn't cover
                        break
                    digest = combine_partials(
                        (lanes for _, lanes in regions.values()),
                        array_size_bytes(shard.sizes, entry.dtype),
                    )
                    if digest != shard.array.device_digest:
                        ok = False
                        break
                if ok:
                    verified.add(lp)
        except Exception:  # noqa: BLE001 - lockstep safety
            # Malformed gathered data (e.g. version skew) must not raise
            # asymmetrically between the gather and the key barrier.
            logger.exception(
                "distributed digest verification: verdicts failed; "
                "reading normally"
            )
            return set()
        # Global verdicts, locally applied: skip only what THIS rank's
        # destination was eligible for.
        applied = verified & eligible
        if applied:
            kept = sum(
                array_size_bytes(
                    available[lp].shape, available[lp].dtype
                )
                for lp in applied
            )
            logger.info(
                "distributed digest verification: %d sharded entr%s "
                "(%.1f MB global) verified across process boundaries — "
                "no payload read",
                len(applied),
                "y" if len(applied) == 1 else "ies",
                kept / 1e6,
            )
        return applied

    def _preverify_worthwhile(
        self, storage: StoragePlugin, explicit: bool
    ) -> bool:
        """Economic gate for distributed preverify (VERDICT round-5
        item 6): fingerprinting every destination region is a full hash
        pass over the state — on fast local storage with a slow hasher
        (1-core hosts are the worst case) just re-reading is cheaper.

        ``explicit=True`` (the caller passed ``device_digests=True``)
        always verifies under the default/auto mode: a direct
        instruction outranks economics, and the zero-read drills rely
        on it. The ambient (env-enabled) path consults
        :func:`~.scheduler.io_governor`: it skips verification only when
        the measured storage read bandwidth clearly exceeds the measured
        hash throughput (probing hash throughput once on device if the
        fingerprint warmup hasn't recorded it yet). Unknown read
        bandwidth — a fresh process that has never restored — keeps the
        status-quo verify. ``TORCHSNAPSHOT_TPU_PREVERIFY=always|never``
        overrides everything. The verdict feeds the COLLECTIVE flag
        agreement in ``_restore_impl``; it is advisory per rank and
        never gates a collective by itself."""
        from .scheduler import io_governor, preverify_mode

        if explicit and preverify_mode() == "auto":
            return True
        governor = io_governor()
        if (
            governor.hash_bps() is None
            and governor.read_bps(type(storage).__name__) is not None
        ):
            # One ~16 MB on-device fingerprint probe, recorded for the
            # process lifetime — without it the gate could never learn
            # the hash side of the crossover.
            from .device_digest import probe_hash_throughput

            probe_hash_throughput()
        # The crossover uses THIS restore's storage backend: read rates
        # measured against some other plugin earlier in the process must
        # not decide for this one.
        decision = governor.should_preverify(type(storage).__name__)
        telemetry.record_election(
            site="preverify",
            plugin=type(storage).__name__,
            decision=decision,
            hash_bps=governor.hash_bps(),
            read_bps=governor.read_bps(type(storage).__name__),
        )
        if not decision:
            logger.info(
                "distributed digest verification skipped: measured read "
                "bandwidth beats hash throughput (%s) — re-reading is "
                "cheaper than fingerprinting",
                governor.measured_rates(),
            )
        return decision

    def _plan_stateful_reads(
        self,
        rank: int,
        key: str,
        available: Manifest,
        metadata: SnapshotMetadata,
        device_digests: bool,
        prepared: "Tuple[Any, Dict[str, Any]]",
        preverified: "Optional[set]" = None,
        reshard: "Optional[Any]" = None,
        pagein: "Optional[Any]" = None,
    ) -> "Tuple[List[ReadReq], Dict[str, Any]]":
        """Plan one app-state key's reads WITHOUT executing them.

        Split out of the load so the cooperative fan-out plan collective
        (fanout.py) can run between planning and execution — the plan is
        an all-gather of each rank's actual request set, so requests
        must exist before it and execution must wait for it. Primitive
        entries are resolved into ``flattened`` here (no I/O).
        ``reshard`` (reshard.ReshardContext) routes multi-requester
        sharded shards over the planned-peer tier; the planner needs no
        collective of its own, so this stays pure planning.

        ``pagein`` (pagein.PageInSession): residency tracking starts at
        this plan/execute split — eligible cold leaves are CLAIMED here
        (their requests never enter the eager set; a ``LeafFuture``
        proxy takes the leaf's place in ``flattened``) and completion
        callbacks route through ``pagein.deliver`` so a page landing in
        the background resolves its future instead of writing into a
        dict the restore has already inflated."""
        _, flattened = prepared
        preverified = preverified or set()

        read_reqs: List[ReadReq] = []
        for logical_path, obj in flattened.items():
            if logical_path not in available:
                raise RuntimeError(
                    f"Unable to find entry for {logical_path!r} in the snapshot "
                    f"(saved with world size {metadata.world_size}, restoring as "
                    f"rank {rank}). Only replicated and sharded entries are "
                    f"restorable after a world-size change; per-rank entries "
                    f"belong to the process index that saved them "
                    f"(see Snapshot docstring for the elasticity rules)."
                )
            entry = available[logical_path]
            if is_container_entry(entry):
                raise RuntimeError(
                    f"Structure mismatch restoring {logical_path!r}: the "
                    f"destination has a leaf there, but the snapshot saved a "
                    f"container ({type(entry).__name__}). Build the "
                    f"destination state with the same nested structure it was "
                    f"saved with (e.g. a dict/list with matching children)."
                )
            if isinstance(entry, PrimitiveEntry):
                flattened[logical_path] = entry.get_value()
                continue

            def _cb(value: Any, lp: str = logical_path) -> None:
                if pagein is not None and pagein.deliver(lp, value):
                    return
                flattened[lp] = value

            reqs = prepare_read(
                entry,
                obj_out=obj,
                callback=_cb,
                device_digests=device_digests,
                assume_verified=logical_path in preverified,
                reshard=reshard,
            )
            if pagein is not None and reqs:
                future = pagein.claim_leaf(key, logical_path, entry, reqs)
                if future is not None:
                    flattened[logical_path] = future
                    continue
                pagein.note_eager_bytes(
                    sum(
                        rr.buffer_consumer.get_consuming_cost_bytes()
                        for rr in reqs
                    )
                )
            read_reqs.extend(reqs)
        return read_reqs, flattened

    def _finish_stateful_load(
        self,
        stateful: Stateful,
        key: str,
        metadata: SnapshotMetadata,
        rank: int,
        flattened: Dict[str, Any],
    ) -> None:
        container_manifest = {
            p: e
            for p, e in get_manifest_for_rank(metadata, rank).items()
            if is_container_entry(e) and (p == key or p.startswith(f"{key}/"))
        }
        inflated = inflate(container_manifest, flattened, prefix=key)
        stateful.load_state_dict(inflated)

    def read_state_dict(
        self,
        key: Optional[str] = None,
        rank: Optional[int] = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> Any:
        """Materialize state WITHOUT a pre-built destination.

        ``restore`` fills an existing app state in place (memory-efficient,
        sharding-aware); this is the structure-free counterpart for
        inspection, conversion, and loading into a program that doesn't
        have the original module tree: arrays come back as host numpy
        (sharded entries merged dense), objects unpickled, primitives
        inlined, containers rebuilt. ``key`` selects one app-state key
        (e.g. ``"model"``); ``None`` returns ``{key: state}`` for every
        key visible to ``rank`` under the elasticity rules.
        """
        event_loop = asyncio.new_event_loop()
        pg_wrapper = PGWrapper(self.pg)
        r = rank if rank is not None else pg_wrapper.get_rank()
        storage = url_to_storage_plugin_in_event_loop(
            self.path, event_loop, self._storage_options
        )
        try:
            metadata = self._read_metadata(storage, event_loop)
            manifest = get_manifest_for_rank(metadata, r)

            def selected(p: str) -> bool:
                return key is None or p == key or p.startswith(f"{key}/")

            flattened: Dict[str, Any] = {}
            read_reqs: List[ReadReq] = []
            for logical_path, entry in manifest.items():
                if not selected(logical_path) or is_container_entry(entry):
                    continue
                if isinstance(entry, PrimitiveEntry):
                    flattened[logical_path] = entry.get_value()
                    continue

                def _cb(value: Any, lp: str = logical_path) -> None:
                    flattened[lp] = value

                read_reqs.extend(prepare_read(entry, callback=_cb))

            containers = {
                p: e
                for p, e in manifest.items()
                if is_container_entry(e) and selected(p)
            }
            if key is not None and not flattened and not read_reqs and not containers:
                raise RuntimeError(
                    f"No entries under {key!r} are visible to rank {r} in "
                    f"this snapshot (world size {metadata.world_size})."
                )
            budget = memory_budget_bytes or get_process_memory_budget_bytes(None)
            self._execute_read_reqs_grouped(
                read_reqs, storage, budget, r, event_loop,
                origin_mirrors=metadata.origin_mirrors,
            )

            if key is not None:
                return inflate(containers, flattened, prefix=key)
            # One inflate per top-level app key, not a synthetic root dict:
            # app keys appear RAW in logical paths (flatten prefixes them
            # unescaped), so a root DictEntry would mis-resolve any key the
            # flattener's escaping would alter (e.g. one with a space).
            out: Dict[str, Any] = {}
            tops = sorted(
                {p.split("/", 1)[0] for p in list(containers) + list(flattened)}
            )
            for top in tops:
                sub_c = {
                    p: e
                    for p, e in containers.items()
                    if p == top or p.startswith(f"{top}/")
                }
                sub_f = {
                    p: v
                    for p, v in flattened.items()
                    if p == top or p.startswith(f"{top}/")
                }
                out[top] = inflate(sub_c, sub_f, prefix=top)
            return out
        finally:
            storage.sync_close(event_loop)
            event_loop.close()

    @staticmethod
    def _group_read_reqs(
        read_reqs: List[ReadReq],
        batch: bool = True,
        priority: "Optional[Callable[[ReadReq], int]]" = None,
    ) -> "List[Tuple[Optional[str], List[ReadReq]]]":
        """Group reads by payload origin and coalesce within each group,
        in DETERMINISTIC order (local snapshot first, then origins
        sorted): multi-rank cooperative restores execute groups in
        lockstep-identical order, so an owner's group-N forwards are
        produced while its peers consume group N — never a group apart
        by construction. Batching (read coalescing) runs per group
        BEFORE the cooperative plan is gathered, so unit keys name the
        exact requests the scheduler will execute.

        Interaction with the planned-reshard tier (reshard.py): sharded
        shard reads carry ``byte_range=None`` and pass through
        ``batch_read_requests`` untouched, so a reshard-claimed request
        can never be merged away between planning and execution. The
        reshard plan needs no gather at all (it is a pure function of
        manifest + destination shardings), and its election vote rides
        the SAME preverify-gate all-gather as the coop election — the
        restore prologue pays exactly ONE flag round trip however many
        peer subsystems engage (pinned by
        tests/test_reshard_restore.py::test_single_election_gather).

        ``priority`` maps each request to an int class (lower executes
        first); classes split groups — a class-0 demand fault and a
        class-1 prefetch against the same origin become two groups, the
        fault's first — and requests never coalesce across classes, so
        a background page can never be merged into (and thereby gate)
        a demand fault's read. ``None`` (the eager restore) is a single
        class and grouping is byte-for-byte what it always was."""
        groups: Dict[Tuple[int, Optional[str]], List[ReadReq]] = {}
        for rr in read_reqs:
            cls = priority(rr) if priority is not None else 0
            groups.setdefault((cls, rr.origin), []).append(rr)
        ordered = sorted(
            groups.items(),
            key=lambda kv: (kv[0][0], kv[0][1] is not None, kv[0][1] or ""),
        )
        if batch:
            # Merge adjacent ranged reads (slab restores, chunked reads)
            # into spanning reads — it only coalesces, never reorders data.
            return [
                (origin, batch_read_requests(reqs))
                for (_cls, origin), reqs in ordered
            ]
        return [(origin, reqs) for (_cls, origin), reqs in ordered]

    def _execute_read_reqs_grouped(
        self,
        read_reqs: List[ReadReq],
        storage: StoragePlugin,
        memory_budget: int,
        rank: int,
        event_loop: asyncio.AbstractEventLoop,
        batch: bool = True,
        origin_mirrors: Optional[Dict[str, str]] = None,
    ) -> None:
        self._execute_grouped(
            self._group_read_reqs(read_reqs, batch=batch),
            storage,
            memory_budget,
            rank,
            event_loop,
            origin_mirrors=origin_mirrors,
        )

    def _execute_grouped(
        self,
        groups: "List[Tuple[Optional[str], List[ReadReq]]]",
        storage: StoragePlugin,
        memory_budget: int,
        rank: int,
        event_loop: asyncio.AbstractEventLoop,
        origin_mirrors: Optional[Dict[str, str]] = None,
        coop=None,
    ) -> None:
        """Execute grouped reads (see ``_group_read_reqs``).

        Incremental snapshots reference unchanged payloads in their base
        snapshot(s); those reads go through a plugin opened on the origin
        URL — wrapped with the origin's OWN mirror (recorded in this
        snapshot's ``origin_mirrors``) so deduplicated payloads survive
        the loss of a base's primary tier.

        Coalescing composes with the streaming read path: adjacent
        byte-ranged reads into the same batched-slab location merge into
        ONE spanning request whose consumer slices a single sequential
        sub-chunk stream to the per-entry consumers
        (BatchedBufferConsumer.consume_stream), so the many-small-
        ranged-GET restore pattern becomes a few large sequential reads
        without ever materializing the spanning payload.

        ``coop``: this key's cooperative fan-out plan (fanout.py) —
        unit keys carry the origin, so each group's execution matches
        only its own units, and origin-borrowed replicated payloads
        (incremental chains) are read once from the BASE's storage by
        their owner and forwarded, exactly like local ones.
        """
        for origin, reqs in groups:
            if origin is None:
                sync_execute_read_reqs(
                    reqs, storage, memory_budget, rank, event_loop, coop=coop
                )
                continue
            from .storage_plugin import strip_mirror_options

            origin_opts = strip_mirror_options(self._storage_options)
            origin_mirror = (origin_mirrors or {}).get(origin)
            if origin_mirror:
                origin_opts = {
                    **(origin_opts or {}),
                    "mirror_url": origin_mirror,
                }
            origin_storage = url_to_storage_plugin_in_event_loop(
                origin, event_loop, origin_opts
            )
            try:
                sync_execute_read_reqs(
                    reqs, origin_storage, memory_budget, rank, event_loop,
                    coop=coop,
                )
            except FileNotFoundError as e:
                where = (
                    f"base snapshot {origin!r} or its mirror {origin_mirror!r}"
                    if origin_mirror
                    else f"base snapshot {origin!r}"
                )
                raise RuntimeError(
                    f"Restoring from incremental snapshot {self.path!r}: a "
                    f"payload referenced in {where} is missing ({e}). "
                    "Incremental snapshots require their base snapshots "
                    "(or, when recorded, the bases' mirrors) to remain "
                    "intact; `consolidate` detaches a chain from its bases."
                ) from e
            finally:
                origin_storage.sync_close(event_loop)

    # ----------------------------------------------------------- read_object

    def read_object(
        self,
        path: str,
        obj_out: Any = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> Any:
        """Random-access read of a single object by manifest path
        ("RANK/logical/path"). ``memory_budget_bytes`` bounds host memory by
        splitting array reads into byte ranges (reference: snapshot.py:518-613).
        """
        event_loop = asyncio.new_event_loop()
        pg_wrapper = PGWrapper(self.pg)
        storage = url_to_storage_plugin_in_event_loop(
            self.path, event_loop, self._storage_options
        )
        try:
            metadata = self._read_metadata(storage, event_loop)
            rank_str, _, logical_path = path.partition("/")
            if not rank_str.isdigit() or not logical_path:
                raise RuntimeError(
                    f"read_object path must look like 'RANK/logical/path', got {path!r}."
                )
            from .manifest import get_available_entries

            available = get_available_entries(metadata.manifest, int(rank_str))
            if logical_path not in available:
                raise RuntimeError(
                    f"{path!r} is not a valid entry in the snapshot "
                    f"(world size {metadata.world_size})."
                )
            entry = available[logical_path]
            if isinstance(entry, PrimitiveEntry):
                return entry.get_value()

            box: List[Any] = [obj_out]

            def _cb(value: Any) -> None:
                box[0] = value

            read_reqs = prepare_read(
                entry,
                obj_out=obj_out,
                callback=_cb,
                buffer_size_limit_bytes=memory_budget_bytes,
            )
            budget = memory_budget_bytes or get_process_memory_budget_bytes(None)
            self._execute_read_reqs_grouped(
                read_reqs, storage, budget, pg_wrapper.get_rank(), event_loop,
                batch=False, origin_mirrors=metadata.origin_mirrors,
            )
            return box[0]
        finally:
            storage.sync_close(event_loop)
            event_loop.close()

    # -------------------------------------------------------------- metadata

    def get_manifest(self) -> Manifest:
        return dict(self.metadata.manifest)

    @property
    def metadata(self) -> SnapshotMetadata:
        if self._metadata is None:
            event_loop = asyncio.new_event_loop()
            storage = url_to_storage_plugin_in_event_loop(
                self.path, event_loop, self._storage_options
            )
            try:
                self._metadata = self._read_metadata(storage, event_loop)
            finally:
                storage.sync_close(event_loop)
                event_loop.close()
        return self._metadata

    def _read_metadata(
        self, storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
    ) -> SnapshotMetadata:
        read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
        event_loop.run_until_complete(storage.read(read_io))
        raw = bytes(read_io.buf)
        # A zero-byte (or whitespace-only) metadata file and a torn one
        # both mean the same operational thing — the commit never fully
        # landed — but used to surface as whatever the decoder tripped
        # over first (JSONDecodeError, YAMLError, KeyError, Unicode
        # errors). Name the condition and the path instead.
        if not raw.strip():
            raise CorruptSnapshotError(self.path, "zero-byte metadata file")
        try:
            if raw[:4] == b"TSCM":
                from . import colmanifest

                return colmanifest.decode_metadata(raw)
            return SnapshotMetadata.from_yaml(raw.decode("utf-8"))
        except Exception as e:  # noqa: BLE001 - any decode failure
            raise CorruptSnapshotError(
                self.path,
                f"undecodable metadata: {type(e).__name__}: {e}",
            ) from e

    @staticmethod
    def _write_fence(
        gen: str,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        telemetry.flightrec.record("fence.plant", gen=gen)
        event_loop.run_until_complete(
            storage.write(
                WriteIO(
                    path=SNAPSHOT_FENCE_FNAME,
                    buf=json.dumps(
                        {
                            "gen": gen,
                            "pid": os.getpid(),
                            "version": __version__,
                        }
                    ).encode("utf-8"),
                )
            )
        )

    @staticmethod
    def _read_fence_gen(
        storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
    ) -> Optional[str]:
        """The generation token currently fencing this snapshot path, or
        None when the fence is missing or torn (both mean: not ours — a
        newer take reclaimed the path, or a foreign writer is mid-plant).

        Only not-found and decode failures map to None: a TRANSPORT error
        reading the fence propagates as itself, so the commit fails with
        the real storage diagnosis instead of a misleading
        StaleCommitError claiming a generation conflict."""
        read_io = ReadIO(path=SNAPSHOT_FENCE_FNAME)
        try:
            event_loop.run_until_complete(storage.read(read_io))
        except Exception as e:  # noqa: BLE001
            from .storage_plugins.retry import is_not_found_error

            if is_not_found_error(e):
                return None
            raise
        try:
            return json.loads(bytes(read_io.buf).decode("utf-8")).get("gen")
        except (ValueError, UnicodeDecodeError, AttributeError):
            return None  # torn fence: a foreign writer is mid-plant

    @staticmethod
    def _write_snapshot_metadata(
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        """The commit point. Generation-fenced when the metadata carries
        a take's commit context (see SNAPSHOT_FENCE_FNAME): commit only
        if the fence still holds THIS take's token, and clear the fence
        once the metadata is durable. Callers without a fence (e.g.
        ``consolidate`` materializing a chain) commit unfenced.

        The check is check-then-act, not compare-and-swap (plain
        filesystems and object stores offer no CAS): a straggler
        suspended BETWEEN its passing fence read and its metadata write,
        reclaimed and re-taken in that exact gap, can still splice. The
        fence shrinks the unprotected window from the whole drain
        (seconds to minutes) to one storage round trip; a splice that
        threads that needle is checksum-detectable by fsck, not
        silent-restorable."""
        gen = getattr(metadata, "_commit_gen", None)
        if gen is not None:
            found = Snapshot._read_fence_gen(storage, event_loop)
            telemetry.flightrec.record(
                "commit.decision", gen=gen, found=found, ok=found == gen
            )
            if found != gen:
                raise StaleCommitError(
                    getattr(metadata, "_commit_path", "<unknown>"), gen, found
                )
        if os.environ.get("TORCHSNAPSHOT_TPU_MANIFEST_FORMAT", "") == "columnar":
            from . import colmanifest

            raw = colmanifest.encode_metadata(metadata)
        else:
            raw = metadata.to_yaml().encode("utf-8")
        buf = faultinject.mutate("commit.metadata", raw)
        event_loop.run_until_complete(
            storage.write(WriteIO(path=SNAPSHOT_METADATA_FNAME, buf=buf))
        )
        if gen is not None:
            try:
                event_loop.run_until_complete(
                    storage.delete(SNAPSHOT_FENCE_FNAME)
                )
            except Exception:  # noqa: BLE001
                # Committed but the fence lingers: harmless (fsck flags
                # it as a stale fence; the next take overwrites it).
                logger.warning(
                    "committed, but could not remove the commit fence %s",
                    SNAPSHOT_FENCE_FNAME,
                    exc_info=True,
                )

    # ------------------------------------------------------------- telemetry

    @classmethod
    def _publish_telemetry(
        cls,
        op: str,
        recorder: "telemetry.OpRecorder",
        timer: Optional[_PhaseTimer],
        pg_wrapper: PGWrapper,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        persist: bool,
        path: Optional[str] = None,
    ) -> None:
        """Finish this rank's per-op telemetry summary, gather every
        rank's over the KV store, merge the fleet view, and (takes only)
        persist the document + per-rank Chrome traces into the snapshot.
        ``path`` (takes) additionally appends one compact record to the
        parent directory's ``.telemetry_history.jsonl`` — the checkpoint
        history the ``stats --trend`` regression gate reads.

        COLLECTIVE CONTRACT: when world_size > 1 the gather runs
        UNCONDITIONALLY — a telemetry-disabled rank contributes None — so
        ``TORCHSNAPSHOT_TPU_TELEMETRY`` skew between ranks degrades to a
        partial fleet view, never a hang (the same flag-agreement lesson
        the preverify gate learned, see _restore_impl). Summary building
        and persistence are individually guarded: after the commit
        barrier nothing here may fail the operation.
        """
        summary = None
        try:
            extra: Dict[str, Any] = {}
            if timer is not None:
                extra["phases"] = {n: round(dt, 6) for n, dt in timer.phases}
            from .scheduler import io_governor

            extra["rates"] = io_governor().measured_rates()
            summary = recorder.finish(extra=extra)
        except Exception:
            logger.exception("telemetry summary failed; continuing without it")
            summary = None
        if summary is not None:
            try:
                # Per-rank critical-path attribution (telemetry/critpath):
                # built from this op's span events (served from the
                # recorder's post-finish cache), gathered with the summary
                # so rank 0 can stitch the cross-rank critical path.
                summary["attribution"] = telemetry.critpath.build_attribution(
                    recorder.events(),
                    wall_s=summary.get("wall_s"),
                    rank=summary.get("rank", 0),
                )
            except Exception:
                logger.exception(
                    "critical-path attribution failed; continuing without it"
                )
        world_size = pg_wrapper.get_world_size()
        try:
            # The gather can only fail for store-level reasons (connection
            # loss, peer death) that surface on EVERY rank's collective —
            # swallowing locally cannot strand a healthy peer mid-gather.
            # Summaries themselves are plain JSON-able dicts by
            # construction, so per-rank payload failures don't exist.
            if world_size > 1:
                gathered = pg_wrapper.all_gather_object(summary)
            else:
                gathered = [summary]
            fleet = telemetry.merge_summaries(gathered)
            telemetry.set_last_fleet(fleet)
            attribution = None
            try:
                attribution = telemetry.critpath.merge_attributions(
                    [
                        (s or {}).get("attribution")
                        if isinstance(s, dict)
                        else None
                        for s in gathered
                    ],
                    aggregate=(fleet or {}).get("aggregate"),
                )
                telemetry.set_last_attribution(attribution)
            except Exception:
                logger.exception(
                    "critical-path merge failed; continuing without it"
                )
            if persist and path is not None and pg_wrapper.get_rank() == 0:
                # History works with the bus OFF too (fleet None): wall
                # time and identity always record; counters/rates ride
                # along when telemetry contributed a fleet view. rank 0
                # only; crash-safe append (telemetry/history.py).
                cls._append_history(
                    op, path, timer, pg_wrapper, fleet, summary,
                    attribution=attribution,
                )
            if fleet is None:
                return  # telemetry off everywhere: zero residue
            agg = fleet.get("aggregate") or {}
            logger.info(
                "telemetry[%s]: fleet wall %.3fs (slowest rank %s, skew "
                "%.3fs), %.2f GB written aggregate%s",
                op,
                fleet.get("wall_s_max", 0.0),
                fleet.get("slowest_rank"),
                fleet.get("skew_s", 0.0),
                (agg.get("bytes_written") or 0) / 1e9,
                f" ({agg['write_gbps']:.2f} GB/s fleet)"
                if agg.get("write_gbps")
                else "",
            )
        except Exception:
            # Post-commit (takes) / pre-raise (restores): a telemetry
            # gather failure must neither fail a committed snapshot nor
            # mask the restore error about to propagate.
            logger.exception(
                "telemetry cross-rank gather failed; continuing without "
                "the fleet view"
            )
            return
        if not persist:
            return
        rank = pg_wrapper.get_rank()
        try:
            if summary is not None:
                trace = telemetry.chrome_trace_json(recorder.events(), pid=rank)
                event_loop.run_until_complete(
                    storage.write(
                        WriteIO(
                            path=telemetry.trace_path_for_rank(rank),
                            buf=trace.encode("utf-8"),
                        )
                    )
                )
            if rank == 0:
                doc = telemetry.build_summary_document(
                    op, world_size, gathered, fleet
                )
                event_loop.run_until_complete(
                    storage.write(
                        WriteIO(
                            path=telemetry.TELEMETRY_SUMMARY_FNAME,
                            buf=json.dumps(doc, indent=1).encode("utf-8"),
                        )
                    )
                )
                if attribution is not None:
                    # The compact per-take attribution record next to the
                    # telemetry summary — what `explain <path>` reads.
                    cp_doc = telemetry.critpath.build_attribution_document(
                        op,
                        world_size,
                        attribution,
                        rates=(summary or {}).get("rates"),
                        governor=(summary or {}).get("governor"),
                    )
                    event_loop.run_until_complete(
                        storage.write(
                            WriteIO(
                                path=telemetry.critpath.ATTRIBUTION_FNAME,
                                buf=json.dumps(cp_doc, indent=1).encode(
                                    "utf-8"
                                ),
                            )
                        )
                    )
        except Exception:
            logger.exception(
                "telemetry persistence failed; the snapshot is unaffected"
            )

    @staticmethod
    def _append_history(
        op: str,
        path: str,
        timer: Optional[_PhaseTimer],
        pg_wrapper: PGWrapper,
        fleet: Optional[Dict[str, Any]],
        summary: Optional[Dict[str, Any]],
        attribution: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Append this committed take to ``<parent>/.telemetry_history
        .jsonl`` (local roots only; guarded — history must never fail a
        committed snapshot)."""
        try:
            from .storage_plugin import local_fs_root

            local = local_fs_root(path)
            if local is None:
                return
            root = os.path.dirname(os.path.abspath(local.rstrip("/")))
            wall = (
                sum(dt for _, dt in timer.phases) if timer is not None else 0.0
            )
            step = ((summary or {}).get("annotations") or {}).get("step")
            record = telemetry.history.build_record(
                op=op,
                path=path,
                wall_s=wall,
                world_size=pg_wrapper.get_world_size(),
                fleet=fleet,
                rank_summary=summary,
                step=step,
                attribution=attribution,
            )
            telemetry.history.append_record(root, record)
        except Exception:  # noqa: BLE001
            logger.exception("history append failed; the snapshot is unaffected")

    # --------------------------------------------------------------- helpers

    @staticmethod
    def _validate_app_state(app_state: AppState) -> None:
        for key, value in app_state.items():
            if not isinstance(value, Stateful):
                raise TypeError(
                    f"App state entry {key!r} (type {type(value).__name__}) "
                    "does not implement state_dict()/load_state_dict(). Wrap "
                    "raw pytrees in torchsnapshot_tpu.StateDict."
                )

    @staticmethod
    def _validate_save_dtype(save_dtype: Optional[Dict[str, str]]) -> None:
        """Fail on malformed ``save_dtype`` BEFORE any collective work: a
        typo like "bf16" otherwise surfaces mid-take as a metadata-version
        error, after the cross-rank materialize barriers already ran."""
        if not save_dtype:
            return
        from .serialization import string_to_dtype

        for pattern, dt in save_dtype.items():
            try:
                string_to_dtype(dt)
            except ValueError:
                raise ValueError(
                    f"save_dtype[{pattern!r}]: unknown dtype name {dt!r} "
                    '(use numpy-style names like "bfloat16", "float32", '
                    '"float8_e4m3fn", "int32").'
                ) from None

    @staticmethod
    def _convert_save_dtypes(
        flattened: Dict[str, Any], save_dtype: Dict[str, str]
    ) -> int:
        """Downcast matching array leaves IN the flattened state before
        write planning, so every downstream stage — DtoH, staging,
        checksum, storage — moves the converted (usually half-size) bytes.

        The conversion decision (glob precedence, dtype-class rules) lives
        in ``serialization.effective_save_dtype``, shared with the staging
        warmup's slab sizing. jax arrays cast ON DEVICE (``astype``
        preserves sharding; the wire then carries the narrow bytes); numpy
        leaves cast on host. Returns bytes elided.

        Memory note: conversion is eager — converted copies of ALL matched
        leaves exist on device until staging drains them, so the transient
        HBM overhead is ratio x matched bytes (+50% of matched fp32 state
        for bf16). For states near HBM capacity, scope the globs or save
        state groups in separate takes.

        No reference analogue — torchsnapshot stores tensors byte-exact
        only. The orbax counterpart is Save-/RestoreArgs dtype casting.
        """
        from .io_preparers.prepare import is_jax_array as _isjax
        from .serialization import effective_save_dtype

        saved = 0
        for lp, obj in flattened.items():
            if not (isinstance(obj, np.ndarray) or _isjax(obj)):
                continue
            target = effective_save_dtype(lp, obj.dtype, save_dtype)
            if target is not None:
                before = obj.nbytes
                flattened[lp] = obj.astype(target)
                saved += before - flattened[lp].nbytes
        return saved

    @staticmethod
    def _coalesce_path(path: str, pg_wrapper: PGWrapper) -> str:
        # All ranks must agree on the snapshot path; rank 0 wins
        # (reference: snapshot.py:798-804).
        return pg_wrapper.broadcast_object(path, src=0)

    @staticmethod
    def _gather_keys(pg_wrapper: PGWrapper, keys: List[str]) -> List[str]:
        gathered = pg_wrapper.all_gather_object(keys)
        return sorted(set().union(*gathered))

    @staticmethod
    def _calculate_replicated_paths(
        flattened: Dict[str, Any],
        replicated_globs: List[str],
        pg_wrapper: PGWrapper,
    ) -> Set[str]:
        """Glob-claimed + auto-detected replicated paths, verified by
        intersection across ranks (reference: snapshot.py:634-667,901-924)."""
        local: Set[str] = set()
        for logical_path, obj in flattened.items():
            if any(fnmatch.fnmatch(logical_path, g) for g in replicated_globs):
                local.add(logical_path)
            elif _is_process_replicated_jax_array(obj):
                local.add(logical_path)

        if pg_wrapper.get_world_size() == 1:
            return local

        # Verify: a path is replicated only if every rank claims it with an
        # identical signature (shape/dtype for arrays).
        def _signature(lp: str) -> Tuple:
            obj = flattened[lp]
            if is_partitionable_array(obj) or is_sharded_jax_array(obj):
                return (lp, tuple(obj.shape), dtype_to_string(obj.dtype))
            return (lp, None, None)

        claims = sorted(_signature(lp) for lp in local)
        all_claims = pg_wrapper.all_gather_object(claims)
        verified = set(all_claims[0])
        for other in all_claims[1:]:
            verified &= set(other)
        return {lp for lp, _, _ in verified}

    @staticmethod
    def _gather_manifest(
        local_manifest: Manifest,
        pg_wrapper: PGWrapper,
        local_error: Optional[str] = None,
    ) -> Tuple[Manifest, List[Optional[str]]]:
        """All-gather per-rank (manifest, staging-error) into the global
        rank-prefixed manifest (reference: snapshot.py:954-986). Replicated
        entries are already complete on every rank (each rank records the
        full chunk set while writing only its stripe), so no stripe merging
        is needed. Errors ride the collective so a failed rank doesn't
        desert it."""
        gathered = pg_wrapper.all_gather_object((local_manifest, local_error))
        manifests = [m for m, _ in gathered]
        errors = [e for _, e in gathered]
        global_manifest: Manifest = {}
        for rank, m in enumerate(manifests):
            for logical_path, entry in m.items():
                if logical_path:
                    global_manifest[f"{rank}/{logical_path}"] = entry
                else:
                    global_manifest[str(rank)] = entry
        _propagate_checksums(global_manifest)
        return global_manifest, errors


def _propagate_checksums(global_manifest: Manifest) -> None:
    """Replicated entries are recorded by every rank but staged only by the
    rank that writes each chunk; copy the stage-time metadata — checksum,
    content digest, dedup origin, and compression codec — to the other
    ranks' copies of the same storage location. Origin propagation is load-bearing: when an
    incremental take deduplicates a replicated chunk, only the writing
    rank learns the payload lives in the base snapshot, and every other
    rank restores its OWN copy of the entry (manifest.get_available_entries),
    which must therefore also point at the base."""
    from .manifest import ArrayEntry, ChunkedArrayEntry, ObjectEntry, ShardedArrayEntry

    def sub_entries(entry):
        if isinstance(entry, (ArrayEntry, ObjectEntry)):
            yield entry
        elif isinstance(entry, (ChunkedArrayEntry, ShardedArrayEntry)):
            parts = entry.chunks if isinstance(entry, ChunkedArrayEntry) else entry.shards
            for part in parts:
                yield part.array

    known: Dict[Tuple[str, str], str] = {}
    blanks: Dict[str, List[Any]] = {
        "checksum": [], "digest": [], "origin": [], "codec": []
    }
    for entry in global_manifest.values():
        for sub in sub_entries(entry):
            for field in ("checksum", "digest", "origin", "codec"):
                value = getattr(sub, field)
                if value is not None:
                    known.setdefault((field, sub.location), value)
                else:
                    blanks[field].append(sub)
    for field, subs in blanks.items():
        for sub in subs:
            value = known.get((field, sub.location))
            if value is not None:
                setattr(sub, field, value)


def _is_process_replicated_jax_array(obj: Any) -> bool:
    """Auto-detect rank-level replication: a jax.Array whose sharding is
    fully replicated across a multi-process device set has identical data on
    every process (the DDP-auto-detect analogue, reference snapshot.py:901-917)."""
    if not is_jax_array(obj):
        return False
    sharding = obj.sharding
    if not sharding.is_fully_replicated:
        return False
    try:
        process_indices = {d.process_index for d in sharding.device_set}
    except Exception:
        return False
    import jax

    return len(process_indices) == jax.process_count() and jax.process_count() > 1


def _prepare_chunked_array_write(
    storage_path_prefix: str,
    arr: Any,
    local_chunks: List[Tuple[List[int], List[int]]],
    replicated: bool,
) -> Tuple[ChunkedArrayEntry, List[WriteReq]]:
    """Chunked write planning where the *entry* always records the full chunk
    set (computable deterministically on every rank) while write requests
    cover only this rank's stripe."""
    dtype_str = dtype_to_string(arr.dtype)
    all_chunks = ChunkedArrayIOPreparer.chunk_shards(tuple(arr.shape), dtype_str)
    entry, write_reqs = ChunkedArrayIOPreparer.prepare_write(
        storage_path_prefix, arr, local_chunks, replicated=replicated
    )
    if replicated:
        # Record the full chunk set in the entry (locations are deterministic).
        # For this rank's own stripe, reuse the sub-entries already wired to
        # the write stagers — they receive stage-time mutations (integrity
        # checksums) that must land in the manifest; fresh objects would
        # orphan them.
        from .manifest import ArrayEntry, Shard
        from .serialization import Serializer

        local_by_loc = {c.array.location: c.array for c in entry.chunks}
        full: List[Shard] = []
        for offsets, sizes in all_chunks:
            suffix = "_".join(str(o) for o in offsets)
            location = (
                f"{storage_path_prefix}_{suffix}" if suffix else storage_path_prefix
            )
            full.append(
                Shard(
                    offsets=list(offsets),
                    sizes=list(sizes),
                    array=local_by_loc.get(location)
                    or ArrayEntry(
                        location=location,
                        serializer=Serializer.BUFFER_PROTOCOL.value,
                        dtype=dtype_str,
                        shape=list(sizes),
                        replicated=replicated,
                    ),
                )
            )
        entry = ChunkedArrayEntry(
            dtype=dtype_str,
            shape=list(arr.shape),
            chunks=full,
            replicated=replicated,
        )
    return entry, write_reqs


def _partition_write_units(
    flattened: Dict[str, Any],
    replicated_paths: Set[str],
    rank: int,
    world_size: int,
) -> Tuple[Dict[str, List[Tuple[List[int], List[int]]]], Set[str]]:
    """Deterministic greedy size-balanced partition of replicated write units
    (array chunks and objects) across ranks.

    The reference computes this on rank 0 and scatters the plan
    (snapshot.py:860-899); here the inputs are verified-identical on every
    rank, so each rank computes the same partition locally — no communication.

    Returns ({logical_path: chunks_this_rank_writes}, {object paths this rank
    writes}).
    """
    chunk_assignments: Dict[str, List[Tuple[List[int], List[int]]]] = {}
    owned_objects: Set[str] = set()

    pool: List[Tuple[int, str, Optional[Tuple[List[int], List[int]]]]] = []
    for logical_path in sorted(flattened.keys()):
        obj = flattened[logical_path]
        if is_partitionable_array(obj):
            dtype_str = dtype_to_string(obj.dtype)
            chunks = ChunkedArrayIOPreparer.chunk_shards(
                tuple(obj.shape), dtype_str
            )
            if logical_path in replicated_paths and world_size > 1:
                chunk_assignments.setdefault(logical_path, [])
                for offsets, sizes in chunks:
                    nbytes = array_size_bytes(sizes, dtype_str)
                    pool.append((nbytes, logical_path, (offsets, sizes)))
            else:
                chunk_assignments[logical_path] = chunks
        elif (
            logical_path in replicated_paths
            and world_size > 1
            and not PrimitivePreparer.should_inline(obj)
            and not is_sharded_jax_array(obj)
        ):
            pool.append((1024, logical_path, None))
        elif not PrimitivePreparer.should_inline(obj) and not is_sharded_jax_array(obj):
            owned_objects.add(logical_path)

    # Greedy: largest first, to the least-loaded rank; all ties broken
    # deterministically so every rank computes the identical plan. The
    # assignment itself lives in fanout.greedy_size_balanced — SHARED
    # with the restore-side cooperative fan-out so save striping and
    # restore partitioning can never skew (bit-identical to the
    # historical inline loop for the same input).
    from .fanout import greedy_size_balanced

    pool.sort(key=lambda t: (-t[0], t[1], t[2] or ([], [])))
    owners = greedy_size_balanced([t[0] for t in pool], world_size)
    for (nbytes, logical_path, chunk), target in zip(pool, owners):
        if target == rank:
            if chunk is None:
                owned_objects.add(logical_path)
            else:
                chunk_assignments[logical_path].append(chunk)
    return chunk_assignments, owned_objects


class PendingSnapshot:
    """Handle to an in-flight async_take (reference: snapshot.py:989-1076).

    The background thread drains storage I/O, synchronizes all ranks through
    a store-based LinearBarrier, and lets rank 0 commit the metadata between
    the barrier phases. On any rank's failure, the error propagates through
    the barrier and **no rank commits** — all-or-nothing. The thread uses
    only the KV store for coordination (safe off the main thread by design).
    """

    def __init__(
        self,
        path: str,
        pending_io_work: PendingIOWork,
        pg_wrapper: PGWrapper,
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        storage_options: Optional[Dict[str, Any]] = None,
        barrier_timeout_s: float = DEFAULT_BARRIER_TIMEOUT_S,
        timer: Optional[_PhaseTimer] = None,
        recorder: Optional["telemetry.OpRecorder"] = None,
        heartbeat: Optional[Any] = None,
        watchdog: Optional[Any] = None,
        admission: Optional[Any] = None,
    ) -> None:
        self.path = path
        self.pg = pg_wrapper.pg
        self._timer = timer
        self._recorder = recorder
        self._heartbeat = heartbeat
        self._watchdog = watchdog
        self._admission = admission
        self._storage_options = storage_options
        self._done_event = threading.Event()
        self._exc: Optional[BaseException] = None
        self._snapshot: Optional[Snapshot] = None

        # Agree on a barrier id on the caller thread (store op), then hand
        # everything to the background thread.
        barrier_id = pg_wrapper.broadcast_object(
            f"commit-{uuid.uuid4().hex}" if pg_wrapper.get_rank() == 0 else None,
            src=0,
        )
        self._thread = threading.Thread(
            target=self._complete_snapshot,
            kwargs=dict(
                pending_io_work=pending_io_work,
                pg_wrapper=pg_wrapper,
                metadata=metadata,
                storage=storage,
                event_loop=event_loop,
                barrier_id=barrier_id,
                barrier_timeout_s=barrier_timeout_s,
            ),
            name="tpusnapshot-commit",
            daemon=True,
        )
        self._thread.start()

    def _complete_snapshot(
        self,
        pending_io_work: PendingIOWork,
        pg_wrapper: PGWrapper,
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        barrier_id: str,
        barrier_timeout_s: float,
    ) -> None:
        barrier = None
        try:
            # The commit fence was planted at plan time, before
            # async_take returned (NOT here: a plant on this thread would
            # be self-satisfying after a fenced-GC reclaim — see the
            # plant site in _take_impl). The commit point below only
            # re-checks the token.
            if pg_wrapper.get_world_size() > 1:
                # Own store connection: the main thread keeps using the
                # primary. Inside the try: a dead store host (clone raises
                # StoreConnectionLostError) must reach wait() as _exc, not
                # kill this thread with _done never set.
                store = pg_wrapper.pg.store.clone()
                # Nested under the wrapper's namespace so the barrier keys
                # are reclaimed together with it once every rank retires.
                barrier = LinearBarrier(
                    prefix=f"{pg_wrapper._namespace()}/commit/{barrier_id}",
                    store=store,
                    rank=pg_wrapper.get_rank(),
                    world_size=pg_wrapper.get_world_size(),
                )
            pending_io_work.sync_complete(event_loop)
            _drain_background_storage(storage, event_loop)
            if self._timer is not None:
                self._timer.mark("io_drain")
            if barrier is not None:
                barrier.arrive(timeout=barrier_timeout_s)
            if pg_wrapper.get_rank() == 0:
                Snapshot._write_snapshot_metadata(metadata, storage, event_loop)
            if barrier is not None:
                barrier.depart(timeout=barrier_timeout_s)
            if self._timer is not None:
                self._timer.mark("commit")
                self._timer.log()
            if self._recorder is not None:
                # Post-commit, on the background thread: the KV-store
                # collectives are thread-safe by design, and this wrapper
                # runs no further collectives after async_take returned.
                Snapshot._publish_telemetry(
                    "take", self._recorder, self._timer, pg_wrapper,
                    storage, event_loop, persist=True, path=self.path,
                )
            snapshot = Snapshot(self.path, self.pg, self._storage_options)
            snapshot._metadata = metadata
            self._snapshot = snapshot
        except BaseException as e:  # noqa: B036
            if barrier is not None:
                try:
                    barrier.report_error(e)
                except Exception:
                    pass
            self._exc = e
            # Background-thread aborts are the flight recorder's hardest
            # case — no caller stack survives; the dump is the artifact.
            telemetry.flightrec.record(
                "op.abort", op="take", error=repr(e), kind=type(e).__name__,
                gen=getattr(metadata, "_commit_gen", None),
            )
            telemetry.flightrec.dump(
                self.path, pg_wrapper.get_rank(),
                f"async commit aborted: {type(e).__name__}",
            )
            if self._recorder is not None:
                self._recorder.abandon()
            logger.exception("async_take failed; snapshot was not committed.")
        finally:
            if self._heartbeat is not None:
                try:
                    self._heartbeat.stop()
                except Exception:  # noqa: BLE001
                    pass
            if self._watchdog is not None:
                try:
                    self._watchdog.stop()
                except Exception:  # noqa: BLE001
                    pass
            try:
                from .tenancy import admission as _tadm

                _tadm.disarm(storage, self._admission)
            except Exception:  # noqa: BLE001
                pass
            try:
                # Final act on this rank: ack namespace retirement so rank 0
                # can reclaim this operation's store keys later.
                pg_wrapper.retire()
            except Exception:
                pass
            try:
                storage.sync_close(event_loop)
            except Exception as e:
                # A close-time failure must reach wait(): mirrored storage
                # commits the mirror tier here, and silently dropping its
                # error would report a durable copy that doesn't exist.
                if self._exc is None:
                    self._exc = e
                logger.exception("storage close failed after commit.")
            try:
                event_loop.close()
            except Exception:
                pass
            self._done_event.set()

    def wait(self) -> Snapshot:
        """Block until the snapshot is committed; re-raises any failure
        (reference: snapshot.py:1066-1073)."""
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        assert self._snapshot is not None
        return self._snapshot

    def done(self) -> bool:
        return self._done_event.is_set()


class PendingRestore:
    """Handle over a restore running on a background thread.

    ``wait()`` joins and re-raises any failure; until then the app state
    being restored must not be touched (see ``Snapshot.async_restore``).
    """

    def __init__(
        self,
        snapshot: Snapshot,
        app_state: AppState,
        pg_wrapper: PGWrapper,
        device_digests: Optional[bool] = None,
    ) -> None:
        self._exc: Optional[BaseException] = None
        self._done_event = threading.Event()
        # Lazy page-in session (pagein.py), when the restore's lazy
        # election engaged; surfaced by wait().
        self.pagein: "Optional[Any]" = None

        def run() -> None:
            try:
                self.pagein = snapshot._restore_impl(
                    app_state, pg_wrapper, device_digests=device_digests
                )
            except BaseException as e:  # noqa: B036
                self._exc = e
            finally:
                self._done_event.set()

        self._thread = threading.Thread(
            target=run, name="tsnap-async-restore", daemon=True
        )
        self._thread.start()

    def wait(self) -> "Optional[Any]":
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        return self.pagein

    def done(self) -> bool:
        return self._done_event.is_set()
