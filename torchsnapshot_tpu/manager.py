"""CheckpointManager: training-loop cadence + retention over Snapshot.

The layer a training loop actually wants (orbax's ``CheckpointManager``
is the ecosystem analogue; the reference has no equivalent): call
``save(step, app_state)`` every step and the manager decides when a
snapshot is due, names it, chains it incrementally against the previous
one, keeps the retention policy enforced, and exposes
``latest_step``/``restore`` for resume. It composes every Snapshot
feature — async saves, incremental dedup, compression, mirrored
two-tier storage — through plain constructor arguments::

    mgr = CheckpointManager(
        "fs:///ckpts",
        save_interval_steps=1000,
        keep_last=3,            # newest 3 survive
        keep_every=10_000,      # plus archival keeps at these steps
        async_save=True,        # block only for staging
        incremental=True,       # dedup against the previous snapshot
        compression="zstd",
        storage_options={"mirror_url": "gs://bucket/ckpts"},
    )
    for step in range(n_steps):
        ...
        mgr.save(step, app_state)     # no-op unless due
    mgr.wait()                        # drain a pending async save

    # on restart:
    step = mgr.latest_step()
    if step is not None:
        mgr.restore(app_state)

Semantics worth knowing:

- Snapshots live at ``<root>/step_<N:010d>`` (lexical sort == numeric).
- At most ONE async save is in flight; a due save first drains the
  previous pending one (its retention pass included).
- Retention runs on rank 0 after each commit, via
  :func:`~torchsnapshot_tpu.retention.plan_retention`: the newest
  ``keep_last`` and every ``keep_every`` multiple survive, PLUS any
  snapshot that is a (transitively, checksum-verified) required base of
  a survivor. Snapshots whose bases cannot be resolved are never
  deleted. Retention — and ``latest_step`` discovery — need a local
  filesystem root; on remote roots retention is skipped and resume
  needs an explicit ``step=``.
- ``device_digests=True`` (with ``incremental``) detects unchanged
  payloads ON DEVICE — the DtoH transfer is skipped too, not just the
  storage write (device_digest.py; opt-in trust model).
- ``incremental=True`` records digests on every save and chains each
  snapshot to the previous COMMITTED one; retention's base-closure
  keeps chains restorable (consolidate before archiving elsewhere).
- Retention governs the PRIMARY tier only: per-step mirror replicas
  accumulate as archival history (bound them with the ``prune`` CLI
  against the mirror root when it is scannable).
"""

from __future__ import annotations

import logging
import os
import re
from typing import Any, Dict, List, Optional

import numpy as np

from . import journal, tenancy
from .pg_wrapper import PGWrapper, ProcessGroup
from .preemption import PreemptionWatcher
from .snapshot import PendingSnapshot, Snapshot
from .stateful import AppState

logger = logging.getLogger(__name__)

# Only the manager's OWN naming (10-digit zero-padded) is discovered:
# accepting foreign step_<N> spellings would make latest_step() find
# snapshots that path_for()/retention then address under a different
# (padded) name — unreachable by restore and wrongly deletable.
_STEP_RE = re.compile(r"^step_(\d{10})$")


def _step_name(step: int) -> str:
    if step < 0:
        raise ValueError(f"step must be >= 0, got {step}")
    return f"step_{step:010d}"


class CheckpointManager:
    def __init__(
        self,
        root: str,
        *,
        save_interval_steps: int = 1,
        keep_last: Optional[int] = None,
        keep_every: Optional[int] = None,
        async_save: bool = False,
        incremental: bool = False,
        device_digests: Optional[bool] = None,
        compression: Optional[str] = None,
        save_dtype: Optional[Dict[str, str]] = None,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        pg: Optional[ProcessGroup] = None,
        preemption: Optional[PreemptionWatcher] = None,
        tenant: Optional[tenancy.Tenant] = None,
    ) -> None:
        if save_interval_steps < 1:
            raise ValueError("save_interval_steps must be >= 1")
        if keep_last is not None and keep_last < 1:
            raise ValueError("keep_last must be >= 1 (or None to keep all)")
        if keep_every is not None and keep_every < 1:
            raise ValueError("keep_every must be >= 1 (or None)")
        # Tenancy: an explicit tenant wins, else the ambient
        # TORCHSNAPSHOT_TPU_TENANT one (the disabled path's single env
        # check). With a tenant, this manager's whole world — steps,
        # retention, fsck scope, coordination keys — lives under the
        # tenant's namespace; ``root`` stays the SHARED bucket root
        # (the cross-tenant payload pool lives beside the tenant trees).
        self._tenant = tenant if tenant is not None else tenancy.tenant_from_env()
        self._shared_root = root
        if self._tenant is not None:
            root = tenancy.tenant_root(root, self._tenant)
        self.root = root
        self._retention_skip_warned = False
        self.save_interval_steps = save_interval_steps
        self.keep_last = keep_last
        self.keep_every = keep_every
        self.async_save = async_save
        self.incremental = incremental
        # Resolved ONCE, here: an explicit option wins, else the
        # TORCHSNAPSHOT_TPU_DEVICE_DIGESTS env fallback is read now and
        # the resolved bool is passed through to every take/restore — so
        # warmup (pool sizing, fingerprint jit pre-compiles) and the
        # saves it warms can never disagree if the env var changes
        # between the two calls.
        if device_digests is None:
            from .device_digest import enabled_by_env

            device_digests = enabled_by_env()
        self.device_digests = bool(device_digests)
        self.compression = compression
        self.save_dtype = save_dtype
        self.replicated = replicated
        self.storage_options = storage_options
        # No explicit group: bootstrap the default one from the env
        # (TORCHSNAPSHOT_TPU_STORE_ADDR + _STORE_REPLICAS) so a manager
        # constructed in a launcher-less deployment still coordinates —
        # and, with replicas configured, still survives a store-leader
        # death mid-save. None (single-process) when the env is unset.
        from .pg_wrapper import ensure_default_pg

        self.pg = pg if pg is not None else ensure_default_pg()
        self.preemption = preemption
        self._pending: Optional[PendingSnapshot] = None
        self._pending_step: Optional[int] = None
        self._last_committed: Optional[int] = self.latest_step()
        # Delta journal bound to the last committed base snapshot (armed by
        # each save when TORCHSNAPSHOT_TPU_JOURNAL=1; see journal_step).
        self._journal: Optional["journal.DeltaJournal"] = None
        # Lazy page-in session of the most recent restore (pagein.py),
        # None when the lazy election did not engage.
        self.last_pagein: Optional[Any] = None
        # Rolling-update push cursor (distrib.py): per live replica, the
        # last journal epoch already shipped — keeps repeat pushes
        # incremental. Receivers dedup regardless, so losing this only
        # costs bytes, never correctness. Reset with each journal seed
        # (a new base step invalidates old epochs).
        self._push_cursor: Dict[str, int] = {}
        # Tenant-registry row published lazily at the first save (the
        # store may not be reachable at construction time).
        self._tenant_registered = False
        # Async geo-replication shipper (georep.py): a rank-0 background
        # daemon armed by TORCHSNAPSHOT_TPU_GEOREP — the one env check on
        # the disabled path. Committed bases enqueue from _committed;
        # committed journal epochs (emergency flushes included) wake it
        # through the journal commit-hook registry; a preemption's
        # consume() runs the bounded drain inside the grace window.
        self._georep: Optional[Any] = None
        self._georep_hook: Optional[Any] = None
        from . import georep

        georep_url = georep.remote_url()
        if georep_url is not None and PGWrapper(self.pg).get_rank() == 0:
            rep = georep.GeoReplicator(
                georep_url, storage_options=self.storage_options
            )
            self._georep = rep

            def _georep_on_epoch(
                base_dir: str, base_step: int, _epoch: int
            ) -> None:
                rep.enqueue(base_dir, base_step)

            self._georep_hook = _georep_on_epoch
            journal.register_commit_hook(_georep_on_epoch)
            if self.preemption is not None:
                self.preemption.add_consume_hook(rep.drain)

    def _register_tenant(self) -> None:
        """Publish this tenant's registry row (rank 0, once, best
        effort) on the GLOBAL store plane — arbitration readers
        (admission, operators) need to see every tenant."""
        if self._tenant is None or self._tenant_registered:
            return
        self._tenant_registered = True
        if PGWrapper(self.pg).get_rank() != 0:
            return
        try:
            from . import distrib
            from .tenancy import registry as tenant_registry

            store = distrib._registry_store_raw(PGWrapper(self.pg))
            if store is not None:
                tenant_registry.register(store, self._tenant)
        except Exception:  # noqa: BLE001 - registry is advisory
            logger.debug("tenant registration skipped", exc_info=True)

    def close(self) -> None:
        """Release lifecycle state: wait out a pending async save, drain
        the geo-replication backlog (bounded by
        TORCHSNAPSHOT_TPU_GEOREP_DRAIN_S), and plant this tenant's
        registry death notice (ghost key) so readers stop counting it
        live."""
        self.wait()
        if self._georep is not None:
            if self._georep_hook is not None:
                journal.unregister_commit_hook(self._georep_hook)
                self._georep_hook = None
            if not self._georep.close():
                logger.warning(
                    "geo-replication drain timed out at close; remote tier "
                    "%s is behind (last error: %s)",
                    self._georep.remote_root,
                    self._georep.last_error,
                )
            self._georep = None
        if self._tenant is not None and self._tenant_registered:
            if PGWrapper(self.pg).get_rank() == 0:
                try:
                    from . import distrib
                    from .tenancy import registry as tenant_registry

                    store = distrib._registry_store_raw(PGWrapper(self.pg))
                    if store is not None:
                        tenant_registry.deregister(store, self._tenant.id)
                except Exception:  # noqa: BLE001
                    logger.debug("tenant deregister skipped", exc_info=True)
            self._tenant_registered = False

    # ----------------------------------------------------------- paths

    def _local_dir(self) -> Optional[str]:
        from .storage_plugin import local_fs_root

        return local_fs_root(self.root)

    def _shared_dir(self) -> Optional[str]:
        """Local fs root of the SHARED (pre-tenant) bucket root — where
        the cross-tenant payload pool lives. None without a tenant."""
        if self._tenant is None:
            return None
        from .storage_plugin import local_fs_root

        return local_fs_root(self._shared_root)

    @staticmethod
    def _step_like(name: str) -> bool:
        """Quota retention may only demote the manager's own steps —
        foreign names in the directory are never eviction victims."""
        return bool(_STEP_RE.match(name))

    def _activated(self):
        """Context manager making this manager's tenant ambient for the
        calling thread — key-construction sites (heartbeat prefixes,
        seed/journal store acquisition) resolve the namespace there."""
        import contextlib

        if self._tenant is None:
            return contextlib.nullcontext()
        return tenancy.activated(self._tenant)

    def path_for(self, step: int) -> str:
        sep = "" if self.root.endswith("/") else "/"
        return f"{self.root}{sep}{_step_name(step)}"

    def _options_for(self, step: int) -> Optional[Dict[str, Any]]:
        """Per-save storage options: a configured ``mirror_url`` is the
        mirror ROOT — each step mirrors into its own subdirectory, or
        every step's replica would overwrite the previous one's payloads
        and metadata in place."""
        if not self.storage_options or not self.storage_options.get("mirror_url"):
            return self.storage_options
        opts = dict(self.storage_options)
        mirror_root = opts["mirror_url"].rstrip("/")
        opts["mirror_url"] = f"{mirror_root}/{_step_name(step)}"
        return opts

    # ------------------------------------------------------- inventory

    def all_steps(self) -> List[int]:
        """Committed steps under a local root, ascending ([] for remote)."""
        dirpath = self._local_dir()
        if dirpath is None or not os.path.isdir(dirpath):
            return []
        steps = []
        for name in os.listdir(dirpath):
            m = _STEP_RE.match(name)
            if m and os.path.isfile(
                os.path.join(dirpath, name, ".snapshot_metadata")
            ):
                steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # ------------------------------------------------------------ save

    def warmup(self, app_state: AppState) -> int:
        """Pre-fault staging buffers for ``app_state`` so the first
        ``save`` blocks like a steady-state one (async saves especially:
        the cold caller-blocked interval is dominated by first-touch page
        faults in fresh staging slabs). Call once after building the app
        state; cheap to call again after shapes change. Returns bytes
        newly faulted.

        Under ``device_digests``, also pre-compiles the on-device
        fingerprint jits for every array shape in the state — the first
        digest-enabled save otherwise pays one XLA compile per distinct
        shape inside its blocking window.

        Pool pre-faulting is a no-op under ``incremental``,
        ``compression``, or ``device_digests``: those staging paths
        (dedup digesting, codec compression, fingerprint recording) never
        draw from the pool, so warming it would pin memory no save
        uses."""
        if self.device_digests:
            self._warmup_fingerprints(app_state)
        if self.incremental or self.compression or self.device_digests:
            return 0
        from .io_preparers.array import warmup_staging

        return warmup_staging(
            app_state,
            pg=self.pg,
            replicated=self.replicated,
            save_dtype=self.save_dtype,
        )

    def _warmup_fingerprints(self, app_state: AppState) -> None:
        """Compile fingerprint jits for every piece the save will hash
        (dispatch on the REAL device pieces; results discarded) — the
        first digest-enabled save otherwise pays one XLA compile per
        distinct shape inside its blocking window. Geometry comes from
        ``iter_staged_pieces`` (the shared write-partition walk), so
        save_dtype conversion, chunk boundaries, sharded owned-piece
        subdivision, and replicated striping all match the real save —
        and dispatching on the real pieces keys the jit cache with the
        exact device placements save-time fingerprinting will use (zeros
        on the default device would miss per-device entries on
        multi-device processes). Host numpy leaves are skipped: the save
        never fingerprints them (``_device_dedup_candidate`` requires a
        jax array)."""
        from .device_digest import _dispatch
        from .io_preparers.array import _is_jax_array, iter_staged_pieces
        from .serialization import string_to_dtype

        pendings = []
        last_piece = None
        for _, dtype_str, _, get_piece in iter_staged_pieces(
            app_state,
            pg=self.pg,
            replicated=self.replicated,
            save_dtype=self.save_dtype,
        ):
            if get_piece is None:
                continue
            piece = get_piece()
            if not _is_jax_array(piece):
                continue
            from .io_preparers.array import dtype_to_string

            if dtype_to_string(piece.dtype) != dtype_str:
                # save_dtype conversion happens on device before staging;
                # compile for the converted aval (transient cast copy).
                piece = piece.astype(string_to_dtype(dtype_str))
            pending = _dispatch(piece)
            if pending is not None:
                pendings.append(pending)
                last_piece = piece
        # Record achieved hash throughput for the I/O governor: the
        # restore-side preverify gate compares it against measured
        # storage read bandwidth to decide whether zero-byte
        # verification is cheaper than re-reading. Timed on a SECOND
        # dispatch of an already-compiled piece — timing the loop above
        # would fold XLA compiles (seconds per distinct shape) into the
        # rate, understating steady-state hashing by orders of magnitude
        # and biasing the gate toward expensive re-reads.
        if pendings:
            import jax

            jax.block_until_ready(pendings)
            from . import telemetry

            nbytes = int(
                np.dtype(last_piece.dtype).itemsize
                * int(np.prod(last_piece.shape, dtype=np.int64))
            )
            t0 = telemetry.monotonic()
            jax.block_until_ready(_dispatch(last_piece))
            # Published on the bus; the governor's rate listener feeds
            # its hash-vs-read preverify economics from there.
            telemetry.record_rate(
                "hash", None, nbytes, telemetry.monotonic() - t0
            )

    def should_save(self, step: int) -> bool:
        return step % self.save_interval_steps == 0

    def _already_committed(self, step: int) -> bool:
        """Collectively-consistent "step already has a committed snapshot".

        ``.snapshot_metadata`` is written by rank 0 only, so the on-disk
        scan is meaningful only there: on a non-shared per-rank root a
        rank-local check would let rank 0 skip while other ranks enter the
        collective ``Snapshot.take`` and hang. Rank 0 decides; the
        decision is broadcast so every rank takes the same branch. On
        remote roots there is nothing to scan — only the in-memory
        ``_last_committed`` (seeded by this manager's own saves/restores)
        guards against re-saving, so a freshly-constructed manager on a
        remote root cannot detect a prior run's committed step.
        """
        def local_opinion() -> bool:
            # _last_committed is seeded from a rank-local disk scan at
            # construction, so even this fast path can diverge across
            # ranks — it must stay inside the broadcast.
            return (
                step == self._last_committed
                or (self._local_dir() is not None and step in self.all_steps())
            )

        pg = PGWrapper(self.pg)
        if pg.get_world_size() == 1:
            return local_opinion()
        committed = local_opinion() if pg.get_rank() == 0 else None
        try:
            return bool(pg.broadcast_object(committed, src=0))
        finally:
            pg.retire()  # release the handshake/bcast store keys

    def save(self, step: int, app_state: AppState, *, force: bool = False) -> bool:
        with self._activated():
            return self._save_impl(step, app_state, force=force)

    def _save_impl(
        self, step: int, app_state: AppState, *, force: bool = False
    ) -> bool:
        """Snapshot ``app_state`` if ``step`` is due (or ``force``).

        Returns True when a save was started/completed. Blocks only for
        staging when ``async_save`` (draining any previous pending save
        first — one in flight at a time).

        With a ``preemption`` watcher configured, every call also makes
        the COLLECTIVE should-we-emergency-save decision (so ``save``
        must be called at the same steps on all ranks — it already must
        be, being a collective itself when due): on a preemption the
        current step saves regardless of cadence, SYNCHRONOUSLY (the
        process is about to die; an async save's background commit could
        be killed mid-write), and the watcher is consumed so the rest of
        the grace-window loop doesn't re-save every step."""
        emergency = False
        if self.preemption is not None and not self.preemption.consumed:
            # The decision rides THIS manager's group: a watcher gathered
            # over a different/absent group could split-brain (the
            # signaled rank alone entering the multi-rank take).
            if self.preemption.should_save(pg=self.pg):
                emergency = True
                logger.warning(
                    "preemption flagged: emergency snapshot at step %d", step
                )
        if emergency and self._journal_emergency_flush(app_state):
            # A committed journal epoch IS a recoverable state: flushing
            # the open journal (milliseconds) replaces the synchronous
            # full emergency save inside the grace window. Collectively
            # consistent: every guard below is rank-consistent and
            # append_epoch raises on all ranks or none.
            self.preemption.consume()
            logger.warning(
                "preemption flagged: journal epoch flushed at step %d "
                "(emergency full save skipped)",
                step,
            )
            return False
        if not force and not emergency and not self.should_save(step):
            return False
        self.wait()  # at most one pending; also runs its retention
        if self._already_committed(step):
            # Resume loops re-run the restored step (README recipe); a
            # re-save would overwrite the committed snapshot in place —
            # non-atomically, and under incremental=True with ITSELF as
            # the dedup base. Never overwrite a committed step.
            if emergency:
                # The committed snapshot of THIS step (a previous run's)
                # already provides a resume point; only the current
                # partial re-run is lost, which eviction makes
                # inevitable. The branch is collectively consistent (the
                # committed check is broadcast), so every rank consumes
                # together and the loop's consumed-break stays in step.
                self.preemption.consume()
                logger.warning(
                    "preemption at already-committed step %d: existing "
                    "snapshot is the resume point; nothing re-saved",
                    step,
                )
                return False
            logger.info("step %d already has a committed snapshot; skipping", step)
            return False

        self._gc_orphaned_partials(step)
        # Order the GC BEFORE any peer's payload writes: rank 0 releases
        # the peers only after its rmtree pass. Without this, the only
        # ordering collective is the hostname all-gather inside
        # get_process_memory_budget_bytes — which the MEMORY_BUDGET env
        # var short-circuits, letting a peer land payloads in the step
        # dir while rank 0's GC still sees it as uncommitted rubble and
        # deletes them (a committed-but-unrestorable snapshot).
        pg = PGWrapper(self.pg)
        if pg.get_world_size() > 1:
            try:
                pg.broadcast_object("gc-done" if pg.get_rank() == 0 else None, src=0)
            finally:
                pg.retire()
        if self._tenant is not None:
            self._register_tenant()
            # The quota gate — BEFORE any payload I/O, so an over-quota
            # save is a clean error, never a torn partial. Collective
            # (rank 0 decides, everyone raises together).
            from .tenancy import quota as _quota

            _quota.ensure_capacity(self)
        path = self.path_for(step)
        base = (
            self.path_for(self._last_committed)
            if self.incremental and self._last_committed is not None
            else None
        )
        use_async = self.async_save and not emergency
        kwargs: Dict[str, Any] = dict(
            pg=self.pg,
            replicated=self.replicated,
            storage_options=self._options_for(step),
            incremental_base=base,
            record_digests=self.incremental,
            device_digests=self.device_digests,
            compression=self.compression,
            save_dtype=self.save_dtype,
        )
        from . import telemetry

        # Queued, not an event: the take's OpRecorder begins inside
        # Snapshot.take, AFTER this point — an instant event emitted here
        # would precede the op mark and never reach the persisted
        # summary/trace. annotate_next_op folds the manager context into
        # the take's own summary instead.
        telemetry.annotate_next_op(
            step=step,
            mode="emergency" if emergency else ("async" if use_async else "sync"),
            incremental_base=base,
        )
        # The live health plane's step field (watch renders it); survives
        # the publisher's per-op reset like the annotation above.
        telemetry.health.update(step=step)
        if use_async:
            self._pending = Snapshot.async_take(path, app_state, **kwargs)
            self._pending_step = step
        else:
            Snapshot.take(path, app_state, **kwargs)
            self._committed(step)
        # Arm the delta journal against the state AS SAVED — capturing
        # lazily at the first journal_step would silently lose any
        # mutation between here and there. For async saves the journal
        # stays un-bound (journal_step checks) until wait() commits.
        self._journal_seed(step, app_state)
        if emergency:
            self.preemption.consume()
            logger.warning("emergency snapshot committed at step %d", step)
        return True

    def _gc_orphaned_partials(self, step: int) -> None:
        """Fenced GC: reclaim partial step directories a crashed writer
        left behind (payloads, no ``.snapshot_metadata``) before taking
        ``step``. Without this, every SIGKILLed save leaks a partial tree
        that resume discovery must skip forever.

        Safety comes from the commit-fence protocol, not from timing:

        - only step directories ``<= step`` are touched — under the
          manager's ordered-save contract nothing older can still be
          in flight on a healthy world (a pending async save was drained
          by ``save`` before this runs);
        - a *resurrected* straggler of a reclaimed directory (the one
          case ordering cannot exclude: an async commit thread from a
          previous incarnation of this world) cannot commit into the
          rubble — its generation fence is gone, so its commit aborts
          with :class:`~torchsnapshot_tpu.snapshot.StaleCommitError`
          (see snapshot.SNAPSHOT_FENCE_FNAME). The residual window is
          one storage round trip — a straggler suspended between its
          passing fence read and its metadata write; see
          ``Snapshot._write_snapshot_metadata`` — and a splice through
          it is fsck-detectable, never silently restorable.

        The mirror tier is scanned too: each step mirrors into its own
        subdirectory of ``mirror_url`` with its own metadata commit, so
        a crashed mirrored save leaves a second partial tree there. The
        fence argument covers it — a straggler's mirror metadata flush
        happens only after its primary commit check passes, which the
        reclaimed fence prevents. A mirror step dir is reclaimed ONLY
        when the primary step is also uncommitted: the mirror's metadata
        commit is deferred (and suppressed after any mirror write
        failure), so a committed primary can legitimately own a
        metadata-less mirror tree — that is degraded failover data for
        the current resume point, not rubble.

        Rank 0 only (the commit barrier already serializes saves), local
        filesystem roots only (remote roots have no cheap scan — fsck
        covers them on demand)."""
        if PGWrapper(self.pg).get_rank() != 0:
            return
        from .storage_plugin import local_fs_root

        primary_dir = self._local_dir()
        roots = [primary_dir]
        mirror_root = (self.storage_options or {}).get("mirror_url")
        if mirror_root and primary_dir is not None:
            # Without a scannable primary we cannot tell committed steps
            # from rubble — leave the mirror tier alone.
            roots.append(local_fs_root(mirror_root.rstrip("/")))
        import shutil

        for dirpath in roots:
            if dirpath is None or not os.path.isdir(dirpath):
                continue
            for name in sorted(os.listdir(dirpath)):
                m = _STEP_RE.match(name)
                if not m or int(m.group(1)) > step:
                    continue
                partial = os.path.join(dirpath, name)
                if not os.path.isdir(partial):
                    continue
                if os.path.exists(
                    os.path.join(partial, ".snapshot_metadata")
                ):
                    continue
                if dirpath is not primary_dir and os.path.exists(
                    os.path.join(primary_dir, name, ".snapshot_metadata")
                ):
                    # Committed primary: this mirror tree is live (if
                    # incomplete) failover redundancy, never reclaimed.
                    continue
                logger.warning(
                    "reclaiming partial snapshot directory %s (no committed "
                    "metadata; a previous writer died mid-save)",
                    partial,
                )
                shutil.rmtree(partial, ignore_errors=True)

    def wait(self) -> None:
        """Drain a pending async save (no-op otherwise); re-raises its
        failure. Runs the retention pass for the committed snapshot."""
        if self._pending is None:
            return
        pending, step = self._pending, self._pending_step
        self._pending = None
        self._pending_step = None
        pending.wait()
        assert step is not None
        self._committed(step)

    def _committed(self, step: int) -> None:
        self._last_committed = step
        if self._georep is not None:
            self._georep.enqueue(self.path_for(step), step)
        self._pool_sweep(step)
        self._apply_retention()

    def _pool_sweep(self, step: int) -> None:
        """Post-commit cross-tenant dedup: move this step's eligible
        payloads into the shared content-addressed pool (tenancy.pool)
        and repoint its manifest. Rank 0, local roots, tenants only;
        best-effort — a sweep failure degrades dedup, never the commit."""
        if self._tenant is None:
            return
        if PGWrapper(self.pg).get_rank() != 0:
            return
        shared = self._shared_dir()
        dirpath = self._local_dir()
        if shared is None or dirpath is None:
            return
        from . import telemetry
        from .tenancy import pool

        try:
            released, n = pool.sweep_step(
                shared, self._tenant.id, os.path.join(dirpath, _step_name(step))
            )
        except Exception:  # noqa: BLE001
            logger.warning("pool sweep failed for step %d", step, exc_info=True)
            return
        if n:
            telemetry.counter_add("pool_bytes_released", released)
            logger.info(
                "pool sweep: step %d shares %d payload(s) (%d bytes "
                "released) via %s",
                step,
                n,
                released,
                pool.pool_root(shared),
            )

    # --------------------------------------------------- delta journal

    def _journal_seed(self, step: int, app_state: AppState) -> None:
        """Bind a fresh journal to the snapshot of ``step`` and fingerprint
        the state as saved (TORCHSNAPSHOT_TPU_JOURNAL=1 only)."""
        self._journal = None
        if not journal.enabled_by_env():
            return
        from .storage_plugin import local_fs_root

        local = local_fs_root(self.path_for(step))
        if local is None:
            logger.warning(
                "delta journaling needs a shared local filesystem root; "
                "%s is remote — journaling disabled",
                self.root,
            )
            return
        j = journal.DeltaJournal(
            local, base_step=step, rank=PGWrapper(self.pg).get_rank()
        )
        j.capture_baseline(app_state)
        self._journal = j
        self._push_cursor = {}

    def _journal_ready(self) -> bool:
        return (
            self._journal is not None
            and self._journal.armed
            and self._pending is None
            and self._journal.base_step == self._last_committed
        )

    def journal_step(self, step: int, app_state: AppState) -> bool:
        with self._activated():
            return self._journal_step_impl(step, app_state)

    def _journal_step_impl(self, step: int, app_state: AppState) -> bool:
        """Append a delta journal epoch for the leaves that changed since
        the last committed state (base snapshot or previous epoch).

        Call between cadence saves; sub-second where a full save is
        minutes. Returns True when state became durable at this step —
        an epoch committed, or a journal bound converted the call into a
        forced full save. Returns False when journaling is disabled, a
        base snapshot has not committed yet (async in flight included),
        or the root is remote. Collective, like ``save``.
        """
        if not journal.enabled_by_env() or not self._journal_ready():
            return False
        pg = PGWrapper(self.pg)
        try:
            n = self._journal.append_epoch(app_state, pg_wrapper=pg)
        except journal.JournalLimitError as e:
            logger.info(
                "journal bound reached (%s); taking a full snapshot at "
                "step %d instead",
                e,
                step,
            )
            return self.save(step, app_state, force=True)
        finally:
            pg.retire()
        logger.debug(
            "journal epoch %d committed (%d record(s)) at step %d",
            self._journal.epoch,
            n,
            step,
        )
        from . import distrib

        if distrib.update_push_enabled():
            try:
                self.push_update()
            except Exception:
                # The push is best-effort by contract; durability was
                # decided by the epoch commit above.
                logger.warning("rolling-update push failed", exc_info=True)
        return True

    def push_update(self) -> Dict[str, Any]:
        with self._activated():
            return self._push_update_impl()

    def _push_update_impl(self) -> Dict[str, Any]:
        """Ship committed journal epochs to live replicas registered as
        holding the current base step (distrib.UpdateReceiver) — a
        rolling update that moves ≈ the committed dirty set instead of
        the full snapshot. Incremental across calls (per-replica epoch
        cursor); receivers apply each (gen, epoch) exactly once, so
        retries and overlapping pushers are safe. Best-effort: a replica
        that misses a push converges through its next restore's replay.

        Returns ``{"replicas", "epochs", "bytes", "nacks"}`` (all zero
        when the journal is unarmed or no registry store is reachable).
        Runs with ``TORCHSNAPSHOT_TPU_UPDATE_PUSH=1`` after every
        ``journal_step`` automatically; callable any time regardless.
        """
        from . import distrib

        empty = {"replicas": 0, "epochs": 0, "bytes": 0, "nacks": 0}
        j = self._journal
        if j is None or not j.armed:
            return empty
        pg = PGWrapper(self.pg)
        try:
            store = distrib._registry_store(pg)
        finally:
            pg.retire()
        if store is None:
            return empty
        try:
            return distrib.push_committed_epochs(
                j.dir, j.base_step, store, cursor=self._push_cursor
            )
        finally:
            try:
                store.close()
            except Exception:
                pass

    def _journal_emergency_flush(self, app_state: AppState) -> bool:
        """On preemption, flush the open journal as one final epoch instead
        of a synchronous full save. Falls back (returns False) when the
        journal is not armed or the flush fails — the caller then takes
        the full emergency save as before."""
        if not self._journal_ready():
            return False
        pg = PGWrapper(self.pg)
        try:
            self._journal.append_epoch(app_state, pg_wrapper=pg)
            return True
        except Exception as e:
            logger.warning(
                "preemption journal flush failed (%s); falling back to a "
                "full emergency save",
                e,
            )
            return False
        finally:
            pg.retire()

    # ------------------------------------------------------- retention

    def _keep_names(self, names: List[str]) -> set:
        """The keep policy, evaluated on plan_retention's own scan."""
        steps = sorted(
            int(m.group(1)) for m in map(_STEP_RE.match, names) if m
        )
        keep = set(steps[-self.keep_last:]) if self.keep_last else set(steps)
        if self.keep_every is not None:
            keep.update(s for s in steps if s % self.keep_every == 0)
        kept_names = {_step_name(s) for s in keep}
        # Foreign (non-manager-named) snapshots in the directory are not
        # this manager's to delete.
        kept_names.update(n for n in names if not _STEP_RE.match(n))
        return kept_names

    def _apply_retention(self) -> None:
        # keep_every without keep_last prunes nothing (every step is
        # kept); only keep_last bounds the set.
        if self.keep_last is None:
            return
        if PGWrapper(self.pg).get_rank() != 0:
            return  # commit already barriered; rank 0 owns deletion
        dirpath = self._local_dir()
        if dirpath is None:
            # Loud, not silent: an operator who configured keep_last on
            # an s3/gcs root believes retention is bounding their spend.
            # One warning per manager + a counter every skip, so both
            # logs and fleet telemetry carry the truth. (A QUOTA on a
            # remote root goes further and raises — see tenancy.quota.)
            from . import telemetry

            if not self._retention_skip_warned:
                self._retention_skip_warned = True
                logger.warning(
                    "retention skipped: root %s is not a local filesystem "
                    "— keep_last/keep_every cannot reclaim there; bound "
                    "the remote tier with the `prune` CLI or lifecycle "
                    "rules",
                    self.root,
                )
            telemetry.counter_add("retention_skipped", 1)
            return
        from .retention import apply_retention, plan_retention

        plan = plan_retention(dirpath, self._keep_names)
        if plan.unresolved:
            logger.warning(
                "retention: kept snapshot(s) under %s reference base(s) "
                "outside this directory (%s); nothing unsafe is deleted",
                dirpath,
                ", ".join(sorted(plan.unresolved)),
            )
        if plan.doomed and self._tenant is not None:
            shared = self._shared_dir()
            if shared is not None:
                from .tenancy import pool

                pool.release_steps(shared, self._tenant.id, plan.doomed)
        n = apply_retention(dirpath, plan)
        if n:
            logger.info(
                "retention: deleted %d snapshot(s) under %s (kept %d + %d "
                "required base(s))",
                n,
                dirpath,
                len(plan.keep),
                len(plan.spared),
            )

    # --------------------------------------------------------- restore

    def restore(self, app_state: AppState, step: Optional[int] = None) -> int:
        with self._activated():
            return self._restore_impl(app_state, step)

    def _restore_impl(
        self, app_state: AppState, step: Optional[int] = None
    ) -> int:
        """Restore ``app_state`` from ``step`` (default: latest). Returns
        the step restored from. The manager's ``device_digests`` option
        applies here too: destinations already holding a payload's
        content skip the read (see Snapshot.restore)."""
        self.wait()
        if step is None:
            step = self.latest_step()
            if step is None:
                raise RuntimeError(
                    f"no committed snapshots under {self.root} (remote "
                    "roots need an explicit step=)"
                )
        # Lazy page-in (TORCHSNAPSHOT_TPU_LAZY_RESTORE): when the lazy
        # election engages, the session keeps paging after this returns;
        # surfaced as ``self.last_pagein`` so callers can fault/wait.
        self.last_pagein = Snapshot(
            self.path_for(step), pg=self.pg,
            storage_options=self._options_for(step),
        ).restore(app_state, device_digests=self.device_digests)
        # Seed the re-save guard: a resumed loop re-runs this step and
        # calls save(step) again; on remote roots this in-memory mark is
        # the ONLY thing preventing a non-atomic in-place overwrite of
        # the committed snapshot. Also makes the next incremental save
        # chain against the restored step. Deliberately NOT _committed():
        # restoring must not trigger a retention pass.
        self._last_committed = step
        # Re-arm the journal on the restored state (base + replay): new
        # epochs chain after the committed ones, so a resumed run keeps
        # journaling without waiting for the next full save. Records hold
        # full leaf values, so epochs appended against the replayed state
        # replay correctly on top of the same chain.
        if journal.enabled_by_env():
            from .storage_plugin import local_fs_root

            # capture_baseline READS every leaf: a lazy restore must be
            # fully resident first, or the baseline would capture proxy
            # objects instead of values. (Lazy normally stands down when
            # a journal exists; this covers a fresh journal being armed
            # over a journal-less snapshot restored lazily.)
            if self.last_pagein is not None:
                self.last_pagein.wait()
            local = local_fs_root(self.path_for(step))
            if local is not None:
                j = journal.DeltaJournal(
                    local, base_step=step, rank=PGWrapper(self.pg).get_rank()
                )
                j.epoch = len(
                    journal.committed_epochs(journal.read_epoch_metas(j.dir))
                )
                j.capture_baseline(app_state)
                self._journal = j
        return step
