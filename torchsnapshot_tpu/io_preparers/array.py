"""Plain-array write/read planning (reference: io_preparer.py:498-726).

The stager performs the TPU->host boundary crossing: for a jax.Array it
issues ``copy_to_host_async`` (true async DMA — no GIL workaround needed,
unlike the reference's CUDA thread-pool dance, io_preparer.py:513-523) and
materializes a zero-copy numpy view in an executor thread. numpy inputs are
viewed without copying at all.

The consumer fills a destination numpy view in-place (memory-efficient
restore, reference rationale: snapshot.py:693-700) and/or reports the value
through a callback; for jax.Array destinations the callback re-materializes
the array on device with its original sharding.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import ctypes
import functools
import logging
import os
import threading
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..compression import MIN_COMPRESS_BYTES
from ..io_types import BufferConsumer, BufferStager, BufferType, ReadReq, WriteReq
from ..manifest import ArrayEntry
from ..serialization import (
    Serializer,
    array_as_memoryview,
    array_from_buffer,
    array_size_bytes,
    dtype_to_string,
    string_to_dtype,
)


# When True (the default), stagers copy host-resident buffers so the staged
# bytes cannot alias caller memory — required by async_take's guarantee that
# mutations after it returns don't affect the snapshot (reference:
# snapshot.py:257-262). Snapshot.take blocks the caller until all I/O is
# drained, so it opts out: zero-copy staging halves host memory traffic.
# The flag is captured at stager construction (prepare time), so it is
# unaffected by which thread later runs the staging.
_copy_for_consistency: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "tsnap_copy_for_consistency", default=True
)

logger = logging.getLogger(__name__)

# One warning per process when a device-digest dedup match inherits a
# missing checksum from a base saved with checksums disabled: unlike the
# host dedup path there are no staged bytes to recompute one from, so
# restore-time verification coverage narrows for those entries.
_warned_none_checksum = False


@contextlib.contextmanager
def zero_copy_staging():
    """Within this context, prepared stagers may alias caller memory.

    Only safe when the caller blocks until storage I/O completes
    (synchronous ``Snapshot.take``)."""
    token = _copy_for_consistency.set(False)
    try:
        yield
    finally:
        _copy_for_consistency.reset(token)


# Bytes of device-to-host transfers one save keeps in flight. Found on a
# v5e by a sweep over the benchmark's save cells (PERF.md, PR 31): kicked
# all at once, 26-48 leaves arrive at a fifth to a third of one stream's
# rate, the runtime de-tiling them against each other.
_DTOH_WINDOW_BYTES = 2 << 30


class DtoHWindow:
    """Byte window on the DtoH transfers of one ``execute_write_reqs``
    call: a device-backed leaf's ``copy_to_host_async`` is kicked when
    the leaves ahead of it have left room, not for every leaf at once.

    Admission is FIFO in the order stagers ask, which is the scheduler's
    staging order and the order the executor's threads pick leaves up,
    so the threads wait on the oldest transfers. A leaf wider than the
    window is admitted alone, when nothing is in flight. A leaf holds
    its bytes until it is staged. All state lives on the event-loop
    thread."""

    def __init__(self, width_bytes: Optional[int] = None) -> None:
        self.width_bytes = (
            _DTOH_WINDOW_BYTES if width_bytes is None else width_bytes
        )
        self.in_flight = 0
        self._waiters: deque[Tuple[asyncio.Future, int]] = deque()

    def _fits(self, nbytes: int) -> bool:
        return self.in_flight == 0 or self.in_flight + nbytes <= self.width_bytes

    def _take(self, nbytes: int) -> None:
        self.in_flight += nbytes
        telemetry.gauge_set("dtoh_inflight_bytes", self.in_flight)

    async def admit(self, nbytes: int) -> None:
        """Wait for room and take ``nbytes`` of it; the caller gives them
        back with ``release``. The ``stage_dtoh_gate`` span opens whether
        or not the leaf waits."""
        with telemetry.span("stage_dtoh_gate", cat="stager", bytes=nbytes):
            if not self._waiters and self._fits(nbytes):
                self._take(nbytes)
                return
            telemetry.counter_add("dtoh_window_waits", 1)
            fut = asyncio.get_running_loop().create_future()
            self._waiters.append((fut, nbytes))
            try:
                await fut
            except asyncio.CancelledError:
                # Admitted in the tick the task was cancelled in: the
                # bytes were taken and the caller will not return them.
                # Either way the leaves behind this one move up.
                admitted = fut.done() and not fut.cancelled()
                self.release(nbytes if admitted else 0)
                raise

    def release(self, nbytes: int) -> None:
        self.in_flight -= nbytes
        while self._waiters:
            fut, head = self._waiters[0]
            if not fut.cancelled():
                if not self._fits(head):
                    break
                self._take(head)
                fut.set_result(None)
            self._waiters.popleft()


# The window of the execute_write_reqs call a stager is staged under (the
# scheduler sets it around its staging tasks, which inherit it); None
# outside one, where a stager kicks its transfer at once.
dtoh_window: contextvars.ContextVar[Optional[DtoHWindow]] = contextvars.ContextVar(
    "tsnap_dtoh_window", default=None
)


STAGING_POOL_ENV_VAR = "TORCHSNAPSHOT_TPU_STAGING_POOL_BYTES"
_DEFAULT_STAGING_POOL_BYTES = 4 << 30

STREAM_WRITES_ENV_VAR = "TORCHSNAPSHOT_TPU_STREAM_WRITES"


def streaming_enabled() -> bool:
    """Kill switch for the sub-chunk streaming write path (default on).
    The scheduler still gates streaming on the storage plugin's own
    opt-in and on the caller blocking until I/O drains (sync take)."""
    return os.environ.get(STREAM_WRITES_ENV_VAR, "1") not in ("0", "false", "")


class _SlabHolder:
    """Weakref-able buffer exporter that owns a pooled slab (PEP 688).

    Arrays built over this holder (``np.frombuffer(holder)``) record it —
    not the slab — as their base, and numpy's base-chain collapsing stops
    at the first non-ndarray base: every numpy view derived from the
    staged buffer therefore keeps the holder (and through it the slab)
    alive. Attaching the recycle finalizer to a plain ndarray view would
    not have this property — numpy collapses ndarray base chains, so a
    derived slice would reference the slab directly and the intermediate
    view could die (recycling the slab) while the slice still aliases it.
    """

    __slots__ = ("__weakref__", "_slab")

    def __init__(self, slab: np.ndarray) -> None:
        self._slab = slab

    def __buffer__(self, flags: int) -> memoryview:
        return memoryview(self._slab)


# Below this size a fresh np.empty beats an mmap-backed native slab
# (two syscalls + page bookkeeping for memory that fits in one page's
# worth of faults anyway) — tiny buffers skip the native pool path.
_NATIVE_SLAB_MIN_BYTES = 4096


class _StagingPool:
    """Bounded free-list of staging buffers, recycled by the GC.

    A training loop calls async_take every N minutes; without a pool each
    call allocates the full state size in fresh buffers, and on
    lazily-backed VMs first-touch page faults cost several x the copy
    itself. ``get`` returns an array over a pooled slab whose base
    carries a finalizer: when every reference dies (scheduler, storage
    plugin, a mirror's background replica, any numpy view a consumer
    derived — whoever holds it longest), the slab returns to the free
    list. GC-driven recycling means no component needs an explicit
    release call, and a buffer still referenced anywhere can never be
    handed out again.

    Slabs are NATIVE when the extension is present (``_native``'s
    pinned allocator: page-aligned for O_DIRECT/io_uring, pre-faulted
    deterministically at allocation — never lazily inside a timed
    staging copy — THP-hinted, mlock'd best-effort), recycled through a
    ``from_address`` ctypes holder. The PEP 688 ``_SlabHolder`` path
    remains for native-absent hosts. Pool traffic is published to the
    telemetry bus (``staging_pool_hits``/``_misses`` counters,
    ``staging_pool_free_bytes``/``_outstanding_bytes`` gauges) for
    ``stats`` and the ``/metrics`` exporter."""

    def __init__(self, limit_bytes: int) -> None:
        self._limit = limit_bytes
        self._lock = threading.Lock()
        self._free: dict = {}
        self._free_bytes = 0
        self._outstanding = 0
        # Slabs whose GC finalizer fired while the lock was unavailable.
        # A finalizer can run at ANY allocation point — including inside
        # this pool's own critical sections — so it must never block on
        # the lock (self-deadlock) nor mutate the counters reentrantly
        # (a += interrupted mid-op would lose one side's update).
        # Deferred returns park here (deque append is GIL-atomic, the
        # flightrec precedent) and are integrated by the next get/prewarm.
        self._deferred_native: "deque" = deque()
        self._deferred_py: "deque" = deque()
        # None = unprobed; False = unavailable (or an alloc failed —
        # never retried); True = native slabs back the pool.
        self._native: Optional[bool] = None

    def _native_ok(self) -> bool:
        if self._native is None:
            try:
                from .._native import slab_allocator_available

                self._native = bool(slab_allocator_available())
            except Exception:  # noqa: BLE001 - probe must never raise
                self._native = False
        return self._native

    def get(self, nbytes: int) -> np.ndarray:
        self._integrate_deferred()
        if nbytes < _NATIVE_SLAB_MIN_BYTES or not self._native_ok():
            return self._get_py(nbytes)
        out = self._get_native(nbytes)
        if out is None:  # allocation failure: degrade for good
            self._native = False
            # Mid-run degradation is a fleet-visible state change, not
            # debug noise: record it so blackbox shows the pool fell
            # back to Python slabs partway through an operation.
            telemetry.flightrec.record(
                "native.degrade", site="staging_pool",
                cause="native slab allocation failed", fallback="python",
            )
            self._drain_native_free()
            return self._get_py(nbytes)
        return out

    def _integrate_deferred(self) -> None:
        """Fold in returns whose finalizer could not take the lock."""
        while True:
            try:
                view = self._deferred_native.popleft()
            except IndexError:
                break
            with self._lock:
                self._outstanding -= view.nbytes
            self._store_native(view)
        while True:
            try:
                base = self._deferred_py.popleft()
            except IndexError:
                break
            with self._lock:
                self._outstanding -= base.nbytes
                if self._free_bytes + base.nbytes <= self._limit:
                    self._free.setdefault(base.nbytes, []).append(base)
                    self._free_bytes += base.nbytes

    # ------------------------------------------------ native slab path

    def _get_native(self, nbytes: int) -> Optional[np.ndarray]:
        with self._lock:
            slabs = self._free.get(nbytes)
            view = slabs.pop() if slabs else None
            if view is not None:
                self._free_bytes -= nbytes
        hit = view is not None
        if view is None:
            from .. import _native

            view = _native.slab_view(nbytes)
            if view is None:
                return None
        with self._lock:
            self._outstanding += nbytes
        # The holder aliases the slab without owning it; numpy's base-
        # chain collapsing stops at the first non-ndarray base, so every
        # derived view keeps the holder (and through its finalizer the
        # slab's pool entry) alive — same property _SlabHolder documents.
        holder = (ctypes.c_ubyte * nbytes).from_address(view.ctypes.data)
        weakref.finalize(holder, self._put_native, view)
        self._publish(hit)
        return np.frombuffer(holder, np.uint8)

    def _put_native(self, view: np.ndarray) -> None:
        # Finalizer context: may fire at any allocation point, possibly
        # while THIS thread already holds the pool lock (GC inside a
        # critical section). Never block — integrate now if the lock is
        # free, else defer to the next get/prewarm.
        if not self._lock.acquire(blocking=False):
            self._deferred_native.append(view)
            return
        try:
            self._outstanding -= view.nbytes
        finally:
            self._lock.release()
        self._store_native(view)

    def _store_native(self, view: np.ndarray) -> None:
        evict = False
        # tsalint: allow[restricted-context] safe from the _put_native finalizer: its acquire(blocking=False) gate proved this thread does NOT hold the pool lock (a holder defers instead), and no pool path blocks while holding it (lock-blocking enforces that), so this acquire cannot self-deadlock
        with self._lock:
            # After a mid-run degrade the free lists feed _get_py, which
            # must never pop an unowned native view (its eviction path
            # would drop the mmap with no munmap): free late returners.
            if self._native is False or (
                self._free_bytes + view.nbytes > self._limit
            ):
                evict = True
            else:
                self._free.setdefault(view.nbytes, []).append(view)
                self._free_bytes += view.nbytes
        if evict:
            from .. import _native

            _native.slab_free(view.ctypes.data, view.nbytes)

    def _drain_native_free(self) -> None:
        """Free every pooled native slab (the True→False degrade
        transition): sizes >= the native floor were allocated natively
        while the pool ran native, and _get_py must never inherit them.
        Sub-floor sizes (PEP 688 slabs) stay pooled."""
        from .. import _native

        drained: List[np.ndarray] = []
        with self._lock:
            for nbytes in [
                n for n in self._free if n >= _NATIVE_SLAB_MIN_BYTES
            ]:
                views = self._free.pop(nbytes)
                drained.extend(views)
                self._free_bytes -= nbytes * len(views)
        for view in drained:
            _native.slab_free(view.ctypes.data, view.nbytes)

    # --------------------------------------------------- PEP 688 path

    def _get_py(self, nbytes: int) -> np.ndarray:
        with self._lock:
            slabs = self._free.get(nbytes)
            base = slabs.pop() if slabs else None
            if base is not None:
                self._free_bytes -= nbytes
            self._outstanding += nbytes
        hit = base is not None
        if base is None:
            base = np.empty(nbytes, np.uint8)
        holder = _SlabHolder(base)
        weakref.finalize(holder, self._put, base)
        self._publish(hit)
        return np.frombuffer(holder, np.uint8)

    def _put(self, base: np.ndarray) -> None:
        # Finalizer context — same never-block rule as _put_native.
        if not self._lock.acquire(blocking=False):
            self._deferred_py.append(base)
            return
        try:
            self._outstanding -= base.nbytes
            if self._free_bytes + base.nbytes <= self._limit:
                self._free.setdefault(base.nbytes, []).append(base)
                self._free_bytes += base.nbytes
        finally:
            self._lock.release()

    # ------------------------------------------------------- telemetry

    def _publish(self, hit: bool) -> None:
        if not telemetry.enabled():
            return
        telemetry.counter_add(
            "staging_pool_hits" if hit else "staging_pool_misses", 1
        )
        with self._lock:
            free_b, out_b = self._free_bytes, self._outstanding
        telemetry.gauge_set("staging_pool_free_bytes", free_b)
        telemetry.gauge_set("staging_pool_outstanding_bytes", out_b)

    # ---------------------------------------------------------- warmup

    def prewarm(self, sizes: Sequence[int]) -> int:
        """Pre-fault slabs so the FIRST staging pass doesn't pay them.

        On lazily-backed VMs, first-touch page faults during the staging
        memcpy cost several times the copy itself — the reason a cold
        async_take blocks far longer than a warm one. ``sizes`` is a
        multiset of exact staged-buffer sizes (the pool's free lists are
        exact-size); slabs already pooled count toward it. Returns the
        bytes newly faulted. Bounded by the pool limit. Native slabs are
        pre-faulted by the allocator itself (deterministically, at slab
        construction), so warming them is pure allocation."""
        from collections import Counter

        self._integrate_deferred()
        native = self._native_ok()
        want = Counter(
            int(s)
            for s in sizes
            if s >= (_NATIVE_SLAB_MIN_BYTES if native else 1)
        )
        warmed = 0
        for nbytes, cnt in want.items():
            with self._lock:
                missing = cnt - len(self._free.get(nbytes, []))
                room = (self._limit - self._free_bytes) // nbytes if nbytes else 0
            for _ in range(min(missing, room)):
                if native:
                    from .. import _native

                    view = _native.slab_view(nbytes)
                    if view is None:
                        return warmed
                    self._store_native(view)
                else:
                    slab = np.empty(nbytes, np.uint8)
                    slab.fill(0)  # touch every page
                    with self._lock:
                        if self._free_bytes + nbytes <= self._limit:
                            self._free.setdefault(nbytes, []).append(slab)
                            self._free_bytes += nbytes
                warmed += nbytes
        return warmed


def _pool_limit() -> int:
    raw = os.environ.get(STAGING_POOL_ENV_VAR, "").strip()
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return _DEFAULT_STAGING_POOL_BYTES


_staging_pool = _StagingPool(_pool_limit())


def pooled_buffer(nbytes: int) -> np.ndarray:
    """A writable uint8 buffer drawn from the process staging pool,
    recycled by the GC when every reference dies (see _StagingPool).

    The public face of the pool for the other byte movers on the restore
    hot path — the fs plugin's pread windows (Python and native engine
    alike) and the cooperative-restore peer receiver (fanout.py) — so
    repeated sub-chunk buffers don't pay first-touch page faults on
    every window/frame. With the native extension present, buffers of at
    least ``_NATIVE_SLAB_MIN_BYTES`` are page-aligned pinned slabs —
    valid O_DIRECT/io_uring targets — and the alignment/lifetime
    contract (aligned reuse, derived views pin the slab, never recycled
    while an SQE holds it) is pinned by tests/test_native_io.py."""
    return _staging_pool.get(nbytes)


def fast_copyto(dst: np.ndarray, src: np.ndarray) -> None:
    """``np.copyto(dst, src, casting="same_kind")``, but through raw bytes
    when the dtypes match exactly and both sides are C-contiguous: numpy's
    generic same-dtype copy loop runs ~3.5x slower than memcpy for custom
    dtypes (ml_dtypes bf16/fp8) and small itemsizes, and restore copies are
    on the critical path."""
    if (
        dst.dtype == src.dtype
        and dst.flags["C_CONTIGUOUS"]
        and src.flags["C_CONTIGUOUS"]
    ):
        np.copyto(dst.reshape(-1).view(np.uint8), src.reshape(-1).view(np.uint8))
    else:
        np.copyto(dst, src, casting="same_kind")


def _is_jax_array(arr) -> bool:
    try:
        import jax

        return isinstance(arr, jax.Array)
    except ImportError:  # pragma: no cover
        return False


def array_nbytes(arr) -> int:
    """Logical byte size of a numpy or jax array."""
    return array_size_bytes(arr.shape, dtype_to_string(arr.dtype))


def _device_backed(arr) -> bool:
    """A jax array whose bytes reach the host by DMA: off the CPU backend."""
    return next(iter(arr.sharding.device_set)).platform != "cpu"


def to_host(arr) -> np.ndarray:
    """Synchronous DtoH materialization (numpy passthrough). For a jax
    array the ``stage_dtoh`` span is the wait for the leaf's bytes on the
    host: the DMA plus the runtime's de-tiling (which runs on the
    runtime's own threads and ends inside this call). A zero-copy view,
    and microseconds, on the CPU backend."""
    if _is_jax_array(arr):
        with telemetry.span("stage_dtoh", cat="stager", bytes=array_nbytes(arr)):
            return np.asarray(arr)
    return np.asarray(arr)


def needs_consistency_copy(arr) -> bool:
    """True when staging ``arr`` must copy so the snapshot can't alias
    caller memory: CPU-backend jax arrays materialize as zero-copy views
    of the device buffer, and numpy inputs alias caller memory directly;
    a TPU DtoH transfer already produces host-owned memory. The single
    source of the pool-draw platform rule — shared by the stager
    (ArrayBufferStager) and the warmup size planner."""
    if _is_jax_array(arr):
        return next(iter(arr.sharding.device_set)).platform == "cpu"
    return True


def iter_staged_pieces(app_state, pg=None, replicated=None, save_dtype=None):
    """Yield ``(shape, dtype_str, needs_copy, get_piece)`` for every
    piece THIS process will stage for ``app_state`` — the single source
    of the write-partition geometry, shared by the staging-pool warmup
    (byte sizes, pieces with ``needs_copy`` only) and CheckpointManager's
    fingerprint warmup (real device pieces via ``get_piece``).

    ``save_dtype`` is applied: pieces are reported at the CONVERTED
    dtype, and chunk/subdivision boundaries are recomputed at its
    itemsize, so consumers warm exactly what the real save stages.
    ``get_piece`` is a thunk returning the UNCONVERTED piece (device
    slice for jax leaves, view for numpy) — it materializes placement-
    accurate data only when called, so size-only consumers never touch
    devices; ``None`` when the piece cannot be cheaply materialized.
    Under a multi-rank ``pg``, replicated dense chunks stripe
    ``[rank::world]`` like the write partition; everything else is fully
    local.
    """
    import fnmatch

    from ..flatten import flatten
    from ..snapshot import _is_process_replicated_jax_array
    from . import chunked
    from .prepare import is_sharded_jax_array
    from .sharded import ShardedArrayIOPreparer

    if pg is not None:
        from ..pg_wrapper import PGWrapper

        wrapper = PGWrapper(pg)
        world, rank = wrapper.get_world_size(), wrapper.get_rank()
    else:
        world, rank = 1, 0
    globs = list(replicated or [])

    def _eff_dtype(logical_path: str, leaf) -> str:
        """Dtype the WRITE PLAN will stage: ``save_dtype`` downcasts
        matching leaves before staging. The decision is shared with the
        take-time converter (serialization.effective_save_dtype) so the
        two can never diverge."""
        src = dtype_to_string(leaf.dtype)
        if not save_dtype:
            return src
        from ..serialization import effective_save_dtype

        target = effective_save_dtype(logical_path, leaf.dtype, save_dtype)
        return dtype_to_string(target) if target is not None else src

    for key, stateful in app_state.items():
        state_dict = getattr(stateful, "state_dict", None)
        if state_dict is None:
            continue
        _, flattened = flatten(state_dict(), prefix=key)
        for logical_path, leaf in flattened.items():
            if is_sharded_jax_array(leaf):
                eff = _eff_dtype(logical_path, leaf)
                # Subdivision boundaries depend on itemsize, so piece
                # sizes are computed at the converted dtype.
                itemsize = string_to_dtype(eff).itemsize
                needs = needs_consistency_copy(leaf)
                for p_off, p_sz, get_piece in ShardedArrayIOPreparer._owned_pieces(
                    leaf, itemsize=itemsize
                ):
                    yield tuple(p_sz), eff, needs, get_piece
            elif _is_jax_array(leaf) or isinstance(leaf, np.ndarray):
                needs = needs_consistency_copy(leaf)
                # Only REPLICATED paths stripe across ranks in the write
                # partition; per-rank arrays are fully staged locally.
                is_repl = world > 1 and (
                    any(fnmatch.fnmatch(logical_path, g) for g in globs)
                    or _is_process_replicated_jax_array(leaf)
                )
                eff = _eff_dtype(logical_path, leaf)
                nbytes = array_size_bytes(leaf.shape, eff)
                if nbytes > chunked.DEFAULT_MAX_CHUNK_SIZE_BYTES and leaf.shape:
                    ranges = chunked.ChunkedArrayIOPreparer.chunk_ranges(
                        leaf.shape, eff
                    )
                    if is_repl:
                        ranges = ranges[rank::world]
                    rest = tuple(leaf.shape[1:])
                    for lo, hi in ranges:
                        yield (
                            (hi - lo, *rest),
                            eff,
                            needs,
                            lambda leaf=leaf, lo=lo, hi=hi: leaf[lo:hi],
                        )
                else:
                    yield tuple(leaf.shape), eff, needs, lambda leaf=leaf: leaf


def warmup_staging(app_state, pg=None, replicated=None, save_dtype=None) -> int:
    """Pre-fault the staging pool for ``app_state`` so the FIRST
    ``async_take`` blocks like a warm one.

    The pool recycles slabs between saves, so steady-state staging never
    faults pages — but the first save of a training run allocates every
    slab fresh, and on lazily-backed VMs first-touch faults during the
    staging memcpy dominate the caller-blocked interval (measured 11x the
    warm cost). Call once after building the app state (CheckpointManager
    does it on its ``warmup`` method); cheap to call again after state
    shapes change. Returns bytes newly faulted.

    No-op (returns 0) whenever staging cannot draw from the pool: the
    pool feeds only the fused copy+CRC path (``_stage_fused``), which
    needs the native extension and checksums enabled — warming slabs no
    save will ever draw would pin pool-limit bytes for nothing. Dedup
    (incremental) and compression also bypass the pool;
    CheckpointManager.warmup checks those, since they are its
    configuration rather than process state.

    Sizes mirror the write partition: for GSPMD-sharded jax arrays the
    exact owned-piece sizes this process stages; large dense arrays
    at the chunk-preparer's ranges. Under a multi-rank ``pg``, ONLY
    replicated paths stripe across ranks — ``replicated`` takes the same
    globs as ``Snapshot.take`` and process-replicated jax arrays are
    auto-detected, matching ``_calculate_replicated_paths``; everything
    else is fully staged per rank and warms fully (striping is an
    approximation of the deterministic partition; under-warming just
    faults the difference on first use). Device arrays whose staging
    needs no consistency copy (TPU-backed: DtoH already produces
    host-owned memory) are skipped.

    Geometry comes from ``iter_staged_pieces`` — the shared write-
    partition walk — so warmed sizes can never drift from what the real
    save stages."""
    from .._native import native_available
    from ..integrity import checksums_enabled

    if not native_available() or not checksums_enabled():
        return 0

    sizes: List[int] = [
        array_size_bytes(shape, dt)
        for shape, dt, needs_copy, _ in iter_staged_pieces(
            app_state, pg=pg, replicated=replicated, save_dtype=save_dtype
        )
        if needs_copy
    ]
    return _staging_pool.prewarm(sizes)


def _stage_crc_span(buf: memoryview):
    """The checksum / digest pass over a leaf's staged bytes."""
    return telemetry.span("stage_crc", cat="stager", bytes=buf.nbytes)


class ArrayBufferStager(BufferStager):
    """Stages one array into a host buffer *owned by the snapshot*.

    Staging is the consistency point of async_take: the staged buffer must
    not alias caller memory, or mutations after async_take returns would leak
    into the snapshot (reference guarantee: snapshot.py:257-262). For TPU
    arrays ``device_get`` inherently copies (DtoH DMA); on the CPU backend
    (and for numpy inputs) an explicit copy is made.
    """

    def __init__(
        self,
        arr,
        entry: Optional[ArrayEntry] = None,
        index: Optional[Tuple[slice, ...]] = None,
    ) -> None:
        # ``index``: this payload is ``arr[index]``, cut when it is staged
        # (``_cut``) and not before, so a chunk of a device array holds no
        # device copy from prepare time on: the cut is made inside the
        # DtoH window and dropped with the staged payload, and the device
        # memory a save adds is bounded by the window, not by the state.
        self.arr = arr
        self.index = index
        self.shape = (
            tuple(arr.shape) if index is None else
            tuple(len(range(*ix.indices(n))) for ix, n in zip(index, arr.shape))
        )
        # When given, the entry's checksum is recorded at stage time (the
        # manifest is gathered/committed after staging completes, so the
        # mutation is visible in the persisted metadata).
        self.entry = entry
        self.copy_for_consistency = _copy_for_consistency.get()
        from ..compression import active_codec
        from ..dedup import active_dedup_context

        self.dedup = active_dedup_context()
        self.codec = active_codec()
        # Set at stage time when the payload matched the dedup base: the
        # scheduler then releases the buffer without writing it.
        self.io_skipped = False

    def _nbytes(self) -> int:
        return array_size_bytes(self.shape, dtype_to_string(self.arr.dtype))

    def _cut(self):
        """The array this stager stages: ``arr`` itself, or its chunk. The
        ``stage_chunk_cut`` span is the dispatch of the device slice (the
        slice itself runs ahead of the chunk's DtoH transfer, on the
        device's own queue)."""
        if self.index is None:
            return self.arr
        with telemetry.span("stage_chunk_cut", cat="stager", bytes=self._nbytes()):
            telemetry.counter_add("chunk_payloads", 1)
            return self.arr[self.index]

    def _needs_consistency_copy(self, arr) -> bool:
        """The module-level platform rule (needs_consistency_copy), gated
        by the zero_copy_staging opt-out: under sync ``Snapshot.take``
        views are safe because the caller is blocked until I/O drains."""
        if not self.copy_for_consistency:
            return False
        return needs_consistency_copy(arr)

    def _stage_sync(self, arr) -> np.ndarray:
        host = to_host(arr)
        if self._needs_consistency_copy(arr):
            # Host to host. Never runs for a TPU array (DtoH already
            # produced host-owned memory), so no chip run has this span.
            with telemetry.span("stage_hostcopy", cat="stager", bytes=host.nbytes):
                host = np.array(host, copy=True)
        return host

    def _device_dedup_candidate(self, arr) -> bool:
        return (
            self.dedup is not None
            and self.dedup.device_digests
            and self.entry is not None
            and self.entry.byte_range is None
            and _is_jax_array(arr)
        )

    def _record_device_fingerprint(self, arr) -> Optional[str]:
        """Fingerprint ``arr`` on device and record it on the entry so
        the NEXT incremental take can match against this snapshot.
        Returns the fingerprint, or None when the array cannot be
        fingerprinted on device (host SHA-256 path takes over)."""
        from ..device_digest import device_fingerprint

        fp = device_fingerprint(arr)
        if fp is not None:
            self.entry.device_digest = fp
        return fp

    def _try_device_dedup(self, arr) -> bool:
        """Fingerprint ``arr`` on device (device_digest.py) and, when the
        base snapshot recorded the same fingerprint for this location,
        skip staging entirely — the DtoH copy never happens, only the
        16-byte fingerprint crosses to the host.

        On a match the entry's digest/checksum/codec are taken from the
        base's ref — fingerprint equality implies content equality under
        the (opt-in, non-cryptographic) trust model documented in
        device_digest.py. Unlike the host path there is no staged buffer
        here, so a base saved without checksums leaves the entry's
        checksum unset rather than recomputing one — a one-time warning
        flags the narrowed verification coverage when that happens."""
        fp = self._record_device_fingerprint(arr)
        if fp is None:
            return False
        ref = self.dedup.refs.get(self.entry.location)
        if ref is None or ref.device_digest != fp:
            return False
        nbytes = array_nbytes(arr)
        if ref.nbytes is not None and ref.nbytes != nbytes:
            return False  # same fingerprint, different size: never trust
        self.entry.digest = ref.digest
        self.entry.origin = ref.origin
        self.entry.codec = ref.codec
        self.entry.checksum = ref.checksum
        if ref.checksum is None:
            from ..integrity import checksums_enabled

            if checksums_enabled():
                global _warned_none_checksum
                if not _warned_none_checksum:
                    _warned_none_checksum = True
                    logger.warning(
                        "device-digest dedup match for %s inherits no "
                        "checksum (base snapshot was saved with checksums "
                        "disabled); restore-time verification will not "
                        "cover deduplicated entries until a full (non-"
                        "dedup) save records checksums again",
                        self.entry.location,
                    )
        return True

    def _stage_fused(self, arr) -> Optional[BufferType]:
        """Consistency copy + CRC32C fused into ONE pass over the source
        (native ts_copy_crc32c). Staging must both copy (the caller may
        mutate/donate after async_take returns) and checksum (entries are
        gathered right after staging), and the state is GBs — a second
        read pass is real wall time. Returns None when not applicable
        (no consistency copy needed, non-contiguous source, no native)."""
        from .._native import copy_crc32c, native_available

        # Check native BEFORE drawing from the pool: on a host without the
        # extension, a pooled slab grabbed here would go unused yet be
        # retained by the pool — doubling staging memory for nothing.
        if not native_available():
            return None
        if not self._needs_consistency_copy(arr):
            return None
        src = to_host(arr)
        if not src.flags["C_CONTIGUOUS"]:
            return None
        src_bytes = array_as_memoryview(src)
        dst = _staging_pool.get(src_bytes.nbytes)
        # The host-to-host copy, here fused with the CRC: one pass over
        # the bytes does both, so it is open under both names.
        with _stage_crc_span(src_bytes), telemetry.span(
            "stage_hostcopy", cat="stager", bytes=src_bytes.nbytes
        ):
            crc = copy_crc32c(dst, src_bytes)
        if crc is None:
            return None
        self.entry.checksum = f"crc32c:{crc:08x}"
        return memoryview(dst)

    def _active_codec(self) -> Optional[str]:
        """The codec this payload will be stored under, or None.

        Byte-ranged payloads (write-batcher slabs) never compress: slab
        offsets were planned from serialized sizes before staging runs."""
        if self.entry is None or self.codec is None:
            return None
        if self.entry.byte_range is not None:
            return None
        return self.codec

    def _stage_and_sum(self, arr) -> BufferType:
        """Runs in an executor thread: DtoH + serialize + (optional)
        compress + hash — keeping GB-scale byte work off the event-loop
        thread."""
        with telemetry.span(
            "stage_hash", cat="stager", bytes=array_nbytes(arr)
        ):
            return self._stage_and_sum_impl(arr)

    def _stage_and_sum_impl(self, arr) -> BufferType:
        codec = self._active_codec()
        if self.entry is not None and self.dedup is None and codec is None:
            from ..integrity import checksums_enabled

            if checksums_enabled():
                fused = self._stage_fused(arr)
                if fused is not None:
                    return fused
        host = self._stage_sync(arr)
        buf = array_as_memoryview(host)
        if self.entry is not None:
            from ..integrity import checksums_enabled, compute_checksum

            if self.dedup is not None:
                from ..dedup import compute_digest

                # Digest covers the UNCOMPRESSED bytes: incremental
                # chains stay stable across codec/level changes.
                with _stage_crc_span(buf):
                    digest = compute_digest(buf)
                self.entry.digest = digest
                # Slab-batched payloads (byte_range) never dedup: the
                # entry's offsets index the SLAB, not the base's file —
                # borrowing a base origin would read the base at slab
                # offsets. (The by-location match could never hit them;
                # the content-address fallback could.)
                ref = (
                    self.dedup.match(self.entry.location, digest, buf.nbytes)
                    if self.entry.byte_range is None
                    else None
                )
                if ref is not None:
                    # Unchanged since the base snapshot: record where the
                    # bytes already live and skip the storage write. The
                    # checksum/codec must describe the BASE's stored
                    # payload — that is what restore will read. A base
                    # saved without checksums: when its payload is raw
                    # its stored bytes equal this staged buffer, so
                    # compute the checksum here rather than losing verify
                    # coverage for the deduplicated entry.
                    self.entry.origin = ref.origin
                    self.entry.codec = ref.codec
                    if ref.location is not None:
                        # Content-address fallback: the base stores these
                        # bytes under its OWN path (e.g. the pool's
                        # ``po/<hex>``) — restore reads origin+location.
                        self.entry.location = ref.location
                    if ref.checksum is None and ref.codec is None:
                        if checksums_enabled():
                            with _stage_crc_span(buf):
                                self.entry.checksum = compute_checksum(buf)
                    else:
                        self.entry.checksum = ref.checksum
                    self.io_skipped = True
                    return buf
            if codec is not None and buf.nbytes >= MIN_COMPRESS_BYTES:
                from ..compression import compress

                packed = compress(codec, buf)
                # Never a size regression: incompressible payloads (bf16
                # noise, already-compressed objects) are stored raw.
                if len(packed) < buf.nbytes:
                    self.entry.codec = codec
                    buf = memoryview(packed)
            if checksums_enabled():
                # Checksum covers the STORED bytes — verification reads
                # exactly what storage returns, before decompression.
                with _stage_crc_span(buf):
                    self.entry.checksum = compute_checksum(buf)
        return buf

    # ----------------------------------------------------- streaming path

    def can_stream(self, sub_chunk_bytes: int) -> bool:
        """True when this payload can be produced as ordered sub-chunks
        (the scheduler then fuses staging with the storage write).

        Only the PLAIN path streams — the exact cases where the staged
        bytes are a straight serialization of the array: no dedup context
        (digest/skip decisions need the whole payload), no compression
        (slab offsets and codecs are whole-buffer), no batcher byte-range
        (the slab stager owns those), and a C-contiguous source (so
        sub-chunks are contiguous byte ranges of the serialized stream).
        Checksums DO stream: the CRC chains across sub-chunks
        (integrity-identical to the buffered path)."""
        if not streaming_enabled():
            return False
        if self.dedup is not None or self._active_codec() is not None:
            return False
        if self.entry is not None and self.entry.byte_range is not None:
            return False
        arr, shape = self.arr, self.shape
        if 0 in shape:
            return False
        nbytes = self._nbytes()
        # A stream of one chunk is a buffered write with extra hops.
        if nbytes < 2 * sub_chunk_bytes:
            return False
        if _is_jax_array(arr):
            if not getattr(arr, "is_fully_addressable", True):
                return False
            if next(iter(arr.sharding.device_set)).platform != "cpu":
                # Device-backed: sub-chunks are WHOLE-ROW slices along
                # dim 0. A row wider than the sub-chunk would make each
                # "sub-chunk" row-sized — far over the window the budget
                # charges, with the pipeline degenerating toward serial
                # — so such shapes stay on the buffered path.
                if len(shape) < 1 or shape[0] < 2:
                    return False
                row_bytes = nbytes // shape[0]
                return row_bytes <= sub_chunk_bytes
            host = np.asarray(arr)
            return host.flags["C_CONTIGUOUS"]
        if isinstance(arr, np.ndarray):
            return arr.flags["C_CONTIGUOUS"]
        return False

    def _stream_checksum_update(self, state: Optional[Tuple], chunk) -> Optional[Tuple]:
        """Advance the running checksum with ``chunk``; ``state`` is
        ``(algo, value)`` or None when checksums are off. Algorithm
        choice mirrors integrity.compute_checksum so streamed and
        buffered writes of the same bytes record identical checksums."""
        if state is None:
            return None
        algo, value = state
        if algo == "crc32c":
            from .._native import crc32c

            return (algo, crc32c(chunk, value))
        import zlib

        return (algo, zlib.crc32(memoryview(chunk).cast("B"), value))

    def _stream_checksum_init(self) -> Optional[Tuple]:
        if self.entry is None:
            return None
        from ..integrity import checksums_enabled

        if not checksums_enabled():
            return None
        from .._native import native_available

        return ("crc32c", 0) if native_available() else ("crc32", 0)

    def _stream_checksum_finish(self, state: Optional[Tuple]) -> None:
        if state is not None:
            algo, value = state
            self.entry.checksum = f"{algo}:{value & 0xFFFFFFFF:08x}"

    def _host_sub_chunk(self, mv: memoryview, lo: int, hi: int, state):
        """One host-backed sub-chunk: a zero-copy byte slice when staging
        may alias caller memory (sync take), else a pooled-slab bounce
        copy FUSED with the running CRC (one pass over the source — the
        streaming analogue of _stage_fused). Returns (buffer, state)."""
        with telemetry.span("sub_chunk_stage", cat="stager", bytes=hi - lo):
            chunk = mv[lo:hi]
            if not self.copy_for_consistency:
                return chunk, self._stream_checksum_update(state, chunk)
            dst = _staging_pool.get(hi - lo)
            if state is not None and state[0] == "crc32c":
                from .._native import copy_crc32c

                crc = copy_crc32c(dst, chunk, state[1])
                if crc is not None:
                    return memoryview(dst), ("crc32c", crc)
            np.copyto(dst, np.frombuffer(chunk, np.uint8))
            return memoryview(dst), self._stream_checksum_update(state, chunk)

    async def stage_stream(self, executor, sub_chunk_bytes: int):
        """Ordered sub-chunk buffers; concatenation == the buffered
        payload, and the entry records the identical checksum.

        Staging runs ONE SUB-CHUNK AHEAD of the consumer: chunk N+1's
        staging future is scheduled BEFORE chunk N is yielded (the
        running CRC allows it — N's checksum state exists by then), so
        while the plugin writes chunk N the executor stages N+1. That
        lookahead is the entire overlap: an async generator is otherwise
        strictly sequential with its consumer. Device-backed jax arrays
        additionally kick ``copy_to_host_async`` for slice N+1 before
        materializing slice N, so the DtoH DMA rides under the current
        slice's checksum + write as well. In-flight memory is bounded by
        the chunk being written plus the chunk being staged — the
        _STREAM_DEPTH window the scheduler's budget charges. All byte
        work runs in the executor, never on the event loop."""
        arr = self._cut()
        loop = asyncio.get_running_loop()
        state = self._stream_checksum_init()
        device_backed = _is_jax_array(arr) and _device_backed(arr)
        if not device_backed:
            host = np.asarray(arr)
            mv = array_as_memoryview(host)
            total = mv.nbytes
            bounds = list(range(0, total, sub_chunk_bytes)) + [total]
            spans = list(zip(bounds[:-1], bounds[1:]))
            fut = loop.run_in_executor(
                executor, self._host_sub_chunk, mv, *spans[0], state
            )
            for nxt in spans[1:]:
                chunk, state = await fut
                # Lookahead: N+1 stages while the consumer writes N.
                fut = loop.run_in_executor(
                    executor, self._host_sub_chunk, mv, *nxt, state
                )
                yield chunk
            chunk, state = await fut
            yield chunk
            self._stream_checksum_finish(state)
            return

        row_bytes = max(1, array_nbytes(arr) // arr.shape[0])
        rows_per = max(1, sub_chunk_bytes // row_bytes)
        ranges = [
            (lo, min(lo + rows_per, arr.shape[0]))
            for lo in range(0, arr.shape[0], rows_per)
        ]

        def _kick(lo: int, hi: int):
            piece = arr[lo:hi]
            # A kick that fails must raise: swallowed, the stream would
            # silently lose its one-slice-ahead DMA and run serial.
            piece.copy_to_host_async()
            return piece

        def _materialize(piece, st):
            # The DtoH landing + running CRC for one device sub-chunk
            # (the DMA itself was kicked asynchronously by _kick).
            with telemetry.span("sub_chunk_dtoh", cat="stager"):
                host = np.asarray(piece)
                if not host.flags["C_CONTIGUOUS"]:
                    host = np.ascontiguousarray(host)
                buf = array_as_memoryview(host)
                return buf, self._stream_checksum_update(st, buf)

        pieces = [_kick(*ranges[0])]
        if len(ranges) > 1:
            pieces.append(_kick(*ranges[1]))  # DMA one slice ahead
        fut = loop.run_in_executor(executor, _materialize, pieces[0], state)
        for i in range(1, len(ranges)):
            if i + 1 < len(ranges):
                pieces.append(_kick(*ranges[i + 1]))
            buf, state = await fut
            # Lookahead: slice i materializes while the consumer writes
            # slice i-1 (its DMA was kicked one iteration earlier).
            fut = loop.run_in_executor(executor, _materialize, pieces[i], state)
            pieces[i - 1] = None  # drop the written slice's device ref
            yield buf
        buf, state = await fut
        yield buf
        self._stream_checksum_finish(state)

    async def stage_buffer(self, executor=None) -> BufferType:
        arr = self.arr
        loop = asyncio.get_running_loop()
        record_fp = False
        if self._device_dedup_candidate(arr):
            # The fingerprint is the payload's own: a chunk is cut ahead of it.
            arr = self._cut()
            ref = self.dedup.refs.get(self.entry.location)
            if ref is not None and ref.device_digest is not None:
                # A skip is possible: fingerprint BEFORE kicking the DtoH
                # DMA — a match makes the transfer unnecessary, which is
                # the entire point.
                if await loop.run_in_executor(
                    executor, self._try_device_dedup, arr
                ):
                    self.io_skipped = True
                    return memoryview(b"")
            else:
                # No base fingerprint to match (first save, or a base
                # taken without device digests): the DMA must happen, so
                # kick it first and let the recording fingerprint — pure
                # on-device compute — overlap the transfer. The dispatch
                # (kick) happens before staging; the 16-byte fetch waits
                # until after, so neither the device pass nor its
                # roundtrip ever sits ahead of the staging copy.
                record_fp = True
        is_jax = _is_jax_array(arr)
        # A DMA is kicked when the leaves ahead of this one in the save
        # have left room in the DtoHWindow, and gives its bytes back when
        # the leaf is staged: landed and checksummed (giving them back at
        # landing, from the executor thread, bought nothing measurable on
        # the chip: PERF.md, PR 31). CPU arrays have no DMA and bypass it.
        window = dtoh_window.get() if is_jax and _device_backed(arr) else None
        if window is not None:
            nbytes = self._nbytes()
            await window.admit(nbytes)
        try:
            if arr is self.arr:
                arr = self._cut()
            if is_jax:
                # Kick off the DMA before blocking. No except: a failed
                # kick would silently turn the overlapped DtoH into a
                # serial one.
                arr.copy_to_host_async()
            pending_fp = None
            if record_fp:
                from ..device_digest import _dispatch

                pending_fp = await loop.run_in_executor(executor, _dispatch, arr)
            buf = await loop.run_in_executor(executor, self._stage_and_sum, arr)
            if pending_fp is not None:
                from ..device_digest import _finalize

                self.entry.device_digest = await loop.run_in_executor(
                    executor, _finalize, arr, pending_fp
                )
            return buf
        finally:
            # Staged, or a stage that raised or was cancelled.
            if window is not None:
                if arr is not self.arr:
                    # The cut's device copy goes with its bytes in the
                    # window, not with the last reference to what was
                    # staged from it (the host copy is the runtime's own
                    # memory): six 480 MiB cuts were alive at once on the
                    # chip under a window that admits four (PERF.md, PR 34).
                    arr.delete()
                window.release(nbytes)

    def get_staging_cost_bytes(self) -> int:
        return self._nbytes()


def _executor_submit(executor, path: str) -> Callable:
    """``await submit(fn, *args)``: ``fn(*args)`` on one of ``executor``'s
    threads (here and now where there is no executor). With telemetry on,
    what the call waits for a thread is a ``consume_queue`` span each
    way: ``thread="worker"`` from here to the start of ``fn`` on the
    executor's thread, ``thread="loop"`` from the end of ``fn`` there
    until the event-loop thread takes the result up (it needs the GIL
    that the other workers' copies hold). Off, this is ``run_in_executor``
    itself. The branch is taken here, once a stream or buffer, not once
    a chunk."""
    if executor is None:

        async def inline(fn, *args):
            return fn(*args)

        return inline
    loop = asyncio.get_running_loop()
    if not telemetry.enabled():
        return functools.partial(loop.run_in_executor, executor)

    def queued(thread: str):
        return telemetry.handoff_span(
            "consume_queue", cat="consumer", path=path, thread=thread
        )

    async def submit(fn, *args):
        out = queued("worker")
        back = None

        def run():
            nonlocal back
            out.__exit__(None, None, None)
            try:
                return fn(*args)
            finally:
                back = queued("loop")

        try:
            return await loop.run_in_executor(executor, run)
        finally:
            if back is not None:
                back.__exit__(None, None, None)

    return submit


def _hostcopy_span(path: Optional[str], nbytes: int):
    """Around a host-to-host move of ``nbytes`` restored bytes of the
    read ``path``."""
    return telemetry.span(
        "consume_hostcopy", cat="consumer", path=path, bytes=nbytes
    )


def _placing_thread() -> str:
    """What a ``consume_place`` span says of its thread: ``"loop"`` where
    an event loop runs (every other read of the restore stalls behind the
    placement), else ``"worker"``."""
    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return "worker"
    return "loop"


@dataclass
class DeviceMaterializer:
    """How a restored array lands on device, captured at prepare time
    (prepare.py's jax-destination branch). The buffered path keeps using
    the host-array callback (one ``device_put`` of the whole payload);
    the STREAMED path uses this instead: each sub-chunk is ``device_put``
    as it arrives, so HtoD of chunk N rides under the read of chunk N+1
    and the host never holds more than the in-flight window."""

    sharding: object
    # False: the destination was uncommitted, and so is what lands.
    committed: bool
    dst_dtype: object
    needs_cast: bool
    callback: Optional[Callable]


class _ScratchSink:
    """Raw-byte sink for verify-before-commit streamed consumes: bytes
    accumulate in a scratch buffer and NOTHING touches the destination
    until the chained checksum validated — the buffered path's
    verify-then-copy safety, kept under streaming at the cost of holding
    the payload (which is why consumers using this sink declare the FULL
    consuming cost to the budget, not the window)."""

    def __init__(self, nbytes: int, path: Optional[str] = None) -> None:
        self.path = path
        # Pooled slab, not a fresh allocation: on lazily-backed VMs the
        # first touch of never-used memory costs several x a normal
        # fault, and a training loop restores repeatedly — the pool's
        # GC-driven recycling (see _StagingPool) hands back pre-faulted
        # slabs, and any view a consumer keeps pins the slab until it
        # dies.
        self.buf = _staging_pool.get(nbytes) if nbytes else np.empty(0, np.uint8)
        self.pos = 0

    def add(self, data) -> None:
        mv = data if isinstance(data, memoryview) else memoryview(data)
        mv = mv.cast("B")
        if self.pos + mv.nbytes > self.buf.nbytes:
            raise IOError(
                f"read stream produced more than the expected "
                f"{self.buf.nbytes} bytes"
            )
        with _hostcopy_span(self.path, mv.nbytes):
            self.buf[self.pos : self.pos + mv.nbytes] = np.frombuffer(mv, np.uint8)
        self.pos += mv.nbytes

    def finish(self) -> memoryview:
        if self.pos != self.buf.nbytes:
            raise IOError(
                f"short read stream: produced {self.pos} of "
                f"{self.buf.nbytes} bytes"
            )
        return memoryview(self.buf)


# The sink's row is the longest run of trailing dimensions within this
# many bytes: a thirty-second of the smallest sub-chunk the governor
# elects (8 MiB), so whatever the shape a sub-chunk holds rows by the
# dozen and what the carry copies at its edges stays under 1/32 of it.
_SINK_ROW_CAP_BYTES = 256 << 10


def _sink_row_shape(shape: Tuple[int, ...], itemsize: int) -> Tuple[int, ...]:
    """The trailing dimensions that make one row of ``_DeviceRowSink``:
    the longest suffix of ``shape`` short of the whole within
    ``_SINK_ROW_CAP_BYTES``, the last dimension alone where two are
    wider than that, and ``()``, one element, for a 1-D leaf."""
    if len(shape) < 2:
        return ()
    keep = len(shape) - 1
    nbytes = itemsize * shape[keep]
    while keep > 1 and nbytes * shape[keep - 1] <= _SINK_ROW_CAP_BYTES:
        keep -= 1
        nbytes *= shape[keep]
    return shape[keep:]


def _sink_row_bytes(entry: "ArrayEntry") -> int:
    row = _sink_row_shape(tuple(entry.shape), string_to_dtype(entry.dtype).itemsize)
    return max(1, array_size_bytes(row, entry.dtype))


@functools.lru_cache(maxsize=None)
def _device_assembler() -> Callable:
    """``assemble(shape, *blocks)``: the blocks joined along axis 0 and
    given the leaf's shape, as one program a set of block shapes, so the
    joined rows are written once and the reshape (leading dimensions
    folded back out of axis 0) is no pass of its own."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=0)
    def assemble(shape, *blocks):
        return jnp.concatenate(blocks, axis=0).reshape(shape)

    return assemble


class _DeviceRowSink:
    """Per-sub-chunk HtoD sink: whole-row blocks of the decoded payload
    are ``device_put`` as they land, assembled on device at the end
    (joined along axis 0, reshaped to the leaf, then placed under the
    destination sharding). A row is a run of trailing dimensions short
    enough that every sub-chunk holds many (``_sink_row_shape``), and a
    sub-chunk's whole rows go to the device as a view of the buffer they
    were read into: nothing is copied but what completes a row that the
    previous sub-chunk left open, and the tail that opens the next. The
    view keeps the buffer (a pooled slab: ``pooled_buffer``) alive until
    the runtime has taken the bytes, and nobody writes it again
    (``ReadStream``). On the CPU backend ``device_put`` may alias the
    host buffer for good: a leaf restored from a single block then lives
    in a pool slab until it dies, which the pool's contract allows. A
    chunk the sink cannot keep a view of (``_view``) is copied whole, as
    every chunk once was. The host holds the carry, under one row, plus
    the chunks in flight — the window the scheduler's budget charges —
    and the destination array is untouched until the checksum validated
    and the callback fires."""

    def __init__(self, entry: "ArrayEntry", dest: DeviceMaterializer) -> None:
        self.path = entry.location
        self.shape = tuple(entry.shape)
        self.np_dtype = string_to_dtype(entry.dtype)
        self.row_shape = _sink_row_shape(self.shape, self.np_dtype.itemsize)
        self.row_bytes = _sink_row_bytes(entry)
        self.total_rows = array_size_bytes(self.shape, entry.dtype) // self.row_bytes
        self.dest = dest
        self.carry = bytearray()
        self.blocks: list = []
        self.rows = 0
        self._device = None

    def _view(self, mv: memoryview, offset: int, nbytes: int):
        """``mv[offset : offset + nbytes]`` as an array of the entry's
        dtype over the chunk's own memory, or None where the sink has
        to copy: the exporter is neither ``bytes`` nor an ndarray (a
        pooled slab is one), so its owner may resize or close it under
        a view the sink still holds (``bytearray``, ``mmap``), or the
        rows start off the dtype's alignment (a decompressor feeds any
        length)."""
        if not isinstance(mv.obj, (bytes, np.ndarray)):
            return None
        block = np.frombuffer(
            mv, self.np_dtype, nbytes // self.np_dtype.itemsize, offset
        )
        return block if block.flags.aligned else None

    def add(self, data) -> None:
        mv = (data if isinstance(data, memoryview) else memoryview(data)).cast("B")
        row = self.row_bytes
        # What this chunk holds: the head that goes on filling the open
        # row, whole rows, and the tail that opens the next one.
        head = min(mv.nbytes, row - len(self.carry)) if self.carry else 0
        whole = (mv.nbytes - head) // row * row
        tail = mv.nbytes - head - whole
        block = self._view(mv, head, whole) if whole else None
        viewed = block is not None
        copied = head + tail + (0 if viewed else whole)
        closed = None
        if copied:
            # Siblings of the HtoD dispatches below, ahead of them all.
            with _hostcopy_span(self.path, copied):
                if head:
                    self.carry += mv[:head]
                    if len(self.carry) == row:
                        closed, self.carry = self.carry, bytearray()
                if whole and not viewed:
                    block = np.frombuffer(
                        bytearray(mv[head : head + whole]), self.np_dtype
                    )
                if tail:
                    self.carry = bytearray(mv[head + whole :])
        if closed is not None:
            self._put(np.frombuffer(closed, self.np_dtype), viewed=False)
        if whole:
            self._put(block, viewed)

    def _put(self, block: np.ndarray, viewed: bool) -> None:
        import jax

        if self._device is None and self.dest.committed:
            self._device = next(iter(self.dest.sharding.device_set))
        rows = block.nbytes // self.row_bytes
        # device_put returns immediately (transfer proceeds in the
        # background) and what `block` views stays alive through the
        # block's buffer reference — and is never mutated again, so a
        # zero-copy CPU device_put is safe too.
        with telemetry.span(
            "sub_chunk_htod", cat="consumer", path=self.path, bytes=block.nbytes
        ):
            self.blocks.append(
                jax.device_put(block.reshape((rows,) + self.row_shape), self._device)
            )
        telemetry.counter_add(
            "bytes_htod_views" if viewed else "bytes_htod_copied", block.nbytes
        )
        self.rows += rows

    def finish(self) -> None:
        import jax

        if self.carry:
            raise IOError(
                f"read stream ended mid-row: {len(self.carry)} trailing "
                f"bytes do not fill a {self.row_bytes}-byte row"
            )
        if self.rows != self.total_rows:
            raise IOError(
                f"short read stream: produced {self.rows} of "
                f"{self.total_rows} rows"
            )
        # Device-side assembly: concatenate, placement, cast, hand-over.
        with telemetry.span(
            "consume_assemble", cat="consumer", path=self.path,
            blocks=len(self.blocks),
        ):
            blocks, self.blocks = self.blocks, []
            full = (
                blocks[0]
                if len(blocks) == 1 and blocks[0].shape == self.shape
                else _device_assembler()(self.shape, *blocks)
            )
            del blocks
            restored = (
                jax.device_put(full, self.dest.sharding)
                if self.dest.committed
                else full
            )
            if self.dest.needs_cast:
                restored = restored.astype(self.dest.dst_dtype)
            if self.dest.callback is not None:
                self.dest.callback(restored)


class _IncrementalEntryDecoder:
    """Per-sub-chunk verify + decompress for one entry's streamed
    payload: the chained CRC advances over the STORED bytes exactly as
    the buffered `verify_checksum` would hash them, decompression (when
    the entry records a codec) feeds the same chunk through a streaming
    decompressor, and decoded raw bytes flow to ``sink_add``. ``finish``
    flushes the codec tail and raises on checksum mismatch BEFORE the
    caller commits anything."""

    def __init__(self, entry: "ArrayEntry", sink_add: Callable) -> None:
        from ..compression import StreamingDecompressor
        from ..integrity import IncrementalVerifier

        self.path = entry.location
        self.verifier = IncrementalVerifier(entry.checksum, entry.location)
        self.decomp = (
            StreamingDecompressor(
                entry.codec,
                expected_size=array_size_bytes(entry.shape, entry.dtype),
            )
            if entry.codec is not None
            else None
        )
        self.sink_add = sink_add

    def add(self, chunk) -> None:
        nbytes = memoryview(chunk).nbytes
        with telemetry.span("consume_chunk", cat="consumer", bytes=nbytes):
            with telemetry.span(
                "consume_verify", cat="consumer", path=self.path, bytes=nbytes
            ):
                self.verifier.update(chunk)
            data = self.decomp.feed(chunk) if self.decomp is not None else chunk
            if memoryview(data).nbytes:
                self.sink_add(data)

    def finish(self) -> None:
        if self.decomp is not None:
            tail = self.decomp.finish()
            if tail:
                self.sink_add(tail)
        with telemetry.span("consume_verify", cat="consumer", path=self.path):
            self.verifier.finish()


def _entry_stored_size(entry: "ArrayEntry") -> int:
    """Bytes storage will deliver for ``entry`` — the byte range for
    slab-packed payloads, the serialized size otherwise (compressed
    payloads' stored size isn't recorded; the raw size is the proxy the
    streaming election uses)."""
    if entry.byte_range is not None:
        lo, hi = entry.byte_range
        return max(0, hi - lo)
    return array_size_bytes(entry.shape, entry.dtype)


class ArrayBufferConsumer(BufferConsumer):
    """Deserializes into ``dst_view`` (if given) and invokes ``callback`` with
    the host array. Exactly one of the two is typically used."""

    def __init__(
        self,
        entry: ArrayEntry,
        dst_view: Optional[np.ndarray] = None,
        callback: Optional[Callable[[np.ndarray], None]] = None,
        ensure_writable: bool = True,
        device_dest: Optional[DeviceMaterializer] = None,
    ) -> None:
        self.entry = entry
        self.dst_view = dst_view
        self.callback = callback
        # User-facing host arrays (read_state_dict, host callbacks) must be
        # writable even when the storage plugin hands back immutable bytes
        # (S3/GCS); device-materialize callbacks opt out — device_put never
        # needs a writable source and the copy would be pure waste.
        self.ensure_writable = ensure_writable
        # Streamed consumes of jax destinations device_put per sub-chunk
        # through this instead of the host-array callback (which is the
        # buffered path's one-shot device_put).
        self.device_dest = device_dest

    def _deliver(self, buf: BufferType) -> None:
        """Commit a VERIFIED, DECOMPRESSED raw payload to the
        destination — the tail both the buffered and the streamed
        scratch path share."""
        arr = array_from_buffer(buf, self.entry.dtype, self.entry.shape)
        if (
            self.dst_view is None
            and self.callback is not None
            and self.ensure_writable
            and not arr.flags["WRITEABLE"]
        ):
            with _hostcopy_span(self.entry.location, arr.nbytes):
                arr = np.array(arr)
        if self.dst_view is not None:
            with _hostcopy_span(self.entry.location, arr.nbytes):
                fast_copyto(self.dst_view, arr)
            if self.callback is not None:
                self.callback(self.dst_view)
        elif self.callback is not None:
            self.callback(arr)

    def _consume_sync(self, buf: BufferType) -> None:
        if self.entry.checksum is not None:
            from ..integrity import verification_enabled, verify_checksum

            # This consumer always receives the entry's complete payload
            # (whole file, or the entry's byte_range within a batched slab),
            # so the recorded checksum applies directly.
            if verification_enabled():
                with telemetry.span(
                    "consume_verify", cat="consumer", path=self.entry.location,
                    bytes=memoryview(buf).nbytes,
                ):
                    verify_checksum(buf, self.entry.checksum, self.entry.location)
        if self.entry.codec is not None:
            from ..compression import decompress

            buf = decompress(
                self.entry.codec,
                buf,
                expected_size=array_size_bytes(
                    self.entry.shape, self.entry.dtype
                ),
            )
        self._deliver(buf)

    async def consume_buffer(self, buf: BufferType, executor=None) -> None:
        submit = _executor_submit(executor, self.entry.location)
        await submit(self._consume_sync, buf)

    def get_consuming_cost_bytes(self) -> int:
        return array_size_bytes(self.entry.shape, self.entry.dtype)

    # ----------------------------------------------------- streaming path

    def _device_sink_ok(self) -> bool:
        """The per-sub-chunk device sink applies to SINGLE-DEVICE
        destinations only: the sink assembles row blocks on one device
        (transiently ~2x the entry there — bounded, since entries
        reaching this consumer are <=512 MB by the chunking policy), and
        for a replicated multi-device destination that assembly would
        add a pointless extra broadcast hop over the buffered path's
        direct sharded device_put — those stream through the scratch
        path instead."""
        if self.dst_view is not None or self.device_dest is None:
            return False
        shape = tuple(self.entry.shape)
        if len(shape) < 1 or shape[0] < 1:
            return False
        try:
            if len(self.device_dest.sharding.device_set) != 1:
                return False
        except AttributeError:
            return False
        return True

    def _device_mode_ok(self, sub_chunk_bytes: int) -> bool:
        """Device sink AND rows no wider than the sub-chunk: wider rows
        would grow the carry past the window the budget charges — such
        shapes still use the device sink but declare full cost."""
        if not self._device_sink_ok():
            return False
        shape = tuple(self.entry.shape)
        raw = array_size_bytes(shape, self.entry.dtype)
        row_bytes = raw // shape[0]
        return 0 < row_bytes <= sub_chunk_bytes

    def can_stream(self, sub_chunk_bytes: int) -> bool:
        """This consumer streams whenever the payload spans several
        sub-chunks and its codec (if any) decompresses incrementally.
        Checksums never block: the chained CRC is bit-identical to the
        whole-buffer hash, and the skip rules (unknown algorithm, crc32c
        without the native extension, verification disabled) mirror the
        buffered path's."""
        from ..compression import StreamingDecompressor

        if _entry_stored_size(self.entry) < 2 * sub_chunk_bytes:
            return False
        return StreamingDecompressor.available(self.entry.codec)

    def stream_admission_cost(self, sub_chunk_bytes: int) -> int:
        cost = self.get_consuming_cost_bytes()
        if self._device_sink_ok() and _sink_row_bytes(self.entry) <= sub_chunk_bytes:
            # Chunk being decoded + the plugin's read-ahead + the row
            # carry: the window the device sink actually holds. Its row
            # is a short run of trailing dimensions; only a LAST
            # dimension wider than the sub-chunk still grows the carry
            # past the window, and declares the full cost below.
            from ..io_types import STREAM_DEPTH

            return min(cost, (STREAM_DEPTH + 1) * sub_chunk_bytes)
        # Scratch assembly (verify-before-commit into host memory) holds
        # the full payload — declare it honestly.
        return cost

    async def consume_stream(self, stream, executor=None) -> None:
        # Sink choice is shape-driven, not size-driven: eligible jax
        # destinations take the windowed device sink regardless of the
        # row/sub-chunk ratio (the budget already charged whichever cost
        # stream_admission_cost declared).
        if self._device_sink_ok():
            sink = _DeviceRowSink(self.entry, self.device_dest)
            scratch = None
        else:
            scratch = _ScratchSink(
                array_size_bytes(self.entry.shape, self.entry.dtype),
                self.entry.location,
            )
            sink = scratch
        decoder = _IncrementalEntryDecoder(self.entry, sink.add)
        submit = _executor_submit(executor, self.entry.location)

        def finish() -> None:
            decoder.finish()  # checksum mismatch raises BEFORE any commit
            if scratch is not None:
                self._deliver(scratch.finish())
            else:
                sink.finish()

        async for chunk in stream.chunks:
            await submit(decoder.add, chunk)
        await submit(finish)


class ArrayIOPreparer:
    @staticmethod
    def prepare_write(
        storage_path: str,
        arr,
        replicated: bool = False,
        index: Optional[Tuple[slice, ...]] = None,
    ) -> Tuple[ArrayEntry, List[WriteReq]]:
        """One payload: ``arr``, or with ``index`` the chunk ``arr[index]``,
        which the stager cuts when it stages it."""
        stager = ArrayBufferStager(arr, index=index)
        stager.entry = entry = ArrayEntry(
            location=storage_path,
            serializer=Serializer.BUFFER_PROTOCOL.value,
            dtype=dtype_to_string(arr.dtype),
            shape=list(stager.shape),
            replicated=replicated,
        )
        return entry, [WriteReq(path=storage_path, buffer_stager=stager)]

    @staticmethod
    def prepare_read(
        entry: ArrayEntry,
        dst_view: Optional[np.ndarray] = None,
        callback: Optional[Callable[[np.ndarray], None]] = None,
        buffer_size_limit_bytes: Optional[int] = None,
        ensure_writable: bool = True,
        device_dest: Optional[DeviceMaterializer] = None,
    ) -> List[ReadReq]:
        # Compressed payloads can't be read by byte sub-ranges (no random
        # access into the stream): whole-entry read, budget or not.
        # Entries are <=512 MB by the chunking policy, so the budget's
        # purpose (bounding single-buffer size) still roughly holds.
        if buffer_size_limit_bytes is None or entry.codec is not None:
            consumer = ArrayBufferConsumer(
                entry,
                dst_view=dst_view,
                callback=callback,
                ensure_writable=ensure_writable,
                device_dest=device_dest,
            )
            byte_range = (
                tuple(entry.byte_range) if entry.byte_range is not None else None
            )
            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=consumer,
                    byte_range=byte_range,
                    origin=entry.origin,
                )
            ]
        return _prepare_chunked_read(entry, dst_view, callback, buffer_size_limit_bytes)


class _SlicedArrayConsumer(BufferConsumer):
    """Consumes one byte-range of a serialized array into the matching flat
    slice of the destination (chunked reads under a memory budget,
    reference: io_preparer.py:672-718)."""

    def __init__(
        self,
        entry: ArrayEntry,
        assembler: "ArrayAssembler",
        elem_lo: int,
        elem_hi: int,
    ) -> None:
        self.entry = entry
        self.assembler = assembler
        self.elem_lo = elem_lo
        self.elem_hi = elem_hi

    def _consume_sync(self, buf: BufferType) -> None:
        from ..serialization import string_to_dtype

        flat = np.frombuffer(buf, dtype=np.uint8).view(string_to_dtype(self.entry.dtype))
        self.assembler.fill_flat(
            self.elem_lo, self.elem_hi, flat, self.entry.location
        )

    async def consume_buffer(self, buf: BufferType, executor=None) -> None:
        submit = _executor_submit(executor, self.entry.location)
        await submit(self._consume_sync, buf)

    def get_consuming_cost_bytes(self) -> int:
        itemsize = array_size_bytes((1,), self.entry.dtype)
        return (self.elem_hi - self.elem_lo) * itemsize

    # ----------------------------------------------------- streaming path

    def _direct_flat_bytes(self) -> Optional[np.ndarray]:
        """The destination's raw-byte view for direct incremental fills,
        or None when bytes can't land verbatim (a same-kind dtype cast is
        pending — the buffered path's element-wise copy handles that)."""
        flat = self.assembler._flat
        if flat.dtype != string_to_dtype(self.entry.dtype):
            return None
        if not flat.flags["C_CONTIGUOUS"]:
            return None
        return flat.view(np.uint8)

    def can_stream(self, sub_chunk_bytes: int) -> bool:
        # Budget-split sub-range reads carry no checksum or codec (the
        # whole-entry consumer owns those), so streaming is a plain
        # incremental byte fill of pre-existing assembler memory — the
        # same partial-fill-on-failure semantics a buffered failure
        # between this entry's sub-reads already has.
        if self.get_consuming_cost_bytes() < 2 * sub_chunk_bytes:
            return False
        return self._direct_flat_bytes() is not None

    def stream_admission_cost(self, sub_chunk_bytes: int) -> int:
        from ..io_types import STREAM_DEPTH

        # The destination is assembler memory that pre-exists this read;
        # only the in-flight chunks are new.
        return min(
            self.get_consuming_cost_bytes(), STREAM_DEPTH * sub_chunk_bytes
        )

    async def consume_stream(self, stream, executor=None) -> None:
        itemsize = array_size_bytes((1,), self.entry.dtype)
        dst = self._direct_flat_bytes()
        base = self.elem_lo * itemsize
        total = (self.elem_hi - self.elem_lo) * itemsize
        pos = 0

        def fill(chunk) -> int:
            mv = memoryview(chunk).cast("B")
            with telemetry.span("consume_chunk", cat="consumer", bytes=mv.nbytes):
                if pos + mv.nbytes > total:
                    raise IOError(
                        f"read stream produced more than the expected "
                        f"{total} bytes for {self.entry.location}"
                    )
                with _hostcopy_span(self.entry.location, mv.nbytes):
                    dst[base + pos : base + pos + mv.nbytes] = np.frombuffer(
                        mv, np.uint8
                    )
            return mv.nbytes

        submit = _executor_submit(executor, self.entry.location)
        async for chunk in stream.chunks:
            pos += await submit(fill, chunk)
        if pos != total:
            raise IOError(
                f"short read stream for {self.entry.location}: produced "
                f"{pos} of {total} bytes"
            )
        self.assembler.part_done(self.entry.location)


class ArrayAssembler:
    """Accumulates partial fills of one destination array; fires ``callback``
    when the last part lands. Shared by chunked and sharded restores."""

    def __init__(
        self,
        dst: np.ndarray,
        num_parts: int,
        callback: Optional[Callable[[np.ndarray], None]] = None,
    ) -> None:
        self.dst = dst
        # reshape(-1) on a non-contiguous view returns a COPY, so flat fills
        # would be lost; assemble into a contiguous scratch instead and copy
        # back once on completion (reference covers strided/offset dst views,
        # tests/test_tensor_io_preparer.py:158-181).
        if dst.flags["C_CONTIGUOUS"]:
            self._scratch = dst
        else:
            # Seed with current contents so partially-covering fills (e.g. a
            # destination only some regions overlap) don't clobber the rest.
            self._scratch = np.ascontiguousarray(dst)
        self._flat = self._scratch.reshape(-1)
        self._remaining = num_parts
        self._lock = threading.Lock()
        self.callback = callback

    def region_view(self, index: Tuple[slice, ...]) -> np.ndarray:
        """A writable view of the assembly target for ``index``. Callers that
        write sub-regions directly (e.g. budgeted chunk reads) MUST write into
        this view, never into ``dst`` itself: when ``dst`` is non-contiguous
        the assembly happens in a scratch buffer that is copied back over
        ``dst`` on completion, which would clobber direct writes."""
        return self._scratch[index] if index else self._scratch

    # ``path`` is the read a part arrived in: what its copy's span carries.

    def fill_flat(
        self, elem_lo: int, elem_hi: int, values: np.ndarray, path: Optional[str] = None
    ) -> None:
        with _hostcopy_span(path, values.nbytes):
            fast_copyto(self._flat[elem_lo:elem_hi], values)
        self.part_done(path)

    def fill_region(
        self,
        index: Tuple[slice, ...],
        values: np.ndarray,
        path: Optional[str] = None,
    ) -> None:
        with _hostcopy_span(path, values.nbytes):
            fast_copyto(self.region_view(index), values)
        self.part_done(path)

    def part_done(self, path: Optional[str] = None) -> None:
        # Parts are consumed concurrently from executor threads.
        with self._lock:
            self._remaining -= 1
            remaining = self._remaining
        if remaining == 0:
            if self._scratch is not self.dst:
                with _hostcopy_span(path, self._scratch.nbytes):
                    fast_copyto(self.dst, self._scratch)
            if self.callback is not None:
                self.callback(self.dst)


def _prepare_chunked_read(
    entry: ArrayEntry,
    dst_view: Optional[np.ndarray],
    callback: Optional[Callable[[np.ndarray], None]],
    buffer_size_limit_bytes: int,
) -> List[ReadReq]:
    itemsize = array_size_bytes((1,), entry.dtype)
    total_elems = int(np.prod(entry.shape, dtype=np.int64)) if entry.shape else 1
    elems_per_read = max(1, buffer_size_limit_bytes // itemsize)

    if dst_view is None:
        from ..serialization import string_to_dtype

        dst_view = np.empty(tuple(entry.shape), dtype=string_to_dtype(entry.dtype))

    ranges = []
    lo = 0
    while lo < total_elems:
        hi = min(lo + elems_per_read, total_elems)
        ranges.append((lo, hi))
        lo = hi
    if not ranges:
        ranges = [(0, 0)]

    assembler = ArrayAssembler(dst_view, num_parts=len(ranges), callback=callback)
    base = entry.byte_range[0] if entry.byte_range is not None else 0
    read_reqs = []
    for elem_lo, elem_hi in ranges:
        read_reqs.append(
            ReadReq(
                path=entry.location,
                buffer_consumer=_SlicedArrayConsumer(entry, assembler, elem_lo, elem_hi),
                byte_range=(base + elem_lo * itemsize, base + elem_hi * itemsize),
                origin=entry.origin,
            )
        )
    return read_reqs


def get_array_size_from_entry(entry: ArrayEntry) -> int:
    return array_size_bytes(entry.shape, entry.dtype)
