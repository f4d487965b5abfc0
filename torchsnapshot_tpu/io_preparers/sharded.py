"""GSPMD-sharded array preparer: the resharding engine.

TPU-native redesign of the reference's ShardedTensorIOPreparer
(io_preparer.py:164-490). The shard spec is ``jax.sharding`` itself: each
shard's N-D global offsets/sizes are derived from
``sharding.devices_indices_map`` — exactly the reference's
``Shard{offsets,sizes}`` schema (manifest.py:72-76), so snapshots are
world-size- and layout-independent.

Save:
- The global device->index map is computed identically on every process.
  Unique shard *boxes* are deduplicated: GSPMD layouts routinely replicate a
  shard across processes (e.g. params sharded over 'model' and replicated
  over 'data'), and without dedup every process would write every shard
  (SURVEY §7 hard-parts). The writer for each box is chosen by a
  deterministic hash over the box, balanced across the processes that hold
  it — no communication needed.
- Each owned box is subdivided along its largest dimension to <=512 MB
  (reference: subdivide_shard, io_preparer.py:167-197) and staged via async
  DtoH DMA per sub-shard.

Restore (reference: io_preparer.py:199-246,315-389):
- Destination boxes come from the *destination* array's sharding (one host
  buffer per unique addressable box — never the full array, so host memory
  scales with 1/num_hosts).
- Each saved shard overlapping any destination box is read once and
  scattered into all overlapping regions.
- When the last region lands, the global array is materialized with
  ``jax.make_array_from_callback`` under the destination sharding (HtoD).
- A plain numpy destination (or none) acts as a single box covering the
  whole array — the ShardedTensor->Tensor path (reference:
  io_preparer.py:330-342).
"""

from __future__ import annotations

import hashlib
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..io_types import BufferConsumer, BufferType, ReadReq, WriteReq
from ..manifest import ArrayEntry, Shard, ShardedArrayEntry
from ..serialization import (
    array_from_buffer,
    array_size_bytes,
    dtype_to_string,
    string_to_dtype,
)
from .. import telemetry
from .array import (
    ArrayBufferStager,
    _executor_submit,
    _hostcopy_span,
    _placing_thread,
    fast_copyto,
)

DEFAULT_MAX_SHARD_SIZE_BYTES = 512 * 1024 * 1024

Box = Tuple[Tuple[int, int], ...]  # ((start, stop) per dim)


def _normalize_index(index: Tuple[slice, ...], shape: Tuple[int, ...]) -> Box:
    out = []
    for sl, dim in zip(index, shape):
        start, stop, step = sl.indices(dim)
        assert step == 1, "strided shardings are not supported"
        out.append((start, stop))
    # 0-d or rank-deficient index tuples: pad to full rank
    for dim in shape[len(index):]:
        out.append((0, dim))
    return tuple(out)


def _box_key(box: Box) -> str:
    return "_".join(f"{a}.{b}" for a, b in box)


def _stable_owner(box: Box, holders: List[int]) -> int:
    """Deterministic, load-spreading choice of writer among holder processes."""
    digest = hashlib.md5(_box_key(box).encode()).digest()
    return sorted(holders)[int.from_bytes(digest[:4], "big") % len(holders)]


def _overlap(
    saved_off: List[int], saved_sz: List[int], box: Box
) -> Optional[Tuple[Tuple[slice, ...], Tuple[slice, ...]]]:
    """(view into saved shard, view into destination box) or None."""
    src_slices = []
    dst_slices = []
    for (d_lo, d_hi), s_lo, s_sz in zip(box, saved_off, saved_sz):
        lo = max(s_lo, d_lo)
        hi = min(s_lo + s_sz, d_hi)
        if lo >= hi:
            return None
        src_slices.append(slice(lo - s_lo, hi - s_lo))
        dst_slices.append(slice(lo - d_lo, hi - d_lo))
    return tuple(src_slices), tuple(dst_slices)


def _subdivide(
    offsets: List[int], sizes: List[int], itemsize: int, max_bytes: int
) -> List[Tuple[List[int], List[int]]]:
    """Split a box into <=max_bytes pieces along its largest dimension."""
    nbytes = int(np.prod(sizes, dtype=np.int64)) * itemsize if sizes else itemsize
    if nbytes <= max_bytes or not sizes:
        return [(list(offsets), list(sizes))]
    dim = int(np.argmax(sizes))
    other = (nbytes // max(sizes[dim], 1)) or 1  # bytes per unit along dim
    rows_per_piece = max(1, max_bytes // other)
    pieces = []
    lo = 0
    while lo < sizes[dim]:
        hi = min(lo + rows_per_piece, sizes[dim])
        p_off = list(offsets)
        p_sz = list(sizes)
        p_off[dim] = offsets[dim] + lo
        p_sz[dim] = hi - lo
        pieces.append((p_off, p_sz))
        lo = hi
    return pieces


def _make_assembler(local: Dict[Box, Any], overlaps, piece_shape):
    """Thunk assembling a saved piece from this process's overlapping
    shard regions, on ONE local device (cross-device moves are DtoD —
    they ride ICI on TPU, never the host). Used by the restore-side
    digest check to verify a piece that no single addressable shard
    contains; called windowed by fingerprints_match, so at most a few
    assembled pieces are live at a time. The caller guarantees the
    overlap regions exactly cover the piece.

    Transient footprint is ~2x the piece's size, not 1x: the zeroed
    assembly target coexists with the device_put copies of every
    overlapping part until the last ``.at[].set`` lands. Window items
    built from this thunk must account the 2x as their cost
    (fingerprints_match's ``cost_bytes``) so a window of assembled
    pieces stays under MATCH_WINDOW_BYTES of REAL device memory."""

    def assemble():
        import jax
        import jax.numpy as jnp

        (box0, (src0, dst0)), *rest = overlaps
        part0 = local[box0][dst0] if dst0 else local[box0]
        dev = next(iter(part0.devices())) if hasattr(part0, "devices") else None
        piece = jax.device_put(jnp.zeros(piece_shape, part0.dtype), dev)
        piece = piece.at[src0].set(part0)
        for box, (src, dst) in rest:
            part = local[box][dst] if dst else local[box]
            piece = piece.at[src].set(jax.device_put(part, dev))
        return piece

    return assemble


class _ShardScatterConsumer(BufferConsumer):
    """Reads one saved shard and scatters it into every overlapping region of
    the destination boxes."""

    def __init__(
        self,
        shard: Shard,
        targets: List[Tuple[np.ndarray, Tuple[slice, ...], Tuple[slice, ...]]],
        completion: "_Completion",
    ) -> None:
        self.shard = shard
        self.targets = targets  # (dst_buffer, src_slices, dst_slices)
        self.completion = completion

    def _decode(self, buf: BufferType) -> np.ndarray:
        """Stored payload -> decoded shard array (verify -> decompress ->
        view). Shared with the planned-reshard owner consumer
        (reshard.PlannedOwnerConsumer), which must forward regions of the
        decoded array before scattering."""
        if self.shard.array.checksum is not None:
            from ..integrity import verification_enabled, verify_checksum

            # Each saved shard is read exactly once, in full.
            if verification_enabled():
                with telemetry.span(
                    "consume_verify", cat="consumer",
                    path=self.shard.array.location,
                    bytes=memoryview(buf).nbytes,
                ):
                    verify_checksum(
                        buf, self.shard.array.checksum, self.shard.array.location
                    )
        if self.shard.array.codec is not None:
            from ..compression import decompress
            from ..serialization import array_size_bytes

            buf = decompress(
                self.shard.array.codec,
                buf,
                expected_size=array_size_bytes(
                    self.shard.array.shape, self.shard.array.dtype
                ),
            )
        return array_from_buffer(
            buf, self.shard.array.dtype, self.shard.array.shape
        )

    def _copy_to_boxes(self, arr: np.ndarray) -> None:
        with _hostcopy_span(self.shard.array.location, arr.nbytes):
            for dst_buf, src_slices, dst_slices in self.targets:
                target = dst_buf[dst_slices] if dst_slices else dst_buf
                fast_copyto(target, arr[src_slices] if src_slices else arr)

    def _scatter(self, arr: np.ndarray) -> None:
        self._copy_to_boxes(arr)
        self.completion.part_done(self.shard.array.location)

    def _consume_sync(self, buf: BufferType) -> None:
        self._scatter(self._decode(buf))

    async def consume_buffer(self, buf: BufferType, executor=None) -> None:
        submit = _executor_submit(executor, self.shard.array.location)
        await submit(self._consume_sync, buf)

    def get_consuming_cost_bytes(self) -> int:
        return array_size_bytes(self.shard.array.shape, self.shard.array.dtype)

    # ----------------------------------------------------- streaming path

    def can_stream(self, sub_chunk_bytes: int) -> bool:
        """Streamed shard consumes verify the chained CRC and feed
        decompression per sub-chunk WHILE later sub-chunks are still on
        the wire; the scatter into destination boxes happens only after
        the checksum validated (verify-before-commit, like the buffered
        path), so the full shard scratch is retained and the declared
        admission cost stays the default full consuming cost."""
        from ..compression import StreamingDecompressor
        from .array import _entry_stored_size

        if _entry_stored_size(self.shard.array) < 2 * sub_chunk_bytes:
            return False
        return StreamingDecompressor.available(self.shard.array.codec)

    async def consume_stream(self, stream, executor=None) -> None:
        from .array import _IncrementalEntryDecoder, _ScratchSink

        entry = self.shard.array
        scratch = _ScratchSink(
            array_size_bytes(entry.shape, entry.dtype), entry.location
        )
        decoder = _IncrementalEntryDecoder(entry, scratch.add)

        def finish() -> None:
            decoder.finish()  # checksum mismatch raises BEFORE the scatter
            self._copy_to_boxes(
                array_from_buffer(scratch.finish(), entry.dtype, entry.shape)
            )

        submit = _executor_submit(executor, entry.location)
        async for chunk in stream.chunks:
            await submit(decoder.add, chunk)
        await submit(finish)
        self.completion.part_done(entry.location)


class _Completion:
    """Counts a leaf's shards in; the last one runs ``finalize(path)``,
    ``path`` being that shard's location: the read under whose span the
    leaf is placed."""

    def __init__(
        self, num_parts: int, finalize: Callable[[Optional[str]], None]
    ) -> None:
        self._remaining = num_parts
        self._finalize = finalize
        self._lock = threading.Lock()

    def part_done(self, path: Optional[str] = None) -> None:
        # Parts are consumed concurrently from executor threads.
        with self._lock:
            self._remaining -= 1
            remaining = self._remaining
        if remaining == 0:
            self._finalize(path)


class ShardedArrayIOPreparer:
    max_shard_size_bytes: int = DEFAULT_MAX_SHARD_SIZE_BYTES

    # ------------------------------------------------------------------ save

    @staticmethod
    def _elected_local_boxes(sharding, shape, addressable_shards):
        """Yield ``(box, data)`` for every unique shard box this process
        is ELECTED to act for: the dedup + hash-balanced election shared
        by the save-side writer partition (``_owned_pieces``) and
        restore-side distributed digest verification
        (``partial_digest_contributions``) — one definition, so the two
        sides can never disagree about ownership."""
        import jax

        process_index = jax.process_index()
        # box -> holder process indices (computed identically everywhere)
        holders: Dict[Box, List[int]] = {}
        for device, index in sharding.devices_indices_map(shape).items():
            box = _normalize_index(index, shape)
            holders.setdefault(box, []).append(device.process_index)
        local_data: Dict[Box, Any] = {}
        for shard in addressable_shards:
            box = _normalize_index(shard.index, shape)
            if box not in local_data:
                local_data[box] = shard.data
        for box in sorted(holders.keys()):
            if _stable_owner(box, holders[box]) != process_index:
                continue
            data = local_data.get(box)
            if data is None:  # pragma: no cover - owner is always a holder
                continue
            yield box, data

    @classmethod
    def _owned_pieces(cls, arr, itemsize: Optional[int] = None):
        """Yield ``(p_off, p_sz, get_piece)`` for every piece THIS process
        writes: its owned boxes (deduped, hash-balanced election), each
        subdivided to the shard size cap. ``get_piece`` is a thunk — the
        device-array slice only dispatches when called, so size-only
        consumers (the staging warmup) never materialize data. The single
        source of the write partition: prepare_write builds entries from
        it, warmup_staging sizes pool slabs from it. ``itemsize`` lets the
        warmup subdivide at the dtype a save_dtype-converted save will
        actually stage (boundaries depend on itemsize)."""
        shape = tuple(arr.shape)
        if itemsize is None:
            itemsize = string_to_dtype(dtype_to_string(arr.dtype)).itemsize

        for box, data in cls._elected_local_boxes(
            arr.sharding, shape, arr.addressable_shards
        ):
            offsets = [lo for lo, _ in box]
            sizes = [hi - lo for lo, hi in box]
            for p_off, p_sz in _subdivide(
                offsets, sizes, itemsize, cls.max_shard_size_bytes
            ):
                local_slices = tuple(
                    slice(po - o, po - o + ps)
                    for po, o, ps in zip(p_off, offsets, p_sz)
                )

                def get_piece(data=data, local_slices=local_slices):
                    return data[local_slices] if local_slices else data

                yield p_off, p_sz, get_piece

    @classmethod
    def staged_piece_sizes(cls, arr, dtype: Optional[str] = None) -> List[int]:
        """Byte sizes of the staging buffers this process will draw for
        ``arr`` (pool-warmup planning; no data is touched). ``dtype``
        overrides the array's own (save_dtype-converted saves)."""
        itemsize = string_to_dtype(
            dtype if dtype is not None else dtype_to_string(arr.dtype)
        ).itemsize
        sizes = []
        for _, p_sz, _ in cls._owned_pieces(arr, itemsize=itemsize):
            n = itemsize
            for s in p_sz:
                n *= s
            sizes.append(n)
        return sizes

    @classmethod
    def prepare_write(
        cls, storage_path_prefix: str, arr
    ) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
        dtype_str = dtype_to_string(arr.dtype)
        shards: List[Shard] = []
        write_reqs: List[WriteReq] = []
        for p_off, p_sz, get_piece in cls._owned_pieces(arr):
            location = f"{storage_path_prefix}_{'_'.join(map(str, p_off))}"
            entry = ArrayEntry(
                location=location,
                serializer="buffer_protocol",
                dtype=dtype_str,
                shape=list(p_sz),
                replicated=False,
            )
            shards.append(Shard(offsets=list(p_off), sizes=list(p_sz), array=entry))
            write_reqs.append(
                WriteReq(
                    path=location,
                    buffer_stager=ArrayBufferStager(get_piece(), entry),
                )
            )
        return (
            ShardedArrayEntry(dtype=dtype_str, shape=list(arr.shape), shards=shards),
            write_reqs,
        )

    # --------------------------------------------------------------- restore

    @classmethod
    def _dst_already_matches(cls, entry: ShardedArrayEntry, obj_out) -> bool:
        """True when the destination already holds every saved piece's
        content, proven by on-device fingerprints (device_digest.py).

        Each rank verifies only pieces overlapping ITS addressable shards
        — remote pieces are verified (or read) by the rank that owns
        them; a skip here never changes what other ranks do, because the
        local decision only keeps/rebuilds the local handle of the same
        logical values. Conservative on every edge: a missing
        fingerprint, dtype difference, or a piece this rank cannot
        fingerprint locally means False (read normally).

        A piece is locally verifiable when it is contained in ONE
        addressable shard (zero-copy slice) or, failing that, when the
        UNION of this process's addressable shards covers it — the
        overlap regions are stitched together on device and the
        assembled piece fingerprinted (pod topologies: a process owning
        several boxes can verify across a layout change, e.g. a serving
        mesh transposed from the training mesh). Only a piece cut
        across PROCESS boundaries still falls back to a normal read:
        its digest covers the whole piece and no single process holds
        all of its bytes."""
        from ..device_digest import fingerprints_match

        if dtype_to_string(obj_out.dtype) != entry.dtype:
            return False
        shape = tuple(entry.shape)
        if getattr(obj_out, "is_fully_addressable", False):
            # Global slices work (XLA gathers across local devices), so
            # pieces from ANY saved sharding layout are verifiable.
            if not entry.shards or any(
                s.array.device_digest is None for s in entry.shards
            ):
                return False
            # Windowed: a few piece slices live at a time (dispatched
            # together per window, dropped before the next window), so
            # verification never duplicates the array's footprint.
            return fingerprints_match(
                (
                    (
                        array_size_bytes(s.sizes, entry.dtype),
                        lambda s=s: obj_out[
                            tuple(
                                slice(o, o + sz)
                                for o, sz in zip(s.offsets, s.sizes)
                            )
                        ],
                        s.array.device_digest,
                    )
                    for s in entry.shards
                )
            )
        # Multi-process: only shard.data (single-device) is sliceable.
        # Verify every piece overlapping an addressable box: contained in
        # one shard -> zero-copy slice; covered by the UNION of local
        # shards -> assembled on device; else unverifiable locally.
        local: Dict[Box, Any] = {}
        for s in obj_out.addressable_shards:
            local.setdefault(_normalize_index(s.index, shape), s.data)
        to_check: List[Tuple[int, Any, str]] = []  # (nbytes, thunk, digest)
        for shard in entry.shards:
            piece: Box = tuple(
                (o, o + sz) for o, sz in zip(shard.offsets, shard.sizes)
            )
            overlaps = [
                (box, ov)
                for box in local
                for ov in (_overlap(shard.offsets, shard.sizes, box),)
                if ov is not None
            ]
            if not overlaps:
                continue  # some other rank's piece
            if shard.array.device_digest is None:
                return False
            container = next(
                (
                    box
                    for box, _ in overlaps
                    if all(
                        lo >= blo and hi <= bhi
                        for (lo, hi), (blo, bhi) in zip(piece, box)
                    )
                ),
                None,
            )
            if container is not None:
                local_slices = tuple(
                    slice(lo - blo, hi - blo)
                    for (lo, hi), (blo, _) in zip(piece, container)
                )
                to_check.append(
                    (
                        array_size_bytes(shard.sizes, entry.dtype),
                        lambda c=container, ls=local_slices: local[c][ls],
                        shard.array.device_digest,
                    )
                )
                continue
            # Union coverage: distinct GSPMD boxes are disjoint, so the
            # piece is fully covered iff the overlap volumes sum to its
            # volume. A cell owned by another process means a shortfall
            # -> this piece is unverifiable here (digest spans bytes this
            # process doesn't hold).
            piece_vol = int(np.prod(shard.sizes, dtype=np.int64))
            covered = sum(
                int(
                    np.prod(
                        [s.stop - s.start for s in src], dtype=np.int64
                    )
                )
                for _, (src, _) in overlaps
            )
            if covered != piece_vol:
                return False
            piece_bytes = array_size_bytes(shard.sizes, entry.dtype)
            to_check.append(
                (
                    piece_bytes,
                    _make_assembler(local, overlaps, tuple(shard.sizes)),
                    shard.array.device_digest,
                    # Assembly transiently holds the zeroed piece PLUS
                    # device copies of the overlapping parts — ~2x the
                    # piece — so the window budget is charged 2x
                    # (ADVICE r5 low #2).
                    2 * piece_bytes,
                )
            )
        if not to_check:
            return False
        # Thunks: slices/assemblies materialize windowed inside
        # fingerprints_match, never all at once.
        return fingerprints_match(to_check)

    @classmethod
    def partial_digest_contributions(
        cls, entry: ShardedArrayEntry, obj_out
    ) -> "Optional[Dict[int, List[Tuple[str, int, Tuple[int, int, int, int]]]]]":
        """This process's contributions to DISTRIBUTED (zero-byte) digest
        verification of ``entry`` against ``obj_out``: for every unique
        destination box ELECTED to this process (the same hash election
        the save-side writer dedup uses), the partial fingerprint lanes
        of each saved piece's intersection with that box, tagged with the
        region's absolute offsets within the piece. Fingerprint lanes are
        additive over disjoint word covers (device_digest.py), so peers
        can sum every process's 16-byte partials and compare against the
        manifest — verifying a piece CUT ACROSS PROCESSES with no payload
        movement at all.

        Returns ``{piece_index: [(box_key, n_elements, lanes4), ...]}``
        (possibly empty — this process elected no boxes), or None when a
        region could not be fingerprinted on device; the caller then
        publishes non-participation so peers see incomplete coverage and
        fall back to normal reads. Dispatch is windowed: at most a few
        region slices are live at a time."""
        from ..device_digest import (
            MATCH_WINDOW,
            MATCH_WINDOW_BYTES,
            partial_dispatch,
            partial_fetch,
        )

        shape = tuple(entry.shape)
        itemsize = string_to_dtype(entry.dtype).itemsize

        # All (piece, elected-box) overlap regions, as geometry + data.
        work: List[Tuple[int, str, Tuple, Tuple, Any]] = []
        for box, data in cls._elected_local_boxes(
            obj_out.sharding, shape, obj_out.addressable_shards
        ):
            for i, shard in enumerate(entry.shards):
                ov = _overlap(shard.offsets, shard.sizes, box)
                if ov is None:
                    continue
                src_slices, dst_slices = ov
                n_elems = 1
                for sl in src_slices:
                    n_elems *= sl.stop - sl.start
                work.append(
                    (
                        i,
                        _box_key(box),
                        tuple(shard.sizes),
                        tuple(sl.start for sl in src_slices),
                        (data, dst_slices, n_elems),
                    )
                )

        out: Dict[int, List[Tuple[str, int, Tuple[int, int, int, int]]]] = {}
        # Windowed dispatch: same bounds as fingerprints_match.
        pos = 0
        while pos < len(work):
            batch = []
            batch_bytes = 0
            while (
                pos < len(work)
                and len(batch) < MATCH_WINDOW
                and batch_bytes < MATCH_WINDOW_BYTES
            ):
                i, box_key, piece_shape, offs, (data, dst_slices, n_elems) = (
                    work[pos]
                )
                nbytes = n_elems * itemsize
                if batch and batch_bytes + nbytes > MATCH_WINDOW_BYTES:
                    break
                region = data[dst_slices] if dst_slices else data
                pending = partial_dispatch(region, piece_shape, offs)
                del region
                if pending is None:
                    return None
                batch.append((i, box_key, n_elems, pending))
                batch_bytes += nbytes
                pos += 1
            for i, box_key, n_elems, pending in batch:
                out.setdefault(i, []).append(
                    (box_key, n_elems, partial_fetch(pending))
                )
        return out

    @classmethod
    def prepare_read(
        cls,
        entry: ShardedArrayEntry,
        obj_out: Any = None,
        callback: Optional[Callable[[Any], None]] = None,
        device_digests: bool = False,
        reshard: Optional[Any] = None,  # reshard.ReshardContext
    ) -> List[ReadReq]:
        shape = tuple(entry.shape)
        np_dtype = string_to_dtype(entry.dtype)

        from .prepare import check_restore_cast, is_jax_array

        if is_jax_array(obj_out):
            import jax

            if tuple(obj_out.shape) != shape:
                raise RuntimeError(
                    f"Shape mismatch restoring sharded array: snapshot has "
                    f"{list(shape)}, destination has {list(obj_out.shape)}."
                )
            if device_digests and cls._dst_already_matches(entry, obj_out):
                return []
            sharding = obj_out.sharding
            needs_cast = check_restore_cast(
                entry.dtype, obj_out.dtype, "sharded array into jax.Array"
            )
            dst_dtype = obj_out.dtype
            # one host buffer per unique addressable destination box
            boxes: Dict[Box, np.ndarray] = {}
            for device, index in sharding.addressable_devices_indices_map(
                shape
            ).items():
                box = _normalize_index(index, shape)
                if box not in boxes:
                    boxes[box] = np.empty(
                        tuple(hi - lo for lo, hi in box), dtype=np_dtype
                    )

            def finalize(path: Optional[str] = None) -> None:
                def cb(index: Tuple[slice, ...]) -> np.ndarray:
                    return boxes[_normalize_index(index, shape)]

                # Runs where the leaf's last shard lands: on an executor
                # thread after a buffered read, on the event-loop thread
                # after a streamed one.
                with telemetry.span(
                    "consume_place", cat="consumer",
                    path=path,
                    bytes=sum(b.nbytes for b in boxes.values()),
                    thread=_placing_thread(),
                ):
                    restored = jax.make_array_from_callback(shape, sharding, cb)
                    if needs_cast:
                        # Cast on device after the (narrower-dtype) transfer;
                        # astype preserves the destination sharding.
                        restored = restored.astype(dst_dtype)
                    if callback is not None:
                        callback(restored)

            # Planned-peer source tier: with an active reshard context,
            # project EVERY rank's destination boxes out of the global
            # device->index map (identical on all ranks — no gather) and
            # let the planner claim multi-requester shards. Claimed
            # shards read from storage once (on the elected owner) and
            # arrive here as peer region bundles; everything else keeps
            # the direct tier below.
            reshard_roles = None
            if reshard is not None:
                global_boxes: Dict[int, set] = {}
                for device, index in sharding.devices_indices_map(
                    shape
                ).items():
                    global_boxes.setdefault(device.process_index, set()).add(
                        _normalize_index(index, shape)
                    )
                reshard_roles = reshard.plan_entry(
                    entry,
                    {r: sorted(bs) for r, bs in global_boxes.items()},
                )

            return cls._plan_scatter_reads(
                entry, boxes, finalize, reshard_roles=reshard_roles
            )

        # numpy / no destination: single box covering the whole array
        if isinstance(obj_out, np.ndarray) and obj_out.flags["WRITEABLE"]:
            if tuple(obj_out.shape) != shape:
                raise RuntimeError(
                    f"Shape mismatch restoring sharded array into numpy "
                    f"destination: {list(shape)} vs {list(obj_out.shape)}."
                )
            # The scatter copies cast element-wise into the destination's
            # dtype (fast_copyto, same_kind); fail before I/O if forbidden.
            check_restore_cast(
                entry.dtype, obj_out.dtype, "sharded array into numpy array"
            )
            dst = obj_out
        else:
            dst = np.empty(shape, dtype=np_dtype)
        whole: Box = tuple((0, dim) for dim in shape)
        boxes = {whole: dst}

        def finalize_np(path: Optional[str] = None) -> None:
            if callback is not None:
                callback(dst)

        return cls._plan_scatter_reads(entry, boxes, finalize_np)

    @classmethod
    def _plan_scatter_reads(
        cls,
        entry: ShardedArrayEntry,
        boxes: Dict[Box, np.ndarray],
        finalize: Callable[[Optional[str]], None],
        reshard_roles: Optional[Dict[int, Any]] = None,
    ) -> List[ReadReq]:
        """One ReadReq per saved shard overlapping a destination box.

        ``reshard_roles`` (shard index -> reshard.OwnerUnit | RecvUnit)
        upgrades individual shards onto the planned-peer tier: an owner
        gets a forwarding consumer (reads storage, bundles regions out),
        a receiver gets a dual-mode consumer whose ReadReq still names
        the shard's real storage location — the peer path delivers a
        region bundle, and any peer failure re-reads the SAME request
        from storage (scheduler fallback), keeping correctness
        independent of the plan."""
        relevant: List[Tuple[int, Shard, List]] = []
        for i, shard in enumerate(entry.shards):
            targets = []
            for box, buf in boxes.items():
                ov = _overlap(shard.offsets, shard.sizes, box)
                if ov is not None:
                    src_slices, dst_slices = ov
                    targets.append((buf, src_slices, dst_slices))
            if targets:
                relevant.append((i, shard, targets))

        if not relevant:
            # nothing overlaps (e.g. zero-size destination) — finalize now
            finalize()
            return []

        completion = _Completion(len(relevant), finalize)
        read_reqs = []
        for i, shard, targets in relevant:
            consumer: Any = _ShardScatterConsumer(shard, targets, completion)
            role = reshard_roles.get(i) if reshard_roles else None
            if role is not None:
                from .. import reshard as reshard_mod

                if isinstance(role, reshard_mod.OwnerUnit):
                    consumer = reshard_mod.PlannedOwnerConsumer(
                        consumer, role
                    )
                else:
                    consumer = reshard_mod.PlannedRecvConsumer(
                        consumer, role, boxes
                    )
            byte_range = (
                tuple(shard.array.byte_range)
                if shard.array.byte_range is not None
                else None
            )
            read_reqs.append(
                ReadReq(
                    path=shard.array.location,
                    buffer_consumer=consumer,
                    byte_range=byte_range,
                    origin=shard.array.origin,
                )
            )
        return read_reqs
