"""Chunked-array preparer (reference: io_preparer.py:73-161).

Large non-sharded arrays are split into <=512 MB chunks along dim 0 so that
(a) replicated arrays can be striped across processes — each process writes a
disjoint subset of chunks and the manifests are merged — and (b) writes
pipeline through the budgeted scheduler instead of staging one giant buffer.

Chunk layout is recorded as N-D offsets/sizes (same schema as shards), so
restore is a region-fill of the destination and works for any chunk subset.

WITHIN a chunk, writes stream: each chunk's WriteReq carries an
ArrayBufferStager, whose sub-chunk streaming protocol
(``can_stream``/``stage_stream``, io_preparers/array.py) the scheduler
fuses with the storage write on sync takes — so even a single 512 MB
chunk's DtoH copy, serialization, and write overlap instead of
serializing (the chunk split bounds memory and enables striping; the
sub-chunk stream bounds the intra-chunk critical path).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from ..io_types import ReadReq, WriteReq
from ..manifest import ArrayEntry, ChunkedArrayEntry, Shard
from ..serialization import array_size_bytes, dtype_to_string, string_to_dtype
from .array import ArrayAssembler, ArrayBufferStager, ArrayIOPreparer, array_nbytes

DEFAULT_MAX_CHUNK_SIZE_BYTES = 512 * 1024 * 1024


class _RegionConsumer:
    """Fills one N-D region of the destination via an ArrayAssembler."""

    def __init__(self, chunk: Shard, assembler: ArrayAssembler) -> None:
        self.chunk = chunk
        self.assembler = assembler

    def make_callback(self) -> Callable[[np.ndarray], None]:
        index = tuple(
            slice(o, o + s) for o, s in zip(self.chunk.offsets, self.chunk.sizes)
        )

        def cb(arr: np.ndarray) -> None:
            self.assembler.fill_region(index, arr, self.chunk.array.location)

        return cb


class ChunkedArrayIOPreparer:
    @staticmethod
    def chunk_ranges(
        shape: Tuple[int, ...],
        dtype_str: str,
        chunk_size_bytes: Optional[int] = None,
    ) -> List[Tuple[int, int]]:
        """[lo, hi) ranges along dim 0 such that each chunk <= chunk_size_bytes
        (single-row chunks if one row exceeds the limit)."""
        if chunk_size_bytes is None:
            # resolved at call time so tests can shrink the module constant
            chunk_size_bytes = DEFAULT_MAX_CHUNK_SIZE_BYTES
        if len(shape) == 0 or 0 in shape:
            return [(0, shape[0] if shape else 0)] if shape else []
        total_bytes = array_size_bytes(shape, dtype_str)
        row_bytes = total_bytes // shape[0] if shape[0] else total_bytes
        rows_per_chunk = max(1, chunk_size_bytes // max(row_bytes, 1))
        ranges = []
        lo = 0
        while lo < shape[0]:
            hi = min(lo + rows_per_chunk, shape[0])
            ranges.append((lo, hi))
            lo = hi
        return ranges

    @staticmethod
    def chunk_shards(
        shape: Tuple[int, ...],
        dtype_str: str,
        chunk_size_bytes: Optional[int] = None,
    ) -> List[Tuple[List[int], List[int]]]:
        """(offsets, sizes) per chunk; scalar arrays produce one empty-offset
        chunk covering the whole array."""
        if len(shape) == 0:
            return [([], [])]
        out = []
        for lo, hi in ChunkedArrayIOPreparer.chunk_ranges(shape, dtype_str, chunk_size_bytes):
            offsets = [lo] + [0] * (len(shape) - 1)
            sizes = [hi - lo] + list(shape[1:])
            out.append((offsets, sizes))
        return out

    @staticmethod
    def prepare_write(
        storage_path_prefix: str,
        arr,
        local_chunks: List[Tuple[List[int], List[int]]],
        replicated: bool = False,
    ) -> Tuple[ChunkedArrayEntry, List[WriteReq]]:
        """Write only ``local_chunks`` (this process's stripe) of ``arr``.

        The returned entry lists only the local chunks; the manifest gather
        merges stripes across processes into the full chunk set
        (reference: snapshot.py:954-986).
        """
        dtype_str = dtype_to_string(arr.dtype)
        chunks: List[Shard] = []
        write_reqs: List[WriteReq] = []
        for offsets, sizes in local_chunks:
            # A chunk is named here and cut when it is staged: a slice of a
            # device array made now would be a device copy that lives until
            # the save is done, one per chunk, a second state beside the
            # first (PERF.md, PRs 22 and 34).
            index = None
            if list(sizes) != list(arr.shape):  # a chunk that is the whole array is not cut
                index = tuple(slice(o, o + s) for o, s in zip(offsets, sizes))
            suffix = "_".join(str(o) for o in offsets)
            location = (
                f"{storage_path_prefix}_{suffix}" if suffix else storage_path_prefix
            )
            chunk_entry, reqs = ArrayIOPreparer.prepare_write(
                location, arr, replicated=replicated, index=index
            )
            chunks.append(Shard(offsets=list(offsets), sizes=list(sizes), array=chunk_entry))
            write_reqs.extend(reqs)
        entry = ChunkedArrayEntry(
            dtype=dtype_str,
            shape=list(arr.shape),
            chunks=chunks,
            replicated=replicated,
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: ChunkedArrayEntry,
        dst_view: Optional[np.ndarray] = None,
        callback: Optional[Callable[[np.ndarray], None]] = None,
        buffer_size_limit_bytes: Optional[int] = None,
        ensure_writable: bool = True,
        device_dest=None,
    ) -> List[ReadReq]:
        if len(entry.chunks) == 1 and list(entry.chunks[0].sizes) == list(
            entry.shape
        ):
            # Whole array in one chunk — the common case (anything under
            # the 512 MB chunk limit). Skip the assembler: its scratch is
            # a full extra memcpy pass per array (and for jax
            # destinations the device_put can consume a zero-copy view
            # over the read buffer directly). Semantics match the
            # assembler path: dst_view is filled in place, the callback
            # fires once with the complete array. device_dest forwards
            # only here — the multi-chunk path assembles regions on the
            # host and device_puts once via the completion callback.
            return ArrayIOPreparer.prepare_read(
                entry.chunks[0].array,
                dst_view=dst_view,
                callback=callback,
                buffer_size_limit_bytes=buffer_size_limit_bytes,
                ensure_writable=ensure_writable,
                device_dest=device_dest,
            )
        if dst_view is None:
            dst_view = np.empty(
                tuple(entry.shape), dtype=string_to_dtype(entry.dtype)
            )
        assembler = ArrayAssembler(
            dst_view, num_parts=len(entry.chunks), callback=callback
        )
        read_reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            index = tuple(
                slice(o, o + s) for o, s in zip(chunk.offsets, chunk.sizes)
            )
            # Write through the assembler's target (its scratch when dst_view
            # is non-contiguous) — direct dst_view writes would be clobbered
            # by the assembler's completion copy-back.
            sub_dst = assembler.region_view(index if chunk.offsets else ())
            if buffer_size_limit_bytes is not None and sub_dst.flags["C_CONTIGUOUS"]:
                # Split this chunk's read into byte ranges under the budget;
                # the sub-assembler inside prepare_read notifies the outer
                # assembler once the whole chunk has landed.
                read_reqs.extend(
                    ArrayIOPreparer.prepare_read(
                        chunk.array,
                        dst_view=sub_dst,
                        callback=lambda _, a=assembler, p=chunk.array.location: (
                            a.part_done(p)
                        ),
                        buffer_size_limit_bytes=buffer_size_limit_bytes,
                    )
                )
            else:
                region = _RegionConsumer(chunk, assembler)
                read_reqs.extend(
                    ArrayIOPreparer.prepare_read(
                        chunk.array, callback=region.make_callback()
                    )
                )
        return read_reqs


def get_chunked_array_size(entry: ChunkedArrayEntry) -> int:
    return array_size_bytes(entry.shape, entry.dtype)
