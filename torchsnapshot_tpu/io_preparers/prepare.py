"""Top-level write/read dispatch by value/entry type
(reference: io_preparer.py:792-892).

Also the storage layout rule: sharded entries live under ``sharded/``,
replicated under ``replicated/``, everything else under ``<rank>/``
(reference: io_preparer.py:792-798).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import numpy as np

from .. import telemetry
from ..io_types import ReadReq
from ..manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    ObjectEntry,
    PrimitiveEntry,
    ShardedArrayEntry,
)
from .array import ArrayIOPreparer, _placing_thread
from .chunked import ChunkedArrayIOPreparer
from .object import ObjectIOPreparer


def get_storage_path(
    logical_path: str, rank: int, replicated: bool = False, sharded: bool = False
) -> str:
    if sharded:
        return f"sharded/{logical_path}"
    elif replicated:
        return f"replicated/{logical_path}"
    else:
        return f"{rank}/{logical_path}"


def _jax():
    import jax

    return jax


def is_jax_array(obj: Any) -> bool:
    try:
        import jax

        return isinstance(obj, jax.Array)
    except ImportError:  # pragma: no cover
        return False


def is_sharded_jax_array(obj: Any) -> bool:
    """True for jax.Arrays that must be saved shard-wise: any array whose
    sharding actually partitions the data across devices (GSPMD TP/FSDP/EP
    layouts, multi-host arrays). Fully-replicated and single-device arrays
    go through the plain/chunked path instead."""
    if not is_jax_array(obj):
        return False
    sharding = obj.sharding
    if getattr(sharding, "num_devices", len(sharding.device_set)) == 1:
        return False
    return not sharding.is_fully_replicated


def is_partitionable_array(obj: Any) -> bool:
    """Arrays handled by the plain/chunked path: numpy arrays/scalars and
    non-partitioned jax.Arrays."""
    if isinstance(obj, (np.ndarray, np.generic)):
        return True
    return is_jax_array(obj) and not is_sharded_jax_array(obj)


def check_restore_cast(entry_dtype: str, dst_dtype: Any, what: str) -> bool:
    """Restore semantics: the DESTINATION is the spec — shape, sharding, and
    dtype. A snapshot saved in a different dtype is cast to the destination's
    on restore, mirroring the reference's ``dst.copy_(src)`` into pre-built
    state (reference: io_preparer.py:426-427) so a jitted train step keeps
    its compiled dtype across a precision-recipe change. Divergence from
    torch: ``copy_`` casts unsafely; here only ``same_kind`` casts (float<->
    float incl. bf16/fp8, int<->int) are allowed — a float checkpoint
    restoring into int params is almost certainly a state-mapping bug, not
    an intended quantization (quantized flows store scales separately).

    Returns True when a cast is needed; raises for forbidden casts.
    """
    from ..serialization import string_to_dtype

    src = string_to_dtype(entry_dtype)
    dst = np.dtype(dst_dtype)
    if src == dst:
        return False
    if not np.can_cast(src, dst, casting="same_kind"):
        raise RuntimeError(
            f"Restoring {what}: snapshot dtype {entry_dtype} cannot be cast "
            f"to destination dtype {dst} (only same-kind casts are "
            "supported; restore into a matching-kind destination or convert "
            "the checkpoint explicitly)."
        )
    return True


def _dst_already_matches(entry: Entry, obj_out: Any) -> bool:
    """True when a jax destination already holds exactly the content the
    entry describes, proven by on-device fingerprints (device_digest.py):
    the read and the HtoD transfer can be skipped and the destination
    kept. Conservative on every edge: any missing fingerprint, dtype or
    shape difference, or unfingerprintable destination means False.
    """
    from ..device_digest import device_fingerprint, fingerprints_match
    from .array import dtype_to_string

    if isinstance(entry, ArrayEntry):
        if entry.device_digest is None or entry.byte_range is not None:
            return False
        if list(obj_out.shape) != list(entry.shape):
            return False
        if dtype_to_string(obj_out.dtype) != entry.dtype:
            return False
        return device_fingerprint(obj_out) == entry.device_digest
    if isinstance(entry, ChunkedArrayEntry):
        # All chunks must match: the jax read path materializes the whole
        # host array before one device_put, so a partial skip has nothing
        # to splice into. (Per-piece skips exist on the sharded path,
        # where reads scatter independently.)
        if list(obj_out.shape) != list(entry.shape):
            return False
        if dtype_to_string(obj_out.dtype) != entry.dtype:
            return False
        if not entry.chunks or any(
            c.array.device_digest is None for c in entry.chunks
        ):
            # fingerprints_match([]) is vacuously True; empty chunks must
            # not keep arbitrary destination content with no verification.
            return False
        # Windowed: a few chunk slices are live at a time (fingerprints
        # in a window dispatch together, then the slices are dropped), so
        # verifying a chunked array — which only exists above 512 MB —
        # never transiently duplicates its whole footprint in device
        # memory the way a full eager slice list would.
        from ..serialization import array_size_bytes

        return fingerprints_match(
            (
                (
                    array_size_bytes(c.sizes, entry.dtype),
                    lambda c=c: obj_out[
                        tuple(
                            slice(o, o + s)
                            for o, s in zip(c.offsets, c.sizes)
                        )
                    ],
                    c.array.device_digest,
                )
                for c in entry.chunks
            )
        )
    return False


def prepare_read(
    entry: Entry,
    obj_out: Any = None,
    callback: Optional[Callable[[Any], None]] = None,
    buffer_size_limit_bytes: Optional[int] = None,
    device_digests: bool = False,
    assume_verified: bool = False,
    reshard: Optional[Any] = None,
) -> List[ReadReq]:
    """Plan reads for ``entry`` into/for ``obj_out``.

    - numpy destination: filled in place (plus ``callback`` on completion);
    - jax.Array destination: a host buffer is filled, then re-materialized on
      device with the destination's sharding and reported via ``callback``;
    - no destination: a host value is materialized and reported via
      ``callback``.

    A destination whose dtype differs from the snapshot's is cast to the
    destination's dtype (``same_kind`` only — see ``check_restore_cast``).

    ``device_digests``: jax destinations already holding an entry's exact
    content (fingerprinted on device against the entry's recorded
    fingerprint) plan NO reads and keep their current array — the
    restore-side mirror of the take-side DtoH skip.

    ``assume_verified``: the destination was already proven to hold this
    entry's exact content by DISTRIBUTED digest verification (partial
    fingerprint lanes summed across processes over the coordination
    plane, snapshot.py) — plan no reads and keep it.

    ``reshard``: an active ``reshard.ReshardContext`` — sharded entries
    route multi-requester shards over the planned-peer tier (one storage
    read on an elected owner, minimal region bundles to everyone else)
    instead of N direct storage reads.

    PrimitiveEntry requires no I/O and must be handled by the caller
    (reference: io_preparer.py:888-890).
    """
    if isinstance(entry, PrimitiveEntry):
        return []

    if assume_verified:
        return []

    if (
        device_digests
        and is_jax_array(obj_out)
        and getattr(obj_out, "is_fully_addressable", False)
        and _dst_already_matches(entry, obj_out)
    ):
        return []

    if isinstance(entry, ObjectEntry):
        read_reqs, consumer = ObjectIOPreparer.prepare_read(entry)
        if callback is not None:
            consumer.set_consume_callback(callback)
        return read_reqs

    if isinstance(entry, ShardedArrayEntry):
        from .sharded import ShardedArrayIOPreparer

        return ShardedArrayIOPreparer.prepare_read(
            entry,
            obj_out,
            callback=callback,
            device_digests=device_digests,
            reshard=reshard,
        )

    if not isinstance(entry, (ArrayEntry, ChunkedArrayEntry)):
        raise TypeError(f"Unsupported entry type for read: {type(entry).__name__}")

    dst_view: Optional[np.ndarray] = None
    final_callback = callback
    # Host consumers (read_state_dict, numpy callbacks) are promised
    # writable arrays; the device-materialize path below opts out —
    # device_put never needs a writable source.
    ensure_writable = True
    device_dest = None

    if isinstance(obj_out, np.ndarray) and obj_out.flags["WRITEABLE"]:
        if list(obj_out.shape) != list(entry.shape):
            raise RuntimeError(
                f"Shape mismatch restoring {entry.location if hasattr(entry, 'location') else '<chunked>'}: "
                f"snapshot has {list(entry.shape)}, destination has {list(obj_out.shape)}."
            )
        # fast_copyto applies the same_kind cast element-wise during the
        # copy into the destination; fail before any I/O if it can't.
        check_restore_cast(entry.dtype, obj_out.dtype, "into numpy array")
        dst_view = obj_out
    elif is_jax_array(obj_out):
        jax = _jax()
        if list(obj_out.shape) != list(entry.shape):
            raise RuntimeError(
                f"Shape mismatch restoring into jax.Array: snapshot has "
                f"{list(entry.shape)}, destination has {list(obj_out.shape)}."
            )
        sharding = obj_out.sharding
        # The destination is the spec, committed-ness included: a
        # destination left uncommitted (plain jnp creation on the default
        # device) comes back uncommitted. A committed copy makes the
        # caller's jitted step lower with explicit argument shardings — a
        # different module from the one the first run compiled, so every
        # resume would miss the persistent compile cache.
        committed = obj_out.committed
        needs_cast = check_restore_cast(
            entry.dtype, obj_out.dtype, "into jax.Array"
        )
        dst_dtype = obj_out.dtype
        # No host scratch here: with dst_view=None the preparers hand the
        # callback either a zero-copy view over the read buffer (whole-file
        # reads — saves a full memcpy pass per array) or their own assembly
        # scratch (budget-split / chunked reads, which genuinely need one).
        # device_put copies host->device either way. Dtype casts run ON
        # DEVICE after the transfer: the wire moves the snapshot's (often
        # narrower) bytes and the VPU does the widening, not the host.

        # The read a leaf is placed in: its own, or its one chunk's. Which
        # of several chunks lands last is not known here, and the span
        # then names none.
        place_path = getattr(entry, "location", None)
        if isinstance(entry, ChunkedArrayEntry) and len(entry.chunks) == 1:
            place_path = entry.chunks[0].array.location

        def _materialize(
            host: np.ndarray,
            _cb=callback,
            _placement=sharding if committed else None,
        ) -> None:
            # One HtoD dispatch of the whole leaf, inside the read that
            # completed it.
            with telemetry.span(
                "consume_place", cat="consumer", path=place_path,
                bytes=host.nbytes, thread=_placing_thread(),
            ):
                restored = jax.device_put(host, _placement)
                if needs_cast:
                    restored = restored.astype(dst_dtype)
                if _cb is not None:
                    _cb(restored)

        final_callback = _materialize
        ensure_writable = False
        # STREAMED reads bypass the host-array callback: the consumer
        # device_puts each sub-chunk as it lands (HtoD of chunk N rides
        # under the read of chunk N+1) and materializes under the same
        # sharding/cast rules this callback applies buffered.
        from .array import DeviceMaterializer

        device_dest = DeviceMaterializer(
            sharding=sharding,
            committed=committed,
            dst_dtype=dst_dtype,
            needs_cast=needs_cast,
            callback=callback,
        )
    # else: no usable destination — allocate inside the preparer and report
    # the host value via callback.

    if isinstance(entry, ChunkedArrayEntry):
        return ChunkedArrayIOPreparer.prepare_read(
            entry,
            dst_view=dst_view,
            callback=final_callback,
            buffer_size_limit_bytes=buffer_size_limit_bytes,
            ensure_writable=ensure_writable,
            device_dest=device_dest,
        )
    else:
        return ArrayIOPreparer.prepare_read(
            entry,
            dst_view=dst_view,
            callback=final_callback,
            buffer_size_limit_bytes=buffer_size_limit_bytes,
            ensure_writable=ensure_writable,
            device_dest=device_dest,
        )


def prepare_write(
    obj: Any,
    logical_path: str,
    rank: int,
    replicated: bool = False,
):
    """Plan writes for a non-array, non-primitive leaf (objects).

    Arrays are planned by the orchestrator through the chunked/sharded
    preparers because chunk striping and shard deduplication need cross-rank
    agreement.
    """
    storage_path = get_storage_path(logical_path, rank, replicated=replicated)
    return ObjectIOPreparer.prepare_write(storage_path, obj, replicated=replicated)
