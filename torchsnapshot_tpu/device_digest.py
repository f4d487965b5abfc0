"""Device-resident array fingerprints for incremental change detection.

The host-side dedup path (dedup.py) must pay the full DtoH transfer and a
SHA-256 pass before it can discover a payload is unchanged — on TPU the
DtoH copy is exactly the scarce resource checkpointing tries to conserve
(SURVEY §7's central hard-part; the reference's CUDA analogue stages
through pinned host memory the same way, io_preparer.py:513-523). This
module computes a 128-bit position-dependent integer fingerprint of an
array ON DEVICE — one pass over the bytes at HBM bandwidth, all VPU
integer ops — and fetches only the 16-byte result. When the fingerprint
matches the one the base snapshot recorded for the same storage location,
staging skips the DtoH copy AND the storage write.

Trust model: the fingerprint is NOT cryptographic. Four independently
seeded 32-bit mixing lanes over position-tagged words give ~2^-128
collision odds for random (non-adversarial) changes — ample for "did
training mutate this weight" — but an adversary could construct a
collision. Device digests are therefore opt-in
(``Snapshot.take(..., device_digests=True)`` or
``TORCHSNAPSHOT_TPU_DEVICE_DIGESTS=1``); the default dedup path keeps
hashing the exact staged bytes with SHA-256.

Determinism: every op is integer arithmetic with defined wraparound
(xor/shift/multiply mod 2^32) — bit-identical across runs, backends
(CPU/TPU), and jit recompiles, so fingerprints recorded on one backend
match recomputations on another.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

PREFIX = "xxh4x32"  # fingerprint scheme tag recorded in manifests

# lowbias32 (Degski) finalizer constants + four lane seeds.
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLDEN = np.uint32(0x9E3779B9)
_SEEDS = (
    np.uint32(0x85EBCA6B),
    np.uint32(0xC2B2AE35),
    np.uint32(0x27D4EB2F),
    np.uint32(0x165667B1),
)


def enabled_by_env() -> bool:
    # Falsy spellings match the repo's other flags (integrity._env_on,
    # batcher.batching_enabled): an explicit "false" must never turn the
    # opt-in trust model ON.
    return os.environ.get("TORCHSNAPSHOT_TPU_DEVICE_DIGESTS", "0") not in (
        "0",
        "",
        "false",
    )


def _mix32(x):
    """Vectorized 32-bit finalizer (lowbias32): every input bit affects
    every output bit. Works on jax uint32 arrays inside jit and on numpy
    uint32 scalars outside (same wraparound semantics)."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def _fingerprint_jit(u32):
    """Core: position-tagged mix + wrapping sum per lane. ``u32`` is a
    1-D uint32 array. jit caches per input length — states have fixed
    shapes, so each array compiles once per training run."""
    import jax.numpy as jnp
    from jax import lax

    n = u32.shape[0]
    idx = lax.iota(jnp.uint32, n)
    lanes = []
    for seed in _SEEDS:
        tag = _mix32(idx * _GOLDEN + seed)
        # Wrapping uint32 sum of well-mixed position-tagged words: a
        # commutative reduce XLA turns into a fast tree reduction, with
        # position sensitivity carried by the tag.
        lanes.append(jnp.sum(_mix32(u32 ^ tag), dtype=jnp.uint32))
    return jnp.stack(lanes)


_jitted = None


def _get_jitted():
    global _jitted
    if _jitted is None:
        import jax

        _jitted = jax.jit(_fingerprint_jit)
    return _jitted


def _as_uint32_words(arr):
    """Bitcast any array to a 1-D uint32 word stream on device.

    Elements narrower than 32 bits are zero-extended per element (the
    stream is then not byte-dense, but it is a fixed deterministic
    function of the bytes, which is all a fingerprint needs); 64-bit
    elements split into two words. Raises TypeError for dtypes without a
    clean bitcast (sub-byte int4 packings).
    """
    import jax.numpy as jnp
    from jax import lax

    flat = arr.reshape(-1)
    itemsize = np.dtype(arr.dtype).itemsize if hasattr(arr.dtype, "itemsize") else 0
    if flat.dtype == jnp.bool_:
        return flat.astype(jnp.uint32)
    if itemsize == 1:
        return lax.bitcast_convert_type(flat, jnp.uint8).astype(jnp.uint32)
    if itemsize == 2:
        return lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    if itemsize == 4:
        return lax.bitcast_convert_type(flat, jnp.uint32)
    if itemsize == 8:
        # Adds a trailing axis of two uint32 words per element.
        return lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    raise TypeError(f"no uint32 bitcast for dtype {arr.dtype}")


def _dispatch(arr):
    """Kick the fingerprint computation for ``arr`` without blocking.
    Returns the in-flight device lanes array, or None if ``arr`` cannot
    be fingerprinted on device."""
    import jax

    if not isinstance(arr, jax.Array):
        return None
    if not getattr(arr, "is_fully_addressable", False):
        return None
    try:
        return _get_jitted()(_as_uint32_words(arr))
    except (TypeError, ValueError):
        # TypeError: our own rejection (no clean bitcast). ValueError: jax's
        # bitcast shape rule rejecting sub-byte packings (int4/uint4 report
        # itemsize 1 but cannot widen elementwise to uint8).
        return None


def _fold_lanes(lanes, nbytes: int) -> str:
    """Fold the byte length into 4 summed lanes and format the digest.
    THE single definition of the final fold: device_fingerprint and the
    distributed combine_partials must agree bit-exactly or cross-process
    verdicts would silently diverge from recorded fingerprints."""
    with np.errstate(over="ignore"):
        final = [
            np.uint32(lane) ^ _mix32(np.uint32(nbytes & 0xFFFFFFFF) ^ seed)
            for lane, seed in zip(np.asarray(lanes, np.uint32), _SEEDS)
        ]
    return PREFIX + ":" + "".join(f"{int(v):08x}" for v in final)


def _finalize_from_nbytes(nbytes: int, pending) -> str:
    """Fetch a dispatched computation's 16 bytes and fold in the length
    (folding on the host: the length is static per shape, and equal word
    streams of different underlying sizes stay distinct)."""
    import jax

    return _fold_lanes(jax.device_get(pending), nbytes)


def _nbytes(arr) -> int:
    return int(np.dtype(arr.dtype).itemsize) * int(
        np.prod(arr.shape, dtype=np.int64)
    )


def _finalize(arr, pending) -> str:
    return _finalize_from_nbytes(_nbytes(arr), pending)


# -------------------------------------------------------- partial lanes
#
# The lanes are COMMUTATIVE uint32 sums over position-tagged words, so a
# piece's fingerprint is ADDITIVE over any disjoint cover of its word
# stream: fingerprint(piece) = fold(sum of partial_lanes(region_i)) for
# regions partitioning the piece, each tagged with its words' absolute
# indices in the piece. This is what lets a piece CUT ACROSS PROCESSES
# be verified with zero payload movement — every process computes the
# 16-byte partial sum over the sub-region it holds, the partials ride
# the coordination plane, and their wrapping sum (plus the length fold)
# must equal the manifest's recorded fingerprint.


def _partial_jit(region, offsets, strides):
    """Lanes contribution of ``region``, an N-D sub-block of a piece:
    identical math to ``_fingerprint_jit`` except each word's tag uses
    its absolute index in the PIECE's row-major word stream, computed
    from the region's ``offsets`` and the piece's row-major ``strides``
    (both uint32 vectors, dynamic so same-shaped regions share one
    compilation)."""
    import jax.numpy as jnp
    from jax import lax

    words = _as_uint32_words(region)
    n_elem = 1
    for s in region.shape:
        n_elem *= s
    wpe = words.shape[0] // max(n_elem, 1)  # words per element (1 or 2)
    e = jnp.zeros(region.shape, jnp.uint32)
    for d in range(region.ndim):
        e = e + (
            offsets[d] + lax.broadcasted_iota(jnp.uint32, region.shape, d)
        ) * strides[d]
    if wpe == 1:
        w = e.reshape(-1)
    else:
        w = (
            e.reshape(-1, 1) * jnp.uint32(wpe)
            + lax.iota(jnp.uint32, wpe)[None, :]
        ).reshape(-1)
    lanes = []
    for seed in _SEEDS:
        tag = _mix32(w * _GOLDEN + seed)
        lanes.append(jnp.sum(_mix32(words ^ tag), dtype=jnp.uint32))
    return jnp.stack(lanes)


_partial_jitted = None


def _get_partial_jitted():
    global _partial_jitted
    if _partial_jitted is None:
        import jax

        _partial_jitted = jax.jit(_partial_jit)
    return _partial_jitted


def partial_dispatch(region, piece_shape, region_offsets):
    """Kick the partial-lanes computation for ``region``, located at
    ``region_offsets`` within a piece of shape ``piece_shape``. Returns
    the in-flight device lanes, or None when the region cannot be
    fingerprinted on device."""
    import jax
    import jax.numpy as jnp

    if not isinstance(region, jax.Array):
        return None
    if not getattr(region, "is_fully_addressable", False):
        return None
    strides = []
    acc = 1
    for dim in reversed(tuple(piece_shape)):
        strides.append(acc)
        acc *= int(dim)
    strides = list(reversed(strides))
    try:
        return _get_partial_jitted()(
            region,
            jnp.asarray(np.asarray(region_offsets, np.uint32)),
            jnp.asarray(np.asarray(strides, np.uint32)),
        )
    except (TypeError, ValueError):
        return None


def partial_fetch(pending) -> "tuple[int, int, int, int]":
    """Fetch a dispatched partial's 16 bytes (4 uint32 lanes)."""
    import jax

    lanes = np.asarray(jax.device_get(pending), dtype=np.uint32)
    return tuple(int(v) for v in lanes)


def combine_partials(lane_groups, nbytes: int) -> str:
    """Wrapping-sum partial lanes covering a whole piece and fold the
    piece's byte length — equals the piece's ``device_fingerprint`` by
    lane additivity. ``lane_groups``: iterables of 4 ints each."""
    total = np.zeros(4, np.uint32)
    with np.errstate(over="ignore"):
        for lanes in lane_groups:
            total = total + np.asarray(lanes, np.uint32)
    return _fold_lanes(total, nbytes)


_HASH_PROBE_BYTES = 16 << 20
_hash_probe_done = False


def probe_hash_throughput() -> Optional[float]:
    """One-time on-device fingerprint throughput probe, recorded into the
    scheduler's I/O governor. The restore-side preverify gate needs the
    hash side of its hash-vs-read crossover even when no fingerprint
    warmup ran in this process; a single ~16 MB fingerprint (dispatched
    twice — the first pays the jit compile, the second is the measured
    steady state) settles it for the process lifetime. Returns the
    measured bytes/sec, or None when no device fingerprinting is
    available (the gate then keeps the status-quo verify)."""
    global _hash_probe_done
    if _hash_probe_done:
        from .scheduler import io_governor

        return io_governor().hash_bps()
    _hash_probe_done = True
    try:
        import jax
        import jax.numpy as jnp

        from . import telemetry

        arr = jnp.zeros((_HASH_PROBE_BYTES // 4,), jnp.uint32)
        jax.block_until_ready(arr)
        pending = _dispatch(arr)  # compile pass, untimed
        if pending is None:
            return None
        jax.block_until_ready(pending)
        t0 = telemetry.monotonic()
        jax.block_until_ready(_dispatch(arr))
        dt = telemetry.monotonic() - t0
        # Importing the scheduler registers the governor's bus listener
        # before the rate is published.
        from .scheduler import io_governor

        governor = io_governor()
        telemetry.record_rate("hash", None, _HASH_PROBE_BYTES, dt)
        return governor.hash_bps()
    except Exception:  # pragma: no cover - probe must never break restore
        return None


def device_fingerprint(arr) -> Optional[str]:
    """128-bit fingerprint of a (fully addressable) jax array's content,
    computed on device; only 16 bytes cross to the host.

    Returns ``"xxh4x32:<32 hex>"``, or None when the array cannot be
    fingerprinted on device (unsupported dtype, non-addressable shards) —
    callers fall back to the host SHA-256 path.
    """
    pending = _dispatch(arr)
    if pending is None:
        return None
    return _finalize(arr, pending)


def fingerprint_any(value) -> "tuple[str, str]":
    """Content fingerprint + leaf kind (``"array"`` | ``"object"``) for ANY
    state leaf — the delta journal's dirty detector (journal.py).

    jax arrays use the on-device digest when dispatchable (no DtoH copy);
    host-visible arrays hash their exact bytes; everything else (python
    scalars, opaque objects) hashes its pickle. The kind tells the journal
    which serialization path round-trips the leaf.
    """
    fp = device_fingerprint(value)
    if fp is not None:
        return fp, "array"
    import hashlib

    from . import serialization

    arr = None
    if isinstance(value, np.ndarray):
        arr = value
    elif type(value).__module__.split(".")[0] == "jax" and hasattr(value, "dtype"):
        try:
            arr = np.asarray(value)
        except Exception:
            arr = None
    if arr is not None:
        data = serialization.array_as_memoryview(np.ascontiguousarray(arr))
        return "sha256:" + hashlib.sha256(data).hexdigest(), "array"
    buf = serialization.object_as_bytes(value)
    return "sha256:" + hashlib.sha256(buf).hexdigest(), "object"


# Restore-side verification window: at most MATCH_WINDOW slices AND
# MATCH_WINDOW_BYTES of slice data in flight per batch. The count bound
# amortizes the host<->device roundtrip; the BYTE bound is what actually
# limits transient device memory — sharded pieces (unlike <=512 MB
# chunks) have no size cap of their own, so a count-only window could
# still hold the whole array's footprint in slice copies.
MATCH_WINDOW = 4
MATCH_WINDOW_BYTES = 512 * 1024 * 1024


def fingerprints_match(
    items, window: int = MATCH_WINDOW, window_bytes: int = MATCH_WINDOW_BYTES
) -> bool:
    """Bounded-memory fingerprint comparison for restore-side skips.

    ``items`` is an iterable of ``(nbytes, get_slice, expected)`` or
    ``(nbytes, get_slice, expected, cost_bytes)``: ``nbytes`` the
    slice's byte size (callers know it from the manifest geometry —
    shapes x dtype — without touching the device; it must equal the
    materialized slice's size, since the digest folds the length in),
    ``get_slice`` a thunk producing the device slice to verify,
    ``expected`` the manifest-recorded digest, and ``cost_bytes`` the
    item's TRANSIENT device footprint when it exceeds ``nbytes`` —
    assembled pieces (see sharded._make_assembler) hold the zeroed
    assembly target plus device copies of the overlapping parts, ~2x
    their logical size, and must say so or a window of them would
    transiently reach ~2x the documented bound. A window of slices is
    dispatched together before the first 16-byte fetch — ~one
    host<->device roundtrip per window, not per slice — then the slice
    references are dropped before the next window
    materializes. A window closes at ``window`` slices or before the
    slice that would push it past ``window_bytes`` of COST (a single
    over-budget slice still goes alone); the budget check runs BEFORE
    ``get_slice``, so nothing is materialized twice and transient device
    memory never exceeds ~window_bytes — not the array's whole
    footprint. Returns False on the first mismatch or unfingerprintable
    slice (callers fall back to a normal read); remaining windows are
    never materialized.
    """
    if window < 1 or window_bytes < 1:
        # An empty first window would return True with ZERO verification
        # — a silent skip of arbitrary content.
        raise ValueError(
            f"window and window_bytes must be >= 1, got {window}/{window_bytes}"
        )
    it = iter(items)
    carried = None  # the item that overflowed the previous window's budget
    while True:
        pendings = []
        batch_bytes = 0
        while len(pendings) < window and batch_bytes < window_bytes:
            if carried is not None:
                item = carried
                carried = None
            else:
                try:
                    item = next(it)
                except StopIteration:
                    break
            nbytes, get_slice, expected = item[0], item[1], item[2]
            cost = item[3] if len(item) > 3 else nbytes
            if pendings and batch_bytes + cost > window_bytes:
                # Over budget with work already in flight: finalize the
                # current window first. Nothing was materialized for this
                # item yet — the size came from the manifest.
                carried = item
                break
            arr = get_slice()
            pending = _dispatch(arr)
            if pending is None:
                return False
            # Keep only (pending, nbytes): the slice buffer itself can be
            # freed as soon as the jit consumes it.
            pendings.append((pending, nbytes, expected))
            batch_bytes += cost
            del arr
        if not pendings:
            return True
        for pending, nbytes, expected in pendings:
            if _finalize_from_nbytes(nbytes, pending) != expected:
                return False
