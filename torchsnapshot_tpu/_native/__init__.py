"""ctypes loader for the native runtime (see native.cpp).

Compiles lazily with g++ on first use (no pybind11 — the binding surface is
three C functions), caches the .so next to the source, and degrades to pure
Python when no toolchain is available:

- ``crc32c(data, crc=0)``   - native (SSE4.2 or slicing-by-8) or a Python
                              table fallback; identical values either way.
- ``scatter_copy(dst, src, regions)`` - batched memcpy, falling back to
                              per-region memoryview slicing.
- ``slab_alloc/slab_free/slab_view`` - pinned, page-aligned, pre-faulted
                              staging slabs (the staging pool's backing
                              store; manual lifetime, pool-owned).
- ``uring_*``               - io_uring engine bindings (int-level; the
                              engine object lives in native_io.py).
- ``native_available()``    - True when the compiled extension is loaded.

Kill switch: ``TORCHSNAPSHOT_TPU_DISABLE_NATIVE=1`` forces the fallbacks
and disables the slab allocator + io_uring surface with them (used by
tests and the CI native-absent leg to cover both paths).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

DISABLE_NATIVE_ENV_VAR = "TORCHSNAPSHOT_TPU_DISABLE_NATIVE"

_SRC = os.path.join(os.path.dirname(__file__), "native.cpp")
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17", "-msse4.2")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_lock = threading.Lock()
# What _try_load did, for entry points that report it (chip_smoke.py).
_build_info: Dict[str, Any] = {"so": None, "compiled_now": False}


def _so_path() -> str:
    """The cached build's path, keyed on the CONTENT of native.cpp and the
    compile flags. ``*.so`` is git-ignored and copies of the tree do not
    keep mtimes, so a binary is trusted only under the name its source
    hashes to: a stale or foreign ``.so`` is never loaded, the matching
    one is simply built again."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    # tsalint: allow[restricted-context] unreachable from UringEngine.__del__ in practice: an engine only exists after the lib loaded, so _load_attempted is True and _load's fast path returns before _try_load can be reached
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(
        os.path.dirname(_SRC), f"_ts_native.{h.hexdigest()[:16]}.so"
    )


def _build(so: str) -> bool:
    # Compile to a unique temp path (first use can race across executor
    # THREADS of one process as well as across processes — pid alone is not
    # unique enough) and publish atomically with os.replace: a CDLL() must
    # never observe a half-written .so.
    tmp = f"{so}.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp"
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp]
    try:
        # tsalint: allow[restricted-context] unreachable from UringEngine.__del__ in practice: an engine only exists after the lib loaded, so _load_attempted is True and _load's fast path returns before _build can be reached
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning(
            "native extension build failed (%s); using Python fallbacks", e
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    # tsalint: allow[restricted-context] safe from UringEngine.__del__: an engine only exists after the lib loaded, so the fast path above already returned; the lock is only ever reachable on true first-touch threads
    with _load_lock:
        return _load_locked()


def _load_locked() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:  # raced another thread to the lock
        return _lib
    try:
        _lib = _try_load()
    finally:
        # Published AFTER _lib: _load()'s unlocked fast path reads
        # `_load_attempted` without the lock, so setting it first would
        # let a concurrent caller observe attempted=True with a stale
        # _lib=None and silently take the slow Python fallback for the
        # rest of ITS call sites (observed as nondeterministic crc32-vs-
        # crc32c checksums when streaming's first-touch raced staging).
        _load_attempted = True
    return _lib


def _try_load() -> Optional[ctypes.CDLL]:
    if os.environ.get(DISABLE_NATIVE_ENV_VAR, "0") not in ("0", "", "false"):
        return None
    so = _so_path()
    if not os.path.exists(so):
        if not _build(so):
            return None
        _build_info["compiled_now"] = True
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:  # pragma: no cover
        logger.warning(
            "native extension load failed (%s); using Python fallbacks", e
        )
        return None
    _build_info["so"] = so
    lib.ts_crc32c.restype = ctypes.c_uint32
    lib.ts_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.ts_has_hw_crc.restype = ctypes.c_int
    lib.ts_scatter_copy.restype = None
    lib.ts_scatter_copy.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
    ]
    lib.ts_gather_copy.restype = None
    lib.ts_gather_copy.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t,
    ]
    lib.ts_copy_crc32c.restype = ctypes.c_uint32
    lib.ts_copy_crc32c.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32,
    ]
    lib.ts_slab_alloc.restype = ctypes.c_void_p
    lib.ts_slab_alloc.argtypes = [
        ctypes.c_size_t, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    lib.ts_slab_free.restype = None
    lib.ts_slab_free.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.ts_uring_init.restype = ctypes.c_void_p
    lib.ts_uring_init.argtypes = [ctypes.c_uint]
    lib.ts_uring_close.restype = None
    lib.ts_uring_close.argtypes = [ctypes.c_void_p]
    lib.ts_uring_probe.restype = ctypes.c_int
    lib.ts_uring_probe.argtypes = []
    lib.ts_uring_submit.restype = ctypes.c_int
    lib.ts_uring_submit.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint,
    ]
    lib.ts_uring_wait_slot.restype = ctypes.c_int
    lib.ts_uring_wait_slot.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ts_uring_drain.restype = ctypes.c_int
    lib.ts_uring_drain.argtypes = [ctypes.c_void_p]
    return lib


def native_available() -> bool:
    return _load() is not None


def build_info() -> Dict[str, Any]:
    """How the extension got here: ``available``; ``built_from_source``
    (the loaded binary is the one native.cpp + the compile flags hash to,
    the only kind the loader accepts); ``compiled_now`` (this process ran
    g++ rather than finding that binary cached); ``so`` (its path)."""
    available = native_available()
    return {
        "available": available,
        "built_from_source": available,
        "compiled_now": _build_info["compiled_now"],
        "so": _build_info["so"],
    }


# ------------------------------------------------------------------ crc32c

_PY_TABLE: Optional[List[int]] = None


def _py_table() -> List[int]:
    global _PY_TABLE
    if _PY_TABLE is None:
        poly = 0x82F63B78
        table = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (poly if crc & 1 else 0)
            table.append(crc)
        _PY_TABLE = table
    return _PY_TABLE


def _crc32c_py(data, crc: int = 0) -> int:
    table = _py_table()
    crc = ~crc & 0xFFFFFFFF
    for b in bytes(data):
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return ~crc & 0xFFFFFFFF


def _as_flat_u8(data, writable_target: bool = False):
    """(numpy u8 view, address) of a contiguous buffer — no copy. numpy is
    the portable way to take the address of a possibly-readonly buffer.

    ``writable_target=True`` marks a buffer that will be WRITTEN through the
    returned address; a non-contiguous input would be silently copied and
    the writes lost, so it is rejected instead."""
    import numpy as np

    mv = memoryview(data)
    if not mv.contiguous:
        if writable_target:
            raise ValueError("destination buffer must be contiguous")
        mv = memoryview(bytes(mv))
    arr = np.frombuffer(mv, dtype=np.uint8)
    return arr, arr.ctypes.data


def crc32c(data, crc: int = 0) -> int:
    """CRC32C (Castagnoli) of ``data`` (any buffer-protocol object).

    Chainable: ``crc32c(b, crc32c(a)) == crc32c(a + b)``.
    """
    lib = _load()
    if lib is None:
        return _crc32c_py(memoryview(data).cast("B"), crc)
    arr, addr = _as_flat_u8(data)
    if arr.nbytes == 0:
        return crc
    return lib.ts_crc32c(
        ctypes.cast(addr, ctypes.c_char_p), arr.nbytes, ctypes.c_uint32(crc)
    )


# ------------------------------------------------------------- scatter copy

Region = Tuple[int, int, int]  # (dst_off, src_off, nbytes)


def scatter_copy(dst, src, regions: Sequence[Region]) -> None:
    """Batched ``dst[d:d+n] = src[s:s+n]`` for every region in one call."""
    if not regions:
        return
    lib = _load()
    if lib is None or len(regions) < 4:
        dst_mv = memoryview(dst).cast("B")
        src_mv = memoryview(src).cast("B")
        for d, s, n in regions:
            dst_mv[d : d + n] = src_mv[s : s + n]
        return
    n = len(regions)
    dst_arr, dst_addr = _as_flat_u8(dst, writable_target=True)
    src_arr, src_addr = _as_flat_u8(src)
    if dst_arr.flags["WRITEABLE"] is False:
        raise ValueError("scatter_copy destination buffer is read-only")
    dst_off = (ctypes.c_uint64 * n)(*(r[0] for r in regions))
    src_off = (ctypes.c_uint64 * n)(*(r[1] for r in regions))
    sizes = (ctypes.c_uint64 * n)(*(r[2] for r in regions))
    for d, s, sz in regions:
        if d + sz > dst_arr.nbytes or s + sz > src_arr.nbytes:
            raise ValueError(
                f"scatter_copy region out of bounds: dst[{d}:{d+sz}) "
                f"src[{s}:{s+sz}) for dst={dst_arr.nbytes}B src={src_arr.nbytes}B"
            )
    lib.ts_scatter_copy(
        ctypes.c_void_p(dst_addr), ctypes.c_void_p(src_addr),
        dst_off, src_off, sizes, n,
    )


def gather_copy(dst, sources: Sequence[Tuple[int, Any]]) -> None:
    """Pack separate source buffers into ``dst``: for each (dst_off, src),
    ``dst[dst_off : dst_off+len(src)] = src`` — one native call for the
    write-batcher's slab packing."""
    if not sources:
        return
    lib = _load()
    if lib is None or len(sources) < 4:
        dst_mv = memoryview(dst).cast("B")
        for off, src in sources:
            mv = memoryview(src).cast("B")
            dst_mv[off : off + mv.nbytes] = mv
        return
    n = len(sources)
    dst_arr, dst_addr = _as_flat_u8(dst, writable_target=True)
    if dst_arr.flags["WRITEABLE"] is False:
        raise ValueError("gather_copy destination buffer is read-only")
    src_keepalive = [_as_flat_u8(src) for _, src in sources]
    sizes_list = [arr.nbytes for arr, _ in src_keepalive]
    for (off, _), sz in zip(sources, sizes_list):
        if off + sz > dst_arr.nbytes:
            raise ValueError(
                f"gather_copy region out of bounds: dst[{off}:{off+sz}) "
                f"for dst={dst_arr.nbytes}B"
            )
    src_ptrs = (ctypes.c_void_p * n)(*(addr for _, addr in src_keepalive))
    dst_off = (ctypes.c_uint64 * n)(*(off for off, _ in sources))
    sizes = (ctypes.c_uint64 * n)(*sizes_list)
    lib.ts_gather_copy(ctypes.c_void_p(dst_addr), src_ptrs, dst_off, sizes, n)


# ------------------------------------------------------- fused copy + crc

def copy_crc32c(dst, src, crc: int = 0) -> Optional[int]:
    """``dst[:] = src[:]`` and return the bytes' CRC32C, reading the source
    ONCE (async_take staging fuses its consistency copy with the integrity
    checksum — one memory pass instead of two). Returns None when the
    native extension is unavailable; callers fall back to copy-then-hash.
    Both buffers must be contiguous and equal-sized.

    Chainable like :func:`crc32c` via ``crc``: the streaming write path
    fuses each sub-chunk's bounce copy with the running checksum —
    ``copy_crc32c(d2, b, copy_crc32c(d1, a)) == crc32c(a + b)``."""
    lib = _load()
    if lib is None:
        return None
    dst_arr, dst_addr = _as_flat_u8(dst, writable_target=True)
    if dst_arr.flags["WRITEABLE"] is False:
        raise ValueError("copy_crc32c destination buffer is read-only")
    src_arr, src_addr = _as_flat_u8(src)
    if dst_arr.nbytes != src_arr.nbytes:
        raise ValueError(
            f"copy_crc32c size mismatch: dst={dst_arr.nbytes}B "
            f"src={src_arr.nbytes}B"
        )
    if src_arr.nbytes == 0:
        return crc
    return lib.ts_copy_crc32c(
        ctypes.c_void_p(dst_addr),
        ctypes.c_void_p(src_addr),
        src_arr.nbytes,
        ctypes.c_uint32(crc),
    )


# ------------------------------------------------------- pinned slabs
#
# Page-aligned, pre-faulted, best-effort-pinned staging memory for the
# process staging pool (io_preparers/array.py). The allocation is
# manual-lifetime: the pool owns each slab and frees it on eviction —
# the capability degradation (no hugetlb pool, RLIMIT_MEMLOCK) happens
# inside the C allocator and is reported via the caps bitmask.

SLAB_HUGETLB = 1
SLAB_MLOCK = 2
SLAB_PREFAULT = 4
SLAB_THP = 8
_SLAB_WANT = SLAB_HUGETLB | SLAB_MLOCK | SLAB_PREFAULT | SLAB_THP

# Union of capability bits achieved by any allocation this process made
# (telemetry/stats surface it; individual slabs may differ).
_slab_caps_seen = 0


def slab_allocator_available() -> bool:
    """True when pinned native slabs can back the staging pool."""
    return _load() is not None


def slab_caps_seen() -> int:
    return _slab_caps_seen


def slab_alloc(nbytes: int) -> Optional[Tuple[int, int]]:
    """Allocate a pre-faulted, page-aligned slab; ``(addr, caps)`` or
    None. The caller owns the mapping and must ``slab_free`` it."""
    global _slab_caps_seen
    lib = _load()
    if lib is None or nbytes <= 0:
        return None
    got = ctypes.c_int(0)
    ptr = lib.ts_slab_alloc(nbytes, _SLAB_WANT, ctypes.byref(got))
    if not ptr:
        return None
    _slab_caps_seen |= got.value
    return int(ptr), got.value


def slab_free(addr: int, nbytes: int) -> None:
    lib = _load()
    if lib is not None and addr:
        lib.ts_slab_free(ctypes.c_void_p(addr), nbytes)


def slab_view(nbytes: int):
    """A writable uint8 ndarray over a fresh pinned slab, or None.

    The array does NOT own the mapping (its base is a ``from_address``
    ctypes array): whoever holds the view must eventually call
    ``slab_free(view.ctypes.data, view.nbytes)`` — the staging pool's
    eviction path does."""
    import numpy as np

    out = slab_alloc(nbytes)
    if out is None:
        return None
    addr, _caps = out
    return np.frombuffer((ctypes.c_ubyte * nbytes).from_address(addr), np.uint8)


# ----------------------------------------------------------- io_uring
#
# Thin int-level passthroughs; the engine object (buffer pinning, slot
# bookkeeping, errno -> exception mapping) lives in native_io.py so this
# loader stays a pure binding surface.

IOSQE_ASYNC = 0x10  # force kernel-worker execution (submit returns fast)


def uring_probe() -> int:
    """0 when an io_uring ring can be set up, else -errno."""
    lib = _load()
    if lib is None:
        return -1
    return int(lib.ts_uring_probe())


def uring_init(depth: int) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    handle = lib.ts_uring_init(ctypes.c_uint(depth))
    return int(handle) if handle else None


def uring_close(handle: int) -> None:
    lib = _load()
    if lib is not None and handle:
        lib.ts_uring_close(ctypes.c_void_p(handle))


def uring_submit(
    handle: int,
    is_write: bool,
    fd: int,
    addr: int,
    nbytes: int,
    offset: int,
    sqe_flags: int = IOSQE_ASYNC,
) -> int:
    lib = _load()
    assert lib is not None
    return int(
        lib.ts_uring_submit(
            ctypes.c_void_p(handle),
            1 if is_write else 0,
            fd,
            ctypes.c_void_p(addr),
            ctypes.c_uint64(nbytes),
            ctypes.c_uint64(offset),
            ctypes.c_uint(sqe_flags),
        )
    )


def uring_wait_slot(handle: int, slot: int) -> int:
    lib = _load()
    assert lib is not None
    return int(lib.ts_uring_wait_slot(ctypes.c_void_p(handle), slot))


def uring_drain(handle: int) -> int:
    lib = _load()
    assert lib is not None
    return int(lib.ts_uring_drain(ctypes.c_void_p(handle)))
