"""Memory-budgeted async execution engine for write/read requests.

TPU-native redesign of the reference scheduler (torchsnapshot/scheduler.py):
two asyncio pipelines under a per-process host-memory budget.

Write pipeline::

    ready_for_staging -> staging -> ready_for_io -> io -> done

Staging performs the device->host boundary crossing (for jax.Arrays the
stager issues ``copy_to_host_async`` DMA and materializes a numpy view) and
serialization; it is capped by the memory budget, with a starvation escape
that admits one over-budget request when nothing is in flight (otherwise a
single huge array could deadlock the pipeline; reference: scheduler.py:255-275).
The budget admits a whole train state at once, so the device transfers pass
a second, narrower gate: each ``execute_write_reqs`` call makes one
``DtoHWindow`` (io_preparers/array.py) and a device-backed leaf's DMA is
kicked, in staging order, when the leaves ahead of it have left room in the
window; a leaf gives its bytes back when it is staged (on the host and
checksummed), so a window's worth of transfers is in flight, and de-tiled by
the runtime, at a time, while the writes of the leaves before run beside
them. Kicked all at once, 26-48 transfers arrive at a fifth to a third of
one stream's rate. Host arrays have no DMA and bypass the window.
I/O concurrency is capped at 16 in-flight requests (scheduler.py:30).

``execute_write_reqs`` returns a :class:`PendingIOWork` as soon as **staging**
completes — this is the consistency point that lets ``async_take`` guarantee
that mutations after it returns do not affect the snapshot, while storage I/O
continues in the background (reference: scheduler.py:297-337).

Read pipeline:: read -> consume, with the same budget accounting
(scheduler.py:384-444).

**Streaming reads** (``TORCHSNAPSHOT_TPU_STREAM_READS``, default on):
entries whose consumer and storage plugin both opt in skip the
read-everything-then-consume two-step — the plugin yields sub-chunks as
the transport delivers them (fs: pread windows with read-ahead; s3/gcs:
a bounded window of in-flight ranged GETs yielded in order) and the
consumer verifies chained CRC32C incrementally, feeds decompression
incrementally, and issues per-sub-chunk ``jax.device_put`` — HtoD of
chunk N overlaps the read of chunk N+1, collapsing a large entry's
restore wall toward ~max(read, consume). The budget charges streamed
entries the consumer-declared retention (``stream_admission_cost`` —
the in-flight window for device-bound and direct-fill consumers), not
their full consuming cost, so large single-entry restores stop
serializing behind the budget.

The per-process budget is ``min(0.6 * available_memory / local_world_size,
32 GiB)``, overridable via ``TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_BYTES``
(scheduler.py:27-65).

**Streaming writes** (``allow_streaming``, sync saves only): entries whose
stager and storage plugin both opt in skip the stage-then-write two-step —
one task streams 32-256 MB sub-chunks from the stager straight into the
plugin, overlapping the DtoH copy/serialization of sub-chunk N+1 with the
storage write of sub-chunk N, so a single large entry's wall is
~max(stage, write) instead of stage + write. The budget charges streamed
entries the plugin-declared retention (``stream_admission_cost`` — the
stager's 2-chunk window for fs, part buffers for s3, the retained stream
for gcs), not their full staging size.

**Cooperative restore fan-out** (fanout.py): when a multi-rank restore
engages cooperation, each read request carries a role — owners read from
storage and FORWARD every sub-chunk to subscribing peers over the peer
byte channel (one-send lookahead, so forwarding rides under the local
decode), peer-fed entries consume forwarded sub-chunks through the same
streaming consumers a storage stream feeds (full CRC re-verified on the
receiver), and any peer failure degrades that entry to a direct storage
read with the budget re-charged. Peer-fed entries are exempt from the
I/O slot cap (they issue no storage request) and dispatch first so
receiver-side buffering stays bounded by the owners' read speed.

**I/O governor** (:class:`IOGovernor`): sub-chunk size, I/O concurrency,
and the restore-side preverify gate adapt to rates this module measures on
its own traffic (per-plugin write/read bandwidth) plus the fingerprint
hash throughput recorded by warmup — static constants tuned for one host
class are wrong on the next.
"""

from __future__ import annotations

import asyncio
import logging
import os
import socket
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Set

import psutil

from . import faultinject, telemetry
from .telemetry import forensics
from .io_preparers.array import DtoHWindow, dtoh_window
from .io_types import (
    ReadIO,
    ReadReq,
    ReadStream,
    StoragePlugin,
    StreamRestartRequired,
    WriteIO,
    WriteReq,
    WriteStream,
)

logger = logging.getLogger(__name__)

def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            logger.warning("ignoring non-integer %s=%r", name, raw)
    return default


try:
    # Respects cgroup cpusets/affinity masks: a pod limited to 2 cores on
    # a 64-core node must get the few-core defaults, not 64's.
    _CPU_COUNT = len(os.sched_getaffinity(0)) or 1
except (AttributeError, OSError):  # pragma: no cover - non-Linux
    _CPU_COUNT = os.cpu_count() or 1
IO_CONCURRENCY_ENV_VAR = "TORCHSNAPSHOT_TPU_IO_CONCURRENCY"
CPU_CONCURRENCY_ENV_VAR = "TORCHSNAPSHOT_TPU_CPU_CONCURRENCY"
# I/O concurrency lives in IOGovernor.io_concurrency (host-scaled
# default, adapted to measured storage bandwidth, pinned by
# IO_CONCURRENCY_ENV_VAR).
_MAX_PER_RANK_CPU_CONCURRENCY = _env_int(
    CPU_CONCURRENCY_ENV_VAR, min(4, max(2, _CPU_COUNT // 2))
)
_AVAILABLE_MEMORY_MULTIPLIER = 0.6
_MAX_PER_RANK_MEMORY_BUDGET_BYTES = 32 * 1024**3
_MEMORY_BUDGET_ENV_VAR = "TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_BYTES"

# ------------------------------------------------------------ I/O governor

SUB_CHUNK_ENV_VAR = "TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES"
SUB_CHUNK_MIN_ENV_VAR = "TORCHSNAPSHOT_TPU_SUB_CHUNK_MIN_BYTES"
SUB_CHUNK_MAX_ENV_VAR = "TORCHSNAPSHOT_TPU_SUB_CHUNK_MAX_BYTES"
PREVERIFY_ENV_VAR = "TORCHSNAPSHOT_TPU_PREVERIFY"
STREAM_READS_ENV_VAR = "TORCHSNAPSHOT_TPU_STREAM_READS"

# Measured read bandwidth below which storage counts as latency-bound:
# streamed reads then pay off even for consumers that retain the whole
# payload (the overlap hides transport latency). At/above it, local
# page-cache reads are memcpy-speed and the buffered mmap path's fewer
# copies win for those consumers. Same 1 GB/s knee io_concurrency uses.
_STREAM_READ_LATENCY_BPS = 1e9


def stream_reads_mode() -> str:
    """THE parser for ``TORCHSNAPSHOT_TPU_STREAM_READS``: ``never``
    disables streamed reads, ``always`` streams every eligible entry,
    and the default ``auto`` streams an entry when doing so buys
    something — a smaller budget charge than the buffered consume
    (device-bound, sliced, and coalesced-slab consumers), or measured
    latency-bound storage where read/consume overlap hides transport
    latency even at full retention."""
    raw = os.environ.get(STREAM_READS_ENV_VAR, "auto").strip().lower()
    if raw in ("0", "false", "off", "no", "never"):
        return "never"
    if raw in ("always", "force"):
        return "always"
    return "auto"


def stream_reads_enabled() -> bool:
    return stream_reads_mode() != "never"

_DEFAULT_SUB_CHUNK_BYTES = 64 << 20
_DEFAULT_SUB_CHUNK_MIN_BYTES = 8 << 20
_DEFAULT_SUB_CHUNK_MAX_BYTES = 256 << 20
# Sub-chunks should take this long to write at the measured bandwidth:
# long enough to amortize per-chunk dispatch (executor hops, pwrite
# syscalls), short enough that the stage/write pipeline has several
# stages in flight per entry.
_SUB_CHUNK_TARGET_SECONDS = 0.05
# Skip the preverify hash pass only when reading is CLEARLY cheaper:
# the margin absorbs rate-measurement noise and the HtoD cost a read
# still pays after the storage fetch.
_PREVERIFY_READ_MARGIN = 1.25
# Depose an elected native engine only when its measured rate falls
# clearly below the plugin's non-native rate — hysteresis against the
# two meters' different windows (whole pipeline vs one stream).
_NATIVE_FALLBACK_MARGIN = 0.75
# Dead band around a boolean gate's knee: once a should_* election is
# made, the measured rate must cross the knee by this fraction to flip
# it back — a rate hovering at the knee (EWMA jitter) must not flip-flop
# a fast path on and off between consecutive ops.
_KNEE_MARGIN = 0.10


class IOGovernor:
    """Process-wide adaptive tuner for the save/restore hot path.

    Static constants tuned for one host class are wrong on the next
    (1-core CI box vs 64-core pod host vs network storage): the governor
    records ACHIEVED rates — per-plugin storage write/read bandwidth
    (from the scheduler's own throughput meters) and on-device hash
    throughput (from the fingerprint warmup / a one-time probe) — and
    derives the tunables from them, within env-var bounds:

    - ``sub_chunk_bytes``: streaming sub-chunk size, sized so one
      sub-chunk takes ~``_SUB_CHUNK_TARGET_SECONDS`` to write at the
      measured bandwidth (fast local storage gets big chunks that
      amortize syscalls; slow network storage gets small chunks that
      keep the pipeline busy). Pinned by ``TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES``.
    - ``io_concurrency``: in-flight storage requests. Bandwidth-bound
      local storage saturates with few streams (extra ones thrash the
      cache hierarchy); latency-bound network storage needs many.
      Pinned by ``TORCHSNAPSHOT_TPU_IO_CONCURRENCY``.
    - ``should_preverify``: whether restore-time distributed digest
      verification is cheaper than just re-reading (VERDICT round-5
      item 6) — hashing wins on slow storage, reading wins on fast
      local disk with a slow hasher. ``TORCHSNAPSHOT_TPU_PREVERIFY``
      forces ``always``/``never``; default ``auto`` verifies unless
      reading is provably cheaper (missing measurements keep the
      status-quo verify).

    Rates are exponentially smoothed (alpha 0.5): one anomalous save
    (page-cache flush, noisy neighbor) moves a tunable halfway at most,
    and the next clean measurement pulls it back.

    Every election resolves env override > measured-rate heuristic: a
    function of the environment, the recorded rates and the knee band's
    one bit of memory, the same with telemetry on or off.
    """

    _EWMA_ALPHA = 0.5

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._write_bps: Dict[str, float] = {}
        self._read_bps: Dict[str, float] = {}
        self._hash_bps: Optional[float] = None
        #: Last (value, source) per (dim, plugin): the decision-change
        #: detector that keeps ``governor.elect`` flight events to
        #: transitions (io_concurrency is consulted inside dispatch loops).
        self._elections: Dict[Any, Any] = {}
        #: Boolean gate memory for the knee dead band (_banded).
        self._gate_state: Dict[Any, bool] = {}

    # ------------------------------------------------------- recording

    def _ewma(self, table: Dict[str, float], key: str, bps: float) -> None:
        with self._lock:
            prev = table.get(key)
            table[key] = (
                bps
                if prev is None
                else prev + self._EWMA_ALPHA * (bps - prev)
            )

    def record_write(self, plugin: str, nbytes: int, seconds: float) -> None:
        if nbytes <= 0 or seconds <= 1e-6:
            return
        self._ewma(self._write_bps, plugin, nbytes / seconds)

    def record_read(self, plugin: str, nbytes: int, seconds: float) -> None:
        if nbytes <= 0 or seconds <= 1e-6:
            return
        self._ewma(self._read_bps, plugin, nbytes / seconds)

    def record_hash(self, nbytes: int, seconds: float) -> None:
        if nbytes <= 0 or seconds <= 1e-6:
            return
        bps = nbytes / seconds
        with self._lock:
            self._hash_bps = (
                bps
                if self._hash_bps is None
                else self._hash_bps + self._EWMA_ALPHA * (bps - self._hash_bps)
            )

    # ------------------------------------------------------- measured rates

    def write_bps(self, plugin: Optional[str] = None) -> Optional[float]:
        with self._lock:
            if plugin is not None:
                return self._write_bps.get(plugin)
            return max(self._write_bps.values()) if self._write_bps else None

    def read_bps(self, plugin: Optional[str] = None) -> Optional[float]:
        with self._lock:
            if plugin is not None:
                return self._read_bps.get(plugin)
            return max(self._read_bps.values()) if self._read_bps else None

    def hash_bps(self) -> Optional[float]:
        with self._lock:
            return self._hash_bps

    def measured_rates(self) -> Dict[str, object]:
        """Snapshot of every measured rate, for logs and benchmarks."""
        with self._lock:
            return {
                "write_bps": dict(self._write_bps),
                "read_bps": dict(self._read_bps),
                "hash_bps": self._hash_bps,
            }

    # -------------------------------------------------- election plumbing

    def _resolved(
        self,
        site: str,
        dim: str,
        plugin: Optional[str],
        value: Any,
        source: str,
        **inputs: Any,
    ) -> Any:
        """Every election site funnels its decision through here: one
        ``governor.elect`` flight event per (dim, plugin) WHEN THE
        DECISION CHANGES (the hot dispatch loops re-consult
        io_concurrency; steady-state re-elections must not flood the
        ring). ``source`` is ``env`` (operator override) or
        ``heuristic`` (sized from the measured rates)."""
        key = (dim, plugin or "")
        with self._lock:
            changed = self._elections.get(key) != (value, source)
            self._elections[key] = (value, source)
        if changed:
            named = {"plugin": plugin} if plugin else {}
            telemetry.record_election(
                site=site, dim=dim, value=value, source=source, **named, **inputs
            )
        return value

    def _banded(
        self, gate: str, plugin: Optional[str], rate: float, knee: float
    ) -> bool:
        """Knee comparison with a dead band: True while the rate is
        below the knee, but once a decision is made the rate must cross
        the knee by ``_KNEE_MARGIN`` to flip it — measurement jitter
        around the knee cannot flip-flop a fast path between ops."""
        key = (gate, plugin or "")
        with self._lock:
            prev = self._gate_state.get(key)
            if prev is None:
                decision = rate < knee
            elif prev:
                decision = rate < knee * (1.0 + _KNEE_MARGIN)
            else:
                decision = rate < knee * (1.0 - _KNEE_MARGIN)
            self._gate_state[key] = decision
        return decision

    # ---------------------------------------------------------- tunables

    def sub_chunk_bytes(self, plugin: Optional[str] = None, op: str = "write") -> int:
        """Streaming sub-chunk size for ``op`` ("write"/"read") — env
        override > sized from the MATCHING measured bandwidth (a fast
        local save must not size a later network restore's read
        windows, and vice versa)."""
        dim = f"sub_chunk.{op}"
        pinned = os.environ.get(SUB_CHUNK_ENV_VAR, "").strip()
        if pinned:
            try:
                # An explicit pin is honored as-is (tests pin tiny chunks
                # to exercise many-sub-chunk streams on small payloads).
                value = max(1, int(pinned))
            except ValueError:
                logger.warning(
                    "ignoring non-integer %s=%r", SUB_CHUNK_ENV_VAR, pinned
                )
            else:
                return self._resolved("sub_chunk", dim, plugin, value, "env")
        lo = _env_int(SUB_CHUNK_MIN_ENV_VAR, _DEFAULT_SUB_CHUNK_MIN_BYTES)
        hi = _env_int(SUB_CHUNK_MAX_ENV_VAR, _DEFAULT_SUB_CHUNK_MAX_BYTES)
        hi = max(lo, hi)
        bps = self.read_bps(plugin) if op == "read" else self.write_bps(plugin)
        if bps is None:
            return self._resolved(
                "sub_chunk", dim, plugin,
                min(max(_DEFAULT_SUB_CHUNK_BYTES, lo), hi), "heuristic",
            )
        target = int(bps * _SUB_CHUNK_TARGET_SECONDS)
        # Round to a 1 MB multiple: exact-size staging-pool free lists
        # recycle far better when sizes don't wander byte-by-byte.
        target = max(1 << 20, (target >> 20) << 20)
        return self._resolved(
            "sub_chunk", dim, plugin, min(max(target, lo), hi), "heuristic",
            bps=round(bps),
        )

    def io_concurrency(
        self, op: str = "write", plugin: Optional[str] = None
    ) -> int:
        """In-flight storage requests for ``op`` ("write"/"read") — env
        override > tuned from the MATCHING measured rate (a fast local
        save must not clamp concurrency for a later latency-bound
        network restore, and vice versa), for ``plugin`` when it has a
        recorded rate."""
        dim = f"io_concurrency.{op}"
        raw = os.environ.get(IO_CONCURRENCY_ENV_VAR, "").strip()
        if raw:
            try:
                value = max(1, int(raw))
            except ValueError:
                pass  # warned at import time by _env_int
            else:
                return self._resolved("io_concurrency", dim, plugin, value, "env")
        default = min(16, max(8, 2 * _CPU_COUNT))
        table = self.read_bps if op == "read" else self.write_bps
        bps = table(plugin)
        if bps is None and plugin is not None:
            bps = table(None)  # best-known rate for this op
        if bps is None:
            value = default
        elif bps >= 1e9:
            # Bandwidth-bound (local SSD/tmpfs): a couple of streams per
            # core saturate the bus; more just thrash caches.
            value = min(default, max(4, 2 * _CPU_COUNT))
        elif bps <= 1e8:
            # Latency-bound (network storage): hide per-request latency
            # with every stream the cap allows.
            value = 16
        else:
            value = default
        return self._resolved(
            "io_concurrency", dim, plugin, value, "heuristic",
            bps=round(bps) if bps is not None else None,
        )

    def should_preverify(self, plugin: Optional[str] = None) -> bool:
        """``plugin``: the storage plugin the CURRENT restore reads
        from. The crossover must use THAT backend's measured read rate —
        a fast local read recorded earlier in the process must not talk
        a later object-store restore out of its near-free verify skip.
        No recorded rate for this plugin means no evidence: verify."""
        mode = preverify_mode()
        if mode == "always":
            return self._resolved("preverify", "preverify", plugin, True, "env")
        if mode == "never":
            return self._resolved("preverify", "preverify", plugin, False, "env")
        hash_bps = self.hash_bps()
        read_bps = self.read_bps(plugin) if plugin is not None else self.read_bps()
        if hash_bps is None or read_bps is None:
            # No evidence: keep the zero-byte verify path.
            return self._resolved(
                "preverify", "preverify", plugin, True, "heuristic"
            )
        # The crossover knee with the gate dead band: hovering at
        # read ~= hash * margin must not flip verification per-restore.
        value = self._banded(
            "preverify", plugin, read_bps, hash_bps * _PREVERIFY_READ_MARGIN
        )
        return self._resolved(
            "preverify", "preverify", plugin, value, "heuristic",
            read_bps=round(read_bps), hash_bps=round(hash_bps),
        )

    def should_native_io(self, plugin: Optional[str] = None, op: str = "write") -> bool:
        """Economic gate for the native I/O engine (native_io.py, under
        ``TORCHSNAPSHOT_TPU_NATIVE_IO=auto``). The fs plugin records
        per-stream native-engine rates under ``<Plugin>.native`` — the
        same EWMA tables every plugin rate lands in — so the engine is
        measured like any backend and elected like streaming:

        - **writes**: optimistic while unmeasured (the way streaming
          writes default on — queued SQEs are never worse than the
          sequential pwrite loop), deposed only when the engine's own
          measured rate falls clearly below what the pipeline achieves
          without it. The margin absorbs the mismatch between the two
          meters (the plugin-keyed rate spans the whole pipeline; the
          ``.native`` rate one stream).
        - **reads**: the streamed-read latency knee. Queue depth pays
          where per-request transport latency can hide behind it; on
          memcpy-speed local reads (page cache) the engine measurably
          loses to the mmap/pread paths, so native reads engage only on
          measured latency-bound storage (no measurement = no evidence
          = Python path, the read-side status quo bias)."""
        dim = f"native.{op}"
        table = self._read_bps if op == "read" else self._write_bps
        with self._lock:
            native = table.get(f"{plugin}.native") if plugin else None
            base = table.get(plugin) if plugin else None
        if op == "read":
            if base is None or not self._banded(
                dim, plugin, base, _STREAM_READ_LATENCY_BPS
            ):
                return self._resolved(
                    "native", dim, plugin, False, "heuristic"
                )
            value = native is None or native >= _NATIVE_FALLBACK_MARGIN * base
            return self._resolved("native", dim, plugin, value, "heuristic")
        if native is None or base is None:
            # No evidence against it: gather measurements.
            return self._resolved("native", dim, plugin, True, "heuristic")
        value = native >= _NATIVE_FALLBACK_MARGIN * base
        return self._resolved(
            "native", dim, plugin, value, "heuristic",
            native_bps=round(native), base_bps=round(base),
        )

    def should_coop_restore(self, plugin: Optional[str] = None) -> bool:
        """Economic gate for cooperative restore fan-out (fanout.py,
        under ``TORCHSNAPSHOT_TPU_COOP_RESTORE=auto``): partitioning
        replicated reads across ranks and redistributing sub-chunks over
        the host network wins ~N× when storage bandwidth is the
        bottleneck, but on memcpy-speed local storage (page-cache reads)
        the socket copy costs more than just re-reading — the same
        latency-bound knee the streamed-read election uses. No recorded
        read rate for this restore's backend means no evidence: direct
        reads (the status quo) stay."""
        return self._knee_gate("coop_restore", plugin)

    def should_planned_reshard(self, plugin: Optional[str] = None) -> bool:
        """Economic gate for the planned-reshard tier (reshard.py, under
        ``TORCHSNAPSHOT_TPU_RESHARD=auto``): replacing R storage reads
        of a multi-requester shard with one read plus minimal peer
        region bundles wins exactly when storage bandwidth — not the
        host network — is the bottleneck, which is the same knee the
        coop-restore and streamed-read elections sit on. Memcpy-speed
        local fs (page-cache reads) stays on the direct overlap-scatter
        path; no recorded read rate means no evidence, so the status quo
        stays."""
        return self._knee_gate("planned_reshard", plugin)

    def should_seed_restore(self, plugin: Optional[str] = None) -> bool:
        """Economic gate for the fleet seeding tier (distrib.py, under
        ``TORCHSNAPSHOT_TPU_SEED_RESTORE=auto``): sourcing shareable
        chunks from peers that already hold them beats a direct storage
        read exactly when storage bandwidth — not the host network — is
        the bottleneck, the same knee as the coop-restore and planned-
        reshard elections. Unlike those, this election is PER-REPLICA
        (every seed miss independently falls back to a direct read), so
        asymmetric decisions across the fleet are safe — but the
        evidence rule is identical: no recorded read rate for this
        backend means no evidence, and direct reads stay."""
        return self._knee_gate("seed_restore", plugin)

    def _knee_gate(self, gate: str, plugin: Optional[str]) -> bool:
        """The shared latency-bound election (coop restore, planned
        reshard, seed restore): the measured-rate knee with the
        flip-flop dead band."""
        bps = self.read_bps(plugin) if plugin is not None else self.read_bps()
        value = bps is not None and self._banded(
            gate, plugin, bps, _STREAM_READ_LATENCY_BPS
        )
        return self._resolved(
            gate, gate, plugin, value, "heuristic",
            read_bps=round(bps) if bps is not None else None,
        )


def preverify_mode() -> str:
    """THE parser for ``TORCHSNAPSHOT_TPU_PREVERIFY`` — every consumer
    (the governor's gate, snapshot's explicit-instruction guard) goes
    through here so the recognized spellings can never drift between
    them. Unrecognized values fall back to ``auto``."""
    raw = os.environ.get(PREVERIFY_ENV_VAR, "auto").strip().lower()
    if raw in ("1", "always", "on", "true", "yes"):
        return "always"
    if raw in ("0", "never", "off", "false", "no"):
        return "never"
    return "auto"


_governor: Optional[IOGovernor] = None
_governor_lock = threading.Lock()


def io_governor() -> IOGovernor:
    global _governor
    if _governor is None:
        with _governor_lock:
            if _governor is None:
                _governor = IOGovernor()
    return _governor


def reset_io_governor() -> IOGovernor:
    """Replace the process governor with a fresh instance. Test/bench
    hook: "a new process on this host" (fresh EWMA tables, no gate
    memory). The bus rate listener resolves the current instance per
    call, so the swap is safe mid-process."""
    global _governor
    with _governor_lock:
        _governor = IOGovernor()
        return _governor


def _feed_governor_rates(
    kind: str, key: Optional[str], nbytes: int, seconds: float
) -> None:
    """Telemetry-bus rate listener: achieved write/read/hash rates are
    published to the bus (telemetry.record_rate) by whoever measured
    them; the governor's EWMA tables consume them here, keeping
    ``measured_rates()`` a VIEW over bus-fed data rather than a second
    measurement mechanism."""
    governor = io_governor()
    if kind == "write":
        governor.record_write(key or "", nbytes, seconds)
    elif kind == "read":
        governor.record_read(key or "", nbytes, seconds)
    elif kind == "hash":
        governor.record_hash(nbytes, seconds)


telemetry.register_rate_listener(_feed_governor_rates)


def get_local_world_size(pg=None) -> int:
    """Number of processes on this host, via hostname all-gather
    (reference: scheduler.py:33-42)."""
    if pg is None or pg.get_world_size() == 1:
        return 1
    hostnames = pg.all_gather_object(socket.gethostname())
    return max(1, hostnames.count(socket.gethostname()))


def get_process_memory_budget_bytes(pg=None) -> int:
    env = os.environ.get(_MEMORY_BUDGET_ENV_VAR)
    if env is not None:
        budget = int(env)
        logger.info("Manually set process memory budget to %d bytes.", budget)
        return budget
    local_world_size = get_local_world_size(pg)
    available = psutil.virtual_memory().available
    budget = min(
        int(available * _AVAILABLE_MEMORY_MULTIPLIER) // local_world_size,
        _MAX_PER_RANK_MEMORY_BUDGET_BYTES,
    )
    logger.debug("Process memory budget: %d bytes.", budget)
    return budget


class _WritePipeline:
    def __init__(
        self,
        write_req: WriteReq,
        sub_chunk_bytes: Optional[int] = None,
        storage: Optional[StoragePlugin] = None,
    ) -> None:
        self.write_req = write_req
        self.staging_cost_bytes: int = (
            write_req.buffer_stager.get_staging_cost_bytes()
        )
        self.buf = None
        self.buf_size_bytes: Optional[int] = None
        self.io_skipped = False
        # Streaming election happens at construction: the stager opts in
        # for THIS sub-chunk size, and the budget then charges the
        # PLUGIN-declared retention (stager window for fs; part buffers
        # for s3; full retained stream for gcs) instead of the whole
        # entry's staging cost.
        self.sub_chunk_bytes = sub_chunk_bytes
        self.streamed = False
        if sub_chunk_bytes is not None and write_req.buffer_stager.can_stream(
            sub_chunk_bytes
        ):
            self.admission_cost_bytes: int = min(
                self.staging_cost_bytes,
                storage.stream_admission_cost(
                    self.staging_cost_bytes, sub_chunk_bytes
                ),
            )
            self.streamed = True
        else:
            self.admission_cost_bytes = self.staging_cost_bytes

    async def stage_buffer(self, executor) -> "_WritePipeline":
        faultinject.site("scheduler.stage")
        with telemetry.span(
            "stage", path=self.write_req.path, bytes=self.staging_cost_bytes
        ):
            self.buf = await self.write_req.buffer_stager.stage_buffer(executor)
            self.buf_size_bytes = memoryview(self.buf).nbytes
        # Incremental snapshots: the stager found the payload unchanged in a
        # base snapshot — drop the buffer instead of writing it.
        if getattr(self.write_req.buffer_stager, "io_skipped", False):
            self.io_skipped = True
            self.buf = None
            self.buf_size_bytes = 0
            telemetry.counter_add("bytes_deduped", self.staging_cost_bytes)
        else:
            telemetry.counter_add("bytes_staged", self.buf_size_bytes)
        return self

    @staticmethod
    async def _timed_write_chunks(chunks, plugin_key: str):
        """Per-sub-chunk latency sampler on the streamed write path: the
        time from requesting a sub-chunk to handing it to the plugin is
        one pipeline step (stage of N+1 overlapping write of N), exactly
        the distribution a stall diagnosis needs — a p99 spike here with
        a flat p50 is the signature of periodic reclaim/throttle stalls
        that averages hide. Installed only while telemetry is enabled."""
        try:
            while True:
                t0 = telemetry.monotonic()
                try:
                    chunk = await chunks.__anext__()
                except StopAsyncIteration:
                    return
                telemetry.histogram_observe(
                    "write.sub_chunk_s",
                    telemetry.monotonic() - t0,
                    key=plugin_key,
                )
                yield chunk
        finally:
            # stream_write's cleanup acloses THIS wrapper; the inner
            # stager stream must unwind with it (pooled staging buffers
            # are released in its finally blocks).
            aclose = getattr(chunks, "aclose", None)
            if aclose is not None:
                await aclose()

    async def stream_write(
        self, storage: StoragePlugin, executor
    ) -> "_WritePipeline":
        """Fused stage+write: the stager yields sub-chunks as they land
        on the host and the plugin writes each while the next stages —
        the entry's wall becomes ~max(stage, write) instead of
        stage + write. Runs as ONE task occupying one I/O slot; by the
        time it completes the entry is both staged and durably written,
        so it never enters ready_for_io."""
        stager = self.write_req.buffer_stager
        chunks = stager.stage_stream(executor, self.sub_chunk_bytes)
        if telemetry.enabled():
            chunks = self._timed_write_chunks(chunks, type(storage).__name__)
        try:
            # The forensics guard is per ENTRY, not per sub-chunk: one
            # registry insert/remove per storage op feeds the watchdog's
            # own p99 baseline (the telemetry histograms are off by
            # default, so the stall trigger cannot lean on them).
            with forensics.storage_op(
                "storage_write", path=self.write_req.path
            ), telemetry.span(
                "stream_write",
                path=self.write_req.path,
                bytes=self.staging_cost_bytes,
                sub_chunk_bytes=self.sub_chunk_bytes,
            ):
                await storage.write_stream(
                    WriteStream(
                        path=self.write_req.path,
                        nbytes=self.staging_cost_bytes,
                        chunks=chunks,
                    )
                )
        finally:
            aclose = getattr(chunks, "aclose", None)
            if aclose is not None:
                await aclose()
        self.buf_size_bytes = self.staging_cost_bytes
        telemetry.counter_add("bytes_staged", self.staging_cost_bytes)
        telemetry.counter_add("entries_streamed", 1)
        return self

    async def write_buffer(self, storage: StoragePlugin) -> "_WritePipeline":
        assert self.buf is not None
        t0 = telemetry.monotonic() if telemetry.enabled() else None
        with forensics.storage_op(
            "storage_write", path=self.write_req.path
        ), telemetry.span(
            "storage_write", path=self.write_req.path, bytes=self.buf_size_bytes
        ):
            await storage.write(WriteIO(path=self.write_req.path, buf=self.buf))
        if t0 is not None:
            telemetry.histogram_observe(
                "write.entry_s",
                telemetry.monotonic() - t0,
                key=type(storage).__name__,
            )
        self.buf = None  # release the staged buffer eagerly
        return self


class _ProgressReporter:
    """Periodic pipeline progress tables (reference: _WriteReporter,
    scheduler.py:96-175): stage counts, bytes staged/written, budget
    remaining, and RSS delta — the observability needed to diagnose a stall
    on a real pod save. Runs as an asyncio task on the pipeline's loop;
    logs at INFO every ``interval_s``.

    One sampler, three sinks: each tick emits the log table, a
    flight-recorder ``progress`` event (so an abort dump shows where the
    pipeline was, tick by tick), and the live health plane's byte/queue
    fields (telemetry.health — what ``watch`` renders). The read and
    write pipelines share ONE assembly: the read pipeline has no staging
    phase, so its staging columns are simply absent — not a second
    format string that drifts."""

    def __init__(
        self,
        op: str,
        rank: int,
        total: int,
        budget: "_MemoryBudget",
        interval_s: Optional[float] = None,
    ) -> None:
        if interval_s is None:
            # TORCHSNAPSHOT_TPU_PROGRESS_S tunes the sampling cadence —
            # the log table, the flight-recorder progress events, and the
            # heartbeat byte feed all tick together (an operator watching
            # a short take wants sub-second frames; default 5 s).
            raw = os.environ.get("TORCHSNAPSHOT_TPU_PROGRESS_S", "").strip()
            try:
                interval_s = float(raw) if raw else 5.0
            except ValueError:
                interval_s = 5.0
        self.op = op
        self.rank = rank
        self.total = total
        # Total payload bytes for this pipeline, when the caller knows it
        # (feeds the heartbeat's ETA; 0 = unknown).
        self.total_bytes = 0
        self.budget = budget
        self.interval_s = interval_s
        self.staged_count = 0
        self.staged_bytes = 0
        # Op-neutral completion counters: "written" entries for the write
        # pipeline, "consumed" reads for the read pipeline (the log wording
        # is per-op; the fields are shared).
        self.completed_count = 0
        self.completed_bytes = 0
        self.inflight_staging = 0
        self.inflight_io = 0
        self._begin = telemetry.monotonic()
        try:
            self._rss_begin = psutil.Process().memory_info().rss
        except Exception:  # pragma: no cover
            self._rss_begin = 0
        self._task: Optional[asyncio.Task] = None
        # Live binding-resource hint (critpath.live_binding over the bus
        # events recorded since the last tick) — fed into the heartbeat
        # so `watch` shows WHAT a straggler is stuck on.
        self._binding_since_id = 0

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._loop())

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
            # One last table: the heartbeat and the log end on the final
            # counts, not on those of whichever tick happened to land
            # (none at all, when the loop was busy for the whole phase).
            self.log_table()

    async def _loop(self) -> None:
        try:
            while True:
                await asyncio.sleep(self.interval_s)
                self.log_table()
        except asyncio.CancelledError:
            pass

    def log_table(self) -> None:
        try:
            rss_delta = psutil.Process().memory_info().rss - self._rss_begin
        except Exception:  # pragma: no cover
            rss_delta = 0
        elapsed = telemetry.monotonic() - self._begin
        # The periodic table doubles as the bus's queue-depth sampler:
        # gauges render as counter tracks in the exported trace.
        telemetry.gauge_set(f"{self.op}_inflight_staging", self.inflight_staging)
        telemetry.gauge_set(f"{self.op}_inflight_io", self.inflight_io)
        telemetry.gauge_set("budget_free_bytes", self.budget.available)
        is_read = self.op == "read"
        done_word = "consumed" if is_read else "written"
        cols = [f"{self.total} total"]
        if not is_read:
            cols.append(f"{self.inflight_staging} staging")
            cols.append(f"{self.staged_count} staged")
        cols.append(f"{self.inflight_io} in {'flight' if is_read else 'io'}")
        cols.append(f"{self.completed_count} {done_word}")
        vols = [] if is_read else [f"{self.staged_bytes / 1e9:.2f} GB staged"]
        vols.append(f"{self.completed_bytes / 1e9:.2f} GB {done_word}")
        logger.info(
            "[rank %d] %s progress +%.0fs | reqs: %s | %s | budget free "
            "%.2f/%.2f GB | rss delta %+.2f GB",
            self.rank,
            self.op,
            elapsed,
            ", ".join(cols),
            ", ".join(vols),
            self.budget.available / 1e9,
            self.budget.budget_bytes / 1e9,
            rss_delta / 1e9,
        )
        telemetry.flightrec.record(
            "progress",
            op=self.op,
            total=self.total,
            done=self.completed_count,
            done_bytes=self.completed_bytes,
            staged_bytes=self.staged_bytes,
            inflight_staging=self.inflight_staging,
            inflight_io=self.inflight_io,
        )
        fields: Dict[str, Any] = {
            "total_entries": self.total,
            "done_entries": self.completed_count,
            "inflight_io": self.inflight_io,
        }
        if self.total_bytes:
            fields["total_bytes"] = self.total_bytes
        if is_read:
            fields["read_bytes"] = self.completed_bytes
        else:
            fields["staged_bytes"] = self.staged_bytes
            fields["written_bytes"] = self.completed_bytes
        binding = self._live_binding(is_read)
        if binding is not None:
            fields["binding"] = binding
        telemetry.health.update(**fields)

    def _live_binding(self, is_read: bool) -> Optional[str]:
        """What this rank is currently bound on, for the heartbeat.
        With the bus on, the attribution engine's window estimate over
        the spans since the last tick; with it off, a coarse queue-shape
        heuristic — a straggler's `watch` row should say "storage_write",
        not just "stalled"."""
        if telemetry.enabled():
            from .telemetry import critpath

            evs = telemetry.events(since_id=self._binding_since_id)
            if evs:
                self._binding_since_id = max(e.get("id", 0) for e in evs)
                binding = critpath.live_binding(evs)
                if binding is not None:
                    return binding
        if self.inflight_io > 0 and self.inflight_staging == 0:
            return "storage_read" if is_read else "storage_write"
        if self.inflight_staging > 0 and self.inflight_io == 0:
            return "stage_copy" if not is_read else None
        return None


class _Throughput:
    """Tracks bytes moved + wall time to log MB/s summaries
    (reference: scheduler.py:96-175,441-442)."""

    def __init__(self, op: str, rank: int) -> None:
        self.op = op
        self.rank = rank
        self.begin = telemetry.monotonic()
        self.total_bytes = 0

    def add(self, nbytes: int) -> None:
        self.total_bytes += nbytes

    def elapsed(self) -> float:
        return max(telemetry.monotonic() - self.begin, 1e-9)

    def log_summary(self) -> None:
        elapsed = self.elapsed()
        logger.info(
            "[rank %d] %s %.1f MB in %.2fs (%.1f MB/s)",
            self.rank,
            self.op,
            self.total_bytes / 1e6,
            elapsed,
            self.total_bytes / 1e6 / elapsed,
        )


class PendingIOWork:
    """Handle over storage I/O still in flight after staging completed."""

    def __init__(
        self,
        ready_for_io: List[_WritePipeline],
        io_tasks: Set[asyncio.Task],
        storage: StoragePlugin,
        memory_budget: "_MemoryBudget",
        executor: ThreadPoolExecutor,
        throughput: _Throughput,
        event_loop: asyncio.AbstractEventLoop,
        reporter: Optional[_ProgressReporter] = None,
    ) -> None:
        self._ready_for_io = ready_for_io
        self._io_tasks = io_tasks
        self._storage = storage
        self._budget = memory_budget
        self._executor = executor
        self._throughput = throughput
        self._event_loop = event_loop
        self._reporter = reporter

    async def complete(self) -> None:
        reporter = self._reporter
        if reporter is not None:
            reporter.start()
        try:
            while self._io_tasks or self._ready_for_io:
                self._dispatch_io()
                if not self._io_tasks:
                    continue
                done, pending = await asyncio.wait(
                    self._io_tasks, return_when=asyncio.FIRST_COMPLETED
                )
                self._io_tasks = pending
                for task in done:
                    pipeline = task.result()
                    self._budget.release(pipeline.buf_size_bytes)
                    self._throughput.add(pipeline.buf_size_bytes)
                    telemetry.counter_add("bytes_written", pipeline.buf_size_bytes)
                    telemetry.counter_add("entries_written", 1)
                    if reporter is not None:
                        reporter.inflight_io -= 1
                        reporter.completed_count += 1
                        reporter.completed_bytes += pipeline.buf_size_bytes
        except BaseException:
            # Same cleanup as execute_write_reqs' failure path: a write
            # failing during the drain must not orphan sibling writes or
            # leak the executor's threads.
            for task in self._io_tasks:
                task.cancel()
            if self._io_tasks:
                await asyncio.gather(*self._io_tasks, return_exceptions=True)
            self._io_tasks = set()
            self._ready_for_io.clear()
            self._executor.shutdown(wait=True)
            raise
        finally:
            if reporter is not None:
                reporter.stop()
        self._executor.shutdown(wait=True)
        self._throughput.log_summary()
        # Publish the ACHIEVED end-to-end write bandwidth on the bus (the
        # meter spans staging + I/O — exactly the rate the next save's
        # sub-chunk sizing and concurrency should be tuned for); the
        # governor consumes it via its registered rate listener.
        telemetry.record_rate(
            "write",
            type(self._storage).__name__,
            self._throughput.total_bytes,
            self._throughput.elapsed(),
        )

    def _dispatch_io(self) -> None:
        while (
            self._ready_for_io
            and len(self._io_tasks)
            < io_governor().io_concurrency(
                "write", type(self._storage).__name__
            )
        ):
            pipeline = self._ready_for_io.pop(0)
            self._io_tasks.add(
                self._event_loop.create_task(pipeline.write_buffer(self._storage))
            )
            if self._reporter is not None:
                self._reporter.inflight_io += 1

    def sync_complete(self, event_loop: asyncio.AbstractEventLoop) -> None:
        event_loop.run_until_complete(self.complete())

    async def abort(self) -> None:
        """Cancel in-flight storage writes and release resources.

        Used when a peer rank's failure aborts the snapshot: without this,
        dispatched writes keep running unawaited (orphaned partial objects,
        swallowed I/O errors) and the executor's threads leak."""
        self._ready_for_io.clear()
        for task in self._io_tasks:
            task.cancel()
        if self._io_tasks:
            await asyncio.gather(*self._io_tasks, return_exceptions=True)
        self._io_tasks = set()
        self._executor.shutdown(wait=True)

    def sync_abort(self, event_loop: asyncio.AbstractEventLoop) -> None:
        event_loop.run_until_complete(self.abort())


class _MemoryBudget:
    def __init__(self, budget_bytes: int) -> None:
        self.budget_bytes = budget_bytes
        self.available = budget_bytes

    def acquire(self, nbytes: int) -> None:
        self.available -= nbytes

    def release(self, nbytes: int) -> None:
        self.available += nbytes


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    allow_streaming: bool = False,
) -> PendingIOWork:
    event_loop = asyncio.get_running_loop()
    executor = ThreadPoolExecutor(max_workers=_MAX_PER_RANK_CPU_CONCURRENCY)
    budget = _MemoryBudget(memory_budget_bytes)
    throughput = _Throughput("wrote", rank)
    reporter = _ProgressReporter("write", rank, len(write_reqs), budget)
    reporter.start()

    governor = io_governor()
    plugin_key = type(storage).__name__
    # Streaming fuses staging with storage I/O, so a streamed entry's
    # write completes before this function returns — callers that rely on
    # the staging-complete consistency point RETURNING EARLY (async_take)
    # must not enable it. Only plugins that consume chunks incrementally
    # are eligible (the buffered write_stream fallback would hold a full
    # entry while the budget charged a sub-chunk window). Sub-chunk size
    # comes from measured bandwidth.
    sub_chunk = (
        governor.sub_chunk_bytes(plugin_key)
        if allow_streaming and getattr(storage, "supports_streaming", False)
        else None
    )
    io_concurrency = governor.io_concurrency("write", plugin_key)
    # Tenancy admission (tenancy/admission.py): a session armed on this
    # op's storage scales the I/O-slot cap by the tenant's bandwidth
    # share and paces each dispatched request through its token bucket.
    # None on every non-tenant op — the attribute probe is the whole
    # disabled-path cost here.
    admission = getattr(storage, "_tsnap_admission", None)
    if admission is not None:
        io_concurrency = admission.scale_concurrency(io_concurrency)

    async def _paced(coro, nbytes):
        await admission.admit(nbytes, "write", plugin_key)
        return await coro

    ready_for_staging = [
        _WritePipeline(req, sub_chunk_bytes=sub_chunk, storage=storage)
        for req in write_reqs
    ]
    reporter.total_bytes = sum(p.staging_cost_bytes for p in ready_for_staging)
    # Stage large requests first: improves budget packing and overlaps the
    # slowest DtoH copies with I/O of everything else.
    ready_for_staging.sort(key=lambda p: p.staging_cost_bytes, reverse=True)
    n_streamed = sum(1 for p in ready_for_staging if p.streamed)
    if n_streamed:
        logger.debug(
            "[rank %d] streaming %d/%d write(s) in %d MB sub-chunks",
            rank,
            n_streamed,
            len(ready_for_staging),
            (sub_chunk or 0) >> 20,
        )
    # Record the governor's write-path election (what was chosen and the
    # rates it saw): the flight recorder carries the always-on copy for
    # abort dumps/`blackbox`, the bus instant rides the per-op summary
    # for `explain`.
    telemetry.record_election(
        site="write",
        plugin=plugin_key,
        streaming=sub_chunk is not None,
        streamed_entries=n_streamed,
        sub_chunk_bytes=sub_chunk,
        io_concurrency=io_concurrency,
        write_bps=governor.write_bps(plugin_key),
    )
    staging_tasks: Set[asyncio.Task] = set()
    io_tasks: Set[asyncio.Task] = set()
    ready_for_io: List[_WritePipeline] = []
    inflight_streams = 0

    def dispatch_staging() -> None:
        nonlocal inflight_streams
        deferred: List[_WritePipeline] = []
        while ready_for_staging:
            head = ready_for_staging[0]
            # A streamed entry occupies a storage stream for its whole
            # lifetime, so streams and buffered writes share ONE
            # io_concurrency cap — counting them separately would let a
            # mixed workload run 2x the intended concurrent requests.
            if head.streamed and (
                inflight_streams + len(io_tasks) >= io_concurrency
            ):
                deferred.append(ready_for_staging.pop(0))
                continue
            cost = head.admission_cost_bytes
            if cost > budget.available:
                # Starvation escape: if nothing is in flight, admit the
                # over-budget request — otherwise it would never run.
                if staging_tasks or io_tasks or ready_for_io or deferred:
                    telemetry.counter_add("budget_defers", 1)
                    break
            pipeline = ready_for_staging.pop(0)
            budget.acquire(pipeline.admission_cost_bytes)
            if pipeline.streamed:
                inflight_streams += 1
                stream_coro = pipeline.stream_write(storage, executor)
                if admission is not None:
                    stream_coro = _paced(
                        stream_coro, pipeline.admission_cost_bytes
                    )
                staging_tasks.add(event_loop.create_task(stream_coro))
            else:
                staging_tasks.add(
                    event_loop.create_task(pipeline.stage_buffer(executor))
                )
            reporter.inflight_staging += 1
        # Stream-slot-deferred entries keep their order at the head.
        ready_for_staging[:0] = deferred

    def dispatch_io() -> None:
        # Streams count against the same cap (see dispatch_staging).
        while ready_for_io and len(io_tasks) + inflight_streams < io_concurrency:
            pipeline = ready_for_io.pop(0)
            io_coro = pipeline.write_buffer(storage)
            if admission is not None:
                # Pacing runs INSIDE the slot: a throttled tenant's
                # request occupies its (already share-scaled) slot while
                # it waits, which is exactly the backpressure intended.
                io_coro = _paced(io_coro, pipeline.admission_cost_bytes)
            io_tasks.add(event_loop.create_task(io_coro))
            reporter.inflight_io += 1

    # One DtoH window for this call: the staging tasks created below
    # inherit it, and the array stagers among them admit their device
    # transfers through it.
    window_token = dtoh_window.set(DtoHWindow())
    try:
        dispatch_staging()
        while staging_tasks or ready_for_staging:
            done, _ = await asyncio.wait(
                staging_tasks | io_tasks, return_when=asyncio.FIRST_COMPLETED
            )
            for task in done:
                if task in staging_tasks:
                    staging_tasks.discard(task)
                    pipeline = task.result()
                    reporter.inflight_staging -= 1
                    reporter.staged_count += 1
                    reporter.staged_bytes += pipeline.buf_size_bytes
                    if pipeline.streamed:
                        # Fused stage+write: the entry is already on
                        # storage. Release the sub-chunk window charge and
                        # account the write here.
                        inflight_streams -= 1
                        budget.release(pipeline.admission_cost_bytes)
                        throughput.add(pipeline.buf_size_bytes)
                        telemetry.counter_add(
                            "bytes_written", pipeline.buf_size_bytes
                        )
                        telemetry.counter_add("entries_written", 1)
                        reporter.completed_count += 1
                        reporter.completed_bytes += pipeline.buf_size_bytes
                        continue
                    # The staged buffer may be smaller than the staging cost
                    # (e.g. a strided view); release the difference now.
                    budget.release(
                        pipeline.staging_cost_bytes - pipeline.buf_size_bytes
                    )
                    if not pipeline.io_skipped:
                        ready_for_io.append(pipeline)
                elif task in io_tasks:
                    io_tasks.discard(task)
                    pipeline = task.result()
                    budget.release(pipeline.buf_size_bytes)
                    throughput.add(pipeline.buf_size_bytes)
                    telemetry.counter_add("bytes_written", pipeline.buf_size_bytes)
                    telemetry.counter_add("entries_written", 1)
                    reporter.inflight_io -= 1
                    reporter.completed_count += 1
                    reporter.completed_bytes += pipeline.buf_size_bytes
            dispatch_io()
            dispatch_staging()
    except BaseException:
        # A staging/I/O failure aborts the snapshot: cancel siblings and
        # release the executor so repeated failures don't leak threads.
        reporter.stop()
        for task in staging_tasks | io_tasks:
            task.cancel()
        if staging_tasks or io_tasks:
            await asyncio.gather(
                *(staging_tasks | io_tasks), return_exceptions=True
            )
        executor.shutdown(wait=True)
        raise
    finally:
        dtoh_window.reset(window_token)
    reporter.stop()

    return PendingIOWork(
        ready_for_io=ready_for_io,
        io_tasks=io_tasks,
        storage=storage,
        memory_budget=budget,
        executor=executor,
        throughput=throughput,
        event_loop=event_loop,
        reporter=reporter,
    )


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    allow_streaming: bool = True,
) -> None:
    # Synchronous callers block until I/O drains, so fusing staging with
    # storage writes (streaming) costs them nothing semantically.
    pending = event_loop.run_until_complete(
        execute_write_reqs(
            write_reqs,
            storage,
            memory_budget_bytes,
            rank,
            allow_streaming=allow_streaming,
        )
    )
    pending.sync_complete(event_loop)


class _ReadPipeline:
    def __init__(
        self,
        read_req: ReadReq,
        sub_chunk_bytes: Optional[int] = None,
        stream_all: bool = False,
        coop_plan=None,
        peer_sub_chunk: Optional[int] = None,
    ) -> None:
        self.read_req = read_req
        self.consuming_cost_bytes: int = (
            read_req.buffer_consumer.get_consuming_cost_bytes()
        )
        # Cooperative restore fan-out (fanout.py): the plan assigns this
        # request a role — SendRole (this rank reads from storage and
        # forwards every sub-chunk to the subscribing peers), RecvRole
        # (another rank reads; the bytes arrive over the peer channel),
        # or None (plain direct read).
        self.coop_role = (
            coop_plan.take_role(read_req) if coop_plan is not None else None
        )
        self.coop_gen = 1
        self.peer_sub_chunk = peer_sub_chunk
        self.peer_streamed = False
        # Shared semaphore capping DIRECT-read fallbacks of peer-fed
        # entries at the governor's I/O concurrency (set by
        # execute_read_reqs when cooperation is active).
        self.fallback_gate: Optional[asyncio.Semaphore] = None
        if self.coop_role is not None and self.coop_role.is_recv:
            # Peer-fed: no storage I/O on the happy path, so the storage
            # streaming election below does not apply (a fallback after
            # peer failure reads buffered). Streaming eligibility is the
            # CONSUMER's alone — the peer channel always produces chunks
            # incrementally, whatever the storage plugin supports.
            self.sub_chunk_bytes = None
            self.streamed = False
            br = read_req.byte_range
            empty = br is not None and br[1] <= br[0]
            if (
                peer_sub_chunk is not None
                and not empty
                and read_req.buffer_consumer.can_stream(peer_sub_chunk)
            ):
                self.peer_streamed = True
                self.admission_cost_bytes: int = min(
                    self.consuming_cost_bytes,
                    read_req.buffer_consumer.stream_admission_cost(
                        peer_sub_chunk
                    ),
                )
            else:
                self.admission_cost_bytes = self.consuming_cost_bytes
            return
        # Streaming election happens at construction, mirroring the write
        # side: the consumer opts in for THIS sub-chunk size, and the
        # budget then charges the consumer-declared streamed retention
        # (the in-flight window for per-sub-chunk device_put and direct
        # destination fills; the full payload for verify-before-commit
        # scratch assembly) instead of the whole consuming cost.
        #
        # Under the default auto policy, full-retention consumers only
        # stream when ``stream_all`` says the storage is latency-bound
        # (or the operator forced it): on memcpy-speed local storage the
        # buffered mmap path's fewer copies beat the pipeline, and
        # streaming there would be a regression, not an optimization.
        self.sub_chunk_bytes = sub_chunk_bytes
        self.streamed = False
        br = read_req.byte_range
        empty = br is not None and br[1] <= br[0]
        if (
            sub_chunk_bytes is not None
            and not empty
            and read_req.buffer_consumer.can_stream(sub_chunk_bytes)
        ):
            window = min(
                self.consuming_cost_bytes,
                read_req.buffer_consumer.stream_admission_cost(sub_chunk_bytes),
            )
            if stream_all or window < self.consuming_cost_bytes:
                self.admission_cost_bytes: int = window
                self.streamed = True
        if not self.streamed:
            self.admission_cost_bytes = self.consuming_cost_bytes

    @property
    def is_recv(self) -> bool:
        return self.coop_role is not None and self.coop_role.is_recv

    @property
    def coop_order(self) -> int:
        """Dispatch priority class. Peer-fed entries first: they do no
        storage I/O (and are exempt from the I/O slot cap), and opening
        them early drains the peer inboxes the owners are already
        filling. Owned (forwarding) entries next, so every peer's
        receive side is fed as early as possible; plain reads last."""
        if self.coop_role is None:
            return 2
        return 0 if self.coop_role.is_recv else 1

    def _recharge(self, budget: Optional["_MemoryBudget"]) -> None:
        """The entry is about to hold its FULL payload (buffered retry
        or fallback) while the budget only charged a streamed window:
        charge the difference — possibly driving availability negative,
        like the starvation escape — so concurrent dispatch throttles
        instead of overshooting. Idempotent."""
        delta = self.consuming_cost_bytes - self.admission_cost_bytes
        if delta > 0 and budget is not None:
            budget.acquire(delta)
            self.admission_cost_bytes = self.consuming_cost_bytes

    # ---------------------------------------------------- peer-fed path

    async def _peer_stream_consume(
        self, role, consumer, executor, throughput: _Throughput
    ) -> None:
        source = role.stream()

        async def counted():
            observe = telemetry.enabled()
            while True:
                t0 = telemetry.monotonic() if observe else None
                with telemetry.span("peer_recv", cat="fanout"):
                    try:
                        chunk = await source.__anext__()
                    except StopAsyncIteration:
                        return
                if t0 is not None:
                    telemetry.histogram_observe(
                        "read.sub_chunk_s",
                        telemetry.monotonic() - t0,
                        key="peer",
                    )
                n = memoryview(chunk).nbytes
                throughput.add(n)
                telemetry.counter_add("bytes_read", n)
                telemetry.counter_add("bytes_from_peers", n)
                yield chunk

        stream = ReadStream(
            path=self.read_req.path,
            nbytes=self.consuming_cost_bytes,
            chunks=counted(),
        )
        try:
            await consumer.consume_stream(stream, executor)
        finally:
            aclose = getattr(source, "aclose", None)
            if aclose is not None:
                await aclose()

    async def _peer_read_and_consume(
        self, executor, throughput: _Throughput, budget: Optional["_MemoryBudget"]
    ) -> bool:
        """Consume this entry from its owner's forwarded sub-chunks.
        Returns False when the bytes cannot be delivered (owner death,
        abort, timeout, or integrity failure of the delivered bytes);
        the caller then degrades to a direct storage read — the fan-out
        failure contract: any peer failure costs one re-read, never a
        hang. The receiver runs the FULL verification chain itself
        (chained CRC, decompression), so a forwarding owner is never
        trusted with integrity."""
        from .fanout import PeerTransferError  # noqa: F401 (doc anchor)
        from .integrity import IntegrityError

        role = self.coop_role
        consumer = self.read_req.buffer_consumer
        path = self.read_req.path
        try:
            with telemetry.span(
                "coop_read", path=path, source=role.owner,
                bytes=self.consuming_cost_bytes,
            ):
                if self.peer_streamed:
                    try:
                        await self._peer_stream_consume(
                            role, consumer, executor, throughput
                        )
                        telemetry.counter_add("entries_read", 1)
                        telemetry.counter_add("entries_from_peers", 1)
                        return True
                    except StreamRestartRequired as e:
                        # The owner's storage stream restarted (mirror
                        # failover): pre-restart bytes are discarded
                        # WHOLESALE and the final generation arrives
                        # complete — never spliced.
                        logger.warning(
                            "peer-fed stream of %s restarting through the "
                            "buffered path: %s",
                            path,
                            e,
                        )
                        telemetry.counter_add("stream_read_restarts", 1)
                        self._recharge(budget)
                with telemetry.span("peer_recv", cat="fanout", path=path):
                    buf = await role.buffered()
                n = memoryview(buf).nbytes
                throughput.add(n)
                telemetry.counter_add("bytes_read", n)
                telemetry.counter_add("bytes_from_peers", n)
                with telemetry.span("consume", path=path, bytes=n):
                    await consumer.consume_buffer(buf, executor)
                telemetry.counter_add("entries_read", 1)
                telemetry.counter_add("entries_from_peers", 1)
                return True
        except (IOError, IntegrityError) as e:
            # IOError covers the whole transport failure family
            # (PeerTransferError, short/over-long transfers);
            # IntegrityError a checksum mismatch of peer-delivered bytes
            # — storage may still hold good bytes, so re-read directly
            # (and surface storage's own error if it does not).
            # The degraded-path exception is accounted exactly like a
            # storage retry: classify_error kind + history attrs on the
            # exception object, one taxonomy for every fallback.
            from .storage_plugins.retry import attach_fallback_history

            kind = attach_fallback_history(e)
            logger.warning(
                "peer-fed read of %s from rank %s failed (%s: %s); falling "
                "back to a direct storage read",
                path,
                role.owner,
                type(e).__name__,
                e,
            )
            telemetry.counter_add("fanout_fallbacks", 1)
            telemetry.flightrec.record(
                "fanout.fallback", key=path, owner=role.owner, kind=kind
            )
            telemetry.event(
                "fanout_fallback",
                cat="retry",
                kind=kind,
                path=path,
                source=role.owner,
                error=type(e).__name__,
            )
            self._recharge(budget)
            return False

    # ------------------------------------------------- owner forwarding

    async def _forward_buffer(self, role, buf) -> None:
        """Forward a buffered owner read to the subscribers, chunked at
        the peer sub-chunk size (one frame per chunk so receivers keep
        their incremental consume window)."""
        mv = memoryview(buf).cast("B")
        step = self.peer_sub_chunk or _DEFAULT_SUB_CHUNK_BYTES
        n = 0
        for lo in range(0, mv.nbytes, step):
            await role.chunk(self.coop_gen, n, mv[lo : lo + step])
            n += 1
        await role.end(self.coop_gen, mv.nbytes, n)

    async def _stream_read_and_consume(
        self, storage: StoragePlugin, executor, throughput: _Throughput
    ) -> bool:
        """Fused read+consume: the plugin yields sub-chunks as the
        transport delivers them and the consumer verifies/decodes each
        while the next is still in flight — the entry's restore wall
        becomes ~max(read, consume) instead of read + consume. Returns
        False when the stream demands a from-offset-0 restart
        (StreamRestartRequired); the caller then re-runs the entry
        through the buffered path.

        Under a cooperative SendRole every sub-chunk is ALSO forwarded
        to the subscribing peers with a one-send lookahead (chunk N
        ships while the local consumer decodes it), so peer consumption
        overlaps this owner's storage read; a restart bumps the
        generation so receivers discard pre-restart bytes wholesale."""
        read_io = ReadIO(
            path=self.read_req.path, byte_range=self.read_req.byte_range
        )
        consumer = self.read_req.buffer_consumer
        role = self.coop_role
        send = role if (role is not None and role.is_send) else None
        sent = {"n": 0, "bytes": 0}

        plugin_key = type(storage).__name__

        async def counted(chunks):
            pending_send = None
            observe = telemetry.enabled()
            try:
                while True:
                    t0 = telemetry.monotonic() if observe else None
                    try:
                        # The stream's wait for storage as the consumer
                        # sees it: the stretch the histogram below times.
                        with telemetry.span(
                            "stream_read_wait", path=self.read_req.path
                        ):
                            chunk = await chunks.__anext__()
                    except StopAsyncIteration:
                        break
                    if t0 is not None:
                        telemetry.histogram_observe(
                            "read.sub_chunk_s",
                            telemetry.monotonic() - t0,
                            key=plugin_key,
                        )
                    n = memoryview(chunk).nbytes
                    throughput.add(n)
                    telemetry.counter_add("bytes_read", n)
                    if send is not None:
                        telemetry.counter_add("bytes_from_storage", n)
                        if pending_send is not None:
                            await pending_send
                        pending_send = asyncio.get_running_loop().create_task(
                            send.chunk(self.coop_gen, sent["n"], chunk)
                        )
                        sent["n"] += 1
                        sent["bytes"] += n
                    yield chunk
                if pending_send is not None:
                    await pending_send
                    pending_send = None
            finally:
                if pending_send is not None:
                    # Unwinding mid-stream (consumer error/restart): let
                    # the in-flight frame land whole before closing.
                    try:
                        await pending_send
                    except Exception:  # noqa: BLE001 - unwind path
                        pass

        try:
            with forensics.storage_op(
                "storage_read", path=self.read_req.path
            ), telemetry.span(
                "stream_read",
                path=self.read_req.path,
                sub_chunk_bytes=self.sub_chunk_bytes,
            ) as sp:
                stream = await storage.read_stream(read_io, self.sub_chunk_bytes)
                sp.set(bytes=stream.nbytes)
                try:
                    await consumer.consume_stream(
                        ReadStream(
                            path=stream.path,
                            nbytes=stream.nbytes,
                            chunks=counted(stream.chunks),
                        ),
                        executor,
                    )
                finally:
                    aclose = getattr(stream.chunks, "aclose", None)
                    if aclose is not None:
                        await aclose()
        except StreamRestartRequired as e:
            logger.warning(
                "streamed read of %s restarting through the buffered "
                "path: %s",
                self.read_req.path,
                e,
            )
            telemetry.counter_add("stream_read_restarts", 1)
            if send is not None:
                # Subscribers must never splice post-restart bytes after
                # pre-restart ones: bump the generation (receivers drop
                # everything older) and re-forward the complete payload
                # from the buffered retry.
                self.coop_gen += 1
                await send.restart(self.coop_gen)
            return False
        if send is not None:
            await send.end(self.coop_gen, sent["bytes"], sent["n"])
        telemetry.counter_add("entries_read", 1)
        telemetry.counter_add("entries_stream_read", 1)
        return True

    async def read_and_consume(
        self,
        storage: StoragePlugin,
        executor,
        throughput: _Throughput,
        budget: Optional["_MemoryBudget"] = None,
    ) -> "_ReadPipeline":
        if self.is_recv:
            if await self._peer_read_and_consume(executor, throughput, budget):
                return self
            # Peer delivery failed (owner death / abort / timeout /
            # integrity): degrade to a direct storage read — the budget
            # difference was already re-charged. Dual-mode consumers
            # (reshard.PlannedRecvConsumer, whose peer payload is a
            # region BUNDLE rather than the stored payload) are told
            # first, so the re-read of the same request decodes as raw
            # storage bytes. The fallback is a REAL storage request that
            # dispatch's slot exemption never counted, so it takes a
            # slot here: a mass peer failure (dead owner with many
            # units) must not flood the backend with more concurrent
            # reads than the governor's cap.
            on_fallback = getattr(
                self.read_req.buffer_consumer, "on_peer_fallback", None
            )
            if on_fallback is not None:
                on_fallback()
            if self.fallback_gate is not None:
                async with self.fallback_gate:
                    await self._buffered_read_and_consume(
                        storage, executor, throughput, budget
                    )
            else:
                await self._buffered_read_and_consume(
                    storage, executor, throughput, budget
                )
            return self
        if self.streamed and await self._stream_read_and_consume(
            storage, executor, throughput
        ):
            return self
        await self._buffered_read_and_consume(storage, executor, throughput, budget)
        return self

    async def _buffered_read_and_consume(
        self,
        storage: StoragePlugin,
        executor,
        throughput: _Throughput,
        budget: Optional["_MemoryBudget"] = None,
    ) -> None:
        # The buffered retry/fallback holds the FULL payload while the
        # budget only charged the streamed window: charge the difference
        # (possibly driving availability negative, like the starvation
        # escape) so concurrent dispatch throttles instead of
        # overshooting the per-rank budget unaccounted.
        self._recharge(budget)
        read_io = ReadIO(
            path=self.read_req.path, byte_range=self.read_req.byte_range
        )
        br = read_io.byte_range
        if br is not None and br[1] <= br[0]:
            # Zero-length range (e.g. a zero-size array packed into a slab):
            # skip storage entirely — remote backends mishandle inverted or
            # empty Range headers (S3 ignores them, GCS returns 416).
            read_io.buf = bytearray()
        else:
            t0 = telemetry.monotonic() if telemetry.enabled() else None
            with forensics.storage_op(
                "storage_read", path=self.read_req.path
            ), telemetry.span("storage_read", path=self.read_req.path) as sp:
                await storage.read(read_io)
                sp.set(bytes=memoryview(read_io.buf).nbytes)
            if t0 is not None:
                telemetry.histogram_observe(
                    "read.entry_s",
                    telemetry.monotonic() - t0,
                    key=type(storage).__name__,
                )
        buf = read_io.buf
        throughput.add(len(buf))
        telemetry.counter_add("bytes_read", len(buf))
        telemetry.counter_add("entries_read", 1)
        role = self.coop_role
        if role is not None and role.is_send:
            telemetry.counter_add("bytes_from_storage", len(buf))
            # Forward BEFORE the local consume: subscribers' decode
            # pipelines start while this rank's consumer works.
            await self._forward_buffer(role, buf)
        with telemetry.span("consume", path=self.read_req.path, bytes=len(buf)):
            await self.read_req.buffer_consumer.consume_buffer(buf, executor)


async def execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    coop=None,
    preempt=None,
) -> None:
    event_loop = asyncio.get_running_loop()
    executor = ThreadPoolExecutor(max_workers=_MAX_PER_RANK_CPU_CONCURRENCY)
    budget = _MemoryBudget(memory_budget_bytes)
    throughput = _Throughput("read", rank)
    reporter = _ProgressReporter("read", rank, len(read_reqs), budget)
    reporter.start()

    governor = io_governor()
    plugin_key = type(storage).__name__
    # Streamed-read election mirrors the write side: only plugins that
    # produce chunks incrementally are eligible (the buffered read_stream
    # fallback would hold a full entry while the budget charged a
    # window), and each consumer still opts in per entry via can_stream.
    # Sub-chunk size comes from the measured READ bandwidth.
    mode = stream_reads_mode()
    sub_chunk = (
        governor.sub_chunk_bytes(plugin_key, op="read")
        if mode != "never"
        and getattr(storage, "supports_streaming_reads", False)
        else None
    )
    # Full-retention consumers stream too when the storage is measurably
    # latency-bound — there, overlap hides transport latency regardless
    # of the budget charge. No measurement means no evidence: buffered.
    read_bps = governor.read_bps(plugin_key)
    stream_all = mode == "always" or (
        read_bps is not None and read_bps < _STREAM_READ_LATENCY_BPS
    )
    # Cooperative fan-out (fanout.py): ``coop`` is this key's CoopKeyPlan.
    # The peer sub-chunk size is independent of the storage plugin's
    # streaming support — the peer channel always produces chunks
    # incrementally, and owners chunk buffered forwards at this size too.
    peer_chunk = (
        governor.sub_chunk_bytes(plugin_key, op="read") if coop is not None else None
    )
    pending = [
        _ReadPipeline(
            req,
            sub_chunk_bytes=sub_chunk,
            stream_all=stream_all,
            coop_plan=coop,
            peer_sub_chunk=peer_chunk,
        )
        for req in read_reqs
    ]
    reporter.total_bytes = sum(p.consuming_cost_bytes for p in pending)
    # Peer-fed entries dispatch first (no storage I/O; draining inboxes
    # early bounds receiver-side buffering), then owned/forwarding
    # entries (peers are waiting on them), then plain reads — and within
    # each class, largest first for budget packing.
    pending.sort(key=lambda p: (p.coop_order, -p.consuming_cost_bytes))
    n_streamed = sum(1 for p in pending if p.streamed)
    if n_streamed:
        logger.debug(
            "[rank %d] streaming %d/%d read(s) in %d MB sub-chunks",
            rank,
            n_streamed,
            len(pending),
            (sub_chunk or 0) >> 20,
        )
    inflight: Set[asyncio.Task] = set()
    inflight_recv = 0
    io_concurrency = governor.io_concurrency("read", plugin_key)
    # Tenancy admission, read side (see execute_write_reqs): scaled slot
    # cap + per-request pacing. Peer-fed entries are never paced — they
    # issue no storage request (their direct fallbacks are).
    admission = getattr(storage, "_tsnap_admission", None)
    if admission is not None:
        io_concurrency = admission.scale_concurrency(io_concurrency)

    async def _paced(coro, nbytes):
        await admission.admit(nbytes, "read", plugin_key)
        return await coro

    telemetry.record_election(
        site="read",
        plugin=plugin_key,
        mode=mode,
        streaming=sub_chunk is not None,
        streamed_entries=n_streamed,
        stream_all=stream_all,
        sub_chunk_bytes=sub_chunk,
        io_concurrency=io_concurrency,
        coop=coop is not None,
        read_bps=read_bps,
    )
    if coop is not None:
        fallback_gate = asyncio.Semaphore(io_concurrency)
        for p in pending:
            if p.is_recv:
                p.fallback_gate = fallback_gate

    def dispatch() -> None:
        nonlocal inflight_recv

        def launch(pipeline: _ReadPipeline) -> None:
            nonlocal inflight_recv
            budget.acquire(pipeline.admission_cost_bytes)
            if pipeline.is_recv:
                inflight_recv += 1
            read_coro = pipeline.read_and_consume(
                storage, executor, throughput, budget
            )
            if admission is not None and not pipeline.is_recv:
                read_coro = _paced(read_coro, pipeline.admission_cost_bytes)
            inflight.add(event_loop.create_task(read_coro))
            reporter.inflight_io += 1

        while pending:
            # Preemptible background pipeline (pagein.py): while the
            # hook reports a demand fault in flight, this execution
            # trickles — at most ONE request in flight (forward progress
            # is guaranteed; a full pause would deadlock a fault that
            # waits on this very batch) — so its I/O slots, and the
            # admission share they draw from, yield to the fault.
            if preempt is not None and inflight and preempt():
                break
            head = pending[0]
            # Peer-fed entries are exempt from the I/O slot cap: they
            # issue no storage request while waiting, and capping them
            # could starve the very sends that feed them. (Their direct
            # fallbacks DO take a slot — the fallback gate below.)
            if not head.is_recv and (len(inflight) - inflight_recv) >= io_concurrency:
                break
            cost = head.admission_cost_bytes
            if cost > budget.available and inflight:
                # Budget-blocked head. Parked peer-fed entries hold
                # budget while WAITING on peers' forwards; if everything
                # in flight is peer-fed, no LOCAL work will ever release
                # budget, and the owned/plain reads that feed the fleet
                # must not sit behind them — that head-of-line stall
                # would idle every rank into the coop timeout. Admit the
                # first non-peer-fed entry over budget instead (the same
                # starvation escape the write pipeline uses); the escape
                # self-closes once any non-recv work is in flight.
                if inflight_recv == len(inflight):
                    idx = next(
                        (i for i, p in enumerate(pending) if not p.is_recv),
                        None,
                    )
                    if idx is not None:
                        telemetry.counter_add("budget_defers", 1)
                        launch(pending.pop(idx))
                        continue
                break
            launch(pending.pop(0))

    dispatch()
    try:
        while inflight or pending:
            done, inflight_set = await asyncio.wait(
                inflight, return_when=asyncio.FIRST_COMPLETED
            )
            inflight = inflight_set
            for task in done:
                pipeline = task.result()
                budget.release(pipeline.admission_cost_bytes)
                if pipeline.is_recv:
                    inflight_recv -= 1
                reporter.inflight_io -= 1
                reporter.completed_count += 1
                reporter.completed_bytes += pipeline.consuming_cost_bytes
            dispatch()
    except BaseException:
        reporter.stop()
        for task in inflight:
            task.cancel()
        if inflight:
            await asyncio.gather(*inflight, return_exceptions=True)
        executor.shutdown(wait=True)
        raise
    reporter.stop()

    executor.shutdown(wait=True)
    throughput.log_summary()
    # Achieved read bandwidth feeds the restore-side preverify economics
    # (hash vs re-read) and concurrency tuning, via the bus's governor
    # listener.
    telemetry.record_rate(
        "read", type(storage).__name__, throughput.total_bytes, throughput.elapsed()
    )


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    coop=None,
    preempt=None,
) -> None:
    event_loop.run_until_complete(
        execute_read_reqs(
            read_reqs, storage, memory_budget_bytes, rank, coop=coop,
            preempt=preempt,
        )
    )
