"""Sub-chunk streaming read pipeline tests.

Four layers of coverage, mirroring the contract's seams:

- **Storage-plugin contract** (``CONTRACT_PLUGINS`` — the registry
  ``scripts/check_stream_contract.py`` lints against): for every plugin
  advertising ``supports_streaming_reads`` (fs real, s3/gcs fakes,
  mirror composition) plus the buffered default fallback, a streamed
  read must produce bytes identical to a buffered read of the same
  request (full and ranged), and zero-length ranged reads short-circuit
  inside the plugin.
- **Consumer semantics**: incremental chained CRC accepts/rejects
  exactly like the whole-buffer hash (raw, compressed, and byte-ranged
  slab payloads), a mid-stream exception leaves the destination array
  unmodified, and corruption is detected before anything commits.
- **Scheduler accounting**: streamed entries charge the budget the
  consumer-declared window (per-sub-chunk device_put: 3 sub-chunks;
  direct sliced fills: 2), never the full payload — two entries larger
  than the budget restore concurrently under it.
- **End-to-end**: streamed restores are bit-exact against buffered ones
  for numpy and jax destinations, slab-coalesced restores ride one
  sequential stream, and the mirror failover never splices replica
  bytes after primary bytes (fault injection).
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

from torchsnapshot_tpu.io_types import (
    STREAM_DEPTH,
    ReadIO,
    ReadReq,
    ReadStream,
    StoragePlugin,
    StreamRestartRequired,
    WriteIO,
)
from torchsnapshot_tpu.manifest import ArrayEntry
from torchsnapshot_tpu.scheduler import (
    IOGovernor,
    _ReadPipeline,
    execute_read_reqs,
)
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.mirror import MirroredStoragePlugin

SUB = 64 << 10


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


class BufferedFallbackPlugin(StoragePlugin):
    """No read_stream override: exercises the buffered default."""

    def __init__(self):
        self.store = {}

    async def write(self, write_io):
        self.store[write_io.path] = bytes(memoryview(write_io.buf))

    async def read(self, read_io):
        data = self.store[read_io.path]
        if read_io.byte_range is not None:
            lo, hi = read_io.byte_range
            data = data[lo:hi]
        read_io.buf = data

    async def delete(self, path):
        del self.store[path]

    async def close(self):
        pass


def _fs_factory(tmp_path):
    return FSStoragePlugin(str(tmp_path))


def _s3_factory(tmp_path):
    from test_s3_storage_plugin import FakeS3Client, make_plugin

    client = FakeS3Client()

    # The real client answers HEAD for full-object streams.
    def head_object(Bucket, Key):
        return {"ContentLength": len(client.store[(Bucket, Key)])}

    client.head_object = head_object
    return make_plugin(client)


def _gcs_factory(tmp_path):
    from test_gcs_storage_plugin import FakeBucket, make_plugin

    return make_plugin(FakeBucket())


def _mirror_factory(tmp_path):
    return MirroredStoragePlugin(
        FSStoragePlugin(str(tmp_path / "primary")),
        FSStoragePlugin(str(tmp_path / "mirror")),
        ".snapshot_metadata",
    )


def _fallback_factory(tmp_path):
    return BufferedFallbackPlugin()


# Keyed by plugin CLASS name: scripts/check_stream_contract.py asserts
# every in-tree plugin advertising supports_streaming_reads appears here.
CONTRACT_PLUGINS = {
    "FSStoragePlugin": _fs_factory,
    "S3StoragePlugin": _s3_factory,
    "GCSStoragePlugin": _gcs_factory,
    "MirroredStoragePlugin": _mirror_factory,
    "BufferedFallbackPlugin": _fallback_factory,
}


async def _collect(plugin, path, sub_chunk, byte_range=None):
    stream = await plugin.read_stream(
        ReadIO(path=path, byte_range=byte_range), sub_chunk
    )
    parts = []
    async for chunk in stream.chunks:
        parts.append(bytes(memoryview(chunk)))
    return stream.nbytes, parts


# --------------------------------------------------------------- contract


@pytest.mark.parametrize("name", sorted(CONTRACT_PLUGINS))
def test_streamed_equals_buffered(name, tmp_path, loop) -> None:
    plugin = CONTRACT_PLUGINS[name](tmp_path)
    payload = os.urandom(700_000)
    loop.run_until_complete(plugin.write(WriteIO(path="obj", buf=payload)))
    loop.run_until_complete(plugin.drain_background())

    nbytes, parts = loop.run_until_complete(_collect(plugin, "obj", SUB))
    assert nbytes == len(payload)
    assert len(parts) > 1  # genuinely multiple sub-chunks
    assert b"".join(parts) == payload

    # Ranged streams slice exactly like ranged buffered reads.
    nbytes, parts = loop.run_until_complete(
        _collect(plugin, "obj", SUB, byte_range=(1000, 500_000))
    )
    assert nbytes == 499_000
    assert b"".join(parts) == payload[1000:500_000]


@pytest.mark.parametrize("name", sorted(CONTRACT_PLUGINS))
def test_zero_length_ranged_read_short_circuits(name, tmp_path, loop) -> None:
    """Direct plugin users must not hit S3 InvalidRange / GCS 416 on
    empty ranges — each plugin short-circuits before its transport."""
    plugin = CONTRACT_PLUGINS[name](tmp_path)
    payload = b"x" * 1000
    loop.run_until_complete(plugin.write(WriteIO(path="obj", buf=payload)))
    loop.run_until_complete(plugin.drain_background())
    read_io = ReadIO(path="obj", byte_range=(10, 10))
    loop.run_until_complete(plugin.read(read_io))
    assert bytes(read_io.buf) == b""


def test_contract_coverage_lint() -> None:
    """Every plugin advertising supports_streaming_reads is in
    CONTRACT_PLUGINS (no plugin silently opts in without tests)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "check_stream_contract.py")
    r = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=120
    )
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------ consumer semantics


def _entry_for(arr, location="x", checksum=True, codec=None):
    from torchsnapshot_tpu.integrity import compute_checksum
    from torchsnapshot_tpu.serialization import dtype_to_string

    payload = arr.tobytes()
    stored = payload
    entry = ArrayEntry(
        location=location,
        serializer="buffer_protocol",
        dtype=dtype_to_string(arr.dtype),
        shape=list(arr.shape),
        replicated=False,
    )
    if codec is not None:
        stored = zlib.compress(payload, 6)
        entry.codec = codec
    if checksum:
        entry.checksum = compute_checksum(stored)
    return entry, stored


async def _consume_streamed(consumer, stored, sub_chunk, mutate=None):
    data = bytearray(stored)
    if mutate is not None:
        mutate(data)

    async def chunks():
        for lo in range(0, len(data), sub_chunk):
            yield memoryview(data)[lo : lo + sub_chunk]

    await consumer.consume_stream(
        ReadStream(path="x", nbytes=len(data), chunks=chunks())
    )


def test_incremental_crc_equals_whole_buffer_crc(loop) -> None:
    """Streamed consumes record/verify the SAME checksum the buffered
    path does — for raw payloads and across arbitrary chunk cuts."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.arange(200_000, dtype=np.int32)
    entry, stored = _entry_for(arr)
    for sub in (1000, 7777, 64 << 10):
        dst = np.zeros_like(arr)
        consumer = ArrayBufferConsumer(entry, dst_view=dst)
        assert consumer.can_stream(sub)
        loop.run_until_complete(_consume_streamed(consumer, stored, sub))
        assert np.array_equal(dst, arr)


def test_streamed_corruption_detected_and_dst_unmodified(loop) -> None:
    from torchsnapshot_tpu.integrity import IntegrityError
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.arange(200_000, dtype=np.int32)
    entry, stored = _entry_for(arr)
    sentinel = np.full_like(arr, -7)
    dst = sentinel.copy()
    consumer = ArrayBufferConsumer(entry, dst_view=dst)

    def flip(data):
        data[123_456] ^= 0xFF

    with pytest.raises(IntegrityError):
        loop.run_until_complete(
            _consume_streamed(consumer, stored, 10_000, mutate=flip)
        )
    # Verify-before-commit: the destination never saw the corrupt bytes.
    assert np.array_equal(dst, sentinel)


def test_streamed_compressed_payload(loop) -> None:
    """Incremental decompression feeds the same bytes the buffered
    decompress would, and the CRC covers the STORED (compressed) bytes."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.zeros(300_000, dtype=np.float32)  # compressible
    entry, stored = _entry_for(arr, codec="zlib:6")
    assert len(stored) < arr.nbytes
    dst = np.ones_like(arr)
    consumer = ArrayBufferConsumer(entry, dst_view=dst)
    assert consumer.can_stream(max(1, len(stored) // 4))
    loop.run_until_complete(
        _consume_streamed(consumer, stored, max(1, len(stored) // 4))
    )
    assert np.array_equal(dst, arr)


def test_streamed_compressed_corruption_rejected(loop) -> None:
    from torchsnapshot_tpu.integrity import IntegrityError
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.zeros(300_000, dtype=np.float32)
    entry, stored = _entry_for(arr, codec="zlib:6")
    sentinel = np.full_like(arr, 3.0)
    dst = sentinel.copy()
    consumer = ArrayBufferConsumer(entry, dst_view=dst)

    def flip(data):
        data[len(data) // 2] ^= 0xFF

    with pytest.raises((IntegrityError, RuntimeError, zlib.error)):
        loop.run_until_complete(
            _consume_streamed(consumer, stored, max(1, len(stored) // 4), mutate=flip)
        )
    assert np.array_equal(dst, sentinel)


def test_midstream_exception_leaves_destination_unmodified(loop) -> None:
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.arange(200_000, dtype=np.int32)
    entry, stored = _entry_for(arr)
    sentinel = np.full_like(arr, 42)
    dst = sentinel.copy()
    consumer = ArrayBufferConsumer(entry, dst_view=dst)

    async def dying_chunks():
        yield memoryview(stored)[:50_000]
        yield memoryview(stored)[50_000:100_000]
        raise RuntimeError("injected mid-stream read failure")

    with pytest.raises(RuntimeError, match="injected"):
        loop.run_until_complete(
            consumer.consume_stream(
                ReadStream(path="x", nbytes=len(stored), chunks=dying_chunks())
            )
        )
    assert np.array_equal(dst, sentinel)


def test_batched_slab_stream_slices_to_consumers(loop) -> None:
    """Cross-entry coalescing: one sequential stream is sliced to the
    per-entry consumers — checksums verify per entry, gaps are skipped,
    and the spanning payload is never materialized."""
    from torchsnapshot_tpu.batcher import batch_read_requests
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    a = np.arange(50_000, dtype=np.int32)
    b = np.arange(70_000, dtype=np.float32) * 0.5
    slab = bytearray(600_000)
    slab[0 : a.nbytes] = a.tobytes()
    b_off = a.nbytes + 4096  # a gap under the merge threshold
    slab[b_off : b_off + b.nbytes] = b.tobytes()

    entry_a, _ = _entry_for(a, location="batched/slab")
    entry_a.byte_range = [0, a.nbytes]
    entry_b, _ = _entry_for(b, location="batched/slab")
    entry_b.byte_range = [b_off, b_off + b.nbytes]

    dst_a, dst_b = np.zeros_like(a), np.zeros_like(b)
    reqs = [
        ReadReq(
            path="batched/slab",
            buffer_consumer=ArrayBufferConsumer(entry_a, dst_view=dst_a),
            byte_range=(0, a.nbytes),
        ),
        ReadReq(
            path="batched/slab",
            buffer_consumer=ArrayBufferConsumer(entry_b, dst_view=dst_b),
            byte_range=(b_off, b_off + b.nbytes),
        ),
    ]
    merged = batch_read_requests(reqs)
    assert len(merged) == 1  # coalesced into one spanning request
    consumer = merged[0].buffer_consumer
    lo, hi = merged[0].byte_range
    assert consumer.can_stream(SUB)
    assert consumer.stream_admission_cost(SUB) < hi - lo

    async def chunks():
        for off in range(lo, hi, SUB):
            yield memoryview(slab)[off : min(off + SUB, hi)]

    loop.run_until_complete(
        consumer.consume_stream(ReadStream(path="batched/slab", nbytes=hi - lo, chunks=chunks()))
    )
    assert np.array_equal(dst_a, a)
    assert np.array_equal(dst_b, b)


# ---------------------------------------------------- scheduler accounting


def _device_consumer(arr, entry):
    import jax
    from jax.sharding import SingleDeviceSharding

    from torchsnapshot_tpu.io_preparers.array import (
        ArrayBufferConsumer,
        DeviceMaterializer,
    )

    restored = []
    sharding = SingleDeviceSharding(jax.devices()[0])
    dest = DeviceMaterializer(
        sharding=sharding,
        committed=True,
        dst_dtype=arr.dtype,
        needs_cast=False,
        callback=restored.append,
    )

    # The buffered path's host-array callback, as prepare.py wires it —
    # a buffered fallback (stream restart) must land the array too.
    def materialize(host):
        restored.append(jax.device_put(host, sharding))

    return (
        ArrayBufferConsumer(
            entry,
            callback=materialize,
            ensure_writable=False,
            device_dest=dest,
        ),
        restored,
    )


def test_streamed_budget_charges_window_not_payload() -> None:
    """The acceptance criterion: a streamed large entry's budget charge
    is the sub-chunk window. Device-bound consumers charge chunk +
    read-ahead + row carry; direct sliced fills charge the in-flight
    window; verify-before-commit scratch consumers honestly charge the
    payload they retain — and under the auto policy only stream when
    the storage is measurably latency-bound (``stream_all``)."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    arr = np.arange(1_000_000, dtype=np.float32).reshape(1000, 1000)
    entry, _ = _entry_for(arr)

    consumer, _ = _device_consumer(arr, entry)
    pipeline = _ReadPipeline(
        ReadReq(path="x", buffer_consumer=consumer), sub_chunk_bytes=SUB
    )
    assert pipeline.streamed
    assert pipeline.admission_cost_bytes == (STREAM_DEPTH + 1) * SUB
    assert pipeline.admission_cost_bytes < arr.nbytes

    # Scratch consumers (host destination + pending verification) retain
    # the payload: no window win, so auto keeps them on the buffered
    # mmap path unless the storage is latency-bound.
    scratch = ArrayBufferConsumer(entry, dst_view=np.zeros_like(arr))
    pipeline = _ReadPipeline(
        ReadReq(path="x", buffer_consumer=scratch), sub_chunk_bytes=SUB
    )
    assert not pipeline.streamed
    pipeline = _ReadPipeline(
        ReadReq(path="x", buffer_consumer=scratch),
        sub_chunk_bytes=SUB,
        stream_all=True,
    )
    assert pipeline.streamed
    assert pipeline.admission_cost_bytes == arr.nbytes  # honest retention

    # Non-streaming election (no sub-chunk size) charges the payload.
    pipeline = _ReadPipeline(ReadReq(path="x", buffer_consumer=scratch))
    assert not pipeline.streamed
    assert pipeline.admission_cost_bytes == arr.nbytes


def test_sliced_consumer_streams_into_window(loop, tmp_path, monkeypatch) -> None:
    """Budget-split sub-range reads stream as direct fills of assembler
    memory: window admission, correct assembly."""
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(SUB))
    arr = np.arange(500_000, dtype=np.float64)
    entry, stored = _entry_for(arr, location="big", checksum=False)
    plugin = FSStoragePlugin(str(tmp_path))
    loop.run_until_complete(plugin.write(WriteIO(path="big", buf=stored)))

    done = []
    reqs = ArrayIOPreparer.prepare_read(
        entry, callback=done.append, buffer_size_limit_bytes=1 << 20
    )
    assert len(reqs) > 1  # genuinely budget-split
    for req in reqs:
        pipeline = _ReadPipeline(req, sub_chunk_bytes=SUB)
        if pipeline.streamed:
            assert pipeline.admission_cost_bytes <= STREAM_DEPTH * SUB
    loop.run_until_complete(execute_read_reqs(reqs, plugin, 1 << 30, rank=0))
    assert np.array_equal(done[0], arr)


def test_large_entries_restore_concurrently_under_budget(
    loop, tmp_path, monkeypatch
) -> None:
    """Two entries each LARGER than the budget stream concurrently:
    window accounting keeps both admitted where buffered reads would
    serialize through the starvation escape."""
    import jax

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(SUB))

    active = {"now": 0, "peak": 0}

    class TrackingFS(FSStoragePlugin):
        async def read_stream(self, read_io, sub_chunk_bytes):
            inner = await super().read_stream(read_io, sub_chunk_bytes)

            async def chunks():
                active["now"] += 1
                active["peak"] = max(active["peak"], active["now"])
                try:
                    async for chunk in inner.chunks:
                        await asyncio.sleep(0)  # let peers interleave
                        yield chunk
                finally:
                    active["now"] -= 1

            return ReadStream(
                path=inner.path, nbytes=inner.nbytes, chunks=chunks()
            )

    plugin = TrackingFS(str(tmp_path))
    reqs = []
    restored = []
    payload_bytes = 2 << 20
    for i in range(2):
        arr = np.full((512, 1024), float(i), np.float32)  # 2 MB each
        entry, stored = _entry_for(arr, location=f"obj_{i}")
        loop.run_until_complete(
            plugin.write(WriteIO(path=f"obj_{i}", buf=stored))
        )
        consumer, out = _device_consumer(arr, entry)
        restored.append((arr, out))
        reqs.append(ReadReq(path=f"obj_{i}", buffer_consumer=consumer))

    budget = 1 << 20  # half of ONE payload; >= two 3-sub-chunk windows
    assert budget < payload_bytes
    loop.run_until_complete(execute_read_reqs(reqs, plugin, budget, rank=0))
    assert active["peak"] == 2
    for arr, out in restored:
        assert np.array_equal(np.asarray(out[0]), arr)


# ------------------------------------------------------------ mirror fault


class _FlakyPrimaryFS(FSStoragePlugin):
    """Yields one streamed chunk, then dies; buffered reads die too —
    the entry is only recoverable from the mirror tier."""

    async def read_stream(self, read_io, sub_chunk_bytes):
        inner = await super().read_stream(read_io, sub_chunk_bytes)

        async def chunks():
            it = inner.chunks
            yield await it.__anext__()
            await it.aclose()
            raise OSError("injected primary mid-stream death")

        return ReadStream(path=inner.path, nbytes=inner.nbytes, chunks=chunks())

    async def read(self, read_io):
        raise OSError("injected primary read death")


def test_mirror_midstream_failover_never_splices(loop, tmp_path) -> None:
    payload = os.urandom(400_000)
    primary_dir, mirror_dir = tmp_path / "p", tmp_path / "m"
    for d in (primary_dir, mirror_dir):
        loop.run_until_complete(
            FSStoragePlugin(str(d)).write(WriteIO(path="obj", buf=payload))
        )
    mirror = MirroredStoragePlugin(
        _FlakyPrimaryFS(str(primary_dir)),
        FSStoragePlugin(str(mirror_dir)),
        ".snapshot_metadata",
    )

    # Direct stream: a partially-consumed primary raises
    # StreamRestartRequired instead of splicing mirror bytes.
    async def direct():
        stream = await mirror.read_stream(ReadIO(path="obj"), SUB)
        parts = []
        with pytest.raises(StreamRestartRequired):
            async for chunk in stream.chunks:
                parts.append(bytes(memoryview(chunk)))
        return parts

    parts = loop.run_until_complete(direct())
    assert len(parts) == 1  # the primary got exactly one chunk out

    # Scheduler-level: the entry restarts buffered from offset 0 and
    # fails over to the mirror — restored bytes are exact, not spliced.
    arr = np.frombuffer(payload, np.uint8).copy()
    entry, _ = _entry_for(arr, location="obj")
    out = []
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    consumer = ArrayBufferConsumer(entry, callback=out.append)
    overrides = {
        "TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES": str(SUB),
        # The host-callback consumer has no window win; force streaming
        # so the restart path is the one under test.
        "TORCHSNAPSHOT_TPU_STREAM_READS": "always",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        loop.run_until_complete(
            execute_read_reqs(
                [ReadReq(path="obj", buffer_consumer=consumer)],
                mirror,
                1 << 30,
                rank=0,
            )
        )
    finally:
        for k, v in saved.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v
    assert out and out[0].tobytes() == payload


def test_mirror_failover_covers_truncated_primary(loop, tmp_path) -> None:
    """A TORN primary object raises EOFError (not OSError) from the fs
    plugin's short-read guard — the mirror must still fail over."""
    payload = os.urandom(300_000)
    primary = FSStoragePlugin(str(tmp_path / "p"))
    loop.run_until_complete(
        primary.write(WriteIO(path="obj", buf=payload[: len(payload) // 2]))
    )
    mirror_fs = FSStoragePlugin(str(tmp_path / "m"))
    loop.run_until_complete(mirror_fs.write(WriteIO(path="obj", buf=payload)))
    mirror = MirroredStoragePlugin(primary, mirror_fs, ".snapshot_metadata")
    # Ranged read past the truncated primary's size: pread hits EOF.
    read_io = ReadIO(path="obj", byte_range=(0, len(payload)))
    loop.run_until_complete(mirror.read(read_io))
    assert bytes(read_io.buf) == payload


def test_restart_fallback_recharges_budget(loop, tmp_path) -> None:
    """After StreamRestartRequired the buffered retry holds the full
    payload — the pipeline must charge the budget the difference so
    concurrent dispatch throttles instead of overshooting."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer
    from torchsnapshot_tpu.scheduler import _MemoryBudget, _Throughput

    payload = os.urandom(400_000)
    for d in ("p", "m"):
        loop.run_until_complete(
            FSStoragePlugin(str(tmp_path / d)).write(
                WriteIO(path="obj", buf=payload)
            )
        )
    mirror = MirroredStoragePlugin(
        _FlakyPrimaryFS(str(tmp_path / "p")),
        FSStoragePlugin(str(tmp_path / "m")),
        ".snapshot_metadata",
    )
    arr = np.frombuffer(payload, np.uint8).copy()
    entry, _ = _entry_for(arr, location="obj")
    consumer, out = _device_consumer(arr, entry)  # windowed admission
    pipeline = _ReadPipeline(
        ReadReq(path="obj", buffer_consumer=consumer), sub_chunk_bytes=SUB
    )
    assert pipeline.streamed
    window = pipeline.admission_cost_bytes
    assert window < len(payload)
    budget = _MemoryBudget(1 << 30)
    budget.acquire(window)
    loop.run_until_complete(
        pipeline.read_and_consume(
            mirror, None, _Throughput("read", 0), budget
        )
    )
    # The fallback re-charged full retention; release symmetry holds.
    assert pipeline.admission_cost_bytes == len(payload)
    assert budget.available == (1 << 30) - len(payload)
    budget.release(pipeline.admission_cost_bytes)
    assert budget.available == 1 << 30
    assert out and np.asarray(out[0]).tobytes() == payload


def test_mirror_zero_produced_failover_is_transparent(loop, tmp_path) -> None:
    """Primary missing up front: the mirror stream starts from offset 0
    with the consumer having seen nothing — no restart needed."""
    payload = os.urandom(300_000)
    mirror_fs = FSStoragePlugin(str(tmp_path / "m"))
    loop.run_until_complete(mirror_fs.write(WriteIO(path="obj", buf=payload)))
    mirror = MirroredStoragePlugin(
        FSStoragePlugin(str(tmp_path / "empty")), mirror_fs, ".snapshot_metadata"
    )
    nbytes, parts = loop.run_until_complete(_collect(mirror, "obj", SUB))
    assert b"".join(parts) == payload


# ------------------------------------------------------ the device row sink


@pytest.fixture
def bus():
    from torchsnapshot_tpu import telemetry

    telemetry.reset()
    telemetry.set_enabled(True)
    yield telemetry
    telemetry.set_enabled(False)
    telemetry.reset()


def _slab(piece):
    """A chunk as the fs plugin yields one: a view of a pooled slab."""
    from torchsnapshot_tpu.io_preparers.array import pooled_buffer

    buf = pooled_buffer(len(piece))
    buf[:] = np.frombuffer(piece, np.uint8)
    return memoryview(buf)


def _through_the_sink(loop, monkeypatch, arr, sub, *, chunk_of=_slab, stored=None, **entry_kw):
    """Stream ``arr``'s stored bytes in chunks of ``sub`` through the
    consumer of a single-device jax destination; returns what reached
    the callback and, for each ``add``, the carry it left and the row."""
    from torchsnapshot_tpu.io_preparers.array import _DeviceRowSink

    entry, whole = _entry_for(arr, **entry_kw)
    stored = whole if stored is None else stored(whole)
    consumer, restored = _device_consumer(arr, entry)
    left = []
    add = _DeviceRowSink.add

    def spied(self, data):
        add(self, data)
        left.append((len(self.carry), self.row_bytes))

    monkeypatch.setattr(_DeviceRowSink, "add", spied)

    async def chunks():
        for lo in range(0, len(stored), sub):
            yield chunk_of(stored[lo : lo + sub])

    run = consumer.consume_stream(ReadStream(path="x", nbytes=len(stored), chunks=chunks()))
    return restored, left, lambda: loop.run_until_complete(run)


def _bf16(shape):
    import ml_dtypes

    n = int(np.prod(shape))
    return (np.arange(n, dtype=np.float32) % 509).astype(ml_dtypes.bfloat16).reshape(shape)


def _f32(shape):
    return np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape)


# name: leaf, sub-chunk, the sink's row in bytes, bytes that may be copied
# (a row a sub-chunk edge that splits one; None: see the case), chunk type
_SINK_CASES = {
    # shape[0]'s row is 512 KiB, over the sub-chunk and over the cap: the
    # row is the last dimension, 2 KiB, and no edge splits one
    "stacked-leaf-with-rows-wider-than-the-sub-chunk": (lambda: _f32((3, 256, 512)), 64 << 10, 2048, 0, _slab),
    "one-dimensional-leaf": (lambda: np.arange(300_000, dtype=np.int32), 100_000, 4, 0, _slab),
    "bfloat16": (lambda: _bf16((600, 1000)), 64 << 10, 2000, 18 * 2000, _slab),
    # two trailing dimensions fit the cap: a row of 100 KiB, every byte through the carry
    "sub-chunk-smaller-than-a-row": (lambda: _f32((4, 100, 256)), 40_000, 102_400, 409_600, _slab),
    "sub-chunk-ends-on-a-row-boundary": (lambda: _f32((512, 1024)), 64 << 10, 4096, 0, _slab),
    "sub-chunk-ends-inside-a-row": (lambda: _f32((500, 1000)), 64 << 10, 4000, 30 * 4000, _slab),
    # 10 001 bytes a chunk: every second chunk's rows start off a float's alignment
    "odd-lengths-off-the-dtypes-alignment": (lambda: _f32((500, 1000)), 10_001, 4000, None, _slab),
    "a-bytearray-the-sink-may-not-keep": (lambda: _f32((500, 1000)), 64 << 10, 4000, 2_000_000,
                                          lambda piece: memoryview(bytearray(piece))),
}


@pytest.mark.parametrize("case", sorted(_SINK_CASES))
def test_device_sink_restores_bit_for_bit_under_a_carry_of_less_than_a_row(case, loop, monkeypatch, bus) -> None:
    make, sub, row, may_copy, chunk_of = _SINK_CASES[case]
    arr = make()
    restored, left, run = _through_the_sink(loop, monkeypatch, arr, sub, chunk_of=chunk_of)
    run()
    (out,) = restored
    assert out.shape == arr.shape and out.dtype == arr.dtype
    assert np.asarray(out).tobytes() == arr.tobytes()
    assert left and all(r == row and carry < r for carry, r in left)
    counters = bus.counters()
    views, copied = counters.get("bytes_htod_views", 0), counters.get("bytes_htod_copied", 0)
    assert views + copied == arr.nbytes
    htod = [e for e in bus.events() if e["ph"] == "span" and e["name"] == "sub_chunk_htod"]
    host = [e for e in bus.events() if e["ph"] == "span" and e["name"] == "consume_hostcopy"]
    assert sum(e["args"]["bytes"] for e in htod) == arr.nbytes
    if may_copy is None:
        # chunks 1, 3, 5, ... start 1, 3, 5, ... bytes into a float
        assert 0 < views < arr.nbytes and copied > arr.nbytes // 3
    else:
        assert copied == may_copy
        # what the carry took in, half a row at a time: never a chunk
        assert sum(e["args"]["bytes"] for e in host) == copied
    assert bool(host) == bool(copied) and all(e["args"]["bytes"] > 0 for e in host)


def test_device_sink_copies_what_a_decompressor_feeds_off_alignment(loop, monkeypatch, bus) -> None:
    """A compressed entry: the decompressor hands over ``bytes`` of any
    length, whole rows rarely start on the dtype's alignment, and the
    sink then copies as it always did."""
    arr = np.random.default_rng(0).integers(0, 16, 500_000).astype(np.float32).reshape(500, 1000)
    restored, left, run = _through_the_sink(loop, monkeypatch, arr, 7777, codec="zlib:6")
    run()
    assert np.asarray(restored[0]).tobytes() == arr.tobytes()
    assert len(left) > 4 and all(carry < row == 4000 for carry, row in left)
    counters = bus.counters()
    assert counters.get("bytes_htod_views", 0) + counters["bytes_htod_copied"] == arr.nbytes
    # more than the two halves of a row a feed: whole rows were copied
    assert counters["bytes_htod_copied"] > 2 * 4000 * len(left)


@pytest.mark.parametrize("cut, says", [(1_999_000, "mid-row"), (1_996_000, "short read stream")])
def test_device_sink_refuses_a_stream_that_ends_early(cut, says, loop, monkeypatch) -> None:
    arr = _f32((500, 1000))
    restored, left, run = _through_the_sink(
        loop, monkeypatch, arr, 64 << 10, stored=lambda whole: whole[:cut], checksum=False
    )
    with pytest.raises(IOError, match=says):
        run()
    assert restored == [] and all(carry < row for carry, row in left)


def test_device_sink_raises_on_a_corrupted_byte_before_any_callback(loop, monkeypatch) -> None:
    from torchsnapshot_tpu.integrity import IntegrityError

    arr = _f32((500, 1000))

    def flip(whole):
        bad = bytearray(whole)
        bad[1_234_567] ^= 0xFF
        return bytes(bad)

    restored, _, run = _through_the_sink(loop, monkeypatch, arr, 64 << 10, stored=flip)
    with pytest.raises(IntegrityError):
        run()
    assert restored == []


@pytest.mark.parametrize("shape, itemsize, row", [
    # olmo1b's stacked leaves: a layer's slice is 16-64 MiB, the row is the last dimension
    ((7, 2048, 8192), 4, (8192,)), ((7, 8192, 2048), 4, (2048,)), ((7, 2048, 6144), 4, (6144,)),
    ((50304, 2048), 4, (2048,)),
    # trailing dimensions are taken while they fit 256 KiB, never the whole shape
    ((6, 16, 2048, 768), 2, (768,)), ((48, 8, 64, 128), 4, (8, 64, 128)), ((3, 64, 128), 4, (64, 128)),
    ((4, 3_000_000), 4, (3_000_000,)), ((1000,), 4, ()),
])
def test_device_sink_row_is_a_short_run_of_trailing_dimensions(shape, itemsize, row) -> None:
    from torchsnapshot_tpu.io_preparers.array import _sink_row_shape

    assert _sink_row_shape(shape, itemsize) == row


def test_device_sink_declares_the_window_unless_the_last_dimension_outgrows_it() -> None:
    """A stacked leaf's admission cost is the window, whatever its
    ``shape[0]`` row; only a last dimension wider than the sub-chunk
    still grows the carry past it."""
    arr = _f32((3, 256, 512))  # shape[0]'s row is 512 KiB, eight sub-chunks
    consumer, _ = _device_consumer(arr, _entry_for(arr)[0])
    assert consumer.stream_admission_cost(SUB) == (STREAM_DEPTH + 1) * SUB
    wide = _f32((4, 100_000))
    consumer, _ = _device_consumer(wide, _entry_for(wide)[0])
    assert consumer.stream_admission_cost(SUB) == wide.nbytes


def test_view_and_copy_counters_reach_stats_and_the_history_record(tmp_path, monkeypatch, bus, capsys) -> None:
    """A restore persists no document of its own; what ``stats -v``
    prints of one and what a history record keeps come from the same
    summary and fleet view, and both hold the two counters."""
    import json

    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.cli import main
    from torchsnapshot_tpu.telemetry import TELEMETRY_SUMMARY_FNAME, build_summary_document, history

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(128 << 10))
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_ENABLE_BATCHING", "0")
    w, v = _f32((400, 1000)), _f32((3, 256, 512))
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=jnp.asarray(w), v=jnp.asarray(v), b=jnp.ones((8, 8)))})
    dst = {"app": StateDict(w=jnp.zeros_like(w), v=jnp.zeros_like(v), b=jnp.zeros((8, 8)))}
    Snapshot(str(tmp_path / "s")).restore(dst)
    assert np.array_equal(np.asarray(dst["app"]["w"]), w) and np.array_equal(np.asarray(dst["app"]["v"]), v)

    summary, fleet = bus.last_summary(), bus.last_fleet()
    assert summary["op"] == "restore"
    streamed = w.nbytes + v.nbytes  # the small leaf is read whole
    views, copied = summary["counters"]["bytes_htod_views"], summary["counters"]["bytes_htod_copied"]
    assert views + copied == streamed
    # w's rows of 4000 bytes are split at 12 sub-chunk edges, v's of 2 KiB at none
    assert copied == 12 * 4000
    assert fleet["aggregate"]["bytes_htod_views"] == views and fleet["aggregate"]["bytes_htod_copied"] == copied

    (tmp_path / "doc").mkdir()
    doc = build_summary_document("restore", 1, [summary], fleet)
    (tmp_path / "doc" / TELEMETRY_SUMMARY_FNAME).write_text(json.dumps(doc, default=repr))
    assert main(["stats", "-v", str(tmp_path / "doc")]) == 0
    out = capsys.readouterr().out
    assert "bytes_htod_views" in out and "bytes_htod_copied" in out

    record = history.build_record(op="restore", path=str(tmp_path / "s"), wall_s=1.0, world_size=1, fleet=fleet)
    assert record["bytes_htod_views"] == views and record["bytes_htod_copied"] == copied


# ------------------------------------------------------------- end to end


def test_restore_streams_and_is_bit_exact(tmp_path, monkeypatch) -> None:
    from torchsnapshot_tpu import Snapshot, StateDict

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(128 << 10))
    arr = np.arange(500_000, dtype=np.float32).reshape(500, 1000)
    state = {"app": StateDict(w=arr, small=np.ones(16, np.float64))}
    Snapshot.take(str(tmp_path / "s"), state)

    # numpy destinations are scratch consumers (no window win): force
    # streaming so this exercises the streamed CRC/consume path.
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "always")
    dst = {
        "app": StateDict(
            w=np.zeros((500, 1000), np.float32), small=np.zeros(16, np.float64)
        )
    }
    Snapshot(str(tmp_path / "s")).restore(dst)  # streamed (verifies CRC)
    assert np.array_equal(dst["app"]["w"], arr)

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "0")
    dst2 = {
        "app": StateDict(
            w=np.zeros((500, 1000), np.float32), small=np.zeros(16, np.float64)
        )
    }
    Snapshot(str(tmp_path / "s")).restore(dst2)  # buffered
    assert np.array_equal(dst2["app"]["w"], dst["app"]["w"])


def test_jax_restore_streams_per_chunk_device_put(tmp_path, monkeypatch) -> None:
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(128 << 10))
    arr = np.arange(400_000, dtype=np.float32).reshape(400, 1000)
    x = jnp.asarray(arr)
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=x)})
    dst = {"app": StateDict(w=jnp.zeros_like(x))}
    Snapshot(str(tmp_path / "s")).restore(dst)
    assert isinstance(dst["app"]["w"], jax.Array)
    assert np.array_equal(np.asarray(dst["app"]["w"]), arr)


def test_batched_snapshot_restores_through_coalesced_stream(
    tmp_path, monkeypatch
) -> None:
    """Slab-packed snapshots restore through ONE spanning stream per
    slab instead of many ranged reads."""
    from torchsnapshot_tpu import Snapshot, StateDict

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_ENABLE_BATCHING", "1")
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(64 << 10))
    state = {
        "app": StateDict(
            **{
                f"w{i}": np.arange(100_000, dtype=np.float32) + i
                for i in range(4)
            }
        )
    }
    Snapshot.take(str(tmp_path / "s"), state)
    dst = {
        "app": StateDict(
            **{f"w{i}": np.zeros(100_000, np.float32) for i in range(4)}
        )
    }
    Snapshot(str(tmp_path / "s")).restore(dst)
    for i in range(4):
        assert np.array_equal(dst["app"][f"w{i}"], state["app"][f"w{i}"])


def test_stream_reads_mode_parsing(tmp_path, monkeypatch) -> None:
    from torchsnapshot_tpu.scheduler import (
        stream_reads_enabled,
        stream_reads_mode,
    )

    monkeypatch.delenv("TORCHSNAPSHOT_TPU_STREAM_READS", raising=False)
    assert stream_reads_mode() == "auto" and stream_reads_enabled()
    for raw in ("0", "false", "off", "never"):
        monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", raw)
        assert stream_reads_mode() == "never" and not stream_reads_enabled()
    for raw in ("always", "force"):
        monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", raw)
        assert stream_reads_mode() == "always"
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "1")
    assert stream_reads_mode() == "auto"


def test_latency_bound_storage_streams_full_retention_consumers(
    loop, tmp_path, monkeypatch
) -> None:
    """Auto policy: once the governor measures a latency-bound read
    rate for the plugin, even full-retention scratch consumers stream
    (overlap hides transport latency); memcpy-speed rates keep them on
    the buffered path."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer
    from torchsnapshot_tpu.scheduler import io_governor

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(SUB))
    monkeypatch.delenv("TORCHSNAPSHOT_TPU_STREAM_READS", raising=False)

    streamed_calls = {"n": 0}

    class CountingFS(FSStoragePlugin):
        async def read_stream(self, read_io, sub_chunk_bytes):
            streamed_calls["n"] += 1
            return await super().read_stream(read_io, sub_chunk_bytes)

    arr = np.arange(300_000, dtype=np.float32)
    entry, stored = _entry_for(arr, location="obj")
    plugin = CountingFS(str(tmp_path))
    loop.run_until_complete(plugin.write(WriteIO(path="obj", buf=stored)))

    def run_restore():
        dst = np.zeros_like(arr)
        consumer = ArrayBufferConsumer(entry, dst_view=dst)
        loop.run_until_complete(
            execute_read_reqs(
                [ReadReq(path="obj", buffer_consumer=consumer)],
                plugin,
                1 << 30,
                rank=0,
            )
        )
        assert np.array_equal(dst, arr)

    # Fast measured storage: buffered.
    io_governor().record_read("CountingFS", 100 << 30, 1.0)
    run_restore()
    assert streamed_calls["n"] == 0
    # Saturate the EWMA down to a latency-bound rate: streams.
    for _ in range(40):
        io_governor().record_read("CountingFS", 10 << 20, 1.0)
    run_restore()
    assert streamed_calls["n"] == 1


# -------------------------------------------------------------- governor


def test_governor_read_sub_chunk_adapts(monkeypatch) -> None:
    monkeypatch.delenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", raising=False)
    gov = IOGovernor()
    assert gov.sub_chunk_bytes(op="read") == 64 << 20  # default
    gov.record_read("FSStoragePlugin", 10 << 30, 1.0)  # 10 GB/s
    assert gov.sub_chunk_bytes("FSStoragePlugin", op="read") == 256 << 20
    # The write-side table must not leak into read sizing.
    gov2 = IOGovernor()
    gov2.record_write("FSStoragePlugin", 10 << 30, 1.0)
    assert gov2.sub_chunk_bytes("FSStoragePlugin", op="read") == 64 << 20
    gov2.record_read("S3StoragePlugin", 50 << 20, 1.0)  # 50 MB/s
    assert gov2.sub_chunk_bytes("S3StoragePlugin", op="read") == 8 << 20
