"""Scheduler pipeline tests: budget compliance, starvation escape, pipelining.

Reference patterns: plan-level tests with in-memory storage
(tests/test_batcher.py:268-281 style) + white-box budget assertions.
"""

import asyncio
from typing import Dict, Optional

import pytest

from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
)
from torchsnapshot_tpu.scheduler import (
    execute_write_reqs,
    execute_read_reqs,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)


class InMemoryStoragePlugin(StoragePlugin):
    def __init__(self, delay: float = 0.0) -> None:
        self.storage: Dict[str, bytes] = {}
        self.delay = delay

    async def write(self, write_io: WriteIO) -> None:
        await asyncio.sleep(self.delay)
        self.storage[write_io.path] = bytes(write_io.buf)

    async def read(self, read_io: ReadIO) -> None:
        await asyncio.sleep(self.delay)
        data = self.storage[read_io.path]
        if read_io.byte_range is not None:
            lo, hi = read_io.byte_range
            data = data[lo:hi]
        read_io.buf = bytearray(data)

    async def delete(self, path: str) -> None:
        del self.storage[path]

    async def close(self) -> None:
        pass


class TrackingStager(BufferStager):
    """Stager instrumented to observe peak concurrent staging cost."""

    live_bytes = 0
    peak_bytes = 0

    def __init__(self, payload: bytes, delay: float = 0.005) -> None:
        self.payload = payload
        self.delay = delay

    async def stage_buffer(self, executor=None):
        cls = TrackingStager
        cls.live_bytes += len(self.payload)
        cls.peak_bytes = max(cls.peak_bytes, cls.live_bytes)
        await asyncio.sleep(self.delay)
        # NOTE: live_bytes decremented when I/O completes (the scheduler holds
        # the buffer until written) — handled by the storage wrapper below.
        return self.payload

    def get_staging_cost_bytes(self) -> int:
        return len(self.payload)


class ReleasingStoragePlugin(InMemoryStoragePlugin):
    async def write(self, write_io: WriteIO) -> None:
        await super().write(write_io)
        TrackingStager.live_bytes -= len(write_io.buf)


class SimpleConsumer(BufferConsumer):
    def __init__(self, sink: Dict[str, bytes], key: str, cost: int) -> None:
        self.sink = sink
        self.key = key
        self.cost = cost

    async def consume_buffer(self, buf, executor=None) -> None:
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.cost


def _make_write_reqs(n: int, size: int):
    return [
        WriteReq(path=f"obj_{i}", buffer_stager=TrackingStager(bytes([i % 256]) * size))
        for i in range(n)
    ]


def _reset_tracking():
    TrackingStager.live_bytes = 0
    TrackingStager.peak_bytes = 0


def test_write_all_completed() -> None:
    _reset_tracking()
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin()
    reqs = _make_write_reqs(20, 100)
    sync_execute_write_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    loop.close()
    assert len(storage.storage) == 20
    assert storage.storage["obj_3"] == bytes([3]) * 100


def test_budget_respected() -> None:
    _reset_tracking()
    loop = asyncio.new_event_loop()
    storage = ReleasingStoragePlugin(delay=0.002)
    reqs = _make_write_reqs(16, 1000)
    sync_execute_write_reqs(reqs, storage, 3000, rank=0, event_loop=loop)
    loop.close()
    assert len(storage.storage) == 16
    assert TrackingStager.peak_bytes <= 3000


def test_oversized_request_does_not_deadlock() -> None:
    _reset_tracking()
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin()
    reqs = _make_write_reqs(3, 5000)  # each bigger than budget
    sync_execute_write_reqs(reqs, storage, 1000, rank=0, event_loop=loop)
    loop.close()
    assert len(storage.storage) == 3


def test_pending_io_work_defers_storage_io() -> None:
    """The returned PendingIOWork is the staging-complete consistency point."""
    _reset_tracking()
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin(delay=0.05)
    reqs = _make_write_reqs(4, 10)
    pending = loop.run_until_complete(
        execute_write_reqs(reqs, storage, 10**9, rank=0)
    )
    # Staging is done for every request, but slow storage I/O may not be.
    staged = [r.buffer_stager for r in reqs]
    assert all(s.payload is not None for s in staged)
    pending.sync_complete(loop)
    loop.close()
    assert len(storage.storage) == 4


def test_read_pipeline() -> None:
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin()
    storage.storage = {f"k{i}": bytes([i]) * 50 for i in range(10)}
    sink: Dict[str, bytes] = {}
    reqs = [
        ReadReq(path=f"k{i}", buffer_consumer=SimpleConsumer(sink, f"k{i}", 50))
        for i in range(10)
    ]
    sync_execute_read_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    loop.close()
    assert sink == storage.storage


def test_read_with_byte_range() -> None:
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin()
    storage.storage = {"blob": bytes(range(100))}
    sink: Dict[str, bytes] = {}
    reqs = [
        ReadReq(
            path="blob",
            buffer_consumer=SimpleConsumer(sink, "mid", 30),
            byte_range=(10, 40),
        )
    ]
    sync_execute_read_reqs(reqs, storage, 10**9, rank=0, event_loop=loop)
    loop.close()
    assert sink["mid"] == bytes(range(10, 40))


def test_read_oversized_budget_escape() -> None:
    loop = asyncio.new_event_loop()
    storage = InMemoryStoragePlugin()
    storage.storage = {"big": b"x" * 10000}
    sink: Dict[str, bytes] = {}
    reqs = [ReadReq(path="big", buffer_consumer=SimpleConsumer(sink, "big", 10000))]
    sync_execute_read_reqs(reqs, storage, 100, rank=0, event_loop=loop)
    loop.close()
    assert sink["big"] == b"x" * 10000


def test_memory_budget_env_override(monkeypatch) -> None:
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_BYTES", "12345")
    assert get_process_memory_budget_bytes() == 12345


def test_memory_budget_default_capped() -> None:
    budget = get_process_memory_budget_bytes()
    assert 0 < budget <= 32 * 1024**3


def test_write_error_propagates() -> None:
    class FaultyStorage(InMemoryStoragePlugin):
        async def write(self, write_io: WriteIO) -> None:
            raise RuntimeError("injected storage failure")

    loop = asyncio.new_event_loop()
    reqs = _make_write_reqs(2, 10)
    with pytest.raises(RuntimeError, match="injected storage failure"):
        sync_execute_write_reqs(reqs, FaultyStorage(), 10**9, rank=0, event_loop=loop)
    loop.close()


def test_progress_reporter_logs_pipeline_table(caplog) -> None:
    """The reporter emits stage counts / bytes / budget / RSS
    (reference: _WriteReporter, scheduler.py:96-175)."""
    import logging

    import torchsnapshot_tpu.scheduler as sched

    budget = sched._MemoryBudget(1 << 30)
    budget.acquire(1 << 29)
    reporter = sched._ProgressReporter("write", rank=0, total=8, budget=budget)
    reporter.inflight_staging = 2
    reporter.staged_count = 3
    reporter.staged_bytes = 3 << 20
    reporter.inflight_io = 1
    reporter.completed_count = 2
    reporter.completed_bytes = 2 << 20
    with caplog.at_level(logging.INFO, logger="torchsnapshot_tpu.scheduler"):
        reporter.log_table()
    assert caplog.records, "no progress table logged"
    line = caplog.records[-1].message
    for token in (
        "8 total",
        "2 staging",
        "3 staged",
        "1 in io",
        "2 written",
        "budget free",
        "rss delta",
    ):
        assert token in line, f"missing {token!r} in {line!r}"


def test_write_pipeline_wires_progress_reporter(tmp_path) -> None:
    """execute_write_reqs attaches a periodic reporter that survives into
    the PendingIOWork drain phase."""
    import asyncio

    import numpy as np

    import torchsnapshot_tpu.scheduler as sched
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    reqs = []
    for i in range(3):
        _, wreqs = ArrayIOPreparer.prepare_write(f"0/p{i}", np.ones((64, 64)))
        reqs.extend(wreqs)
    loop = asyncio.new_event_loop()
    storage = FSStoragePlugin(str(tmp_path))
    pending = loop.run_until_complete(
        sched.execute_write_reqs(reqs, storage, 1 << 30, rank=0)
    )
    reporter = pending._reporter
    assert reporter is not None
    assert reporter.staged_count == 3
    pending.sync_complete(loop)
    assert reporter.completed_count == 3
    assert reporter.completed_bytes == 3 * 64 * 64 * 8
    loop.run_until_complete(storage.close())
    loop.close()


# ------------------------------------------------------------ DtoH window
#
# A save admits its device-to-host transfers through a byte window
# (io_preparers/array.py DtoHWindow): a leaf's copy_to_host_async is
# kicked when the transfers ahead of it have landed. Tier-1 has no device,
# so a stub stands in for a TPU jax.Array: what the stager reads of one
# (shape, dtype, a sharding off the CPU backend), a kick that records its
# call, and an ``__array__`` that lands the bytes.


class _StubDeviceArray:
    def __init__(self, name: str, nbytes: int, log: list, land=None, fail=False):
        import numpy as np
        from types import SimpleNamespace

        self.name = name
        self.host = np.full(nbytes, len(name) % 251, dtype=np.uint8)
        self.shape, self.dtype = self.host.shape, self.host.dtype
        self.sharding = SimpleNamespace(device_set=[SimpleNamespace(platform="tpu")])
        self.log, self.land, self.fail = log, land, fail

    def copy_to_host_async(self) -> None:
        self.log.append(("kick", self.name, self.host.nbytes))

    def __array__(self, dtype=None, copy=None):
        if self.land is not None:
            assert self.land.wait(timeout=30), "the test never let this leaf land"
        if self.fail:
            raise RuntimeError(f"injected DtoH failure in {self.name}")
        self.log.append(("land", self.name, self.host.nbytes))
        return self.host


@pytest.fixture
def dtoh(monkeypatch):
    """The stager takes stubs for jax arrays; telemetry on for the test."""
    from torchsnapshot_tpu import telemetry
    from torchsnapshot_tpu.io_preparers import array as A

    real = A._is_jax_array
    monkeypatch.setattr(
        A, "_is_jax_array", lambda a: isinstance(a, _StubDeviceArray) or real(a)
    )
    telemetry.reset()
    telemetry.set_enabled(True)
    yield A
    telemetry.set_enabled(False)
    telemetry.reset()


def _stub_reqs(sizes, log, **kw):
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager

    arrs = [_StubDeviceArray(f"leaf{i}", n, log, **kw) for i, n in enumerate(sizes)]
    return arrs, [
        WriteReq(path=a.name, buffer_stager=ArrayBufferStager(a)) for a in arrs
    ]


def _save(reqs, timeout=60.0):
    """A whole save under a timeout of the test's own: a window that never
    gives its bytes back would otherwise hang the suite."""
    storage = InMemoryStoragePlugin()

    async def run():
        pending = await asyncio.wait_for(
            execute_write_reqs(reqs, storage, 10**9, rank=0), timeout
        )
        await asyncio.wait_for(pending.complete(), timeout)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(run())
    finally:
        loop.close()
    return storage


def _in_flight_at_each_kick(log):
    """(leaf, bytes kicked and not yet landed, this kick included) per kick."""
    out, flying = [], {}
    for what, name, nbytes in list(log):
        if what == "kick":
            flying[name] = nbytes
            out.append((name, sum(flying.values())))
        else:
            flying.pop(name, None)
    return out


def _spans(name):
    from torchsnapshot_tpu import telemetry

    return [e for e in telemetry.events() if e["ph"] == "span" and e["name"] == name]


@pytest.mark.parametrize("width, sizes", [
    (3000, [1000] * 12),
    (2500, [1000, 900, 800, 700, 600, 500, 400, 300]),
    (1000, [1000] * 5),  # one transfer at a time
])
def test_dtoh_window_bounds_the_bytes_in_flight(dtoh, monkeypatch, width, sizes) -> None:
    from torchsnapshot_tpu import telemetry

    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", width)
    log = []
    _, reqs = _stub_reqs(sizes, log)
    storage = _save(reqs)
    assert len(storage.storage) == len(sizes)
    kicks = _in_flight_at_each_kick(log)
    assert len(kicks) == len(sizes)
    assert max(b for _, b in kicks) <= width
    # The k-th kick came only after enough earlier leaves had landed.
    landed_before = 0
    for i, (what, _, _) in enumerate(log):
        if what == "kick":
            k = sum(1 for w, _, _ in log[:i] if w == "kick")
            assert k - landed_before <= width // min(sizes)
        else:
            landed_before += 1
    # The window's own evidence: a gate span a leaf, the counter, the gauge.
    assert len(_spans("stage_dtoh_gate")) == len(sizes)
    assert telemetry.counters()["dtoh_window_waits"] >= len(sizes) - width // min(sizes)
    assert telemetry.gauges()["dtoh_inflight_bytes"] <= width


def test_dtoh_window_kicks_in_staging_order(dtoh, monkeypatch) -> None:
    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", 2048)
    log = []
    sizes = [300, 1000, 700, 100, 900, 500, 800, 200, 600, 400]
    _, reqs = _stub_reqs(sizes, log)
    _save(reqs)
    kicked = [n for what, _, n in log if what == "kick"]
    assert kicked == sorted(sizes, reverse=True)  # the scheduler's: largest first


def test_dtoh_window_waits_for_a_landing_before_the_next_kick(dtoh, monkeypatch) -> None:
    """Nothing lands until the test says so: exactly the leaves the window
    holds are kicked, and each landing lets one more go."""
    import threading

    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", 2000)
    log, gates = [], [threading.Event() for _ in range(5)]
    arrs, reqs = _stub_reqs([1000] * 5, log)
    for a, g in zip(arrs, gates):
        a.land = g

    async def run():
        task = asyncio.ensure_future(
            execute_write_reqs(reqs, InMemoryStoragePlugin(), 10**9, rank=0)
        )
        for landed in range(4):
            want = 2 + landed
            for _ in range(400):
                if sum(1 for w, *_ in log if w == "kick") >= want:
                    break
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.05)  # room for a kick that should not come
            assert [n for w, n, _ in log if w == "kick"] == [f"leaf{i}" for i in range(want)]
            gates[landed].set()
        gates[4].set()
        pending = await asyncio.wait_for(task, 30)
        await asyncio.wait_for(pending.complete(), 30)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(run())
    finally:
        for g in gates:
            g.set()
        loop.close()
    assert sum(1 for w, *_ in log if w == "land") == 5


def test_dtoh_window_admits_an_oversize_leaf_alone(dtoh, monkeypatch) -> None:
    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", 1000)
    log = []
    _, reqs = _stub_reqs([5000, 400, 4000, 300, 200], log)
    storage = _save(reqs)
    assert len(storage.storage) == 5
    for name, flying in _in_flight_at_each_kick(log):
        nbytes = next(n for w, leaf, n in log if w == "kick" and leaf == name)
        assert flying <= 1000 or flying == nbytes  # over the width only alone


def test_dtoh_window_waits_counts_the_leaves_that_found_it_full(dtoh, monkeypatch) -> None:
    """Held landings make the count exact: of 6 equal leaves under a
    window of 2, the first 2 walk in and 4 wait; the gauge peaks at 2."""
    import threading

    from torchsnapshot_tpu import telemetry

    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", 2000)
    log, land = [], threading.Event()
    _, reqs = _stub_reqs([1000] * 6, log, land=land)
    peaks = []
    real = telemetry.gauge_set

    def spy(name, value):
        if name == "dtoh_inflight_bytes":
            peaks.append(value)
        real(name, value)

    monkeypatch.setattr(dtoh.telemetry, "gauge_set", spy)
    threading.Timer(0.3, land.set).start()
    _save(reqs)
    assert telemetry.counters()["dtoh_window_waits"] == 4
    assert max(peaks) == 2000 and len(peaks) == 6
    waited = sorted(e["dur"] for e in _spans("stage_dtoh_gate"))
    assert len(waited) == 6 and waited[1] < 0.05 <= waited[2]


def test_a_stage_that_raises_gives_its_bytes_back_and_the_save_aborts(dtoh, monkeypatch) -> None:
    monkeypatch.setattr(dtoh, "_DTOH_WINDOW_BYTES", 2000)
    log = []
    arrs, reqs = _stub_reqs([1000] * 8, log)
    arrs[2].fail = True
    windows = []
    real = dtoh.DtoHWindow

    def keep(*a, **kw):
        windows.append(real(*a, **kw))
        return windows[-1]

    import torchsnapshot_tpu.scheduler as sched

    monkeypatch.setattr(sched, "DtoHWindow", keep)
    with pytest.raises(RuntimeError, match="injected DtoH failure in leaf2"):
        _save(reqs, timeout=30)
    (window,) = windows
    assert window.in_flight == 0 and not window._waiters
    assert dtoh.dtoh_window.get() is None  # reset on the abort path too


@pytest.mark.parametrize("kind", ["numpy", "cpu_jax"])
def test_host_arrays_bypass_the_dtoh_window(dtoh, kind, tmp_path) -> None:
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, telemetry

    make = np.ones if kind == "numpy" else jnp.ones
    state = StateDict(w=make((64, 64)), b=make((8,)))
    Snapshot.async_take(str(tmp_path / "snap"), {"m": state}).wait()
    assert _spans("stage_hash") and not _spans("stage_dtoh_gate")
    assert "dtoh_window_waits" not in telemetry.counters()


def test_a_stager_outside_a_save_kicks_at_once(dtoh) -> None:
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager

    log = []
    arr = _StubDeviceArray("lone", 100, log)
    buf = asyncio.run(ArrayBufferStager(arr).stage_buffer(None))
    assert bytes(buf) == arr.host.tobytes()
    assert [w for w, *_ in log] == ["kick", "land"] and not _spans("stage_dtoh_gate")


@pytest.mark.parametrize("base_has_digest", [True, False])
def test_device_digest_orderings_hold_under_the_window(dtoh, monkeypatch, base_has_digest) -> None:
    """With a base fingerprint a skip is possible: fingerprint first, and a
    match never reaches the window. Without one the DMA must happen:
    admission, kick, then the recording fingerprint's dispatch."""
    from types import SimpleNamespace

    import torchsnapshot_tpu.device_digest as dd
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager
    from torchsnapshot_tpu.manifest import ArrayEntry

    log = []
    arr = _StubDeviceArray("leaf", 1000, log)
    entry = ArrayEntry("0/leaf", "buffer_protocol", "uint8", [1000], False)
    stager = ArrayBufferStager(arr, entry)
    ref = SimpleNamespace(device_digest="xxh4x32:00" if base_has_digest else None)
    stager.dedup = SimpleNamespace(
        device_digests=True, refs={entry.location: ref}, match=lambda *a: None
    )
    monkeypatch.setattr(
        stager, "_try_device_dedup", lambda a: log.append(("fingerprint", "leaf", 0)) or True
    )
    monkeypatch.setattr(dd, "_dispatch", lambda a: log.append(("dispatch", "leaf", 0)) or "pending")
    monkeypatch.setattr(dd, "_finalize", lambda a, p: log.append(("finalize", "leaf", 0)) or "xxh4x32:11")
    _save([WriteReq(path="leaf", buffer_stager=stager)])
    got = [w for w, *_ in log]
    if base_has_digest:
        assert got == ["fingerprint"] and stager.io_skipped
        assert not _spans("stage_dtoh_gate")
    else:
        assert got == ["kick", "dispatch", "land", "finalize"]
        assert len(_spans("stage_dtoh_gate")) == 1 and entry.device_digest == "xxh4x32:11"


def test_a_cancelled_waiter_lets_the_leaves_behind_it_move_up() -> None:
    from torchsnapshot_tpu.io_preparers.array import DtoHWindow

    async def run():
        window = DtoHWindow(1000)
        await window.admit(800)
        big = asyncio.ensure_future(window.admit(900))
        small = asyncio.ensure_future(window.admit(100))
        await asyncio.sleep(0)
        assert not big.done() and not small.done()  # FIFO: small waits behind big
        big.cancel()
        await asyncio.wait_for(small, 5)
        assert window.in_flight == 900
        window.release(800), window.release(100)
        assert window.in_flight == 0 and not window._waiters

    asyncio.run(run())


def test_a_gated_save_writes_the_manifest_an_ungated_one_writes(monkeypatch, tmp_path) -> None:
    """The repo's sharded fixture (tests/data/transformer_2x2_snapshot, a
    save of the parent's) against today's save of the same state, with its
    41 pieces (3 KiB at most) pushed through a window of 4 KiB and with no
    window at all: the same entries, locations, shapes and checksums."""
    import dataclasses
    import importlib.util
    import json
    import os

    import jax

    from torchsnapshot_tpu import Snapshot, StateDict, telemetry
    from torchsnapshot_tpu.io_preparers import array as A
    from torchsnapshot_tpu.models import transformer as T

    data = os.path.join(os.path.dirname(__file__), "data")
    spec = importlib.util.spec_from_file_location(
        "gen", os.path.join(data, "gen_transformer_2x2_snapshot.py")
    )
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    cfg, tx, mesh, _, _ = gen.build()
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)

    def manifest(root, drop=()):
        def strip(x):
            if isinstance(x, dict):
                return {k: strip(v) for k, v in x.items() if k not in drop}
            return [strip(v) for v in x] if isinstance(x, list) else x

        m = Snapshot(root).get_manifest()
        return strip(json.loads(json.dumps(m, default=dataclasses.asdict)))

    Snapshot.async_take(str(tmp_path / "plain"), {"train": StateDict(**state)}).wait()
    telemetry.reset()
    telemetry.set_enabled(True)
    try:
        with monkeypatch.context() as m:
            m.setattr(A, "_device_backed", lambda a: True)
            m.setattr(A, "_DTOH_WINDOW_BYTES", 4 << 10)
            Snapshot.async_take(str(tmp_path / "gated"), {"train": StateDict(**state)}).wait()
        gates = len(_spans("stage_dtoh_gate"))
        waits = telemetry.counters().get("dtoh_window_waits", 0)
    finally:
        telemetry.set_enabled(False)
        telemetry.reset()
    assert gates == 41 and waits > 0
    plain, gated = manifest(str(tmp_path / "plain")), manifest(str(tmp_path / "gated"))
    assert gated == plain and len(plain) > 0
    # The values of the fixture's state are one train step on: its
    # checksums differ, nothing else may.
    fixture = manifest(os.path.join(data, "transformer_2x2_snapshot"), drop=("checksum",))
    assert manifest(str(tmp_path / "gated"), drop=("checksum",)) == fixture
