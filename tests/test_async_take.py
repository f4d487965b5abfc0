"""async_take: consistency point, commit protocol, fault injection
(reference: tests/test_async_take.py — SlowFS/FaultyFS plugin subclassing,
error propagation through wait(), metadata-not-committed assertions)."""

import asyncio
import os
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.io_types import WriteIO
from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.test_utils import run_with_subprocesses


# The commit fence (.snapshot_fence) is a control file written
# synchronously at plan time — BEFORE async_take returns, which is what
# makes the fenced GC sound (see snapshot._take_impl). Slow/faulty
# payload-write plugins must exempt it: these tests target the PAYLOAD
# write path (staged in the background), not the fence plant.
def _is_payload(write_io: WriteIO) -> bool:
    return not (
        write_io.path == SNAPSHOT_METADATA_FNAME
        or write_io.path.endswith(".snapshot_fence")
    )


class SlowFSStoragePlugin(FSStoragePlugin):
    WRITE_DELAY_S = 1.0

    async def write(self, write_io: WriteIO) -> None:
        if _is_payload(write_io):
            await asyncio.sleep(self.WRITE_DELAY_S)
        await super().write(write_io)


class FaultyFSStoragePlugin(FSStoragePlugin):
    async def write(self, write_io: WriteIO) -> None:
        if _is_payload(write_io):
            raise RuntimeError("injected storage failure")
        await super().write(write_io)


def test_async_take_completes(tmp_path, monkeypatch) -> None:
    monkeypatch.setattr(
        "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
        SlowFSStoragePlugin,
    )
    app_state = {"m": StateDict(w=np.arange(1000, dtype=np.float32))}
    t0 = time.monotonic()
    pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
    returned_after = time.monotonic() - t0
    snapshot = pending.wait()
    assert pending.done()
    # The slow write must not have blocked the caller. Cold-start overhead
    # (first event loop, thread pools) can cost a few hundred ms on its own,
    # so the bound is a margin below the write delay, not near-zero.
    assert returned_after < SlowFSStoragePlugin.WRITE_DELAY_S * 0.9
    dst = StateDict(w=np.zeros(1000, dtype=np.float32))
    snapshot.restore({"m": dst})
    np.testing.assert_array_equal(dst["w"], app_state["m"]["w"])


def test_async_take_consistency_point(tmp_path, monkeypatch) -> None:
    """Mutations after async_take returns must not affect the snapshot —
    staging completes before return (reference: snapshot.py:257-262)."""
    monkeypatch.setattr(
        "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
        SlowFSStoragePlugin,
    )
    arr = np.arange(256, dtype=np.float64)
    app_state = {"m": StateDict(w=arr, step=1)}
    pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
    arr[:] = -1.0  # mutate while storage I/O is still in flight
    snapshot = pending.wait()
    dst = StateDict(w=np.zeros(256, dtype=np.float64), step=0)
    snapshot.restore({"m": dst})
    np.testing.assert_array_equal(dst["w"], np.arange(256, dtype=np.float64))


def test_async_take_error_propagation(tmp_path, monkeypatch) -> None:
    """Failures surface through wait() AND the metadata is never committed
    (reference: tests/test_async_take.py:53-64)."""
    monkeypatch.setattr(
        "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
        FaultyFSStoragePlugin,
    )
    app_state = {"m": StateDict(w=np.ones(64, dtype=np.float32))}
    pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
    with pytest.raises(RuntimeError, match="injected storage failure"):
        pending.wait()
    assert pending.done()
    assert not (tmp_path / "snap" / SNAPSHOT_METADATA_FNAME).exists()


def test_sync_take_error_no_commit(tmp_path, monkeypatch) -> None:
    monkeypatch.setattr(
        "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
        FaultyFSStoragePlugin,
    )
    with pytest.raises(RuntimeError, match="injected storage failure"):
        Snapshot.take(
            str(tmp_path / "snap"),
            {"m": StateDict(w=np.ones(64, dtype=np.float32))},
        )
    assert not (tmp_path / "snap" / SNAPSHOT_METADATA_FNAME).exists()


def _async_take_worker(rank: int, world_size: int, snap_path: str):
    from torchsnapshot_tpu import Snapshot, StateDict

    app_state = {
        "model": StateDict(w=np.arange(100, dtype=np.float32)),
        "local": StateDict(step=rank),
    }
    pending = Snapshot.async_take(snap_path, app_state, replicated=["model/*"])
    snapshot = pending.wait()
    return sorted(snapshot.get_manifest().keys())


@pytest.mark.multiprocess
def test_async_take_multiprocess(tmp_path) -> None:
    snap_path = str(tmp_path / "snap")
    results = run_with_subprocesses(_async_take_worker, 2, snap_path)
    assert results[0] == results[1]
    assert os.path.exists(os.path.join(snap_path, SNAPSHOT_METADATA_FNAME))


class _Rank1FaultyPlugin(FSStoragePlugin):
    async def write(self, write_io) -> None:
        raise RuntimeError("rank-1 injected failure")


def _async_take_one_rank_fails_worker(rank: int, world_size: int, snap_path: str):
    import unittest.mock as mock

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME as MD

    app_state = {"local": StateDict(data=np.full(1000, rank, dtype=np.float32))}

    if rank == 1:
        ctx = mock.patch(
            "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
            _Rank1FaultyPlugin,
        )
    else:
        ctx = mock.patch(
            "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
            SlowFSStoragePlugin,
        )

    with ctx:
        pending = Snapshot.async_take(snap_path, app_state)
        try:
            pending.wait()
            return "committed"
        except RuntimeError as e:
            return f"error: {e}"


@pytest.mark.multiprocess
def test_async_take_all_or_nothing(tmp_path) -> None:
    """If any rank fails, no rank commits and everyone sees an error
    (reference: tests/test_async_take.py:107-115)."""
    snap_path = str(tmp_path / "snap")
    results = run_with_subprocesses(
        _async_take_one_rank_fails_worker, 2, snap_path
    )
    assert all(r.startswith("error") for r in results.values()), results
    assert not os.path.exists(os.path.join(snap_path, SNAPSHOT_METADATA_FNAME))


def test_warmup_staging_prefaults_exact_sizes(tmp_path, monkeypatch):
    """warmup_staging must draw the same slab sizes the real staging pass
    will: a second warmup reports nothing left to fault, and an
    async_take after warmup recycles the warmed slabs instead of
    allocating fresh ones."""
    import gc

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, warmup_staging
    from torchsnapshot_tpu.io_preparers import array as array_mod

    # A pool of the test's own: the process's pool keeps the slabs of
    # whatever test files this worker ran before (a free 512 KiB slab
    # left by tests/test_governor.py made "everything faulted" false).
    _staging_pool = array_mod._StagingPool(array_mod._pool_limit())
    monkeypatch.setattr(array_mod, "_staging_pool", _staging_pool)

    state = {
        "app": StateDict(
            a=np.random.default_rng(0).standard_normal((1 << 18,)).astype(np.float32),
            b=np.arange(1 << 16, dtype=np.int64),
        )
    }
    nbytes = sum(x.nbytes for x in state["app"].values())
    warmed = warmup_staging(state)
    assert warmed >= nbytes  # everything faulted up front
    assert warmup_staging(state) == 0  # already pooled: nothing to do

    before = {
        n: [s.ctypes.data for s in slabs] for n, slabs in _staging_pool._free.items()
    }
    Snapshot.async_take(str(tmp_path / "s"), state).wait()
    gc.collect()
    # The staged buffers came from (and returned to) the warmed slabs.
    after = {
        n: [s.ctypes.data for s in slabs] for n, slabs in _staging_pool._free.items()
    }
    for size, ptrs in before.items():
        assert set(ptrs) <= set(after.get(size, [])), size
    assert warmup_staging(state) == 0


def test_warmup_staging_sharded_piece_sizes():
    """For a GSPMD-sharded array, warmup sizes the pool from the owned
    write pieces, not the full array."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from torchsnapshot_tpu import StateDict, warmup_staging
    from torchsnapshot_tpu.io_preparers.sharded import ShardedArrayIOPreparer

    devs = jax.devices()
    if len(devs) < 2:
        import pytest

        pytest.skip("needs multiple devices")
    mesh = Mesh(np.array(devs), ("x",))
    arr = jax.device_put(
        jnp.arange(8 * len(devs) * 128, dtype=jnp.float32).reshape(
            8 * len(devs), 128
        ),
        NamedSharding(mesh, PartitionSpec("x", None)),
    )
    piece_sizes = ShardedArrayIOPreparer.staged_piece_sizes(arr)
    assert sum(piece_sizes) == arr.nbytes  # single process owns every piece
    assert len(piece_sizes) == len(devs)
    warmed = warmup_staging({"app": StateDict(w=arr)})
    assert warmed >= sum(piece_sizes)
