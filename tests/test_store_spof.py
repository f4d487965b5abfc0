"""Coordination-plane SPOF drill: the store-hosting process dies mid-take.

The KV store lives in rank 0's process (the same single point of failure
as the reference's rank-0-hosted TCPStore, dist_store.py:53-88). This
drill proves the failure story end to end in a REAL multi-process world:

1. the world commits a snapshot normally;
2. a second take starts and rank 0 (the store host) is SIGKILLed mid-
   staging — every surviving rank's take must raise within SECONDS (the
   client-side connection-loss detection of dist_store.TCPStore), naming
   the coordination store, instead of blocking out the 1800 s barrier
   timeout;
3. nothing is committed for the doomed take (metadata-last protocol);
4. a FRESH world — at a different world size, with a new store — restores
   the last committed snapshot and sees the exact saved content.

The drill runs over the snapshot library's OWN process group (KV-store
collectives via pg_wrapper — what the launcher's workers already join)
WITHOUT jax.distributed: jax's coordination service is rank-0-hosted
too and F-aborts surviving processes on leader death, which would mask
the behavior under test. The snapshot coordination plane is independent
of the XLA runtime by design (SURVEY §5.8), so its failure story must
hold on its own.

Recovery recipe documented in docs/source/elasticity.rst
("Coordination-plane failure").
"""

from __future__ import annotations

import os
import signal
import time

import numpy as np
import pytest

from torchsnapshot_tpu.test_utils import run_with_subprocesses

pytestmark = [pytest.mark.multiprocess]

SHAPE = (6, 8)


def _data(rank: int = 0) -> np.ndarray:
    return np.arange(48, dtype=np.float32).reshape(SHAPE) + rank


def _spof_worker(rank, world_size, committed_root, doomed_root):
    """Phase 1: commit a snapshot. Phase 2: take again; rank 0 (the store
    host) SIGKILLs itself mid-staging; survivors must abort fast."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.dist_store import StoreConnectionLostError

    app = {
        "m": StateDict(
            emb=jnp.asarray(_data(rank)),  # per-rank device state
            host=_data(),  # replicated host state
        )
    }
    Snapshot.take(committed_root, app, replicated=["m/host"])

    if rank == 0:
        from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager

        orig = ArrayBufferStager._stage_and_sum

        def die_mid_staging(self, a):
            # Let peers finish their own staging and reach the blocking
            # manifest gather first, then die without cleanup — the
            # store server dies with this process.
            time.sleep(2.0)
            os.kill(os.getpid(), signal.SIGKILL)
            return orig(self, a)  # pragma: no cover

        ArrayBufferStager._stage_and_sum = die_mid_staging

    t0 = time.monotonic()
    try:
        Snapshot.take(
            doomed_root,
            {"m": StateDict(emb=jnp.asarray(_data(rank)) + 1, host=_data())},
            replicated=["m/host"],
        )
    except BaseException as e:  # noqa: B036
        elapsed = time.monotonic() - t0
        # The connection-loss error must be the cause (directly or
        # chained) and must name the coordination store.
        chain, cur, seen = [], e, set()
        while cur is not None and id(cur) not in seen:
            seen.add(id(cur))
            chain.append(cur)
            cur = cur.__cause__ or cur.__context__
        assert any(
            isinstance(c, StoreConnectionLostError) for c in chain
        ), f"rank {rank}: {type(e).__name__}: {e}"
        assert any("coordination store" in str(c) for c in chain)
        return ("aborted", elapsed)
    return ("NOT-ABORTED", time.monotonic() - t0)


def _recovery_worker(rank, world_size, committed_root):
    """A fresh, SMALLER world (new store, changed world size) restores
    the committed snapshot: replicated entries are available to every
    rank, per-rank entries to their original owner (elasticity rules)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict

    dst = StateDict(
        emb=jnp.zeros(SHAPE, jnp.float32),
        host=np.zeros(SHAPE, np.float32),
    )
    Snapshot(committed_root).restore({"m": dst})
    np.testing.assert_array_equal(dst["host"], _data())
    np.testing.assert_array_equal(np.asarray(dst["emb"]), _data(rank))
    return "ok"


def _wait_any_worker(rank, world_size):
    """Rank 0 (the store host) SIGKILLs itself while peers are blocked
    in a long-timeout wait_any; survivors must raise within seconds."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from torchsnapshot_tpu.dist_store import StoreConnectionLostError
    from torchsnapshot_tpu.pg_wrapper import get_default_pg

    store = get_default_pg().store
    store.add("armed", 1)  # everyone reaches the store first
    store.get("armed")  # (value irrelevant; one warm round trip each)
    if rank == 0:
        time.sleep(1.5)  # let peers block in wait_any server-side
        os.kill(os.getpid(), signal.SIGKILL)
    t0 = time.monotonic()
    try:
        store.wait_any(["never-set"], timeout=600.0)
    except StoreConnectionLostError:
        return ("aborted", time.monotonic() - t0)
    return ("NOT-ABORTED", time.monotonic() - t0)


def test_leader_death_mid_wait_any_no_replicas_bounded() -> None:
    """Satellite regression guard: with ZERO replicas configured, leader
    death under a blocked wait_any fails every survivor in seconds (the
    PR 5 detection behavior is the non-replicated fallback path)."""
    results = run_with_subprocesses(
        _wait_any_worker, 3, timeout=120.0, expect_dead=(0,)
    )
    assert set(results) == {1, 2}, results
    for rank, (status, elapsed) in results.items():
        assert status == "aborted", results
        assert elapsed < 60.0, f"rank {rank} took {elapsed:.1f}s"


def _commit_barrier_worker(rank, world_size, root):
    """Phase 1 commits ``prev``. Phase 2: rank 0 — the store host — is
    SIGKILLed at the exact metadata commit point, leaving peers parked
    in the two-phase commit barrier."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict, faultinject
    from torchsnapshot_tpu.dist_store import StoreConnectionLostError

    state = {"m": StateDict(emb=jnp.asarray(_data(rank)))}
    Snapshot.take(os.path.join(root, "prev"), state)
    if rank == 0:
        faultinject.configure("commit.metadata@1=kill")
    t0 = time.monotonic()
    try:
        Snapshot.take(
            os.path.join(root, "doomed"),
            {"m": StateDict(emb=jnp.asarray(_data(rank)) + 1)},
        )
    except BaseException as e:  # noqa: B036
        chain, cur, seen = [], e, set()
        while cur is not None and id(cur) not in seen:
            seen.add(id(cur))
            chain.append(cur)
            cur = cur.__cause__ or cur.__context__
        assert any(
            isinstance(c, StoreConnectionLostError) for c in chain
        ), f"rank {rank}: {type(e).__name__}: {e}"
        return ("aborted", time.monotonic() - t0)
    return ("NOT-ABORTED", time.monotonic() - t0)


def test_leader_death_mid_commit_barrier_no_replicas_bounded(tmp_path) -> None:
    """The kill-during-commit-barrier schedule with no replicas: the
    world must end prev-restorable + fsck-clean within the bounded
    deadline — never a 1800 s hang and never a torn commit."""
    from torchsnapshot_tpu.cli import run_fsck

    results = run_with_subprocesses(
        _commit_barrier_worker, 2, str(tmp_path), timeout=180.0,
        expect_dead=(0,),
    )
    assert set(results) == {1}, results
    status, elapsed = results[1]
    assert status == "aborted", results
    assert elapsed < 60.0, f"survivor took {elapsed:.1f}s to abort"
    # The doomed take committed nothing (the kill landed AT the commit
    # point, before the metadata write); prev is intact and fsck-clean.
    assert not os.path.exists(
        os.path.join(tmp_path, "doomed", ".snapshot_metadata")
    )
    prev = os.path.join(str(tmp_path), "prev")
    assert run_fsck(prev, echo=lambda *a, **k: None)[0] == 0
    import jax.numpy as jnp  # noqa: F401 - jax configured by conftest

    import numpy as _np

    from torchsnapshot_tpu import Snapshot, StateDict

    # The parent restores as rank 0 of a world-1 group: it sees rank 0's
    # per-rank entry from the committed prev snapshot.
    dst = {"m": StateDict(emb=_np.zeros(SHAPE, _np.float32))}
    Snapshot(prev).restore(dst)
    _np.testing.assert_array_equal(_np.asarray(dst["m"]["emb"]), _data(0))


def test_store_host_death_aborts_fast_and_world_recovers(tmp_path) -> None:
    committed = str(tmp_path / "committed")
    doomed = str(tmp_path / "doomed")

    results = run_with_subprocesses(
        _spof_worker,
        3,
        committed,
        doomed,
        timeout=240.0,
        expect_dead=(0,),
    )
    # Rank 0 died (no result); both survivors aborted, in seconds.
    assert set(results) == {1, 2}, results
    for rank, (status, elapsed) in results.items():
        assert status == "aborted", results
        assert elapsed < 60.0, f"rank {rank} took {elapsed:.1f}s to abort"

    # The doomed take committed nothing; the earlier snapshot is intact.
    assert not os.path.exists(os.path.join(doomed, ".snapshot_metadata"))
    assert os.path.isfile(os.path.join(committed, ".snapshot_metadata"))

    # A fresh 2-process world (new store, changed world size) restores
    # the committed snapshot.
    results = run_with_subprocesses(
        _recovery_worker, 2, committed, timeout=240.0
    )
    assert all(v == "ok" for v in results.values())
