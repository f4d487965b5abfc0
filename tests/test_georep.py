"""Cross-region disaster recovery (georep.py): async geo-replication
via journal-epoch shipping with a durable cursor.

The contract under test (ISSUE 20): a rank-0 background shipper
replicates committed full snapshots and committed journal epochs to a
remote tier; the remote is a REAL snapshot + journal tree, so disaster
restore is the ordinary restore path folding base + committed epochs
bit-exact; a durable cursor makes shipping resume exactly-once across
shipper death; three fences (record CRCs, offset continuity, generation
chaining) mean a deposed or resurrected shipper can never splice a torn
tail or a stale generation over newer remote state; fsck understands
the cursor on both tiers and repairs a stale one.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from torchsnapshot_tpu import (
    CheckpointManager,
    Snapshot,
    StateDict,
    georep,
    journal,
    telemetry,
)
from torchsnapshot_tpu.cli import main as cli_main, run_fsck
from torchsnapshot_tpu.journal import DeltaJournal


@pytest.fixture
def replicated(tmp_path, monkeypatch):
    """A primary root + armed remote root, fast shipper cadence."""
    remote = str(tmp_path / "remote")
    os.makedirs(remote)
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_JOURNAL", "1")
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_GEOREP", remote)
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_GEOREP_INTERVAL_S", "0.05")
    telemetry.set_enabled(True)
    yield str(tmp_path / "primary"), remote
    telemetry.reset()
    telemetry.set_enabled(False)


def _state(v: float) -> StateDict:
    return StateDict(
        w=np.arange(512, dtype=np.float32) + v,
        b=np.full((32,), v, np.float64),
        step=int(v),
    )


def _assert_state(dst: StateDict, v: float) -> None:
    np.testing.assert_array_equal(
        dst["w"], np.arange(512, dtype=np.float32) + v
    )
    np.testing.assert_array_equal(dst["b"], np.full((32,), v, np.float64))
    assert dst["step"] == int(v)


def _journaled_step(root: str, epochs: int = 2):
    """A committed base + ``epochs`` committed journal epochs, built
    below the manager so tests can drive the shipper directly. Returns
    the live DeltaJournal so tests can CONTINUE the chain (a fresh
    DeltaJournal restarts epoch numbering — that is the deposed-writer
    scenario, not a continuation)."""
    step_dir = os.path.join(root, "step_0000000001")
    state = {"app": _state(0)}
    Snapshot.take(step_dir, state)
    j = DeltaJournal(step_dir, base_step=1, rank=0)
    j.capture_baseline(state)
    for e in range(1, epochs + 1):
        state["app"]["w"][: 16 * e] = float(100 + e)
        state["app"]["step"] = e
        assert j.append_epoch(state) > 0
    return step_dir, state, j


def _remote_segment(remote_step: str, rank: int = 0) -> str:
    return os.path.join(
        remote_step, journal.JOURNAL_DIRNAME, journal.segment_name(rank)
    )


# ------------------------------------------------------- headline drill


def test_region_loss_restores_remote_bit_exact(replicated, monkeypatch):
    """Primary region lost: the remote tier restores base + every
    committed epoch bit-exact through the ORDINARY restore path."""
    root, remote = replicated
    mgr = CheckpointManager(root, save_interval_steps=100)
    assert mgr._georep is not None  # armed by the env
    st = _state(0)
    mgr.save(0, {"app": st})
    for v in (1, 2, 3):
        st["w"] = np.arange(512, dtype=np.float32) + v
        st["b"] = np.full((32,), float(v), np.float64)
        st["step"] = v
        assert mgr.journal_step(v, {"app": st})
    assert mgr._georep.drain(timeout=30.0), mgr._georep.last_error
    mgr.close()

    shutil.rmtree(root)  # the disaster
    monkeypatch.delenv("TORCHSNAPSHOT_TPU_GEOREP")
    before = telemetry.counters().get("dr_replica_restores", 0)
    dst = _state(-1)
    assert CheckpointManager(remote).restore({"app": dst}) == 0
    _assert_state(dst, 3)
    # Restore provenance: the replica restore is counted + logged.
    assert telemetry.counters().get("dr_replica_restores", 0) == before + 1


def test_remote_is_never_ahead_mid_epoch(replicated):
    """Only COMMITTED state ships: with the shipper drained, the remote
    journal chain equals the local committed chain exactly (a torn or
    open local tail never travels)."""
    root, remote = replicated
    del remote
    step_dir, _, _j = _journaled_step(root, epochs=3)
    remote_root = os.environ["TORCHSNAPSHOT_TPU_GEOREP"]
    rep = georep.GeoReplicator(remote_root, interval=0.05)
    try:
        rep.enqueue(step_dir, 1)
        assert rep.drain(timeout=30.0), rep.last_error
    finally:
        rep.close(0)
    local = journal.committed_epochs(
        journal.read_epoch_metas(
            os.path.join(step_dir, journal.JOURNAL_DIRNAME)
        )
    )
    remote_step = os.path.join(remote_root, "step_0000000001")
    shipped = journal.committed_epochs(
        journal.read_epoch_metas(
            os.path.join(remote_step, journal.JOURNAL_DIRNAME)
        )
    )
    assert [m["epoch"] for m in shipped] == [m["epoch"] for m in local]
    assert [m["gen"] for m in shipped] == [m["gen"] for m in local]


# --------------------------------------------------- cursor exactly-once


def test_cursor_resumes_shipping_mid_stream(replicated, monkeypatch):
    """A restarted shipper resumes from the durable cursor: only the
    epochs past it cross the WAN, appended (not rewritten) onto the
    remote segment."""
    root, remote = replicated
    step_dir, state, j = _journaled_step(root, epochs=1)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)  # the shipper dies

    remote_step = os.path.join(remote, "step_0000000001")
    seg_after_e1 = os.path.getsize(_remote_segment(remote_step))

    state["app"]["w"][:8] = -5.0  # epoch 2 continues the chain
    assert j.append_epoch(state) > 0

    appended = []
    orig = georep._RemoteTier.append

    def counting_append(self, rel, existing, region, _orig=orig):
        appended.append((rel, len(existing), len(region)))
        _orig(self, rel, existing, region)

    monkeypatch.setattr(georep._RemoteTier, "append", counting_append)
    rep2 = georep.GeoReplicator(remote, interval=0.05)
    try:
        rep2.enqueue(step_dir, 1)
        assert rep2.drain(timeout=30.0), rep2.last_error
    finally:
        rep2.close(0)
    # Exactly one extension, from exactly the epoch-1 committed offset.
    assert [(n, e) for n, e, _ in appended] == [
        (os.path.join(journal.JOURNAL_DIRNAME, journal.segment_name(0)),
         seg_after_e1)
    ]
    cur = georep.read_cursor(remote_step)
    assert cur is not None and cur["epoch"] == 2


def test_death_between_remote_commit_and_cursor_is_exactly_once(
    replicated, monkeypatch
):
    """Shipper died after committing epoch k remotely but before the
    cursor write: the resurrected shipper probes the remote metadata,
    advances the cursor, and never re-applies a byte."""
    root, remote = replicated
    step_dir, _, _j = _journaled_step(root, epochs=2)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)

    remote_step = os.path.join(remote, "step_0000000001")
    cur = georep.read_cursor(remote_step)
    assert cur["epoch"] == 2
    metas = journal.committed_epochs(
        journal.read_epoch_metas(
            os.path.join(remote_step, journal.JOURNAL_DIRNAME)
        )
    )
    # Rewind the cursor to simulate the crash window.
    with open(os.path.join(remote_step, georep.CURSOR_FNAME), "w") as f:
        json.dump({**cur, "epoch": 1, "gen": metas[0]["gen"]}, f)

    def no_writes(self, rel, *a, **k):
        raise AssertionError(f"remote write during advance-only: {rel}")

    monkeypatch.setattr(georep._RemoteTier, "append", no_writes)
    rep2 = georep.GeoReplicator(remote, interval=0.05)
    try:
        rep2.enqueue(step_dir, 1)
        assert rep2.drain(timeout=30.0), rep2.last_error
    finally:
        rep2.close(0)
    assert georep.read_cursor(remote_step)["epoch"] == 2


# ------------------------------------------------------------ the fences


def test_diverged_generation_is_refused(replicated):
    """A remote chain carrying a different generation for epoch k-1
    refuses epoch k before any byte moves (the deposed-shipper fence)."""
    root, remote = replicated
    step_dir, state, j = _journaled_step(root, epochs=1)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)

    remote_step = os.path.join(remote, "step_0000000001")
    jdir = os.path.join(remote_step, journal.JOURNAL_DIRNAME)
    meta_path = os.path.join(jdir, journal.epoch_meta_name(1))
    with open(meta_path) as f:
        meta = json.load(f)
    meta["gen"] = "0" * 32  # the remote chain now belongs to someone else
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    # Cursor agrees with the tampered chain (a resurrected shipper
    # whose local journal diverged from what the remote holds).
    cur = georep.read_cursor(remote_step)
    with open(os.path.join(remote_step, georep.CURSOR_FNAME), "w") as f:
        json.dump({**cur, "gen": "0" * 32}, f)

    state["app"]["w"][:4] = 7.0  # epoch 2 continues the LOCAL chain
    assert j.append_epoch(state) > 0

    seg = _remote_segment(remote_step)
    before_bytes = open(seg, "rb").read()
    refusals0 = telemetry.counters().get("georep_splice_refusals", 0)
    rep2 = georep.GeoReplicator(remote, interval=0.05)
    try:
        rep2.enqueue(step_dir, 1)
        assert not rep2.drain(timeout=1.0)  # refused, stays pending
        assert "generation" in (rep2.last_error or "")
    finally:
        rep2.close(0)
    assert telemetry.counters().get("georep_splice_refusals", 0) > refusals0
    assert open(seg, "rb").read() == before_bytes  # not a byte moved


def test_offset_discontinuity_is_refused(replicated):
    """A remote segment that is not exactly at the epoch's start offset
    refuses the splice (never overwrite, never leave a gap)."""
    root, remote = replicated
    step_dir, _, _j = _journaled_step(root, epochs=2)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)

    remote_step = os.path.join(remote, "step_0000000001")
    seg = _remote_segment(remote_step)
    blob = open(seg, "rb").read()
    # Truncate the remote segment INTO a committed region (off any
    # epoch boundary) and erase the cursor + remote metas: the re-ship
    # must refuse to extend a segment at no committed offset.
    with open(seg, "wb") as f:
        f.write(blob[: len(blob) - 3])
    os.remove(os.path.join(remote_step, georep.CURSOR_FNAME))
    for n in os.listdir(os.path.join(remote_step, journal.JOURNAL_DIRNAME)):
        if journal._EPOCH_META_RE.match(n):
            os.remove(
                os.path.join(remote_step, journal.JOURNAL_DIRNAME, n)
            )

    rep2 = georep.GeoReplicator(remote, interval=0.05)
    try:
        rep2.enqueue(step_dir, 1)
        assert not rep2.drain(timeout=1.0)
        assert "extend" in (rep2.last_error or "") or "segment" in (
            rep2.last_error or ""
        )
    finally:
        rep2.close(0)


# ------------------------------------------------------- status + fsck


def test_status_and_cli(replicated, capsys):
    root, remote = replicated
    step_dir, _, _j = _journaled_step(root, epochs=2)

    # Nothing shipped yet: the full backlog is visible.
    st = georep.status(root, remote_root=remote)
    assert st["enabled"] and st["step"] == 1
    assert not st["base_replicated"]
    assert st["backlog_epochs"] == 1 + 2  # base + both epochs
    assert cli_main(["georep-status", root]) == 1  # behind
    capsys.readouterr()  # drop the human rendering

    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)

    st = georep.status(root, remote_root=remote)
    assert st["base_replicated"]
    assert st["applied_epoch"] == 2 == st["local_epochs"]
    assert st["applied_gen"] == st["local_gen"]
    assert st["backlog_epochs"] == 0
    assert cli_main(["georep-status", root, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["backlog_epochs"] == 0
    # Unconfigured root: cannot-check.
    os.environ.pop("TORCHSNAPSHOT_TPU_GEOREP")
    assert cli_main(["georep-status", root]) == 2


def test_fsck_clean_on_both_tiers(replicated):
    """The regression the satellite pins: a replicated snapshot fscks
    clean on BOTH tiers — cursor and ship temps are known artifacts,
    and the shipped journal chain passes the journal checks."""
    root, remote = replicated
    step_dir, _, _j = _journaled_step(root, epochs=2)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)
    for tier_dir in (step_dir, os.path.join(remote, "step_0000000001")):
        code, report = run_fsck(tier_dir)
        assert code == 0, (tier_dir, report.findings)


def test_fsck_repairs_stale_cursor(replicated):
    root, remote = replicated
    step_dir, _, _j = _journaled_step(root, epochs=1)
    rep = georep.GeoReplicator(remote, interval=0.05)
    rep.enqueue(step_dir, 1)
    assert rep.drain(timeout=30.0), rep.last_error
    rep.close(0)

    remote_step = os.path.join(remote, "step_0000000001")
    cur = georep.read_cursor(remote_step)
    with open(os.path.join(remote_step, georep.CURSOR_FNAME), "w") as f:
        json.dump({**cur, "epoch": 99}, f)  # claims epochs that never shipped
    code, report = run_fsck(remote_step)
    assert code == 1
    assert report.classes() == {"georep-stale-cursor"}
    code, report = run_fsck(remote_step, repair=True)
    assert code == 0, report.findings
    assert ("georep-stale-cursor", georep.CURSOR_FNAME) in report.repaired
    # Convergent: a second pass is clean, and the shipper re-derives.
    code, _ = run_fsck(remote_step)
    assert code == 0
    rep2 = georep.GeoReplicator(remote, interval=0.05)
    try:
        rep2.enqueue(step_dir, 1)
        assert rep2.drain(timeout=30.0), rep2.last_error
    finally:
        rep2.close(0)
    assert georep.read_cursor(remote_step)["epoch"] == 1


# ------------------------------------------------- foreground isolation


def test_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("TORCHSNAPSHOT_TPU_GEOREP", raising=False)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr._georep is None
    mgr.save(0, {"app": _state(0)})
    mgr.close()


def test_backlog_is_bounded_drop_oldest(replicated, monkeypatch):
    """A dead remote tier means a BOUNDED backlog: oldest pending steps
    drop (a newer committed base supersedes them), counted loudly."""
    root, remote = replicated
    del root, remote
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_GEOREP_BACKLOG", "2")
    rep = georep.GeoReplicator("/nonexistent/remote", interval=3600.0)
    try:
        for step in range(5):
            rep.enqueue(f"/primary/step_{step:010d}", step)
        assert len(rep._pending) == 2
        assert sorted(rep._pending) == [3, 4]  # newest survive
        assert rep.dropped_steps == 3
        assert rep.lag_s() >= 0.0
    finally:
        rep.close(0)


def test_enqueue_coalesces_keeping_oldest_timestamp(replicated):
    root, remote = replicated
    del root, remote
    rep = georep.GeoReplicator("/nonexistent/remote", interval=3600.0)
    try:
        rep.enqueue("/primary/step_0000000001", 1)
        _, ts0 = rep._pending[1]
        rep.enqueue("/primary/step_0000000001", 1)  # another epoch commit
        assert rep._pending[1][1] == ts0  # lag measures the OLDEST state
        assert len(rep._pending) == 1
    finally:
        rep.close(0)


def test_commit_that_races_a_sync_is_shipped_not_retired(replicated, monkeypatch):
    """An epoch committed while its step is being synced keeps the step
    pending (the coalesced entry keeps its OLD timestamp, so the
    timestamp cannot show the race): drain may not report idle until a
    second sync has covered it."""
    root, remote = replicated
    del root, remote
    rep = georep.GeoReplicator("/nonexistent/remote", interval=3600.0)
    syncs = []

    def sync_step(path, step):
        syncs.append(step)
        if len(syncs) == 1:
            rep.enqueue(path, step)  # the racing commit
        return {"epoch": len(syncs)}

    monkeypatch.setattr(rep, "_sync_step", sync_step)
    monkeypatch.setattr(rep, "_publish_gauges", lambda: None)
    try:
        rep.enqueue("/primary/step_0000000001", 1)
        assert rep.drain(timeout=30.0)
        assert syncs == [1, 1]
        assert not rep._pending and not rep._tickets
    finally:
        rep.close(0)


def test_preemption_consume_drains_the_shipper(replicated):
    """The grace window: consume() runs the registered bounded drain so
    the final flushed epoch reaches the remote tier before teardown."""
    from torchsnapshot_tpu.preemption import PreemptionWatcher

    watcher = PreemptionWatcher.__new__(PreemptionWatcher)
    watcher._consume_hooks = []
    watcher._consumed = False
    watcher._pending = []
    drained = []
    watcher.add_consume_hook(lambda: drained.append(True))
    watcher.add_consume_hook(lambda: (_ for _ in ()).throw(RuntimeError()))
    watcher._log_pending = lambda: None
    watcher.consume()
    assert drained == [True] and watcher.consumed  # isolated + fired
