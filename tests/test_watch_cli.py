"""``watch`` CLI + the live health plane (telemetry/health.py).

The acceptance drill: watch a live throttled w2 take end to end from a
separate process — per-rank phase/bytes render in flight, an
injected-delay straggler is flagged STALLED before any timeout fires,
and the watcher rides out a store-leader SIGKILL mid-take (the PR 6
failover schedule) without dying.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import StateDict
from torchsnapshot_tpu.telemetry import health


# ------------------------------------------------------------ unit layer


def test_tracker_flags_frozen_progress_not_frozen_seq():
    tracker = health.FleetTracker(stall_s=0.2)
    rec = {"seq": 1, "op": "take", "phase": "stage", "written_bytes": 100}
    tracker.observe({0: dict(rec)})
    time.sleep(0.25)
    # seq advances (the publisher is alive) but progress is frozen.
    rec["seq"] = 7
    ages = tracker.observe({0: dict(rec)})
    assert tracker.stalled(ages)[0] is True
    # Progress moves: the stall clears.
    rec["written_bytes"] = 200
    ages = tracker.observe({0: dict(rec)})
    assert tracker.stalled(ages)[0] is False


def test_tracker_drops_vanished_ranks():
    tracker = health.FleetTracker(stall_s=10.0)
    tracker.observe({0: {"seq": 1}, 1: {"seq": 1}})
    ages = tracker.observe({0: {"seq": 2}})
    assert set(ages) == {0}


def test_render_fleet_shows_phase_bytes_and_stall():
    fleet = {
        0: {"op": "take", "phase": "stage", "staged_bytes": 1 << 20,
            "written_bytes": 1 << 19, "seq": 3, "wall_s": 2.0},
        1: {"op": "take", "phase": "begin", "seq": 2, "wall_s": 2.5},
    }
    out = health.render_fleet(fleet, {0: 0.1, 1: 9.0}, stall_s=5.0)
    assert "stage" in out
    assert "1.0MiB" in out
    assert "STALLED" in out
    assert "stalled rank(s): 1" in out
    assert "skew" in out


def test_render_fleet_empty():
    assert "no in-flight" in health.render_fleet({}, {}, 5.0)


def test_render_fleet_shows_binding_resource():
    """ISSUE 8 satellite: a heartbeat carrying the binding-resource
    hint renders it, so a STALLED row says WHAT the rank is stuck on;
    ranks without one show a placeholder."""
    fleet = {
        0: {"op": "take", "phase": "stage", "written_bytes": 1 << 20,
            "seq": 3, "wall_s": 2.0, "binding": "storage_write"},
        1: {"op": "take", "phase": "begin", "seq": 2, "wall_s": 2.1},
    }
    out = health.render_fleet(fleet, {0: 9.0, 1: 0.1}, stall_s=5.0)
    assert "bound on" in out  # the column header
    assert "storage_write" in out
    stalled_row = [ln for ln in out.splitlines() if "STALLED" in ln][0]
    assert "storage_write" in stalled_row


def test_publisher_noop_without_store():
    class _PG:
        pg = None

        def get_world_size(self):
            return 1

        def get_rank(self):
            return 0

    assert health.maybe_start(_PG(), "take", "/tmp/x") is None


def test_heartbeat_cadence_env(monkeypatch):
    monkeypatch.setenv(health.HEARTBEAT_ENV_VAR, "2.5")
    assert health.heartbeat_cadence_s() == 2.5
    monkeypatch.setenv(health.HEARTBEAT_ENV_VAR, "junk")
    assert health.heartbeat_cadence_s() == 1.0
    monkeypatch.delenv(health.HEARTBEAT_ENV_VAR)


def test_publish_and_read_roundtrip_single_store():
    """Publisher -> store -> read_fleet over a real local KV server."""
    from torchsnapshot_tpu.dist_store import TCPStore

    store = TCPStore("127.0.0.1", is_server=True, timeout=10.0)
    try:
        health.clear()
        health.update(phase="stage", written_bytes=123, step=7)
        pub = health.HeartbeatPublisher(
            store, rank=0, op="take", path="/tmp/s", cadence_s=0.05
        ).start()
        time.sleep(0.2)
        fleet = health.read_fleet(store)
        assert 0 in fleet
        rec = fleet[0]
        assert rec["op"] == "take"
        assert rec["phase"] == "stage"
        assert rec["written_bytes"] == 123
        assert rec["step"] == 7
        assert rec["seq"] >= 2
        pub.stop()
        assert health.read_fleet(store) == {}  # key retracted on stop
    finally:
        store.close()
        health.clear()


def test_watch_ticks_bounds_the_run_and_exits_cleanly():
    """``watch --ticks N`` renders N frames of the live fleet, closes its
    store connection and exits 0."""
    from torchsnapshot_tpu.dist_store import TCPStore

    store = TCPStore("127.0.0.1", is_server=True, timeout=10.0)
    pub = None
    try:
        health.clear()
        health.update(phase="stage", written_bytes=123)
        pub = health.HeartbeatPublisher(
            store, rank=0, op="take", path="/tmp/s", cadence_s=0.05
        ).start()
        watch = subprocess.run(
            [
                sys.executable, "-m", "torchsnapshot_tpu", "watch", store.addr,
                "--interval", "0.05", "--ticks", "2",
            ],
            capture_output=True,
            text=True,
            timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    finally:
        if pub is not None:
            pub.stop()
        store.close()
        health.clear()
    assert watch.returncode == 0, watch.stderr[-2000:]
    ticks = [ln for ln in watch.stdout.splitlines() if ln.startswith("--- watch ")]
    assert ticks == [f"--- watch {store.addr} tick {n}" for n in (1, 2)]
    assert "stage" in watch.stdout and "unreachable" not in watch.stdout


# ------------------------------------------------- live w2 watch drill


STORE_KILL_PLAN = "dist_store.serve_op@60=kill;seed=601"


def _throttled_take_worker(rank: int, world_size: int, root: str):
    from torchsnapshot_tpu import Snapshot, faultinject as fi
    from torchsnapshot_tpu.pg_wrapper import get_default_pg

    os.environ["TORCHSNAPSHOT_TPU_HEARTBEAT_S"] = "0.1"
    os.environ["TORCHSNAPSHOT_TPU_PROGRESS_S"] = "0.15"
    # Stall forensics tuned to drill speed: sample fast, call a 0.4 s
    # frozen fingerprint a stall (the injected delay holds it for 1 s).
    os.environ["TORCHSNAPSHOT_TPU_FORENSICS_SAMPLE_S"] = "0.1"
    os.environ["TORCHSNAPSHOT_TPU_FORENSICS_STALL_S"] = "0.4"
    store = get_default_pg().store
    if rank == 0:
        # Publish the coordination-store address for the out-of-band
        # watcher (the launcher allocates the port internally).
        with open(os.path.join(root, "store_addr.txt"), "w") as f:
            f.write(store.bootstrap_addr)
    # Let the watcher connect before the take begins (it must learn the
    # replica set from live responses to survive the leader kill): its
    # first write is the forensic request for rank 1 (--dump 1), which
    # stays in the store until rank 1's watchdog serves it mid-take.
    from torchsnapshot_tpu.telemetry.forensics import FORENSIC_REQ_PREFIX

    store.get(f"{FORENSIC_REQ_PREFIX}1", timeout=120.0)
    rng = np.random.default_rng(100 + rank)
    state = {
        "model": StateDict(
            **{f"p{i}": rng.standard_normal(50_000).astype(np.float32)
               for i in range(4)}
        )
    }
    if rank == 1:
        # The straggler: every fs write stalls 1 s — comfortably past
        # the watcher's 0.5 s stall threshold even under suite load.
        fi.configure("fs.write@1+=delay:1.0")
    try:
        Snapshot.take(os.path.join(root, "cur"), state)
    finally:
        fi.disable()
    return {"failovers": store.failovers}


@pytest.mark.multiprocess
def test_watch_observes_live_take_flags_straggler_and_survives_failover(
    tmp_path,
):
    """watch renders a LIVE throttled w2 take: per-rank phase/bytes
    frames, the delay-injected rank 1 flagged STALLED, and the frames
    keep coming across a store-leader SIGKILL mid-take (one replica
    promotes; the watcher fails over like any client)."""
    from torchsnapshot_tpu.test_utils import run_with_subprocesses

    results = {}
    errors = []

    def drill():
        try:
            results.update(run_with_subprocesses(
                _throttled_take_worker,
                2,
                str(tmp_path),
                timeout=180.0,
                store_replicas=1,
                store_lease_s=0.5,
                external_store=True,
                store_host_plan=STORE_KILL_PLAN,
            ))
        except BaseException as e:  # noqa: B036
            errors.append(e)

    t = threading.Thread(target=drill)
    t.start()
    watch = None
    try:
        # Every wait here ends on an event of the drill, not on the wall
        # clock: under a loaded machine the job's start, the straggler's
        # stall, the forensic answer and the leader kill all come late,
        # and a watcher that stops after a fixed number of frames can
        # stop before them. The drill's own timeout bounds them all.
        addr_file = os.path.join(str(tmp_path), "store_addr.txt")
        while not os.path.exists(addr_file):
            assert t.is_alive(), ("store addr never published", errors)
            time.sleep(0.05)
        addr = open(addr_file).read().strip()
        # Frames go to files: a pipe nobody reads until the end would
        # fill and block the watcher mid-take.
        with open(tmp_path / "watch.out", "w") as stdout, open(tmp_path / "watch.err", "w") as stderr:
            watch = subprocess.Popen(
                [
                    sys.executable, "-m", "torchsnapshot_tpu", "watch", addr,
                    "--interval", "0.15", "--stall", "0.5", "--dump", "1",
                ],
                stdout=stdout,
                stderr=stderr,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
        t.join(timeout=240)
        assert not t.is_alive(), "the drill outlived its own timeout"
        # The watcher rode out the whole take, the leader kill among it.
        alive = watch.poll() is None
    finally:
        if watch is not None:
            watch.terminate()
            watch.wait(timeout=60)
        t.join(timeout=120)
    out, err = (tmp_path / "watch.out").read_text(), (tmp_path / "watch.err").read_text()
    assert alive, err[-2000:]
    assert not errors, errors
    # End-to-end: the take committed, each rank failed over exactly once
    # (the leader kill really happened mid-take).
    assert os.path.exists(tmp_path / "cur" / ".snapshot_metadata")
    for rank, res in results.items():
        assert res["failovers"] == 1, (rank, results)
    # Live per-rank rows: both ranks rendered with the take's op/phase.
    assert "take" in out
    frames = out.split("--- watch")
    rank_frames = [
        fr for fr in frames
        if "\n   0  take" in fr and "\n   1  take" in fr
    ]
    assert rank_frames, out[-3000:]
    # Bytes rendered for at least one in-flight frame (fmt_bytes units).
    assert any(("KiB" in fr or "MiB" in fr) for fr in rank_frames), out[-3000:]
    # The injected-delay straggler was flagged STALLED on its own row.
    # (Rank 0 may legitimately flag too — it freezes at the manifest
    # gather waiting for the crawling rank 1; the drill's requirement is
    # that the straggler is flagged, not that it is flagged alone.)
    def rank1_stalled(fr: str) -> bool:
        return any(
            line.lstrip().startswith("1 ") and "STALLED" in line
            for line in fr.splitlines()
        )

    assert any(rank1_stalled(fr) for fr in frames), out[-4000:]
    # Survival across the leader kill (which provably happened mid-take:
    # failovers==1 on every rank): either the watcher's own client
    # adopted the promoted leader (its store logs say so), or a degraded
    # unreachable frame was followed by a later successful one. (After
    # the JOB exits, the whole tier legitimately goes down — trailing
    # unreachable frames are the truthful render, not a failure.)
    adopted = "adopted leader" in err
    success_idx = [
        i for i, fr in enumerate(frames)
        if "take" in fr or "no in-flight operation" in fr
    ]
    unreachable_idx = [
        i for i, fr in enumerate(frames) if "store unreachable" in fr
    ]
    recovered = bool(
        unreachable_idx
        and success_idx
        and max(success_idx) > min(unreachable_idx)
    )
    assert adopted or recovered or not unreachable_idx or (
        min(unreachable_idx) > max(success_idx)
    ), err[-2000:]

    # --- ISSUE 13: stall forensics rode the same drill ---------------
    from torchsnapshot_tpu.telemetry import forensics

    # The stalled rank self-dumped its stacks (frozen-progress trigger:
    # the 1 s injected delay holds the fingerprint past the 0.4 s
    # window), and at least one dump catches a thread executing under
    # the injected site's category — the delay is wired at fs.write, so
    # the honest attribution is storage_write (faultinject's own frames
    # are observer-excluded).
    stacks = forensics.load_stack_dumps(str(tmp_path / "cur"))
    assert stacks.get(1), "stalled rank 1 never self-dumped its stacks"
    assert any(
        rec.get("trigger") in ("frozen-progress", "remote")
        for rec in stacks[1]
    ), [r.get("trigger") for r in stacks[1]]
    assert any(
        t.get("category") == "storage_write" and "fs.py" in (t.get("leaf") or "")
        for rec in stacks[1]
        for t in rec.get("threads", [])
    ), [t.get("leaf") for rec in stacks[1] for t in rec.get("threads", [])]
    # The remote request (--dump 1) round-tripped: the watchdog answered
    # on the store (surviving the leader kill like every client) and the
    # watcher rendered the wedged frame inline on rank 1's row.
    wedged_rows = [
        line for fr in frames for line in fr.splitlines()
        if line.lstrip().startswith("1 ") and "wedged" in line
    ]
    assert wedged_rows, out[-4000:]
    # Blackbox reads the stacks-only wreck (the take COMMITTED — no ring
    # dumps) and names the wedge: consecutive same-frame dumps earn a
    # WEDGE finding, exit 1.
    blackbox = subprocess.run(
        [sys.executable, "-m", "torchsnapshot_tpu", "blackbox",
         str(tmp_path / "cur")],
        capture_output=True,
        text=True,
        timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert blackbox.returncode == 1, (blackbox.stdout, blackbox.stderr)
    assert "WEDGE" in blackbox.stdout, blackbox.stdout
    assert "storage_write" in blackbox.stdout, blackbox.stdout
