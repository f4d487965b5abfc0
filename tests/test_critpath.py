"""Critical-path attribution engine (telemetry/critpath.py) and the
``explain`` CLI.

Covers the ISSUE 8 acceptance criteria directly: a throttled
(storage-bound) take must be named storage-write-bound with the injected
bandwidth recovered within 25%, an unthrottled tmpfs take must name a
pipeline category instead (both via the `explain` exit code a bench can
assert), and a w2 take's fleet-merged histograms must equal the
bucket-wise sum of the rank histograms.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.cli import main
from torchsnapshot_tpu.telemetry import critpath
from torchsnapshot_tpu.test_utils import run_with_subprocesses


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.refresh_from_env()
    telemetry.set_enabled(False)
    telemetry.reset()
    yield
    telemetry.set_enabled(False)
    telemetry.reset()


# -------------------------------------------------------- interval math


def test_union_seconds_merges_overlaps():
    assert critpath._union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert critpath._union_seconds([]) == 0.0
    # Clipping to a window.
    assert critpath._union_seconds([(0, 10)], lo=2, hi=5) == pytest.approx(3.0)


def test_subtract_intervals():
    out = critpath._subtract_intervals([(0, 10)], [(2, 3), (5, 7)])
    assert out == [(0, 2), (3, 5), (7, 10)]
    # Full cover -> nothing left; no cover -> identity.
    assert critpath._subtract_intervals([(1, 2)], [(0, 5)]) == []
    assert critpath._subtract_intervals([(1, 2)], []) == [(1, 2)]


# -------------------------------------------------- per-rank attribution


def _span(name, ts, dur, cat="pipeline", **args):
    ev = {"ph": "span", "name": name, "ts": ts, "dur": dur, "cat": cat}
    if args:
        ev["args"] = args
    return ev


def test_build_attribution_categories_and_idle():
    events = [
        _span("stage_hash", 0.0, 1.0),
        _span("storage_write", 1.0, 2.0),
        _span("storage_write", 2.0, 2.0),  # overlaps: union, not sum
    ]
    attr = critpath.build_attribution(events, wall_s=5.0, rank=3)
    assert attr["rank"] == 3
    assert attr["categories"]["hash"] == pytest.approx(1.0)
    assert attr["categories"]["storage_write"] == pytest.approx(3.0)
    assert attr["categories"]["sched_idle"] == pytest.approx(1.0)


def test_build_attribution_fused_residual():
    """A fused stream_write window covered 60% by staging spans must
    attribute only the residual 40% to storage — the whole-window
    mapping would call every streamed tmpfs save storage-bound."""
    events = [
        _span("stream_write", 0.0, 10.0),
        _span("sub_chunk_stage", 0.0, 3.0),
        _span("sub_chunk_stage", 4.0, 3.0),
    ]
    attr = critpath.build_attribution(events, wall_s=10.0)
    assert attr["categories"]["stage_copy"] == pytest.approx(6.0)
    assert attr["categories"]["storage_write"] == pytest.approx(4.0)


def test_build_attribution_segments_cut_at_collectives():
    events = [
        _span("stage_hash", 0.0, 2.0),
        _span(
            "collective_wait", 2.0, 1.0, cat="collective",
            ns="pgw/ns/7", cseq=1, kind="all_gather",
        ),
        _span("storage_write", 3.0, 4.0),
    ]
    attr = critpath.build_attribution(events, wall_s=7.0)
    segs = attr["segments"]
    assert [s["key"] for s in segs] == ["pgw/ns/7#1", "tail"]
    assert segs[0]["dur_s"] == pytest.approx(2.0)
    assert segs[0]["wait_s"] == pytest.approx(1.0)
    assert segs[0]["categories"]["hash"] == pytest.approx(2.0)
    assert segs[1]["categories"]["storage_write"] == pytest.approx(4.0)


def test_build_attribution_empty_events():
    attr = critpath.build_attribution([], wall_s=1.5)
    assert attr["wall_s"] == 1.5
    assert attr["categories"] == {"sched_idle": 1.5}
    assert attr["segments"] == []


#: The spans that split a lump where the work happens. Neither table of
#: critpath.py names them, so they attribute through the span around them.
_SPLIT_SPANS = ("stage_dtoh", "stage_hostcopy", "stage_crc", "consume_assemble")


@pytest.mark.parametrize("op", ["take", "restore"])
def test_build_attribution_ignores_the_spans_that_split_a_lump(
    tmp_path, monkeypatch, op
):
    """The attribution of a real take (every leaf staged under
    stage_hash) and of a real streamed restore (the large leaf assembled
    on the device) is the same, category for category, with the child
    spans in the event list and with them taken out."""
    import jax.numpy as jnp

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_WRITES", "never")
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "always")
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(128 << 10))
    telemetry.set_enabled(True)
    w = jnp.arange(400_000, dtype=jnp.float32).reshape(400, 1000)
    snap = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": StateDict(w=w, b=jnp.ones((8, 8)))}
    ).wait()
    if op == "restore":
        snap.restore({"m": StateDict(w=jnp.zeros_like(w), b=jnp.zeros((8, 8)))})
    events = telemetry.events()
    names = {e["name"] for e in events if e["ph"] == "span"}
    want = (
        {"stage_hash", "stage_dtoh", "stage_crc"}
        if op == "take"
        else {"stream_read", "consume_assemble"}
    )
    assert want <= names
    without = [e for e in events if e.get("name") not in _SPLIT_SPANS]
    assert len(without) < len(events)
    wall = telemetry.last_summary()["wall_s"]
    a = critpath.build_attribution(events, wall_s=wall)
    b = critpath.build_attribution(without, wall_s=wall)
    assert a["categories"] == b["categories"]
    assert a["segments"] == b["segments"]
    assert not set(_SPLIT_SPANS) & (
        set(critpath.SPAN_CATEGORIES) | set(critpath.FUSED_SPANS)
    )


# ------------------------------------------------------- fleet stitching


def _rank_attr(rank, wall, segs):
    return {
        "rank": rank,
        "wall_s": wall,
        "categories": {},
        "segments": [
            {
                "key": k,
                "kind": "all_gather",
                "dur_s": d,
                "wait_s": w,
                "categories": cats,
            }
            for (k, d, w, cats) in segs
        ],
    }


def test_merge_attributions_picks_gating_rank_per_segment():
    """Rank 1 gates segment A (peers waited on it); rank 0 gates B. The
    critical path must name each gating rank and sum ITS categories —
    the waiting rank's collective_wait never enters the fleet view."""
    a0 = _rank_attr(0, 10.0, [
        ("ns#1", 1.0, 4.0, {"stage_copy": 1.0}),
        ("ns#2", 5.0, 0.0, {"storage_write": 5.0}),
    ])
    a1 = _rank_attr(1, 10.0, [
        ("ns#1", 5.0, 0.0, {"storage_write": 5.0}),
        ("ns#2", 1.0, 4.0, {"decode": 1.0}),
    ])
    a0["categories"] = {"storage_write": 6.0}
    a1["categories"] = {"storage_write": 5.0}
    fleet = critpath.merge_attributions([a0, a1])
    path = fleet["critical_path"]
    assert [(s["key"], s["rank"]) for s in path] == [("ns#1", 1), ("ns#2", 0)]
    assert fleet["critical_wall_s"] == pytest.approx(10.0)
    assert fleet["categories"]["storage_write"] == pytest.approx(10.0)
    assert fleet["binding"]["category"] == "storage_write"
    assert fleet["binding"]["class"] == "storage"
    assert "collective_wait" not in fleet["categories"]


def test_merge_attributions_fallback_without_shared_segments():
    a0 = {"rank": 0, "wall_s": 2.0,
          "categories": {"stage_copy": 1.8}, "segments": []}
    fleet = critpath.merge_attributions([a0, None])
    assert fleet["reporting"] == 1
    assert fleet["binding"]["category"] == "stage_copy"
    assert fleet["binding"]["class"] == "pipeline"
    assert critpath.merge_attributions([None, None]) is None


def test_merge_attributions_rate_from_aggregate():
    a0 = {"rank": 0, "wall_s": 2.0,
          "categories": {"storage_write": 2.0}, "segments": []}
    fleet = critpath.merge_attributions(
        [a0], aggregate={"bytes_written": 4e9}
    )
    assert fleet["binding"]["gbps"] == pytest.approx(2.0)


def test_live_binding():
    assert critpath.live_binding([]) is None
    events = [
        _span("storage_write", 0.0, 3.0),
        _span("stage_hash", 0.0, 1.0),
    ]
    assert critpath.live_binding(events) == "storage_write"


def test_binding_exit_code_and_verdict_threshold():
    assert critpath.binding_exit_code(
        {"fleet": {"verdict": "storage-bound"}}
    ) == 1
    assert critpath.binding_exit_code(
        {"fleet": {"verdict": "pipeline-bound"}}
    ) == 0
    # A storage category that is merely the LARGEST slice (not the
    # majority of the critical path) stays pipeline-bound: a fast local
    # save's pwrite at 30% of wall must not read as "buy faster disks".
    minority = {
        "rank": 0, "wall_s": 10.0, "segments": [],
        "categories": {"storage_write": 3.0, "stage_copy": 2.0,
                       "sched_idle": 5.0},
    }
    fleet = critpath.merge_attributions([minority])
    assert fleet["binding"]["category"] == "sched_idle"
    assert fleet["verdict"] == "pipeline-bound"
    majority = {
        "rank": 0, "wall_s": 10.0, "segments": [],
        "categories": {"storage_write": 8.0, "stage_copy": 2.0},
    }
    fleet = critpath.merge_attributions([majority])
    assert fleet["verdict"] == "storage-bound"


# ----------------------------------------------------------- e2e verdicts


_PAYLOAD_ELEMS = 12_000_000  # 48 MB fp32


def _throttled_fs(bandwidth_bps: float):
    """An FSStoragePlugin whose writes share one rate gate — models a
    storage tier with a hard bandwidth ceiling (each write's TOTAL
    service time is nbytes/bandwidth, so the injected rate is exact).
    Buffered-only so the write path exercises the plain storage_write
    spans."""
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    class ThrottledFS(FSStoragePlugin):
        supports_streaming = False

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self._gate = asyncio.Lock()

        async def write(self, write_io):
            nbytes = memoryview(write_io.buf).nbytes
            async with self._gate:
                loop = asyncio.get_running_loop()
                t0 = loop.time()
                await super().write(write_io)
                await asyncio.sleep(
                    max(0.0, nbytes / bandwidth_bps - (loop.time() - t0))
                )

    return ThrottledFS


def test_throttled_take_is_storage_bound(tmp_path, monkeypatch, capsys):
    """Acceptance: on a bandwidth-throttled take, `explain` names
    storage write as the binding category, recovers the injected
    bandwidth within 25%, and exits 1 (storage-bound)."""
    bandwidth = 40e6  # 40 MB/s
    monkeypatch.setattr(
        "torchsnapshot_tpu.storage_plugins.fs.FSStoragePlugin",
        _throttled_fs(bandwidth),
    )
    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    state = {
        "m": StateDict(
            w=np.random.default_rng(0)
            .standard_normal(_PAYLOAD_ELEMS)
            .astype(np.float32)
        )
    }
    Snapshot.take(snap, state)
    attr = telemetry.last_attribution()
    assert attr is not None
    binding = attr["binding"]
    assert binding["category"] == "storage_write"
    assert binding["class"] == "storage"
    assert binding["gbps"] == pytest.approx(bandwidth / 1e9, rel=0.25)
    # The persisted record drives the CLI to the same verdict.
    assert os.path.isfile(os.path.join(snap, critpath.ATTRIBUTION_FNAME))
    assert main(["explain", snap]) == 1
    out = capsys.readouterr().out
    assert "storage_write" in out
    assert "storage-write-bound" in out


def test_tmpfs_take_is_pipeline_bound(tmp_path, capsys):
    """Acceptance: an unthrottled local take whose pipeline does real
    host-side work (zlib staging — the deterministic stand-in for the
    DtoH/serialize/compress pipeline cost a TPU save pays) names a
    PIPELINE category and `explain` exits 0, the ROADMAP-claim
    assertion. Storage is tmpfs at memcpy speed, so any storage-bound
    verdict here would be an attribution bug, not a slow disk."""
    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    state = {
        "m": StateDict(
            w=np.random.default_rng(0)
            .standard_normal(_PAYLOAD_ELEMS)
            .astype(np.float32)
        )
    }
    Snapshot.take(snap, state, compression="zlib:1")
    attr = telemetry.last_attribution()
    assert attr is not None
    assert attr["verdict"] == "pipeline-bound"
    assert main(["explain", snap]) == 0
    assert "binding:" in capsys.readouterr().out


def test_explain_falls_back_to_telemetry_document(tmp_path, capsys):
    """Snapshots without .snapshot_critpath (rank-0 persist failure,
    older format) re-derive the verdict from the telemetry document's
    per-rank attribution blobs."""
    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    Snapshot.take(
        snap, {"m": StateDict(w=np.arange(100_000, dtype=np.float32))}
    )
    os.remove(os.path.join(snap, critpath.ATTRIBUTION_FNAME))
    code = main(["explain", snap])
    assert code in (0, 1)
    assert "binding:" in capsys.readouterr().out


def test_explain_missing_attribution_exits_2(tmp_path, capsys):
    # A committed snapshot taken with telemetry OFF has no attribution.
    snap = str(tmp_path / "snap")
    Snapshot.take(snap, {"m": StateDict(w=np.arange(10, dtype=np.float32))})
    assert main(["explain", snap]) == 2
    assert "no critical-path attribution" in capsys.readouterr().err


def test_explain_json_dump(tmp_path, capsys):
    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    Snapshot.take(
        snap, {"m": StateDict(w=np.arange(100_000, dtype=np.float32))}
    )
    main(["explain", snap, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 1
    assert doc["fleet"]["binding"]["category"]


def test_governor_elections_ride_summary_and_critpath_doc(tmp_path):
    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    Snapshot.take(
        snap, {"m": StateDict(w=np.arange(100_000, dtype=np.float32))}
    )
    summary = telemetry.last_summary()
    sites = {row.get("site") for row in summary.get("governor") or []}
    assert "write" in sites
    doc = json.loads(
        open(os.path.join(snap, critpath.ATTRIBUTION_FNAME)).read()
    )
    assert any(r.get("site") == "write" for r in doc.get("governor") or [])


def test_fsck_exempts_critpath_record(tmp_path):
    from torchsnapshot_tpu.cli import run_fsck

    telemetry.set_enabled(True)
    snap = str(tmp_path / "snap")
    Snapshot.take(
        snap, {"m": StateDict(w=np.arange(10_000, dtype=np.float32))}
    )
    assert os.path.isfile(os.path.join(snap, critpath.ATTRIBUTION_FNAME))
    code, report = run_fsck(snap, echo=lambda *a, **k: None)
    assert code == 0, report.findings


# ---------------------------------------------------------- distributed


def _critpath_take_worker(rank: int, world_size: int, snap_path: str):
    import numpy as np  # noqa: F811

    from torchsnapshot_tpu import Snapshot, StateDict, telemetry  # noqa: F811

    telemetry.set_enabled(True)
    state = {
        "local": StateDict(
            data=np.full((65_536,), rank, dtype=np.float32)
        ),
    }
    Snapshot.take(snap_path, state)
    summary = telemetry.last_summary()
    return {
        "histograms": summary.get("histograms") or {},
        "attribution": telemetry.last_attribution(),
    }


@pytest.mark.multiprocess
def test_w2_histograms_merge_bucketwise_and_critpath_stitches(tmp_path):
    """Acceptance: fleet-merged histograms sum bucket-wise across a w2
    take, and the persisted attribution stitched at least one shared
    collective segment."""
    snap_path = str(tmp_path / "snap")
    results = run_with_subprocesses(_critpath_take_worker, 2, snap_path)
    doc = json.loads(
        (tmp_path / "snap" / ".snapshot_telemetry").read_text()
    )
    fleet_hist = (doc["fleet"] or {}).get("histograms") or {}
    assert fleet_hist, "fleet view carries no histograms"
    # Bucket-wise: every (name, key) family in the fleet view equals the
    # element-wise sum of the per-rank contributions.
    for name, by_key in fleet_hist.items():
        for key, merged in by_key.items():
            per_rank = [
                (results[r]["histograms"].get(name) or {}).get(key)
                for r in results
            ]
            contributing = [h for h in per_rank if h]
            assert contributing, (name, key)
            assert merged["count"] == sum(h["count"] for h in contributing)
            width = max(len(h["counts"]) for h in contributing)
            summed = [0] * width
            for h in contributing:
                for i, n in enumerate(h["counts"]):
                    summed[i] += n
            assert merged["counts"] == summed, (name, key)
    # The stitched critical path exists and every segment names a rank.
    cp_doc = json.loads(
        (tmp_path / "snap" / ".snapshot_critpath").read_text()
    )
    fleet = cp_doc["fleet"]
    assert fleet["reporting"] == 2
    assert fleet["critical_path"], "no shared collective segments stitched"
    assert all(s["rank"] in (0, 1) for s in fleet["critical_path"])
    # Both ranks computed the same merged view from the gather.
    attrs = [results[r]["attribution"] for r in results]
    assert attrs[0] == attrs[1]
