"""bench.py's contract with the driver: the device is never chosen by the
benchmark, one process holds the chip at a time, and a leg that was
started and failed makes the run exit non-zero after printing what it has.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


class FakeResult:
    """Shape of bench._run_in_own_group's result."""

    def __init__(self, returncode=0, stdout="", stderr="", killed=False):
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr
        self.killed = killed


_DMA_OUT = (
    '{"benchmark": "dma_overlap/ceiling", "dtoh_ceiling_mbps": 1500.0, '
    '"host_memcpy_gbps": 8.0}\n'
    '{"benchmark": "dma_overlap/stage", "overlap_ratio": 1.8, '
    '"async_pct_of_ceiling": 160.0}\n'
    '{"benchmark": "dma_overlap/async_take", "step_inflation": 1.02}\n'
    '{"benchmark": "dma_overlap/sync_take", "take_mbps": 1240.0, '
    '"state_mb": 600.0, "take_pct_of_ceiling": 82.7, "bit_exact": true}\n'
)
_DEDUP_OUT = '{"benchmark": "device_dedup/unchanged_resave", "speedup": 3.0}\n'


def _fake_scripts(outputs):
    """_run_in_own_group stand-in keyed by script name; records each call's
    environment."""
    calls = []

    def run(cmd, timeout, env=None):
        script = os.path.basename(cmd[1])
        calls.append((script, env))
        return outputs[script]

    return run, calls


def test_cpu_drill_children_get_platform_cpu_outright(monkeypatch):
    """The parent holds the chip while the subsystem drills run: a child
    that inherited the machine's JAX_PLATFORMS would wait for the chip
    until its deadline (setdefault did exactly that)."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    run, calls = _fake_scripts({"journal_rpo.py": FakeResult(0, "{}\n")})
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    bench._run_script("journal_rpo.py", timeout_s=5)
    assert calls[0][1]["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # the parent's own is untouched


def test_chip_children_inherit_the_environment(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    run, calls = _fake_scripts({"dma_overlap.py": FakeResult(0, "{}\n")})
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    bench._run_script("dma_overlap.py", timeout_s=5, on_chip=True)
    assert calls[0][1]["JAX_PLATFORMS"] == "tpu"


@pytest.mark.parametrize(
    "result", [FakeResult(1, "", "boom"), FakeResult(-9, "", "", killed=True)]
)
def test_failed_or_killed_script_raises(monkeypatch, result):
    monkeypatch.setattr(
        bench, "_run_in_own_group", lambda cmd, timeout, env=None: result
    )
    with pytest.raises(bench.LegFailed):
        bench._run_script("journal_rpo.py", timeout_s=5)


def test_tpu_hw_leg_parses_output(monkeypatch):
    run, calls = _fake_scripts(
        {
            "dma_overlap.py": FakeResult(0, _DMA_OUT),
            "device_dedup.py": FakeResult(0, _DEDUP_OUT),
        }
    )
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    assert bench._tpu_hw_leg() == {
        "dma_overlap_ratio": 1.8,
        "async_step_inflation": 1.02,
        "sync_take_mbps": 1240.0,
        "sync_take_state_mb": 600.0,
        "sync_take_bit_exact": True,
        "ceiling_gbps": 1.5,
        "host_memcpy_gbps": 8.0,
        "achieved_pct": 82.7,
        "async_stage_pct_of_ceiling": 160.0,
        "device_dedup_speedup": 3.0,
    }
    # one process per chip user, in sequence
    assert [c[0] for c in calls] == ["dma_overlap.py", "device_dedup.py"]


def test_tpu_hw_leg_incomplete_output_fails(monkeypatch):
    out = '{"benchmark": "dma_overlap/stage", "overlap_ratio": 1.8}\n'
    run, _ = _fake_scripts({"dma_overlap.py": FakeResult(0, out)})
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    with pytest.raises(bench.LegFailed):
        bench._tpu_hw_leg()


def test_tpu_hw_leg_second_script_failure_fails(monkeypatch):
    run, _ = _fake_scripts(
        {
            "dma_overlap.py": FakeResult(0, _DMA_OUT),
            "device_dedup.py": FakeResult(-9, "", "", killed=True),
        }
    )
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    with pytest.raises(bench.LegFailed):
        bench._tpu_hw_leg()


def test_summary_leg_writes_a_cpu_labelled_artifact(monkeypatch, tmp_path):
    out = (
        '{"benchmark": "journal_rpo/full", "s": 2.0}\n'
        '{"benchmark": "journal_rpo/summary", "rpo_reduction_x": 12.0}\n'
    )
    run, _ = _fake_scripts({"journal_rpo.py": FakeResult(0, out)})
    monkeypatch.setattr(bench, "_run_in_own_group", run)
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    leg = next(leg for leg in bench._SUMMARY_LEGS if leg.key == "journal")
    assert leg.run() == {"rpo_reduction_x": 12.0}
    artifact = json.loads((tmp_path / leg.artifact).read_text())
    assert artifact["platform"] == "cpu"
    assert artifact["env"]["JAX_PLATFORMS"] == "cpu"
    assert [r["benchmark"] for r in artifact["legs"]] == ["journal_rpo/full"]


def test_main_refuses_off_tpu(monkeypatch, capsys):
    """No TPU and the caller did not ask for the cpu: non-zero, nothing
    measured, nothing printed under a TPU metric's name."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)

    def no_chip():
        raise bench.LegFailed("not a TPU backend")

    monkeypatch.setattr(bench, "_tpu_hw_leg", no_chip)
    monkeypatch.setattr(
        bench,
        "_init_backend",
        lambda: {"platform": "cpu", "kind": "cpu", "count": 1},
    )
    assert bench.main() == 2
    assert capsys.readouterr().out == ""


def test_main_exits_nonzero_when_a_started_leg_fails(monkeypatch, tmp_path, capsys):
    """The whole main leg at a toy size on the cpu the CALLER selected
    (conftest sets JAX_PLATFORMS=cpu), then one leg that works and one
    that fails: the record is still printed, names the device, lists the
    failure, and the exit code is non-zero."""

    def bad():
        raise bench.LegFailed("script rc=1")

    monkeypatch.setattr(bench, "_SUBSYSTEM_LEGS", (("good", lambda: {"x": 1}), ("bad", bad)))
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["bench.py", "0.01"])
    monkeypatch.setenv("BENCH_TRIALS", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    try:
        rc = bench.main()
    finally:
        import jax

        # enable_compilation_cache() is process-wide; leave the suite as it was.
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert "bad" in record["failed_legs"]
    assert record["good"] == {"x": 1}
    assert record["device"]["platform"] == "cpu" == record["platform"]
    assert "tpu_hw" not in record  # chip legs never start on a cpu run


def test_run_in_own_group_kills_descendants():
    """A timed-out subprocess's CHILDREN die with it: an orphan would
    compete for the host's cores during the timed saves."""
    code = (
        "import subprocess, sys, time\n"
        "subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)'])\n"
        "print('spawned', flush=True)\n"
        "time.sleep(60)\n"
    )
    r = bench._run_in_own_group([sys.executable, "-c", code], timeout=3)
    assert r.killed
    # The whole group (leader + grandchild) must be gone.
    with pytest.raises(ProcessLookupError):
        os.killpg(r.pgid, 0)


def test_run_in_own_group_plain_success():
    r = bench._run_in_own_group(
        [sys.executable, "-c", "print('ok')"], timeout=30
    )
    assert not r.killed
    assert r.returncode == 0
    assert "ok" in r.stdout


def test_host_calibration_reports_shape():
    cal = bench._host_calibration()
    assert set(cal) >= {"load1", "cpu_count", "memcpy_gbps", "contaminated"}
    assert isinstance(cal["contaminated"], bool)
    assert cal["memcpy_gbps"] > 0
