"""chip_smoke.py's contract, as far as a CPU can show it: the parent stays
off JAX, no TPU means a non-zero exit and no result line, a failed or
overrunning phase fails the run, and the CPU dry run (explicit argument,
labelled cpu) goes through train -> kill -> resume bit-exact.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _env(tmp_path, **extra):
    env = dict(os.environ)
    # The cache where the caller says, and nowhere else: not in the checkout.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    env.update(extra)
    return env


def test_parent_imports_neither_jax_nor_the_package():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', "
        "'jaxlib', 'torchsnapshot_tpu'))]; assert not bad, bad" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    """No dry-run argument: the cpu is never inferred from the environment
    (here JAX_PLATFORMS=cpu), it is a failure."""
    r = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")],
        env=_env(tmp_path, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == chip_smoke.EXIT_NO_ACCELERATOR
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_cpu_dry_run_trains_is_killed_and_resumes_bit_exact(tmp_path):
    out = tmp_path / "out"
    r = subprocess.run(
        [sys.executable, SMOKE, "--cpu-dry-run", "1", "--out", str(out)],
        env=_env(tmp_path), capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    report, verdict = map(json.loads, r.stdout.splitlines())
    assert report == json.loads((out / "report.json").read_text())
    # The last line: exactly these keys, nothing else.
    assert verdict == {"ok": True, "device": report["device"]}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert report["ok"] is True and report["dry_run"] is True
    assert report["device"]["platform"] == "cpu"  # labelled, never a device number

    train, resume = report["phases"]["train"], report["phases"]["resume"]
    assert train["returncode"] == -signal.SIGKILL  # the kill
    assert train["torn"] and resume["torn_dirs"] == ["step_0000000006"]
    assert resume["latest_step"] == resume["restored_step"] == chip_smoke.RESUME_FROM
    assert resume["leaves_bit_exact"] == resume["leaves"] > 0
    assert resume["losses"] == resume["losses_expected"]  # equality, same layout
    assert set(resume["losses"]) == {"5", "6"}
    assert len(train["saves"]) == 3 and all("blocked_s" in s for s in train["saves"].values())

    # A new process compiling the same donated train step finds it cached,
    # and the cache is where the environment put it, nowhere else.
    assert resume["step_compile"]["cache_hit"] is True
    assert resume["compile_cache"]["dir"] == str(tmp_path / "cc")
    assert os.listdir(tmp_path / "cc")
    # Interpret mode is reported, not hidden (it fails the run on a chip).
    assert report["phases"]["kernels"]["kernels"]["flash"]["interpret"] is True
    assert report["phases"]["kernels"]["native"]["built_from_source"] is True
    # The snapshot roots are gone.
    assert not [d for d in os.listdir(report["snapshot_root"]) if d.startswith("chip_smoke_")]


def test_a_phase_that_overruns_its_deadline_fails(tmp_path):
    out = tmp_path / "out"
    out.mkdir()
    result = chip_smoke._run_phase(
        "kernels", ["--out", str(out), "--work", str(tmp_path), "--cpu-dry-run", "1"],
        _env(tmp_path, JAX_PLATFORMS="cpu"), str(out), deadline_s=0.2,
    )
    assert result["ok"] is False and result["timed_out"] is True
    assert result["returncode"] == -signal.SIGKILL


def test_a_phase_that_crashes_fails(tmp_path):
    """resume with nothing to resume from: the child dies without a result."""
    out = tmp_path / "out"
    out.mkdir()
    result = chip_smoke._run_phase(
        "resume", ["--out", str(out), "--work", str(tmp_path), "--cpu-dry-run", "1"],
        _env(tmp_path, JAX_PLATFORMS="cpu"), str(out), deadline_s=120,
    )
    assert result["ok"] is False and result["returncode"] not in (0, None)
    assert "FileNotFoundError" in (out / "resume.log").read_text()


def test_a_failed_phase_fails_the_run_and_prints_no_result(tmp_path, monkeypatch, capsys):
    """The parent's own logic: later phases are not started, the exit code
    is non-zero, stdout stays empty, the report says which phase."""
    started = []

    def fake_phase(name, argv, env, out, deadline_s):
        started.append(name)
        ok = name != "train"
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        rc = 0 if name == "kernels" else 1
        return {"ok": ok, "device": device, "returncode": rc, "wall_s": 0.0}

    monkeypatch.setattr(chip_smoke, "_run_phase", fake_phase)
    assert chip_smoke.main(["--out", str(tmp_path / "out")]) == 1
    assert started == ["kernels", "train"]
    assert capsys.readouterr().out == ""
    report = json.loads((tmp_path / "out" / "report.json").read_text())
    assert report["ok"] is False and report["phases"]["train"]["ok"] is False


def test_trainer_that_exits_instead_of_being_killed_fails(tmp_path, monkeypatch):
    def fake_phase(name, argv, env, out, deadline_s):
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        return {"ok": True, "device": device, "returncode": 0, "wall_s": 0.0}

    monkeypatch.setattr(chip_smoke, "_run_phase", fake_phase)
    assert chip_smoke.main(["--out", str(tmp_path / "out")]) == 1


@pytest.mark.slow
def test_cpu_dry_run_on_four_devices_resumes_under_another_layout(tmp_path):
    out = tmp_path / "out"
    r = subprocess.run(
        [sys.executable, SMOKE, "--cpu-dry-run", "4", "--out", str(out)],
        env=_env(tmp_path), capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    report, verdict = map(json.loads, r.stdout.splitlines())
    assert verdict == {"ok": True, "device": report["device"]}
    mesh = report["phases"]["resume_mesh"]
    assert report["phases"]["train_mesh"]["mesh"] == chip_smoke.MESH_TRAIN
    assert mesh["mesh"] == chip_smoke.MESH_RESUME
    assert mesh["leaves_bit_exact"] == mesh["leaves"] > 0
    assert mesh["loss_rtol"] == chip_smoke.RESHARD_LOSS_RTOL
