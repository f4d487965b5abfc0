"""Tests of the chip benchmark's harness. CPU only: ``pytest benchmarks/chip``.

What runs the program runs it through ``run.py --dry-run 1`` in a child
process (toy widths, CPU devices, every metric prefixed ``dryrun.``); nothing
here produces or asserts a device number.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from lib import spans, spec, stats, xtrace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def _run(args, cwd=REPO, env=None, timeout=300):
    cmd = [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"), *args]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------- contract


def test_benchmark_json_meets_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["benchmarks/chip"] and 1 <= len(bench["command"]) <= 32
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    names = [e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for e in bench[g]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert 1 <= len(bench["configs"]) <= 24 and 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(bench["workloads"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(len(e["why"]) <= 200 for e in bench["configs"] + bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in spec.cell_metrics(bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert spec.cell_metrics(bench, w["name"], "per_layer"), w["name"]


def test_every_name_resolves_to_a_file(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(bench, w["name"])
        assert os.path.isfile(os.path.join(HERE, "kinds", cell.kind + ".py"))
        assert os.path.isfile(os.path.join(HERE, "families", cell.config["program"]["family"] + ".py"))
        assert os.path.isfile(os.path.join(HERE, "reference", cell.config["program"]["reference"] + ".py"))
        spec.resolve_cell(bench, w["name"], dry_run=True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        definition = spec.load_metric(m["name"])
        assert hasattr(spec.load_module("readers", definition["reader"]), "read")
    for root, _dirs, files in os.walk(HERE):
        if ".cache" in root or "__pycache__" in root:
            continue
        for f in files:
            assert PLAIN_PATH.match(os.path.relpath(os.path.join(root, f), REPO)), f


def test_config_files_keep_every_published_number(bench):
    """A catalogued model's file holds every number of the catalog's
    ``config`` under the same key, but for what ``reduced`` lists; and
    ``reduced`` names no width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = {}
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = {r["source_url"]: r for r in map(json.loads, f)}
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|expand|experts_per_tok")
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert not any(width.search(k) for k in c["reduced"])
        for key, value in rows.get(c["source"], {}).get("config", {}).items():
            if key not in c["reduced"] and not isinstance(value, (dict, list)):
                assert cfg[key] == value, (c["name"], key)


# ------------------------------------------------------ pure reductions


def test_medians_and_spread():
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5
    assert stats.median([]) is None
    assert stats.spread([10, 10, 10, 10]) == 0
    assert stats.spread([8, 9, 10, 11, 12]) == pytest.approx(0.2)


def test_span_attribution_counts_concurrent_spans_once_and_charges_fused_residue():
    sp = [
        ("sub_chunk_dtoh", 0.0, 1.0), ("sub_chunk_dtoh", 0.5, 1.0),  # union 1.5
        ("stream_write", 0.0, 4.0),  # fused: 4.0 - 1.5 covered by staging = 2.5 of storage_write
        ("storage_read", 10.0, 1.0),  # outside the clip
    ]
    got = spans.attribute(sp, 0.0, 5.0)
    assert got["stage_copy"] == pytest.approx(1.5)
    assert got["storage_write"] == pytest.approx(2.5)
    assert got["sched_idle"] == pytest.approx(1.0) and got["wall"] == 5.0
    assert "storage_read" not in got


def test_span_tables_still_match_the_program():
    """The yardstick's copy and the program's table are compared here, so
    a PR that renames a span in the program is told that the benchmark's
    readers no longer see it (the copy is not updated by such a PR)."""
    sys.path.insert(0, REPO)
    from torchsnapshot_tpu.telemetry import critpath

    assert spans.SPAN_CATEGORIES == critpath.SPAN_CATEGORIES
    assert spans.FUSED_SPANS == critpath.FUSED_SPANS


def test_trace_reduction_on_the_recorded_fixture():
    """Busy time, idle share and operation totals of a trace recorded on a
    v5e (three train steps and the start of a save), against an independent
    sweep over the same events."""
    trace = xtrace.load(os.path.join(HERE, "fixtures", "v5e_steps_then_save.trace.json.gz"))
    got = xtrace.reduce(trace)
    lo, dur = next((s, d) for n, s, d in trace["host"] if n == xtrace.WINDOW)
    hi = lo + dur
    events = trace["devices"]["/device:TPU:0"]["XLA Ops"]
    # independent sweep: sort the edges, count open operations
    edges = sorted([(max(s, lo), 1) for _, s, d in events if s + d > lo and s < hi]
                   + [(min(s + d, hi), -1) for _, s, d in events if s + d > lo and s < hi])
    busy, open_ops, last = 0, 0, lo
    for t, step in edges:
        if open_ops > 0:
            busy += t - last
        open_ops, last = open_ops + step, t
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert got["window_s"] == pytest.approx(dur / 1e9)
    assert got["idle_share"] == pytest.approx(1 - busy / dur, rel=1e-9)
    with open(os.path.join(HERE, "fixtures", "v5e_steps_then_save.expected.json")) as f:
        known = json.load(f)  # written down when the fixture was recorded
    assert got["busy_s"] == pytest.approx(known["busy_s"], rel=1e-6)
    assert got["idle_share"] == pytest.approx(known["idle_share"], rel=1e-6)
    assert dict(map(tuple, got["device_ops"]))[known["top_op"]] == pytest.approx(known["top_op_s"], rel=1e-6)
    # the whole programs on the device cover the operations inside them
    modules = sum(min(s + d, hi) - max(s, lo) for _, s, d in trace["devices"]["/device:TPU:0"]["XLA Modules"]
                  if s + d > lo and s < hi)
    assert busy <= modules and busy > 0.95 * modules
    names = [n for n, _ in got["idle_gaps"]]
    assert names[0] == known["top_gap"] and all(v > 0 for _, v in got["idle_gaps"])
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_op_label_parses_plain_and_tuple_results():
    assert xtrace.op_label("%fusion.2 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2]{0} %p), kind=kLoop") == (
        "fusion.2", "fusion", "bf16[2,8]")
    assert xtrace.op_label("%while.7 = (s32[]{:T(128)}, bf16[2,4]{1,0}) while((s32[]) %t), body=%b") == (
        "while.7", "while", "s32[]")


def test_host_checksum_equals_the_device_checksum():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from lib import model as M

    rng = np.random.default_rng(0)
    for shape, dtype in [((3, 5, 7), np.float32), ((1 << 13,), np.int32), ((), np.int32), ((4, 6), jnp.bfloat16)]:
        a = rng.standard_normal(shape).astype(np.float32).astype(dtype) if shape else np.asarray(7, dtype)
        dev = tuple(int(v) for v in jax.jit(M._leaf_checksum)(jnp.asarray(a)))
        assert dev == M.host_checksum(np.asarray(a), block=1000)
    a = rng.standard_normal((64,)).astype(np.float32)
    b = a.copy()
    b[[3, 9]] = b[[9, 3]]  # a permutation changes the weighted sum
    assert M.host_checksum(a)[0] != M.host_checksum(b)[0] and M.host_checksum(a)[1] == M.host_checksum(b)[1]


# ------------------------------------------------------------ whole runs


def test_no_tpu_and_no_dry_run_fails_and_prints_nothing(bench):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = _run(["--workload", bench["workloads"][0]["name"], "--seed", "0", "--seconds", "1", "--trace", "0"], env=env)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_workload_fails_and_prints_nothing():
    p = _run(["--workload", "nope", "--seed", "0", "--seconds", "1", "--trace", "0", "--dry-run", "1"])
    assert p.returncode != 0 and p.stdout == "" and "nope" in p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["olmo1b.save", "olmo1b.resume", "olmoe1b7b.save", "olmo1b.reshard4"])
def test_dry_run_prints_the_contract_keys_last(bench, cell, trace):
    p = _run(["--workload", cell, "--seed", "3", "--seconds", "4", "--trace", str(trace), "--dry-run", "1"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) - {"dry_run", "breakdown"} == RESULT_KEYS and line["dry_run"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    wanted = {m["name"]: m for m in spec.cell_metrics(bench, cell, spec.GROUPS[trace])}
    assert line["metrics"] and all(k.startswith("dryrun.") for k in line["metrics"])
    for key, value in line["metrics"].items():
        entry = wanted[key[len("dryrun."):]]  # only this cell's metrics of this group
        assert value["unit"] == entry["unit"] and isinstance(value["value"], float)
    if trace == 0:  # every end-to-end metric of the cell is there
        assert {k[len("dryrun."):] for k in line["metrics"]} == set(wanted)


def test_a_cell_a_mix_and_a_metric_are_added_as_new_files_only(tmp_path):
    """New files and new BENCHMARK.json entries; no existing file is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in (root / "benchmarks").rglob("*") if p.is_file()}
    chip = root / "benchmarks" / "chip"
    mix = json.loads((chip / "traffic" / "save-cadence.json").read_text())
    mix.update(save_every_steps=5, why="a denser cadence, as data only")
    (chip / "traffic" / "save-dense.json").write_text(json.dumps(mix))
    (chip / "metrics" / "restore_to_step_s.json").write_text(json.dumps(
        {"reader": "median_span", "args": {"of": "restores", "start": "t0", "end": "t_first_step"},
         "what": "restore and first step together"}))
    (chip / "metrics" / "save_calls.json").write_text(json.dumps(
        {"reader": "count_saves", "args": {}, "what": "a reader of its own"}))
    (chip / "readers" / "count_saves.py").write_text(
        "def read(record):\n    return {'value': float(len(record['saves']))} if record['saves'] else None\n")
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "olmo1b.dense", "config": "olmo1b", "traffic": "save-dense", "chips": 1, "why": "t"})
    for name, moves in (("restore_to_step_s", "first_step_ms"), ("save_calls", "step_ms")):
        bench["per_layer"].append({"name": name, "unit": "s", "better": "lower", "source": "host_clock",
                                   "layer": "entry", "moves": moves})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "olmo1b.save" in m.get("workloads", []):
            m["workloads"].append("olmo1b.dense")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "PYTHONPATH": REPO}  # the program itself is not part of the copy
    p = _run(["--workload", "olmo1b.dense", "--seed", "0", "--seconds", "3", "--trace", "1", "--dry-run", "1"],
             cwd=str(root), env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert metrics["dryrun.save_calls"]["value"] >= 2  # the denser mix, read by the new reader
    assert "dryrun.restore_to_step_s" not in metrics and "dryrun.blocked_s" in metrics
    p = _run(["--workload", "olmo1b.resume", "--seed", "0", "--seconds", "3", "--trace", "1", "--dry-run", "1"],
             cwd=str(root), env=env)
    assert "dryrun.restore_to_step_s" in json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    after = {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in before}
    assert after == before


def test_alone_in_a_directory_it_fails_and_prints_nothing(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run(["--workload", "olmo1b.save", "--seed", "0", "--seconds", "1", "--trace", "0", "--dry-run", "1"],
             cwd=str(tmp_path), env=env)
    assert p.returncode != 0 and p.stdout == ""
