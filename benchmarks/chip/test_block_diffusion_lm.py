"""The block-diffusion family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, six scanned
layers, CPU devices), the file's keys against the source, the adapter's
count of active parameters, and the cell's own metric ``stage_chunk_s``;
nothing here produces or asserts a device number.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, REPO]

from lib import spec  # noqa: E402

CELL = "sdar30b.save"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_prints_the_contract_keys_last(bench, trace):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL, "--seed", "2147483999",
           "--seconds", "4", "--trace", str(trace), "--dry-run", "1"]
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) - {"dry_run", "breakdown"} == RESULT_KEYS and line["dry_run"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    wanted = {m["name"]: m for m in spec.cell_metrics(bench, CELL, spec.GROUPS[trace])}
    got = {k[len("dryrun."):]: v for k, v in line["metrics"].items()}
    assert got and all(k.startswith("dryrun.") for k in line["metrics"]) and set(got) <= set(wanted)
    assert all(v["unit"] == wanted[k]["unit"] and isinstance(v["value"], float) for k, v in got.items())
    if trace == 0:
        assert set(got) == set(wanted) == {"step_ms", "setup_s"}
    else:
        # no leaf of the toy size passes the chunk limit, so the cut's span
        # does not open and its metric is left out
        assert got["step_lower_s"]["value"] > 0 and json.loads(p.stdout.strip().splitlines()[-2])["setup"]["mesh_train"]["leaves"] == 48
        assert "stage_chunk_s" in wanted and "stage_chunk_s" not in got
        checks = json.loads(p.stdout.strip().splitlines()[-2])["checks"]
        assert any("plain reference" in c["what"] and c["ok"] for c in checks), checks


def test_the_cell_reports_what_the_other_save_cells_do_and_its_own_metric(bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == "stage_chunk_s")
    assert entry == {"name": "stage_chunk_s", "unit": "s", "better": "lower", "source": "program_span",
                     "layer": "stage", "moves": "setup_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] is entry and bench["workloads"][-1]["name"] == CELL  # appended, nothing moved
    listed = lambda cell: {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if cell in m.get("workloads", [])}  # noqa: E731
    # state_leaves stays nemotron2t30b.save's alone: test_hybrid_lm.py holds it to that, and may not be edited here
    assert listed(CELL) == (listed("nemotron2t30b.save") - {"state_leaves"}) | {"stage_chunk_s"}
    assert all(m["workloads"][-1] == CELL for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", []))


def test_stage_chunk_s_is_the_union_of_the_cuts_spans():
    definition = spec.load_metric("stage_chunk_s")
    assert definition["reader"] == "span_stat"
    assert definition["args"] == {"op": "take", "names": ["stage_chunk_cut"], "stat": "union"}
    read = spec.load_module("readers", "span_stat").read
    take = lambda spans: {"op": "take", "lo": 0.0, "hi": 9.0, "spans": spans, "phases": []}  # noqa: E731
    cuts = [("stage_chunk_cut", 1.0, 0.5), ("stage_chunk_cut", 1.25, 0.5), ("stage_dtoh", 2.0, 3.0)]
    got = read({"ops": [take(cuts), take([("stage_chunk_cut", 4.0, 0.25)])]}, **definition["args"])
    assert got == {"value": pytest.approx(0.5), "n": 2}  # median of 0.75 and 0.25
    # a program without the span (the parent of the PR that brought it), or a take with no chunked leaf
    assert read({"ops": [take([("stage_dtoh", 2.0, 3.0)])]}, **definition["args"]) is None


def test_the_file_keeps_every_width_and_cuts_depth_experts_held_and_vocabulary(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 12 and cell.traffic["kind"] == "save_cadence"
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 16, 18992)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"], cfg["published_vocab_size"]) == (48, 128, 151936)
    assert cfg["experts_held"] == list(range(16)) and cfg["chips_sharing_a_layer"] * 16 == 128
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]
    assert {"block_length", "schedule", "shift", "mask_token_id", "qk_norm", "rotation", "init", "dtypes", "seq", "lr"} <= set(cfg["assumed"])
    assert any("load-balancing" in d for d in cfg["departures"])
    run = cfg["program"]
    assert (run["seq"], run["batch"], run["block_length"]) == (4096, 1, 4) and run["seq"] % 512 == 0
    assert 0 < run["lr"] <= 1e-5 and "13.61" in run["why_batch"] and "15.99" in run["why_batch"]
    for said in ("48 -> 6", "128 -> 16", "151936 -> 18992", "one chip of 8"):
        assert said in cfg["cut"], said
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
        assert row["name"] == "SDAR-30B-A3B-Chat" and set(row["not_given"]) == {"block length", "noise schedule"}
        for key, value in row["config"].items():  # nested groups and lists too: copied whole
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    dry = spec.resolve_cell(bench, CELL, dry_run=True).config
    assert dry["num_hidden_layers"] == 6 and len(dry["experts_held"]) == dry["num_experts"] < dry["published_num_experts"]
    assert dry["num_experts_per_tok"] > dry["num_experts"]  # a position cannot put all its eight here


def test_the_adapter_maps_the_file_onto_the_programs_config(bench):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "block_diffusion_lm")
    cfg = spec.resolve_cell(bench, CELL).config
    c = family.bconfig(cfg)
    assert (c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.head_dim, c.expert_ff) == (2048, 6, 32, 4, 128, 768)
    assert (c.n_experts, c.top_k, c.held, c.vocab_size, c.mask_id) == (128, 8, tuple(range(16)), 18992, 18991)
    assert (c.rope_theta, c.norm_eps, c.block_length, c.t_min) == (1e6, 1e-6, 4, 1e-3)
    with pytest.raises(ValueError, match="experts_held"):
        family.bconfig({**cfg, "experts_held": [0, 1]})
    args = family.reference_args(cfg)
    assert set(args) == {"masked", "n_heads", "n_kv_heads", "top_k", "held", "norm_eps", "rope_theta",
                         "block_length", "mask_token_id"}
    # program and reference are compared under one noise, an input like the tokens
    noise = family.reference_noise(cfg)
    assert noise.shape == (2, 4096) and noise.dtype == bool and 0.4 < noise.mean() < 0.6
    assert (noise == args["masked"]).all() and (noise == family.reference_noise(cfg)).all()


def test_active_parameters_count_the_stack_twice_and_the_head_once(bench):
    """A token is two positions of the stack: 2 x (q, k, v, o, the router
    and one expert's three matrices a layer) + the head: 325.2 M of the
    645.6 M at the cell's cut."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "block_diffusion_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.bconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
        got = family.active_params(cfg, flat)
        assert abs(got - c.matmul_params_per_token) <= 8  # rounding of the share, a leaf
        leaves = sum(math.prod(s) for s in flat.values())
    assert got == 325_156_864 and leaves == 645_623_296
    assert len(jax.tree_util.tree_leaves(shapes)) == 48
    over = [s for s in flat.values() if math.prod(s) * 4 > 512 << 20]
    assert sorted(over) == [(6, 16, 768, 2048), (6, 16, 2048, 768), (6, 16, 2048, 768)]  # 576 MiB each: chunked
