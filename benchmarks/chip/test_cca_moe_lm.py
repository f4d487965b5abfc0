"""The CCA / MLP-routed family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, six scanned
layers, CPU devices), the file's keys against the source, the adapter's
mapping and its count of active parameters, and the probe's rehearsal;
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

CELL = "zaya1_8b.save"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


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
        record = json.loads(p.stdout.strip().splitlines()[-2])
        assert got["step_lower_s"]["value"] > 0 and record["setup"]["mesh_train"]["leaves"] == 107
        # no leaf of the toy size passes the chunk limit, so the cut's span does not open
        assert "stage_chunk_s" in wanted and "stage_chunk_s" not in got
        assert any("plain reference" in c["what"] and c["ok"] for c in record["checks"]), record["checks"]


def test_the_cell_reports_what_sdar30b_save_does(bench):
    """One config, one one-chip cell on the mix that exists, appended; its
    name on the list of every metric that lists ``sdar30b.save``
    (``stage_chunk_s`` among them: nine chunked leaves open the span in
    every take), and on no other (``state_leaves`` stays
    ``nemotron2t30b.save``'s alone)."""
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "name": CELL, "config": "zaya1_8b",
                                      "traffic": "save-cadence-12", "chips": 1}
    assert bench["configs"][-1]["name"] == "zaya1_8b" and bench["configs"][-1]["reduced"] == REDUCED
    listed = lambda cell: {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if cell in m.get("workloads", [])}  # noqa: E731
    assert listed(CELL) == listed("sdar30b.save") and "stage_chunk_s" in listed(CELL) and "state_leaves" not in listed(CELL)
    assert all(m["workloads"][-1] == CELL for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", []))


def test_the_file_keeps_every_catalog_key_but_the_three_reduced_ones(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 12 and cell.traffic["kind"] == "save_cadence"
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 8, 32784)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"], cfg["published_vocab_size"]) == (40, 16, 262272)
    assert cfg["experts_held"] == list(range(8)) and cfg["expert_groups"] * 8 == 16
    assert cfg["vocab_size"] * cfg["chips_sharing_a_layer"] == cfg["published_vocab_size"]
    assert {"sources", "qk_mean", "convolutions", "value_shift", "l2_norm_and_temperature", "rotation", "router",
            "residual_scaling", "tying", "init", "dtypes", "seq", "lr", "expert_tile", "head_block"} <= set(cfg["assumed"])
    assert all("arXiv:" in cfg["assumed"][k] for k in ("sources", "qk_mean", "convolutions", "value_shift", "router"))
    assert len(cfg["departures"]) == 4 and "beta_sel" in cfg["departures"][0] and "skip" in cfg["departures"][1]
    run = cfg["program"]
    assert (run["seq"], run["batch"], run["head_block"]) == (8192, 1, 2048) and run["seq"] % 512 == 0
    assert 0 < run["lr"] <= 1e-5 and "14.98" in run["why_batch"] and "17.37" in run["why_batch"]
    for said in ("40 -> 6", "16 -> 8", "262272 -> 32784", "one chip of 8", "two halves", "eight slices", "708 664 940"):
        assert said in cfg["cut"], said
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
        assert row["name"] == "ZAYA1-8B" and row["not_given"] == []
        for key, value in row["config"].items():  # nested groups and lists too: copied whole
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert all(cfg[key] != row["config"][key] for key in REDUCED)
    dry = spec.resolve_cell(bench, CELL, dry_run=True).config
    assert dry["num_hidden_layers"] == 6 and len(dry["experts_held"]) == dry["num_experts"] < dry["published_num_experts"]


def test_the_adapter_maps_the_file_onto_the_programs_config(bench):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "cca_moe_lm")
    cfg = spec.resolve_cell(bench, CELL).config
    c = family.cconfig(cfg)
    assert (c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.head_dim, c.expert_ff) == (2048, 6, 8, 2, 128, 2048)
    assert (c.n_experts, c.held, c.vocab_size, c.router_dim, c.conv_kernels) == (16, tuple(range(8)), 32784, 256, (2, 2))
    assert (c.rope_theta, c.rotary_dim, c.norm_eps, c.head_block) == (5e6, 64, 1e-5, 2048)
    with pytest.raises(ValueError, match="experts_held"):
        family.cconfig({**cfg, "experts_held": [0, 1]})
    with pytest.raises(ValueError, match="top-1"):
        family.cconfig({**cfg, "num_experts_per_tok": 2})
    assert set(family.reference_args(cfg)) == {"n_heads", "n_kv_heads", "held", "norm_eps", "rope_theta", "rotary_dim"}


def test_active_parameters_count_half_an_expert_a_layer_and_the_tied_embedding_once(bench):
    """q, k, v, o, the per-head convolution, the router's four matrices and
    half of one expert's three matrices a layer, and the embedding as the
    head: 142.3 M of the 708.7 M at the cell's cut."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "cca_moe_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.cconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
        got = family.active_params(cfg, flat)
        assert got == c.matmul_params_per_token
        leaves = sum(math.prod(s) for s in flat.values())
    assert got == 142_270_464 and leaves == 708_664_940
    assert len(jax.tree_util.tree_leaves(shapes)) == 107
    over = [s for s in flat.values() if math.prod(s) * 4 > 512 << 20]
    assert over == [(6, 8, 2048, 2048)] * 3  # 768 MiB each: chunked, with both moments nine leaves


def test_the_probe_rehearses_on_the_cpu():
    cmd = [sys.executable, os.path.join(HERE, "tools", "routing_probe_cca.py"), "--workload", CELL, "--seeds", "3", "4", "--dry-run", "1"]
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [line["seed"] for line in lines] == [3, 4] and all(line["dry_run"] and line["even_share"] == 0.5 for line in lines)
    for line in lines:
        assert {"held_share", "max_over_mean", "trips", "tile_fill", "ids_differ"} <= set(line)
        assert all(len(line[k]) == 6 for k in ("held_share", "max_over_mean", "trips", "tile_fill", "ids_differ"))
        assert all(0 < s < 1 for s in line["held_share"]) and max(line["ids_differ"]) < 0.1
