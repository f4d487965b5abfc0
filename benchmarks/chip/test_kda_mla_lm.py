"""The KDA / MLA expert family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, the stage's six
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

CELL = "ling3flash.save"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list"]


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
        assert got["step_lower_s"]["value"] > 0 and record["setup"]["mesh_train"]["leaves"] == 188
        assert "stage_chunk_s" not in wanted and "state_leaves" not in wanted
        assert any("plain reference" in c["what"] and c["ok"] for c in record["checks"]), record["checks"]


def test_the_cell_reports_what_zaya1_8b_save_does_but_the_chunk_cut(bench):
    """One config, one one-chip cell on the mix that exists, appended; its
    name on the list of every metric that lists ``zaya1_8b.save`` but
    ``stage_chunk_s`` (no leaf of this state passes the chunk limit: the
    largest is 201 MB) and on no other."""
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "name": CELL, "config": "ling3flash",
                                      "traffic": "save-cadence-12", "chips": 1}
    assert bench["configs"][-1]["name"] == "ling3flash" and bench["configs"][-1]["reduced"] == REDUCED
    listed = lambda cell: {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if cell in m.get("workloads", [])}  # noqa: E731
    assert listed(CELL) == listed("zaya1_8b.save") - {"stage_chunk_s"} and "state_leaves" not in listed(CELL)
    assert all(m["workloads"][-1] == CELL for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", []))


def test_the_file_keeps_every_catalog_key_but_the_reduced_ones(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 12 and cell.traffic["kind"] == "save_cadence"
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 19648)
    assert (cfg["published_num_hidden_layers"], cfg["published_first_k_dense_replace"], cfg["published_num_experts"],
            cfg["published_vocab_size"]) == (42, 2, 512, 157184)
    assert cfg["experts_held"] == list(range(8)) and cfg["layers_held"] == [0, 2, 3, 4, 5]
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"] and cfg["chips_sharing_a_layer"] == 64
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert cfg[key] == [cfg[f"published_{key}"][p] for p in cfg["layers_held"]] == [0] * 5
    assert {"sources", "layer_kinds", "kda_gate", "kda_conv", "kda_heads", "kda_proj_rank", "mla_qk_norm",
            "mla_gate", "rotation", "clamp", "router", "init", "dtypes", "seq", "lr", "num_experts",
            "published_*"} <= set(cfg["assumed"])
    assert all("arXiv:" in cfg["assumed"][k] for k in ("sources", "kda_gate", "router"))
    assert len(cfg["departures"]) == 4 and "multi-token" in cfg["departures"][0] and "vision" in cfg["departures"][1]
    run = cfg["program"]
    assert (run["seq"], run["batch"], run["kda_chunk"]) == (8192, 1, 64)
    assert 0 < run["lr"] <= 1e-5 and "13.55" in run["why_seq"]
    for said in ("42 -> 5", "512 -> 8", "157184 -> 19648", "64 chips", "layers 0 and 2-5", "649 559 040", "31.2 GB", "40 GiB"):
        assert said in cfg["cut"], said
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
        assert row["name"] == "Ling-3.0-flash-VL" and row["not_given"] == []
        for key, value in row["config"].items():  # lists too: copied whole
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert all(cfg[key] != row["config"][key] for key in REDUCED)
    dry = spec.resolve_cell(bench, CELL, dry_run=True).config
    assert dry["num_hidden_layers"] == 5 and len(dry["experts_held"]) == dry["num_experts"] < dry["published_num_experts"]


def test_the_adapter_maps_the_file_onto_the_programs_config(bench):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "kda_mla_lm")
    cfg = spec.resolve_cell(bench, CELL).config
    c = family.kconfig(cfg)
    assert (c.d_model, c.layers, c.layer_group_size, c.first_k_dense, c.n_heads, c.head_dim) == (
        2560, (0, 2, 3, 4, 5), 6, 1, 32, 128)
    assert (c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim, c.rope_theta) == (512, 128, 64, 128, 6e6)
    assert (c.n_experts, c.top_k, c.n_group, c.topk_group, c.routed_scale, c.held) == (512, 8, 8, 4, 2.5, tuple(range(8)))
    assert (c.dense_ff, c.expert_ff, c.shared_ff, c.vocab_size, c.kda_lower_bound, c.conv_kernel) == (
        6144, 768, 768, 19648, -5.0, 4)
    assert c.kinds == ["kda_dense", "kda_moe", "kda_moe", "kda_moe", "mla_moe"]
    with pytest.raises(ValueError, match="experts_held"):
        family.kconfig({**cfg, "experts_held": [0, 1]})
    with pytest.raises(ValueError, match="layers_held"):
        family.kconfig({**cfg, "layers_held": [0, 2]})
    assert set(family.reference_args(cfg)) >= {"layers", "held", "n_group", "topk_group", "expert_limits"}


def test_layers_held_give_four_kda_layers_and_one_mla_layer(bench):
    """Published ids 0 and 2-5: the first group of ``layer_group_size`` 6, a
    whole period (``(p + 1) % 6 == 0`` is MLA: id 5), its second dense layer
    left out (leading dense layers count once), four expert layers after
    the dense one."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "kda_mla_lm")
    c = family.kconfig(spec.resolve_cell(bench, CELL).config)
    mixers = [k.split("_")[0] for k in c.kinds]
    assert mixers.count("kda") == 4 and mixers.count("mla") == 1 and c.kinds[4] == "mla_moe"
    assert sum(k.endswith("_moe") for k in c.kinds) == 4 and c.kinds[0] == "kda_dense"


def test_active_parameters_count_the_held_experts_at_their_even_share(bench):
    """Every projection, the router, the shared expert, the dense MLP and
    the head, and of the eight held experts ``8 / 512`` each a token: 413.2
    M of the 649.6 M at the cell's cut."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "kda_mla_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.kconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
        got = family.active_params(cfg, flat)
        assert got == c.matmul_params_per_token
        leaves = sum(math.prod(s) for s in flat.values())
    assert got == 413_220_864 and leaves == 649_559_040
    assert len(jax.tree_util.tree_leaves(shapes)) == 188
    assert max(math.prod(s) * 4 for s in flat.values()) <= 512 << 20  # no leaf is saved chunked


def test_the_probe_rehearses_on_the_cpu():
    cmd = [sys.executable, os.path.join(HERE, "tools", "routing_probe_kda.py"), "--workload", CELL, "--seeds", "3", "4",
           "--dry-run", "1"]
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [line["seed"] for line in lines] == [3, 4] and all(line["dry_run"] for line in lines)
    for line in lines:
        assert line["even_share"] == 8 / 64 and line["even_group_share"] == 0.5
        keys = ("held_share", "group_share", "max_over_mean", "trips", "tile_fill", "held_set_differs", "ids_differ")
        assert all(len(line[k]) == 4 for k in keys)
        assert all(0 < s < 1 for s in line["held_share"] + line["group_share"]) and max(line["held_set_differs"]) < 0.1
