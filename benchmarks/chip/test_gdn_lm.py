"""The Gated DeltaNet / gated attention expert family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, the stage's four
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

CELL = "qwen3next80b.save"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# The shape keys of the source's config.json, as published; the file keeps
# each of them but the reduced ones.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


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
        assert got["step_lower_s"]["value"] > 0 and record["setup"]["mesh_train"]["leaves"] == 110
        assert got["state_leaves"]["value"] == 110.0 and "stage_chunk_s" not in wanted
        assert any("plain reference" in c["what"] and c["ok"] for c in record["checks"]), record["checks"]


def test_the_cell_reports_what_ling3flash_save_does_and_its_leaves(bench):
    """One config and one one-chip cell on the mix that exists, after
    ``ling3flash``'s; its name on the list of every metric that lists
    ``ling3flash.save``, and on ``state_leaves``'s, and on no other; every
    list in the order of ``workloads`` (later cells append after it)."""
    names = [w["name"] for w in bench["workloads"]]
    cell = bench["workloads"][names.index(CELL)]
    assert cell == {**cell, "config": "qwen3next80b", "traffic": "save-cadence-12", "chips": 1}
    assert names.index(CELL) == names.index("ling3flash.save") + 1 and names.count(CELL) == 1
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("qwen3next80b") == configs.index("ling3flash") + 1
    assert bench["configs"][configs.index("qwen3next80b")]["reduced"] == REDUCED
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = bench["end_to_end"] + bench["per_layer"]
    listed = lambda cell: {m["name"] for m in metrics if cell in m.get("workloads", [])}  # noqa: E731
    assert listed(CELL) == listed("ling3flash.save") | {"state_leaves"} and "stage_chunk_s" not in listed(CELL)
    assert all(m["workloads"] == sorted(m["workloads"], key=names.index) for m in metrics if "workloads" in m)


def test_the_file_keeps_every_catalog_key_but_the_reduced_ones(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 12 and cell.traffic["kind"] == "save_cadence"
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 32, 18992)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"], cfg["published_vocab_size"]) == (
        48, 512, 151936)
    assert cfg["experts_held"] == list(range(32)) and cfg["layers_held"] == [0, 1, 2, 3]
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"] and cfg["chips_sharing_a_layer"] == 16
    assert {"sources", "layer_kinds", "norms", "gdn_proj", "gdn_conv", "gdn_qk", "gdn_gate", "gdn_rule", "attention",
            "rotation", "router", "shared_expert", "init", "dtypes", "seq", "lr", "num_experts",
            "published_*"} <= set(cfg["assumed"])
    assert "arXiv:2412.06464" in cfg["assumed"]["sources"]
    assert len(cfg["departures"]) == 2 and "multi-token" in cfg["departures"][0] and "auxiliary" in cfg["departures"][1]
    run = cfg["program"]
    assert (run["family"], run["reference"], run["seq"], run["batch"], run["gdn_chunk"]) == ("gdn_lm", "gdn_lm", 8192, 1, 64)
    assert 0 < run["lr"] <= 1e-5 and "13.28" in run["why_seq"]
    for said in ("48 -> 4", "512 -> 32", "151936 -> 18992", "16 chips", "layers 0-3", "625 667 136", "30.0 GB", "40 GiB"):
        assert said in cfg["cut"], said
    assert cfg["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[f"published_{key}"] == value != cfg[key], key
        else:
            assert cfg[key] == value, key
    dry = spec.resolve_cell(bench, CELL, dry_run=True).config
    assert dry["num_hidden_layers"] == 4 and len(dry["experts_held"]) == dry["num_experts"] < dry["published_num_experts"]


def test_the_adapter_maps_the_file_onto_the_programs_config(bench):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "gdn_lm")
    cfg = spec.resolve_cell(bench, CELL).config
    c = family.kconfig(cfg)
    assert (c.d_model, c.layers, c.full_attention_interval, c.vocab_size) == (2048, (0, 1, 2, 3), 4, 18992)
    assert (c.n_key_heads, c.n_value_heads, c.key_head_dim, c.value_head_dim, c.conv_kernel, c.chunk) == (
        16, 32, 128, 128, 4, 64)
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.rotary_dim, c.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (c.n_experts, c.top_k, c.expert_ff, c.shared_ff, c.held) == (512, 10, 512, 512, tuple(range(32)))
    with pytest.raises(ValueError, match="experts_held"):
        family.kconfig({**cfg, "experts_held": [0, 1]})
    with pytest.raises(ValueError, match="layers_held"):
        family.kconfig({**cfg, "layers_held": [0, 2]})
    with pytest.raises(ValueError, match="expert MLP every layer"):
        family.kconfig({**cfg, "mlp_only_layers": [0]})
    assert set(family.reference_args(cfg)) >= {"layers", "held", "n_key_heads", "n_value_heads", "rotary_dim"}


def test_layers_held_give_three_gdn_layers_and_one_attention_layer(bench):
    """Published ids 0-3: one whole period of ``full_attention_interval`` 4
    (``(p + 1) % 4 == 0`` is attention: id 3), no leading dense layers; three
    GDN layers run as one scan, then the attention layer."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "gdn_lm")
    c = family.kconfig(spec.resolve_cell(bench, CELL).config)
    assert c.kinds == ["gdn", "gdn", "gdn", "attn"] and c.runs == [("gdn", 0, 3), ("attn", 0, 1)]


def test_active_parameters_count_the_held_experts_at_their_even_share(bench):
    """Every projection, the router, the shared expert and its gate, and
    the head, and of the 32 held experts ``10 / 512`` each a token (10 x 32
    / 512 of one expert): 191.9 M of the 625.7 M at the cell's cut."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "gdn_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.kconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
        got = family.active_params(cfg, flat)
        assert got == c.matmul_params_per_token
        leaves = sum(math.prod(s) for s in flat.values())
    expert = 3 * 2048 * 512
    held_share = sum(math.prod(s) for k, s in flat.items() if "expert_" in k and "shared" not in k) * 10 // 512
    assert held_share == 4 * 10 * 32 * expert // 512  # 10 x 32 / 512 of one expert a layer
    assert got == 191_864_832 and leaves == 625_667_136
    assert len(jax.tree_util.tree_leaves(shapes)) == 110
    assert max(math.prod(s) * 4 for s in flat.values()) <= 512 << 20  # no leaf is saved chunked


def test_the_probe_rehearses_on_the_cpu():
    cmd = [sys.executable, os.path.join(HERE, "tools", "routing_probe_gdn.py"), "--workload", CELL, "--seeds", "3", "4",
           "--steps", "3", "--dry-run", "1"]
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [line["seed"] for line in lines] == [3, 4] and all(line["dry_run"] for line in lines)
    for line in lines:
        assert line["even_share"] == 8 / 64 and line["steps"] == 3
        keys = ("share_over_even_min", "share_over_even_max", "max_over_mean_min", "max_over_mean_max", "trips_min")
        assert all(len(line[k]) == 4 for k in keys)
        assert all(0.5 < s < 2 for s in line["share_over_even_min"] + line["share_over_even_max"])
