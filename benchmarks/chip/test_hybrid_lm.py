"""The hybrid-LM family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, the nine layers
``MEMEM*EME``, CPU devices), the file's keys against the source, and the
adapter's count of active parameters; nothing here produces or asserts a
device number.
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

CELL = "nemotron2t30b.save"
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
        # This cell's own metric, the one it shares with ouro2b6.save, and
        # the reference comparison of the nine layers in the rehearsal too.
        assert got["state_leaves"]["value"] == 218 and got["step_lower_s"]["value"] > 0
        checks = json.loads(p.stdout.strip().splitlines()[-2])["checks"]
        assert any("plain reference" in c["what"] and c["ok"] for c in checks), checks


def test_state_leaves_is_listed_for_this_cell_alone(bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == "state_leaves")
    assert entry["workloads"] == [CELL] and entry["layer"] == "plan" and entry["moves"] == "setup_s"
    assert spec.load_metric("state_leaves")["reader"] == "value"
    with_ouro = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if "ouro2b6.save" in m.get("workloads", [])}
    with_this = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", [])}
    assert with_this == with_ouro | {"state_leaves"}


def test_the_file_keeps_every_width_and_cuts_depth_experts_held_and_vocabulary(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 12 and cell.traffic["kind"] == "save_cadence"
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (9, 8, 16384)
    assert (cfg["published_num_hidden_layers"], cfg["published_n_routed_experts"], cfg["published_vocab_size"]) == (52, 128, 131072)
    assert cfg["experts_held"] == list(range(8)) and cfg["chips_sharing_a_layer"] * 8 == 128
    assert cfg["num_experts"] == cfg["n_routed_experts"] and "num_experts" in cfg["assumed"]
    assert cfg["hybrid_override_pattern"][:9] == "MEMEM*EME" and len(cfg["hybrid_override_pattern"]) == 52
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]
    assert "second" in cfg["departures"][0] and "tower" in cfg["departures"][0]  # said first
    assert {"rotation", "init", "dtypes", "seq"} <= set(cfg["assumed"])
    assert cfg["program"]["seq"] == 8192 and cfg["program"]["batch"] in (1, 2)
    assert 0 < cfg["program"]["lr"] <= 1e-5 and "lr" in cfg["assumed"]  # the routers must not collapse in a run
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
        for key, value in row["config"].items():  # nested groups and lists too: copied whole
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    dry = spec.resolve_cell(bench, CELL, dry_run=True).config
    assert dry["num_hidden_layers"] == 9 and len(dry["experts_held"]) == dry["n_routed_experts"] < dry["published_n_routed_experts"]
    assert dry["num_experts_per_tok"] > dry["n_routed_experts"]  # a token cannot put all its six here


def test_the_adapter_maps_the_file_onto_the_programs_config(bench):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    family = spec.load_module("families", "hybrid_lm")
    cfg = spec.resolve_cell(bench, CELL).config
    c = family.hconfig(cfg)
    assert (c.d_model, c.mamba_inner, c.conv_width, c.expert_ff, c.shared_ff) == (2688, 4096, 6144, 1856, 3712)
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.ssm_state, c.ssm_groups, c.chunk) == (32, 2, 128, 128, 8, 128)
    assert (c.n_experts, c.top_k, c.routed_scale, c.held) == (128, 6, 2.5, tuple(range(8)))
    assert c.kinds == "MEMEM*EME" and c.published_layers == 52 and c.norm_eps == 1e-5
    with pytest.raises(ValueError, match="experts_held"):
        family.hconfig({**cfg, "experts_held": [0, 1]})
    assert set(family.reference_args(cfg)) == {"n_heads", "n_kv_heads", "mamba_heads", "ssm_groups", "ssm_state",
                                              "top_k", "routed_scale", "held", "norm_eps"}


def test_active_parameters_take_the_held_experts_at_their_expected_share(bench):
    """Every matrix but the embedding's lookup once, the routed experts at
    6 / 128 of a token each: 318.4 M of the 667.0 M at the cell's cut."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "hybrid_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.hconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))["params"]
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
        got = family.active_params(cfg, flat)
        assert abs(got - c.matmul_params_per_token) <= 8  # rounding of the share, a layer
        leaves = sum(math.prod(s) for s in flat.values())
        assert got < leaves
    assert got == 318_431_232 and leaves == 666_963_456
    assert max(math.prod(s) * 4 for s in flat.values()) == 16384 * 2688 * 4 < 512 << 20  # no leaf is chunked
