"""The looped-LM family in the harness. CPU only: ``pytest benchmarks/chip``.

The cell's rehearsal (``run.py --dry-run 1``: toy widths, two passes, CPU
devices) and the adapter's count of active parameters; nothing here
produces or asserts a device number.
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

CELL = "ouro2b6.save"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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
        # The new metric, and the reference comparison of every pass in the rehearsal too.
        assert got["step_lower_s"]["value"] > 0
        checks = json.loads(p.stdout.strip().splitlines()[-2])["checks"]
        assert any("plain reference" in c["what"] and c["ok"] for c in checks), checks


def test_the_file_holds_the_published_block_uncut_but_for_depth(bench):
    cell = spec.resolve_cell(bench, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["save_every_steps"] == 8
    assert cfg["reduced"] == ["num_hidden_layers"] and 4 <= cfg["num_hidden_layers"] <= 9
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"], cfg["vocab_size"]) == (2048, 5632, 128, 49152)
    assert cfg["total_ut_steps"] == 4 and cfg["tie_word_embeddings"] is False and cfg["departures"] == []
    assert {"norm between passes", "exit gate", "exit_beta", "dtypes", "seq"} <= set(cfg["assumed"])
    assert spec.resolve_cell(bench, CELL, dry_run=True).config["total_ut_steps"] >= 2


def test_active_parameters_count_applications_not_leaves(bench):
    """T x the layers' matrices + T x the head + the gate; the lookup, the
    norm scales and the gate's bias multiply nothing."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    family = spec.load_module("families", "looped_lm")
    for dry_run in (True, False):
        cfg = spec.resolve_cell(bench, CELL, dry_run=dry_run).config
        c = family.lconfig(cfg)
        shapes = jax.eval_shape(lambda k: family.init_state(k, cfg), jax.random.PRNGKey(0))["params"]
        flat = {jax.tree_util.keystr(p): x.shape for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
        layer = 4 * c.d_model * c.n_heads * c.head_dim + 3 * c.d_model * c.d_ff
        want = c.ut_steps * c.n_layers * layer + c.ut_steps * c.vocab_size * c.d_model + c.d_model
        assert family.active_params(cfg, flat) == want == c.matmul_params_per_token
        leaves = sum(math.prod(s) for s in flat.values())
        assert want > 2 * leaves if c.ut_steps >= 4 else want > leaves  # applications, not leaves
