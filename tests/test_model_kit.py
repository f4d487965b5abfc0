"""The model zoo's layering: each model file stands on ``models/kit.py`` and
the ops, and reaches no other model's private names, so that a change to
one model's private helper cannot move another model's step."""

from __future__ import annotations

import ast
import pathlib

MODELS = pathlib.Path(__file__).resolve().parents[1] / "torchsnapshot_tpu" / "models"


def test_no_model_imports_another_models_private_name():
    models = {p.stem for p in MODELS.glob("*.py")} - {"__init__", "kit"}
    assert {"hybrid_lm", "block_diffusion_lm", "kda_mla_lm"} <= models
    reached = []
    for path in sorted(MODELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.level == 1 and node.module in models:
                reached += [f"{path.name}: {node.module}.{a.name}" for a in node.names if a.name.startswith("_")]
    assert reached == []
