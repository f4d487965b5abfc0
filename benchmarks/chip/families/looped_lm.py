"""Adapter: a configuration file -> the program's looped LM, its normal path.

Published key names (Hugging Face ``config.json`` of Ouro) map onto
``models/looped_lm.py``'s ``LoopedLMConfig``; the state, the optimizer and
the train step are the program's own (``init_state``, ``make_optimizer``,
``make_train_step``). ``exit_beta`` is no published key: the file gives it
under ``program`` and says why under ``assumed``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import looped_lm as M

BATCH_SPEC = P("data", None)
# The reference comparison holds the last REFERENCE_POSITIONS positions of
# each sequence (they depend on every earlier one): all 2048 are four
# passes x 2 x 2048 x 49153 float32 = 3.2 GB a side, and the two arrays do
# not fit beside the 7.35 GB train state (reference/looped_lm.py).
REFERENCE_POSITIONS = 1024


def lconfig(cfg: Dict[str, Any]) -> M.LoopedLMConfig:
    run = cfg["program"]
    return M.LoopedLMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"],
        ut_steps=cfg["total_ut_steps"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        exit_beta=run["exit_beta"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def optimizer():
    return M.make_optimizer()


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return M.init_state(key, lconfig(cfg), optimizer(), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return M.state_specs(lconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return M.make_train_step(lconfig(cfg), optimizer(), mesh=mesh)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> (B, positions, T, vocab + 1) float32: every
    pass's logits and, as the last column, that pass's gate logit before
    the sigmoid, in the shape ``reference/looped_lm.py`` returns."""

    def fn(params, tokens):
        logits, gate_logits = M.pass_outputs(params, tokens, lconfig(cfg), mesh)
        logits = jnp.moveaxis(logits[:, :, -REFERENCE_POSITIONS:], 0, 2).astype(jnp.float32)
        gate_logits = jnp.moveaxis(gate_logits[:, :, -REFERENCE_POSITIONS:], 0, 2)
        return jnp.concatenate([logits, gate_logits[..., None]], axis=-1)

    return fn


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/looped_lm.py``'s ``forward`` needs beside the tree."""
    c = lconfig(cfg)
    return {"n_heads": c.n_heads, "ut_steps": c.ut_steps, "rope_theta": c.rope_theta,
            "norm_eps": c.norm_eps, "positions": REFERENCE_POSITIONS}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(lconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token's training forward pass multiplies by, counted
    from the state's own leaf shapes and from **applications, not leaves**:
    every matrix of the stack once per pass (T x L layer applications read
    L layers' weights), the untied head once per pass (the objective takes
    the cross-entropy of all T passes' logits, so the head runs T times),
    and the gate's vector. The lookup is free, norm scales and the gate's
    bias multiply nothing, and **recomputation is not counted**: these are
    model FLOPs, as ``step_mfu`` says, so a step that recomputes its forward
    pass reads lower than its hardware utilization. If the head were ever
    applied fewer than T times a step, this count, and ``step_mfu`` with it,
    would read too high."""
    passes = cfg["total_ut_steps"]
    n = 0
    for path, shape in param_shapes.items():
        if path == "['head']" or ("['layers']" in path and len(shape) == 3):  # (L, in, out) matrices
            n += passes * math.prod(shape)
        elif path == "['exit_gate_w']":
            n += math.prod(shape)
    return n
