"""Adapter: a configuration file -> the program's transformer, its normal path.

Published key names (Hugging Face ``config.json``) map onto
``models/transformer.py``'s ``TransformerConfig``; the state, the optimizer
and the train step are the program's own (``init_state``, ``make_optimizer``,
``make_train_step``). What the program's block does differently from the
published architecture is listed in each configuration file under
``departures``; nothing here imitates it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import transformer as T

BATCH_SPEC = P("data", None)


def tconfig(cfg: Dict[str, Any]) -> T.TransformerConfig:
    run = cfg["program"]
    return T.TransformerConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attn_impl=run["attn_impl"],
        n_experts=cfg.get("num_experts", 0),
    )


def optimizer():
    return T.make_optimizer()


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return T.init_state(key, tconfig(cfg), optimizer(), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return T.state_specs(tconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return T.make_train_step(tconfig(cfg), optimizer(), mesh=mesh)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> logits, the program's forward pass."""
    return lambda params, tokens: T.forward(params, tokens, tconfig(cfg), mesh=mesh)


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/<name>.py``'s ``forward`` needs beside the tree."""
    return {"n_heads": cfg["num_attention_heads"], "capacity_factor": tconfig(cfg).moe_capacity_factor}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return T.select_attention(tconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token's forward pass multiplies by, counted from the
    state's own leaf shapes: every matrix, and of the expert stacks only
    the experts a token is routed to (the program routes top-2). The tied
    embedding counts once: its lookup is free, its use as the head is not."""
    n = 0
    for path, shape in param_shapes.items():
        size = 1
        for d in shape:
            size *= d
        if "moe_w_" in path:  # (L, E, ., .): top-2 of E experts
            size = size // shape[1] * 2
        n += size
    return n
