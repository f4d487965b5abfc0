"""Adapter: a configuration file -> the program's CCA / MLP-routed LM, its normal path.

Published key names (a Hugging Face ``zaya`` ``config.json``) map onto
``models/cca_moe_lm.py``'s ``CCAMoELMConfig``; the state, the optimizer and
the train step are the program's own (``init_state``, ``make_optimizer``,
``make_train_step``). Two keys are this benchmark's: ``experts_held`` (the
ids of the experts whose weights live on this chip; ``num_experts`` is the
count held) and ``published_num_experts`` (the router's width). The
rotation is ``rope_parameters.hybrid``'s (every layer is of that type and
``sliding_window`` is null).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import cca_moe_lm as M

BATCH_SPEC = P("data", None)


def cconfig(cfg: Dict[str, Any]) -> M.CCAMoELMConfig:
    run = cfg["program"]
    held = tuple(cfg["experts_held"])
    if len(held) != cfg["num_experts"]:
        raise ValueError(f"experts_held names {len(held)} experts, num_experts says {cfg['num_experts']}")
    if cfg["num_experts_per_tok"] != 1 or not cfg["tie_word_embeddings"] or set(cfg["layer_types"]) != {"hybrid"}:
        raise ValueError("the program's zaya stack is top-1, tied, and every layer 'hybrid'")
    rope = cfg["rope_parameters"]["hybrid"]
    return M.CCAMoELMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_experts=cfg["published_num_experts"],
        expert_ff=cfg["moe_intermediate_size"],
        held=held,
        router_dim=cfg["router_hidden_size"],
        conv_kernels=(cfg["cca_time0"], cfg["cca_time1"]),
        rotary_factor=rope["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        head_block=run["head_block"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def optimizer(cfg: Dict[str, Any]):
    """The program's adamw at the file's ``program.lr`` (``assumed.lr`` says
    why not ``make_optimizer``'s default: no balancing rule levels the
    experts' loads while a router trains)."""
    return M.make_optimizer(cfg["program"]["lr"])


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return M.init_state(key, cconfig(cfg), optimizer(cfg), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return M.state_specs(cconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return M.make_train_step(cconfig(cfg), optimizer(cfg), mesh=mesh)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> (B, S, vocab) float32 logits."""
    c = cconfig(cfg)
    return lambda params, tokens: M.forward(params, tokens, c, mesh)


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/cca_moe_lm.py``'s ``forward`` needs beside the tree."""
    c = cconfig(cfg)
    return {"n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads, "held": c.held, "norm_eps": c.norm_eps,
            "rope_theta": c.rope_theta, "rotary_dim": c.rotary_dim}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(cconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token multiplies by in a training forward pass,
    counted from the state's own leaf shapes: q, k, v, o, the per-head
    convolution's matrices, the router's four matrices, **of the experts
    held here their expected share under even routing** (``1 /
    published_num_experts`` of each a token: half an expert's three
    matrices at the cell's cut, where 8 of 16 are held) and the tied
    embedding once, as the head. ``step_mfu`` is 6 x this x tokens: it
    leaves out the attention scores (at S 8192 about 8.4 M multiply-adds a
    token and layer against these 12.5 M), the depthwise convolution and
    recomputation, so it reads low. What the routers really send here is
    ``routing_stats``' count."""
    share = cfg["num_experts_per_tok"] / cfg["published_num_experts"]
    total = 0
    for path, shape in param_shapes.items():
        leaf = path.rsplit("'", 2)[-2]
        if leaf in ("expert_gate", "expert_up", "expert_down"):
            total += round(share * math.prod(shape))
        elif leaf in ("q", "k", "v1", "v2", "o", "conv1_w", "router_down", "router_w1", "router_w2", "router_w3", "embed"):
            total += math.prod(shape)
    return total
