"""Adapter: a configuration file -> the program's hybrid LM, its normal path.

Published key names (a Hugging Face ``nemotron_h`` ``config.json``) map onto
``models/hybrid_lm.py``'s ``HybridLMConfig``; the state, the optimizer and
the train step are the program's own (``init_state``, ``make_optimizer``,
``make_train_step``). Three keys are this benchmark's: ``experts_held`` (the
ids of the routed experts whose weights live on this chip),
``published_n_routed_experts`` (the router's width; ``n_routed_experts`` is
the count held) and ``published_num_hidden_layers`` (what
``rescale_prenorm_residual`` divides by).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import hybrid_lm as M

BATCH_SPEC = P("data", None)


def hconfig(cfg: Dict[str, Any]) -> M.HybridLMConfig:
    run = cfg["program"]
    held = tuple(cfg["experts_held"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError(f"experts_held names {len(held)} experts, n_routed_experts says {cfg['n_routed_experts']}")
    return M.HybridLMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        n_layers=cfg["num_hidden_layers"],
        published_layers=cfg["published_num_hidden_layers"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"],
        dt_min=cfg["time_step_min"],
        dt_max=cfg["time_step_max"],
        dt_floor=cfg["time_step_floor"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_experts=cfg["published_n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        held=held,
        norm_eps=cfg["norm_eps"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def optimizer(cfg: Dict[str, Any]):
    """The program's adamw at the file's ``program.lr`` (``assumed.lr`` says
    why not ``make_optimizer``'s default: with the selection bias held fixed
    nothing levels the experts' loads while a router trains)."""
    return M.make_optimizer(cfg["program"]["lr"])


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return M.init_state(key, hconfig(cfg), optimizer(cfg), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return M.state_specs(hconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return M.make_train_step(hconfig(cfg), optimizer(cfg), mesh=mesh)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> (B, S, vocab) float32 logits, every position."""
    return lambda params, tokens: M.forward(params, tokens, hconfig(cfg), mesh)


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/hybrid_lm.py``'s ``forward`` needs beside the tree."""
    c = hconfig(cfg)
    return {"n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads, "mamba_heads": c.mamba_heads,
            "ssm_groups": c.ssm_groups, "ssm_state": c.ssm_state, "top_k": c.top_k,
            "routed_scale": c.routed_scale, "held": c.held, "norm_eps": c.norm_eps}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(hconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token's training forward pass multiplies by, counted
    from the state's own leaf shapes: every matrix of the Mamba-2 and
    attention layers, the router and the shared expert, the untied head,
    and of the routed experts held here **their expected share under even
    routing**, ``num_experts_per_tok / published_n_routed_experts`` of each
    held expert a token (6 x 8 / 128 of a token a layer at the cell's
    cut). What the routers really send here is ``routing_stats``' count;
    if it is less, ``step_mfu`` reads too high by the routed experts' part
    (14 % of the count). The lookup is free; scales, the convolution and
    the per-head scalars multiply nothing a matmul does; recomputation is
    not counted (model FLOPs, as ``step_mfu`` says)."""
    share = cfg["num_experts_per_tok"] / cfg["published_n_routed_experts"]
    n = 0
    for path, shape in param_shapes.items():
        leaf = path.rsplit("'", 2)[-2]
        if leaf in ("expert_up", "expert_down"):
            n += round(share * math.prod(shape))
        elif leaf == "head" or (len(shape) == 2 and leaf not in ("embed", "conv_w")):
            n += math.prod(shape)
    return n
