"""Adapter: a configuration file -> the program's KDA / MLA expert LM, its normal path.

Published key names (a Hugging Face ``bailing_hybrid`` ``config.json``) map
onto ``models/kda_mla_lm.py``'s ``KDAMLALMConfig``; the state, the optimizer
and the train step are the program's own (``init_state``,
``make_optimizer``, ``make_train_step``). Three keys are this benchmark's:
``layers_held`` (the published ids of the layers on this chip; the clamp
lists are cut to them), ``experts_held`` (the ids of the experts whose
weights live here; ``num_experts`` is the count held) and
``published_num_experts`` (the router's width). KDA's heads are
``num_attention_heads`` of ``head_dim`` (``num_kv_heads_for_linear_attn`` 0:
as many).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import kda_mla_lm as M

BATCH_SPEC = P("data", None)


def kconfig(cfg: Dict[str, Any]) -> M.KDAMLALMConfig:
    run = cfg["program"]
    held, layers = tuple(cfg["experts_held"]), tuple(cfg["layers_held"])
    if len(held) != cfg["num_experts"]:
        raise ValueError(f"experts_held names {len(held)} experts, num_experts says {cfg['num_experts']}")
    if len(layers) != cfg["num_hidden_layers"]:
        raise ValueError(f"layers_held names {len(layers)} layers, num_hidden_layers says {cfg['num_hidden_layers']}")
    if cfg["q_lora_rank"] is not None or cfg["score_function"] != "sigmoid" or not cfg["use_qk_norm"]:
        raise ValueError("the program's bailing_hybrid stack has no q LoRA, sigmoid scores and a q/k norm")
    if cfg["num_kv_heads_for_linear_attn"] not in (0, cfg["num_attention_heads"]):
        raise ValueError("KDA has as many key heads as query heads here")
    return M.KDAMLALMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        layers=layers,
        layer_group_size=cfg["layer_group_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"],
        conv_kernel=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        chunk=run["kda_chunk"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        dense_ff=cfg["intermediate_size"],
        n_experts=cfg["published_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        held=held,
        expert_limits=tuple(cfg["expert_swiglu_limit_list"]),
        shared_limits=tuple(cfg["share_expert_swiglu_limit_list"]),
        norm_eps=cfg["rms_norm_eps"],
        head_block=run["head_block"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def optimizer(cfg: Dict[str, Any]):
    """The program's adamw at the file's ``program.lr`` (``assumed.lr`` says
    why not ``make_optimizer``'s default)."""
    return M.make_optimizer(cfg["program"]["lr"])


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return M.init_state(key, kconfig(cfg), optimizer(cfg), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return M.state_specs(kconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return M.make_train_step(kconfig(cfg), optimizer(cfg), mesh=mesh)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> (B, S, vocab) float32 logits."""
    c = kconfig(cfg)
    return lambda params, tokens: M.forward(params, tokens, c, mesh)


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/kda_mla_lm.py``'s ``forward`` needs beside the tree."""
    c = kconfig(cfg)
    return {"layers": c.layers, "layer_group_size": c.layer_group_size, "first_k_dense": c.first_k_dense,
            "n_heads": c.n_heads, "lower_bound": c.kda_lower_bound, "kv_lora_rank": c.kv_lora_rank,
            "qk_rope_dim": c.qk_rope_dim, "v_head_dim": c.v_head_dim, "rope_theta": c.rope_theta,
            "top_k": c.top_k, "routed_scale": c.routed_scale, "n_group": c.n_group, "topk_group": c.topk_group,
            "held": c.held, "expert_limits": c.expert_limits, "shared_limits": c.shared_limits,
            "norm_eps": c.norm_eps}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(kconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token's training forward pass multiplies by, counted
    from the state's own leaf shapes: every matrix of the KDA and MLA
    mixers, the dense MLP, the router and the shared expert, the untied
    head, and of the routed experts held here **their expected share under
    even routing**, ``num_experts_per_tok / published_num_experts`` of each
    held expert a token (8 x 8 / 512 of one at the cell's cut). What the
    routers really send here is ``routing_stats``' count. The lookup, the
    scales, the convolutions and the per-head scalars multiply nothing a
    matmul does; KDA's chunk algebra, the attention scores and
    recomputation are not counted (model FLOPs, as ``step_mfu`` says)."""
    share = cfg["num_experts_per_tok"] / cfg["published_num_experts"]
    n = 0
    for path, shape in param_shapes.items():
        leaf = path.rsplit("'", 2)[-2]
        if leaf in ("expert_gate", "expert_up", "expert_down"):
            n += round(share * math.prod(shape))
        elif leaf == "head" or (len(shape) == 3 and leaf not in ("q_conv", "k_conv", "v_conv")):
            n += math.prod(shape)
    return n
