"""Adapter: a configuration file -> the program's Gated DeltaNet / gated attention expert LM, its normal path.

Published key names (a Hugging Face ``qwen3_next`` ``config.json``) map onto
``models/gdn_lm.py``'s ``GDNLMConfig``; the state, the optimizer and the
train step are the program's own (``init_state``, ``make_optimizer``,
``make_train_step``). Three keys are this benchmark's: ``layers_held`` (the
published ids of the layers on this chip), ``experts_held`` (the ids of the
experts whose weights live here; ``num_experts`` is the count held) and
``published_num_experts`` (the router's width). The rotated share of a head
is ``partial_rotary_factor * head_dim``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import gdn_lm as M

BATCH_SPEC = P("data", None)


def kconfig(cfg: Dict[str, Any]) -> M.GDNLMConfig:
    run = cfg["program"]
    held, layers = tuple(cfg["experts_held"]), tuple(cfg["layers_held"])
    if len(held) != cfg["num_experts"]:
        raise ValueError(f"experts_held names {len(held)} experts, num_experts says {cfg['num_experts']}")
    if len(layers) != cfg["num_hidden_layers"]:
        raise ValueError(f"layers_held names {len(layers)} layers, num_hidden_layers says {cfg['num_hidden_layers']}")
    if (cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] or cfg["hidden_act"] != "silu"
            or cfg["use_sliding_window"] or cfg["tie_word_embeddings"] or cfg["rope_scaling"] is not None
            or not cfg["norm_topk_prob"]):
        raise ValueError("the program's qwen3_next stack has an expert MLP every layer, silu, full causal "
                         "attention, an untied head, plain rotation and renormalised top-k weights")
    return M.GDNLMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        layers=layers,
        full_attention_interval=cfg["full_attention_interval"],
        n_key_heads=cfg["linear_num_key_heads"],
        n_value_heads=cfg["linear_num_value_heads"],
        key_head_dim=cfg["linear_key_head_dim"],
        value_head_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        chunk=run["gdn_chunk"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        rotary_dim=round(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        n_experts=cfg["published_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["shared_expert_intermediate_size"],
        held=held,
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
    """What ``reference/gdn_lm.py``'s ``forward`` needs beside the tree."""
    c = kconfig(cfg)
    return {"layers": c.layers, "full_attention_interval": c.full_attention_interval, "n_key_heads": c.n_key_heads,
            "n_value_heads": c.n_value_heads, "n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads,
            "rotary_dim": c.rotary_dim, "rope_theta": c.rope_theta, "top_k": c.top_k, "held": c.held,
            "norm_eps": c.norm_eps}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(kconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one token's training forward pass multiplies by, counted
    from the state's own leaf shapes: every matrix of the GDN and attention
    mixers, the router, the shared expert and its gate, the untied head, and
    of the routed experts held here **their expected share under even
    routing**, ``num_experts_per_tok / published_num_experts`` of each held
    expert a token (10 x 32 / 512 of one at the cell's cut). What the
    routers really send here is ``routing_stats``' count. The lookup, the
    scales, the convolution and the per-head scalars multiply nothing a
    matmul does; GDN's chunk algebra, the attention scores and recomputation
    are not counted (model FLOPs, as ``step_mfu`` says)."""
    share = cfg["num_experts_per_tok"] / cfg["published_num_experts"]
    n = 0
    for path, shape in param_shapes.items():
        leaf = path.rsplit("'", 2)[-2]
        if leaf in ("expert_gate", "expert_up", "expert_down"):
            n += round(share * math.prod(shape))
        elif leaf == "head" or (len(shape) == 3 and leaf != "conv"):
            n += math.prod(shape)
    return n
