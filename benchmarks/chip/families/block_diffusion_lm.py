"""Adapter: a configuration file -> the program's block-diffusion LM, its normal path.

Published key names (a Hugging Face ``sdar_moe`` / ``qwen3_moe``
``config.json``) map onto ``models/block_diffusion_lm.py``'s
``BlockDiffusionLMConfig``; the state, the optimizer and the train step are
the program's own (``init_state``, ``make_optimizer``, ``make_train_step``).
Two keys are this benchmark's: ``experts_held`` (the ids of the experts
whose weights live on this chip; ``num_experts`` is the count held) and
``published_num_experts`` (the router's width). The objective's constants,
which the source does not give, are under ``program`` (``block_length``,
``t_min``; the file's ``assumed`` says where each comes from).

The train step draws its noise from the state's key. The comparison with
the plain reference needs program and reference to see **one** noise, so
``forward`` and ``reference_args`` take it from ``reference_noise``: two
sequences' masks from a fixed numpy seed, an input like the tokens.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu.models import block_diffusion_lm as M

BATCH_SPEC = P("data", None)


def bconfig(cfg: Dict[str, Any]) -> M.BlockDiffusionLMConfig:
    run = cfg["program"]
    held = tuple(cfg["experts_held"])
    if len(held) != cfg["num_experts"]:
        raise ValueError(f"experts_held names {len(held)} experts, num_experts says {cfg['num_experts']}")
    return M.BlockDiffusionLMConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_experts=cfg["published_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        held=held,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        block_length=run["block_length"],
        t_min=run["t_min"],
        dtype=jnp.dtype(run["compute_dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
    )


def optimizer(cfg: Dict[str, Any]):
    """The program's adamw at the file's ``program.lr`` (``assumed.lr`` says
    why not ``make_optimizer``'s default: no balancing loss levels the
    experts' loads while a router trains)."""
    return M.make_optimizer(cfg["program"]["lr"])


def init_state(key, cfg: Dict[str, Any]):
    """Single-device state; the harness jits this with the mesh's output
    shardings, so one program makes every leaf where it lives."""
    return M.init_state(key, bconfig(cfg), optimizer(cfg), mesh=None)


def state_specs(cfg: Dict[str, Any], state_shapes) -> Any:
    return M.state_specs(bconfig(cfg), state_shapes)


def train_step(cfg: Dict[str, Any], mesh: Optional[Any]):
    return M.make_train_step(bconfig(cfg), optimizer(cfg), mesh=mesh)


def reference_noise(cfg: Dict[str, Any]) -> np.ndarray:
    """The (2, S) bool of masked positions the reference comparison runs
    under: per sequence and block ``t ~ U(t_min, 1)``, a position masked
    with probability t, from numpy's seed 0."""
    run = cfg["program"]
    rng = np.random.default_rng(0)
    t = rng.uniform(run["t_min"], 1.0, (2, run["seq"] // run["block_length"]))
    return rng.uniform(size=(2, run["seq"])) < np.repeat(t, run["block_length"], axis=1)


def forward(cfg: Dict[str, Any], mesh: Optional[Any]):
    """(params, tokens) -> (B, S, vocab) float32 logits at the noised
    positions, under ``reference_noise``'s masks."""
    c, noise = bconfig(cfg), reference_noise(cfg)
    return lambda params, tokens: M.forward(params, tokens, jnp.asarray(noise[: tokens.shape[0]]), c, mesh)


def reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/block_diffusion_lm.py``'s ``forward`` needs beside the tree."""
    c = bconfig(cfg)
    return {"masked": reference_noise(cfg), "n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads, "top_k": c.top_k,
            "held": c.held, "norm_eps": c.norm_eps, "rope_theta": c.rope_theta, "block_length": c.block_length,
            "mask_token_id": c.mask_id}


def attention(cfg: Dict[str, Any], mesh: Optional[Any]) -> str:
    run = cfg["program"]
    return M.select_attention(bconfig(cfg), mesh, run["batch"], run["seq"])


def active_params(cfg: Dict[str, Any], param_shapes: Dict[str, tuple]) -> int:
    """Parameters one **token of the batch** multiplies by in a training
    forward pass, counted from the state's own leaf shapes. A token is two
    positions of the stack, its clean and its noised copy, so **the stack
    counts twice** (q, k, v, o, the router, and of the experts held here
    their expected share under even routing, ``num_experts_per_tok /
    published_num_experts`` of each a position: one expert's three matrices
    a position at the cell's cut) and the untied head once, on the noised
    copy. ``step_mfu`` is 6 x this x tokens: it leaves out the attention
    scores (at S 4096 about as much work again as 40 % of the matrices',
    even with the dead tiles skipped) and recomputation, so it reads low.
    What the routers really send here is ``routing_stats``' count."""
    share = cfg["num_experts_per_tok"] / cfg["published_num_experts"]
    stack = head = 0
    for path, shape in param_shapes.items():
        leaf = path.rsplit("'", 2)[-2]
        if leaf in ("expert_gate", "expert_up", "expert_down"):
            stack += round(share * math.prod(shape))
        elif leaf in ("q", "k", "v", "o", "router"):
            stack += math.prod(shape)
        elif leaf == "head":
            head = math.prod(shape)
    return 2 * stack + head
