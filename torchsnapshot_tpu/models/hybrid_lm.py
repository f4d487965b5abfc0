"""Hybrid language model: Mamba-2, routed-FFN and attention layers in a
published order, one mixer a layer.

The ``nemotron_h`` stack (NVIDIA; the ``config.json`` of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 is such a config key for key), as
a train state on the same path as the other families (``init_state`` /
``make_train_step`` / ``CheckpointManager``). **What is not here**: that
release also describes a second, denoising tower and block-diffusion
generation; its config has no key for either and no equation is in hand, so
neither is built or imitated. This is the stack the config describes, under
the next-token objective of a base model.

With ``rms(x; w) = x rsqrt(mean(x^2) + eps) w`` in float32, every layer is
``x <- x + mixer(rms(x; w_norm))`` and the kind of its mixer is one
character of ``pattern``::

    M  Mamba-2   [z, xBC, dt] = a W_in;  xBC <- silu(conv1d_k(xBC) + b_conv)   (causal, depthwise)
                 [u, B, C] = xBC;  delta = softplus(dt + dt_bias);  A = -exp(A_log)
                 per head:  h_t = exp(delta_t A) h_{t-1} + delta_t u_t B_t^T;   y_t = h_t C_t + D u_t
                 out = (group_rms(y silu(z)) w_gnorm) W_out
    E  experts   s = sigmoid(a W_r) (float32);  the top_k experts by s + b (b: a saved leaf, no gradient)
                 w_i = scale s_i / (sum of the chosen s + 1e-20);  f(x; U, V) = relu(x U)^2 V
                 out = sum over the chosen experts HELD HERE of w_i f(a; U_i, V_i)  +  f(a; U_shared, V_shared)
    *  attention q = a W_q (H heads), k, v = a W_k, a W_v (H_kv heads), no rotation, no bias
                 out = causal_softmax(q k^T / sqrt(hd)) v W_o, a KV head serving H / H_kv query heads

    logits = rms(x; w_f) W_head^T (untied);  loss = mean next-token cross-entropy

**The chip's share of the experts**: the layer is told the ids of the
experts it holds (``cfg.held``), scores and chooses over all
``n_experts``, normalises over all ``top_k`` chosen, and adds only its own
experts' terms. What the absent experts would add is left out and that
partial result goes on to the next layer; no code stands in for the other
chips (``ops/moe.py`` ``sigmoid_topk_routed``; no token is dropped).

How it is compiled: **a block list**, not a scan. Layers of three kinds have
three parameter trees, so each layer is its own subtree, named by index and
kind (``layer05_attn``), and its own ``jax.checkpoint``: the backward pass
keeps one residual a layer and recomputes the rest. Nothing is stacked over
layers (a layer's held experts are one ``(n, D, F)`` leaf, a layer). The
matrices are cast to the compute dtype once a step (``compute_params``) and
the train step differentiates that tree, as ``looped_lm.py`` does; float32
stay the residual stream, the norms, the convolution, the softplus, the
decays and their sums, the router, and every matmul result. The Mamba-2
input projection is **one leaf and four products**: ``mamba_mixer`` cuts the
cast ``in_proj`` by columns where it is used and multiplies the stream by
each part, so ``z``, ``x``, ``B C`` and ``dt`` are matmul results of their
own (the convolution is depthwise, so it runs on ``x`` and on ``B C`` apart)
and no slice of a ``(B, S, 10304)`` or ``(B, S, 6144)`` result is copied
out, nor its gradient pieced together; the cut lies inside the
differentiated function, so its transpose puts the four gradients back
into the one ``(D, 10304)`` gradient the optimizer's tree has. The chunked
recurrence holds its tensors with the chunk position on the last axis
(``ops/ssm.py`` ``mamba2_chunked`` says why), and the gated output keeps its
``(B, S, inner)`` through the group norm (``_group_rms``: the groups' sums
are products with their indicator matrix, not a reshape into other tiles).

Sharding: the batch over 'data', ``embed`` and ``head`` over the vocabulary
on 'model'; the layers' leaves are replicated (no tensor-parallel layout of
the three mixers exists yet, and no multi-chip cell runs this family).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.moe import held_tile_stats, relu2_ffn, sigmoid_topk_routed
from ..ops.ssm import mamba2_chunked
from . import kit
from .kit import causal_conv, constrainer, make_optimizer, mm, rmsnorm  # noqa: F401  (make_optimizer: the same optimizer)

Params = Dict[str, Any]
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
NEMOTRON_H_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    """Published sizes only (defaults: the ``nemotron_h`` config of
    Nemotron-Labs-TwoTower-30B-A3B-Base-BF16). ``n_layers`` takes the first
    so many characters of ``pattern``; ``held`` names the routed experts
    whose weights live here (all of them by default)."""

    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = NEMOTRON_H_PATTERN
    n_layers: int = 52
    published_layers: int = 52  # what rescale_prenorm_residual divides by, whatever is held here
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 128
    top_k: int = 6
    expert_ff: int = 1856
    shared_ff: int = 3712
    routed_scale: float = 2.5
    held: Tuple[int, ...] = tuple(range(128))
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not 0 < self.n_layers <= len(self.pattern) or set(self.pattern) - set(KINDS):
            raise ValueError(f"n_layers {self.n_layers} of pattern {self.pattern!r}: kinds are {sorted(KINDS)}")
        if len(set(self.held)) != len(self.held) or not all(0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"held expert ids {self.held} are not distinct ids below {self.n_experts}")

    @property
    def kinds(self) -> str:
        return self.pattern[: self.n_layers]

    @property
    def layer_names(self) -> List[str]:
        return [f"layer{i:02d}_{KINDS[k]}" for i, k in enumerate(self.kinds)]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: u, B and C."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def matmul_params_per_token(self) -> int:
        """Parameters one token's forward pass multiplies by, the routed
        experts held here at their expected share under even routing
        (``top_k * len(held) / n_experts`` of a token a layer)."""
        D = self.d_model
        per = {
            "M": D * (2 * self.mamba_inner + 2 * self.ssm_groups * self.ssm_state + self.mamba_heads)
            + self.mamba_inner * D,
            "E": D * self.n_experts + 2 * D * self.shared_ff
            + round(self.top_k * len(self.held) / self.n_experts * 2 * D * self.expert_ff),
            "*": 2 * D * self.n_heads * self.head_dim + 2 * D * self.n_kv_heads * self.head_dim,
        }
        return sum(per[k] for k in self.kinds) + self.vocab_size * D


def _init_layer(key: jax.Array, kind: str, cfg: HybridLMConfig) -> Params:
    c, dt = cfg, cfg.param_dtype
    D = c.d_model
    keys = iter(jax.random.split(key, 8))
    out_scale = 1.0 / math.sqrt(2 * c.published_layers)  # rescale_prenorm_residual

    def norm(shape, fan_in, scale=1.0):
        return jax.random.normal(next(keys), shape, dt) * (scale * fan_in**-0.5)

    def ffn_out(shape, fan_in):
        # relu(.)^2 is positive: a sixth of what a random second matrix
        # makes of it is one vector, the same for every token. It piles up
        # in the residual stream and tilts every later random router toward
        # a few experts (their loads' spread doubled by the eighth layer,
        # and the step's time moved 4 % with the seed: PERF.md, PR 32),
        # which a trained selection bias levels and a random one cannot.
        # Rows that sum to zero over the hidden units put nothing there.
        w = norm(shape, fan_in, out_scale)
        return w - jnp.mean(w, axis=-2, keepdims=True)

    w = {"norm_scale": jnp.ones((D,), dt)}
    if kind == "M":
        H, K = c.mamba_heads, c.conv_kernel
        step = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32, math.log(c.dt_min), math.log(c.dt_max)))
        step = jnp.maximum(step, c.dt_floor)
        w.update(
            in_proj=norm((D, 2 * c.mamba_inner + 2 * c.ssm_groups * c.ssm_state + H), D),
            conv_w=jax.random.uniform(next(keys), (K, c.conv_width), dt, -(K**-0.5), K**-0.5),
            conv_b=jax.random.uniform(next(keys), (c.conv_width,), dt, -0.1, 0.1),  # a common offset too: kept small
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dt),  # softplus^-1(step)
            A_log=jnp.log(jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)).astype(dt),
            D=jnp.ones((H,), dt),
            gnorm_scale=jnp.ones((c.mamba_inner,), dt),
            out_proj=norm((c.mamba_inner, D), c.mamba_inner, out_scale),
        )
    elif kind == "E":
        n, F = len(c.held), c.expert_ff
        w.update(
            router=norm((D, c.n_experts), D),
            # Small beside the scores' spread (0.2): a trained bias levels the
            # experts' loads, a large random one tilts them (std 0.1 gave one
            # held expert 4-5 x the mean load and a step time that moved 4 %
            # with the seed: PERF.md, PR 32).
            router_bias=jax.random.normal(next(keys), (c.n_experts,), dt) * 0.01,
            expert_up=norm((n, D, F), D),
            expert_down=ffn_out((n, F, D), F),
            shared_up=norm((D, c.shared_ff), D),
            shared_down=ffn_out((c.shared_ff, D), c.shared_ff),
        )
    else:
        A, Akv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        w.update(q=norm((D, A), D), k=norm((D, Akv), D), v=norm((D, Akv), D), o=norm((A, D), A, out_scale))
    return w


def init_params(rng: jax.Array, cfg: HybridLMConfig) -> Params:
    """The parameter pytree: one subtree a layer, named by index and kind."""
    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    D = cfg.d_model
    layers = {
        name: _init_layer(jax.random.fold_in(k_layers, i), kind, cfg)
        for i, (name, kind) in enumerate(zip(cfg.layer_names, cfg.kinds))
    }
    return {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, D), cfg.param_dtype) * D**-0.5,
        "head": jax.random.normal(k_head, (cfg.vocab_size, D), cfg.param_dtype) * D**-0.5,
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), cfg.param_dtype),
    }


def param_specs(cfg: HybridLMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh: ``embed`` and ``head`` over
    the vocabulary, every layer leaf replicated (module docstring)."""
    return kit.vocab_sharded(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))


# Leaves that feed a matmul in the compute dtype. The router's product is
# float32 at full precision and the convolution is elementwise: they stay.
_MATRICES = {"in_proj", "out_proj", "expert_up", "expert_down", "shared_up", "shared_down", "q", "k", "v", "o"}


def compute_params(params: Params, cfg: HybridLMConfig) -> Params:
    """The tree the layers read: every matrix that feeds a matmul (and the
    head) in the compute dtype, cast once a step; the embedding, the
    scales, the convolution, the per-head scalars and the router as stored."""
    return kit.cast_matrices(params, cfg.dtype, _MATRICES)


def _group_rms(y: jax.Array, groups: int, eps: float) -> jax.Array:
    """``y / rms`` with the mean square taken over each of ``groups`` equal
    runs of the last axis, float32. The runs are summed, and the result
    handed back to their columns, by products with the groups' indicator
    matrix at ``HIGHEST`` (ones are exact in any operand format, the
    accumulation is float32): ``y`` stays ``(..., width)`` as the matmuls on
    both sides hold it, where a reshape to ``(..., groups, width / groups)``
    is a copy into other tiles and another back."""
    width = y.shape[-1] // groups
    member = jnp.repeat(jnp.eye(groups, dtype=jnp.float32), width, axis=0)  # (groups x width, groups)
    mean = jnp.matmul(jnp.square(y), member, precision=jax.lax.Precision.HIGHEST) / width
    return y * jnp.matmul(jax.lax.rsqrt(mean + eps), member.T, precision=jax.lax.Precision.HIGHEST)


def mamba_mixer(w: Params, a: jax.Array, cfg: HybridLMConfig) -> jax.Array:
    """The ``M`` mixer on the normed stream ``a: (B, S, D)`` -> float32."""
    c, f32 = cfg, jnp.float32
    B, S, _ = a.shape
    H, Pd, G, N = c.mamba_heads, c.mamba_head_dim, c.ssm_groups, c.ssm_state
    with jax.named_scope("mamba2"):
        # The leaf cut by columns, not the product (module docstring): z | x | B C | dt.
        inner = c.mamba_inner
        z, x, bc, dt = (mm(a, cols) for cols in jnp.split(w["in_proj"], [inner, 2 * inner, inner + c.conv_width], axis=1))
        u = jax.nn.silu(causal_conv(x, w["conv_w"][:, :inner], w["conv_b"][:inner])).reshape(B, S, H, Pd)
        b, cmat = jnp.split(jax.nn.silu(causal_conv(bc, w["conv_w"][:, inner:], w["conv_b"][inner:])), 2, axis=-1)
        delta = jax.nn.softplus(dt + w["dt_bias"].astype(f32))  # time_step_limit (0, inf) clamps nothing
        decay = -jnp.exp(w["A_log"].astype(f32))
        y = mamba2_chunked(
            u.astype(c.dtype), delta, decay,
            b.reshape(B, S, G, N).astype(c.dtype), cmat.reshape(B, S, G, N).astype(c.dtype),
            chunk=c.chunk,
        )
        y = (y + w["D"].astype(f32)[:, None] * u).reshape(B, S, inner) * jax.nn.silu(z)
        y = _group_rms(y, G, c.norm_eps)  # the norm's statistics are a group's
        return mm(y * w["gnorm_scale"].astype(f32), w["out_proj"])


def moe_mixer(w: Params, a: jax.Array, cfg: HybridLMConfig) -> Tuple[jax.Array, jax.Array]:
    """The ``E`` mixer -> (float32 output, the chosen expert ids (T, k))."""
    routed, ids = sigmoid_topk_routed(
        w, a, top_k=cfg.top_k, held=cfg.held, routed_scale=cfg.routed_scale
    )
    with jax.named_scope("moe_shared"):
        return routed + relu2_ffn(a, w["shared_up"], w["shared_down"]), ids


def attention_mixer(w: Params, a: jax.Array, cfg: HybridLMConfig, attend: Callable) -> jax.Array:
    """The ``*`` mixer: grouped-query causal attention, no rotation."""
    c = cfg
    B, S, _ = a.shape
    with jax.named_scope("gqa"):
        q = mm(a, w["q"]).astype(c.dtype).reshape(B, S, c.n_heads, c.head_dim)
        k = mm(a, w["k"]).astype(c.dtype).reshape(B, S, c.n_kv_heads, c.head_dim)
        v = mm(a, w["v"]).astype(c.dtype).reshape(B, S, c.n_kv_heads, c.head_dim)
        return mm(attend(q, k, v).reshape(B, S, c.n_heads * c.head_dim), w["o"])


def select_attention(cfg: HybridLMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the ``*`` layers run for this mesh and shape."""
    return kit.route_name(cfg.n_heads, mesh, B, S)


def _run_layers(cparams: Params, tokens: jax.Array, cfg: HybridLMConfig, mesh: Optional[Mesh]):
    """The closed hidden state ``rms(x; w_f)`` in float32 and, per ``E``
    layer, the chosen expert ids. ``cparams`` is ``compute_params``' tree."""
    B, S = tokens.shape
    cs = constrainer(mesh)
    _, attend = kit.attention_route(cfg.n_heads, mesh, B, S)
    chosen = []
    x = cs(cparams["embed"][tokens].astype(jnp.float32), P("data", None, None))
    for i, (name, kind) in enumerate(zip(cfg.layer_names, cfg.kinds)):

        @jax.checkpoint
        def layer(x, w, kind=kind, i=i):
            with jax.named_scope(f"layer{i}"):
                a = rmsnorm(x, w["norm_scale"], cfg.norm_eps)
                if kind == "M":
                    return x + mamba_mixer(w, a, cfg), None
                if kind == "E":
                    out, ids = moe_mixer(w, a, cfg)
                    return x + out, ids
                return x + attention_mixer(w, a, cfg, attend), None

        x, ids = layer(x, cparams["layers"][name])
        x = cs(x, P("data", None, None))
        if ids is not None:
            chosen.append(ids)
    return rmsnorm(x, cparams["ln_f_scale"], cfg.norm_eps), chosen


def forward(
    params: Params, tokens: jax.Array, cfg: HybridLMConfig, mesh: Optional[Mesh] = None
) -> jax.Array:
    """(B, S) int32 -> (B, S, vocab) float32 logits."""
    p = compute_params(params, cfg)
    return kit.head(_run_layers(p, tokens, cfg, mesh)[0], p["head"], constrainer(mesh))


def chosen_experts(params: Params, tokens: jax.Array, cfg: HybridLMConfig) -> Dict[str, jax.Array]:
    """Per ``E`` layer, by name, the ids ``(T, top_k)`` each token of this
    batch chose, from the forward pass the train step runs."""
    _, chosen = _run_layers(compute_params(params, cfg), tokens, cfg, None)
    return dict(zip((n for n, k in zip(cfg.layer_names, cfg.kinds) if k == "E"), chosen))


def routing_stats(params: Params, tokens: jax.Array, cfg: HybridLMConfig) -> Dict[str, Dict[str, jax.Array]]:
    """What the routers did with this batch, per ``E`` layer: ``held_share``,
    the share of the ``top_k * T`` assignments that fall on experts held
    here (``len(held) / n_experts`` under even routing),
    ``max_over_mean``, the most tokens a held expert gets over their mean
    (1 when they are level; the expert loops' longest trip over the
    average one), and the loops' ``trips`` and ``tile_fill``
    (``ops/moe.py`` ``held_tile_stats``)."""
    held = jnp.asarray(cfg.held, jnp.int32)
    out = {}
    for name, ids in chosen_experts(params, tokens, cfg).items():
        counts = jnp.sum(ids[None] == held[:, None, None], axis=(1, 2))
        out[name] = {
            "held_share": jnp.sum(counts) / ids.size,
            "max_over_mean": jnp.max(counts) / jnp.maximum(jnp.mean(counts.astype(jnp.float32)), 1e-9),
            **held_tile_stats(counts, ids.shape[0]),
        }
    return out


def _objective(cparams: Params, batch: Dict[str, jax.Array], cfg: HybridLMConfig, mesh: Optional[Mesh]):
    cs = constrainer(mesh)
    targets = batch["targets"]

    @jax.checkpoint
    def head_and_ce(h, head):
        logits = kit.head(h, head, cs)
        at_target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - at_target)

    return head_and_ce(_run_layers(cparams, batch["tokens"], cfg, mesh)[0], cparams["head"])


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: HybridLMConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Mean next-token cross-entropy over the vocabulary held here. No
    auxiliary loss: the config gives no coefficient."""
    return _objective(compute_params(params, cfg), batch, cfg, mesh)


def state_specs(cfg: HybridLMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output (``kit.state_specs``)."""
    return kit.state_specs(param_specs(cfg), state)


def init_state(
    rng: jax.Array, cfg: HybridLMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Dict[str, Any]:
    """{params, opt_state, step}, the whole of it placed per ``state_specs``
    under a mesh."""
    return kit.init_state(init_params(rng, cfg), param_specs(cfg), tx, mesh)


def make_train_step(
    cfg: HybridLMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit
    (``kit.make_train_step``). The selection bias is held fixed: it takes no
    gradient, its balancing update has no rate in the config, and adamw's
    weight decay would otherwise shrink it."""
    gauges = {
        "hybrid_lm.layers": cfg.n_layers,
        "hybrid_lm.experts_held": len(cfg.held),
        "hybrid_lm.matmul_params_per_token": cfg.matmul_params_per_token,
    }
    return kit.make_train_step(
        _objective, lambda p: compute_params(p, cfg), lambda state, batch: (batch, cfg, mesh), tx, mesh,
        param_specs(cfg), gauges, frozen=("router_bias",),
    )
