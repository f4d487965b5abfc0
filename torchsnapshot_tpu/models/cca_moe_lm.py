"""A routed-expert decoder whose attention runs in a compressed, convolved
latent and whose top-1 router is an MLP over a stream carried through the
depth.

The ``zaya`` stack (Zyphra; ``config.json`` of ZAYA1-8B), as a train state
on the same path as the other families (``init_state`` / ``make_train_step``
/ ``CheckpointManager``). The config gives the widths, the head and expert
counts, top-1, the router's width, the convolutions' kernels, the partial
rotation, tying and the norm's epsilon; **the form of each mechanism is
written from memory of the papers, no network** (compressed convolutional
attention: arXiv:2510.04476; the router and residual scaling: the ZAYA1
report, arXiv:2511.17127), and ``benchmarks/chip/configs/zaya1_8b.json``
lists every such form under ``assumed``. Every layer is an attention
sublayer and an expert sublayer. With ``rms(x; g) = x rsqrt(mean(x^2) +
eps) g`` in float32, on the stream ``x: (B, S, D)`` and the router's carry
``r: (B, S, R)``, zeros ahead of the first layer::

    x = E[tokens];  r = 0
    per layer:
        a = rms(x; g1);  q, k, v = cca_qkv(a)                                   ops/cca.py says what that is
        out = softmax(q k^T / sqrt(hd) + causal) v Wo,  a KV head serving H / H_kv query heads,  Wo: (H hd, D)
        x <- (x + beta_r) * sigma_r + (out + beta_h) * sigma_h                  residual scaling, four vectors
        b = rms(x; g2);  e*, w, r <- mlp_top1_route(b, r)                       ops/moe.py: r is what the next layer receives
        y = w (silu(b G_e*) * (b U_e*)) D_e*  if e* is HELD HERE else 0
        x <- (x + beta_r') * sigma_r' + (y + beta_h') * sigma_h'                this sublayer's own four
    logits = rms(x; g_f) E^T                                                    the embedding, tied
    loss = mean over B S of CE(logits[t], targets[t])

``H hd`` is not ``D`` (1024 against 2048 as published). The first layer's
``gamma`` multiplies zeros, so its row of the stacked leaf takes no
gradient; nor does any row of the selection bias (it only selects).
**What is not here** (the file's ``departures``): a rule that updates the
selection bias to balance the experts (held at 0), a skip choice in the
router, an auxiliary loss, generation and CCA's decode cache.

**The chip's share of the experts** is ``hybrid_lm``'s: the layer is told
the ids it holds (``cfg.held``), scores and chooses over all ``n_experts``
and adds only its own experts' terms (``ops/moe.py`` ``mlp_top1_routed``;
no token is dropped). Every chip that shares a layer computes the same
attention, route, carry and residual scaling. **The vocabulary held here**
is a slice: ids are drawn from it, logits and loss are over it.

How it is compiled: the layers are **stacked and scanned**, each a
``jax.checkpoint``, and the scan carries **two** streams, ``x`` and ``r``;
the held experts of all layers are three leaves ``(L, n, D, F)``. Head
and loss run in blocks of ``head_block`` positions under ``jax.checkpoint``
(one block's logits alive at a time). The matrices are cast to the compute
dtype once a step and the train step differentiates that tree, as
``looped_lm.py`` says; the embedding is read as stored by the lookup and
cast inside the head, so **one float32 gradient sums the lookup's and the
head's**. float32 stay the residual stream, the norms, the convolutions,
the L2 norm, the rotation, the router end to end, and every matmul result.

Sharding: the batch over 'data', ``embed`` over the vocabulary on 'model',
the layers' leaves replicated (as ``block_diffusion_lm``: no multi-chip
cell runs this family).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.cca import cca_qkv
from ..ops.moe import held_row_tile, held_tile_stats, mlp_top1_routed
from . import kit
from .kit import constrainer, make_optimizer, mm, rmsnorm  # noqa: F401  (make_optimizer: the same optimizer)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class CCAMoELMConfig:
    """Published sizes (defaults: ZAYA1-8B's ``config.json``). ``held``
    names the experts whose weights live here (all by default);
    ``conv_kernels`` are ``cca_time0`` and ``cca_time1``."""

    vocab_size: int = 262272
    d_model: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 16
    expert_ff: int = 2048
    held: Tuple[int, ...] = tuple(range(16))
    router_dim: int = 256
    conv_kernels: Tuple[int, int] = (2, 2)
    rotary_factor: float = 0.5
    rope_theta: float = 5e6
    norm_eps: float = 1e-5
    head_block: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(set(self.held)) != len(self.held) or not all(0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"held expert ids {self.held} are not distinct ids below {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2 or self.rotary_dim % 2:
            raise ValueError(
                f"{self.n_heads} query heads over {self.n_kv_heads} KV heads (half of them shifted) of "
                f"{self.head_dim}, {self.rotary_dim} of it rotated"
            )

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_factor)

    @property
    def layer_matmul_params(self) -> int:
        """Parameters one position multiplies by in a layer: q, k, v, o,
        the per-head convolution, the router's four matrices, and of the
        held experts their expected share under even routing (``len(held)
        / n_experts`` of one expert a position)."""
        D, R, hd = self.d_model, self.router_dim, self.head_dim
        heads = self.n_heads + self.n_kv_heads
        attn = 2 * D * self.n_heads * hd + 2 * D * self.n_kv_heads * hd + heads * self.conv_kernels[1] * hd * hd
        router = D * R + 2 * R * R + R * self.n_experts
        return attn + router + round(len(self.held) / self.n_experts * 3 * D * self.expert_ff)

    @property
    def matmul_params_per_token(self) -> int:
        return self.n_layers * self.layer_matmul_params + self.vocab_size * self.d_model


# What the init below sets so that random routers spread their load evenly
# over the experts (``init_params`` says why each).
_BRANCH_OUT_SCALE = 0.3  # o and expert_down, against fan_in^-0.5 at the stream's own scale
_DECAY_INIT = 0.5  # gamma: the carry of the layer before counts half


def init_params(rng: jax.Array, cfg: CCAMoELMConfig) -> Params:
    """The parameter pytree, stacked over layers. Matrices are normal with
    std ``fan_in^-0.5``; the tied embedding with std ``D^-0.5``, so that the
    head's logits start at unit variance; norm scales 1, biases 0,
    temperatures 0, residual scaling at the identity (sigma 1, beta 0), the
    convolutions at the identity (``w0 = (0, .., 1)``, ``W1[h, last] = I``,
    the other taps 0), the selection bias 0, ``gamma`` 0.5. Three choices
    **keep random routers level**, as ``block_diffusion_lm.init_params``
    says a cell's step needs (a deployment's routers are trained level by
    a bias-update rule that is absent here, and the experts' loops cost what
    the routing sends them):

    - the residual branches write small: ``o`` and ``expert_down`` at 0.3
      of ``fan_in^-0.5``, times the embedding's ``D^-0.5``, so a position's
      stream stays its own token's embedding through the stack (an
      attention layer passes what its keys have in common whole and
      averages the rest away, until every position reads alike);
    - the router's second and third matrices have each column's mean over
      its inputs taken out: ``gelu`` is positive on average (0.28 at unit
      variance), so a random matrix after it adds one offset an expert,
      common to every token, a third the size of what tells tokens apart,
      and the argmax over 16 would favour the same few experts everywhere
      (PERF.md, PR 32, met the same under ``relu^2``);
    - convolutions at the identity keep q and k functions of their own
      position at the start, so scores tell keys apart by content."""
    c, dt = cfg, cfg.param_dtype
    L, D, n, F, R, hd = c.n_layers, c.d_model, len(c.held), c.expert_ff, c.router_dim, c.head_dim
    A, Akv, heads = c.n_heads * hd, c.n_kv_heads * hd, c.n_heads + c.n_kv_heads
    K0, K1 = c.conv_kernels
    keys = iter(jax.random.split(rng, 16))

    def norm(shape, fan_in, scale=1.0):
        return jax.random.normal(next(keys), shape, dt) * (scale * fan_in**-0.5)

    def centred(shape, fan_in):
        w = norm(shape, fan_in)
        return w - jnp.mean(w, axis=-2, keepdims=True)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def zeros(*shape):
        return jnp.zeros(shape, dt)

    out = _BRANCH_OUT_SCALE * D**-0.5
    layers = {
        "q": norm((L, D, A), D),
        "k": norm((L, D, Akv), D),
        "v1": norm((L, D, Akv // 2), D),
        "v2": norm((L, D, Akv // 2), D),
        "o": norm((L, A, D), A, out),
        "conv0_w": zeros(L, A + Akv, K0).at[..., -1].set(1.0),
        "conv0_b": zeros(L, A + Akv),
        "conv1_w": zeros(L, heads, K1, hd, hd).at[:, :, -1].set(jnp.eye(hd, dtype=dt)),
        "conv1_b": zeros(L, heads, hd),
        "temp": zeros(L, c.n_kv_heads),
        "router_down": norm((L, D, R), D),
        "router_down_b": zeros(L, R),
        "router_decay": jnp.full((L, R), _DECAY_INIT, dt),
        "router_norm_scale": ones(L, R),
        "router_w1": norm((L, R, R), R),
        "router_b1": zeros(L, R),
        "router_w2": centred((L, R, R), R),
        "router_b2": zeros(L, R),
        "router_w3": centred((L, R, c.n_experts), R),
        "router_bias": zeros(L, c.n_experts),
        "expert_gate": norm((L, n, D, F), D),
        "expert_up": norm((L, n, D, F), D),
        "expert_down": norm((L, n, F, D), F, out),
        "ln1_scale": ones(L, D),
        "ln2_scale": ones(L, D),
    }
    for sub in ("attn", "moe"):  # residual scaling: (x + res_bias) res_scale + (branch + out_bias) out_scale
        layers.update({f"{sub}_res_scale": ones(L, D), f"{sub}_res_bias": zeros(L, D),
                       f"{sub}_out_scale": ones(L, D), f"{sub}_out_bias": zeros(L, D)})
    return {"embed": norm((c.vocab_size, D), D), "layers": layers, "ln_f_scale": ones(D)}


def param_specs(cfg: CCAMoELMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh: ``embed`` over the
    vocabulary, every layer leaf replicated (module docstring)."""
    return kit.vocab_sharded(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))


# Leaves that feed a matmul in the compute dtype; the convolutions' and the
# router's products are float32 at full precision and stay as stored.
_MATRICES = {"q", "k", "v1", "v2", "o", "expert_gate", "expert_up", "expert_down"}


def compute_params(params: Params, cfg: CCAMoELMConfig) -> Params:
    """The tree the layers read: the matrices in the compute dtype, cast
    once a step; the embedding (the head casts it: module docstring), the
    scales, the convolutions and the router as stored."""
    return kit.cast_matrices(params, cfg.dtype, _MATRICES, head=False)


def expert_tile(cfg: CCAMoELMConfig, positions: int) -> int:
    """Rows of a row tile of the held experts' list, as
    ``block_diffusion_lm.expert_tile`` says."""
    del cfg
    return held_row_tile(positions)


def select_attention(cfg: CCAMoELMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the layers run for this mesh and shape."""
    return kit.route_name(cfg.n_heads, mesh, B, S)


def _res_scale(w: Params, sub: str, x: jax.Array, branch: jax.Array) -> jax.Array:
    with jax.named_scope("res_scale"):
        f32 = jnp.float32
        kept = (x + w[f"{sub}_res_bias"].astype(f32)) * w[f"{sub}_res_scale"].astype(f32)
        return kept + (branch + w[f"{sub}_out_bias"].astype(f32)) * w[f"{sub}_out_scale"].astype(f32)


def layer(
    w: Params, x: jax.Array, r: jax.Array, cfg: CCAMoELMConfig, attend: Callable, cs: Callable = lambda x, spec: x
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer on the stream ``x (B, S, D)`` and the router's carry ``r
    (B, S, R)``, both float32: the new ``x``, the new ``r`` and the chosen
    expert ids ``(B S, 1)``. ``w`` is the layer's slice of
    ``compute_params``' stacked leaves."""
    c = cfg
    B, S, _ = x.shape
    stream = P("data", None, None)
    a = rmsnorm(x, w["ln1_scale"], c.norm_eps).astype(c.dtype)
    q, k, v = cca_qkv(w, a, rope_theta=c.rope_theta, rotary_dim=c.rotary_dim)
    with jax.named_scope("cca_attn"):
        out = attend(q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype))
        out = mm(out.reshape(B, S, c.n_heads * c.head_dim), w["o"])
    x = cs(_res_scale(w, "attn", x, out), stream)
    b = rmsnorm(x, w["ln2_scale"], c.norm_eps)
    y, chosen, r = mlp_top1_routed(w, b, r, held=c.held, norm_eps=c.norm_eps)
    return cs(_res_scale(w, "moe", x, y), stream), cs(r, stream), chosen


def _run_layers(cparams: Params, tokens: jax.Array, cfg: CCAMoELMConfig, mesh: Optional[Mesh]):
    """The closed hidden state ``rms(x; g_f)`` ``(B, S, D)`` in float32
    and the chosen expert ids ``(L, B S, 1)``. ``cparams`` is
    ``compute_params``' tree. The scan carries the stream and the router's."""
    B, S = tokens.shape
    cs = constrainer(mesh)
    _, attend = kit.attention_route(cfg.n_heads, mesh, B, S)

    @jax.checkpoint
    def scanned(carry, w):
        x, r, chosen = layer(w, *carry, cfg, attend, cs)
        return (x, r), chosen

    x = cs(cparams["embed"][tokens].astype(jnp.float32), P("data", None, None))
    r = jnp.zeros((B, S, cfg.router_dim), jnp.float32)
    (x, _), chosen = jax.lax.scan(scanned, (x, r), cparams["layers"])
    return rmsnorm(x, cparams["ln_f_scale"], cfg.norm_eps), chosen


def _head(h: jax.Array, embed: jax.Array, cfg: CCAMoELMConfig, cs) -> jax.Array:
    """(B, positions, vocab) float32 logits against the tied embedding as stored:
    the cast to the compute dtype is here, so its transpose hands the
    embedding a float32 gradient."""
    with jax.named_scope("lm_head"):
        logits = jnp.matmul(h.astype(cfg.dtype), embed.astype(cfg.dtype).T, preferred_element_type=jnp.float32)
        return cs(logits, P("data", None, "model"))


def forward(params: Params, tokens: jax.Array, cfg: CCAMoELMConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    """(B, S) int32 tokens -> (B, S, vocab) float32 logits."""
    p = compute_params(params, cfg)
    return _head(_run_layers(p, tokens, cfg, mesh)[0], p["embed"], cfg, constrainer(mesh))


def chosen_experts(params: Params, tokens: jax.Array, cfg: CCAMoELMConfig) -> jax.Array:
    """The id ``(L, B S, 1)`` each position chose in each layer, from the
    forward pass the train step runs."""
    return _run_layers(compute_params(params, cfg), tokens, cfg, None)[1]


def routing_stats(params: Params, tokens: jax.Array, cfg: CCAMoELMConfig) -> Dict[str, jax.Array]:
    """What the routers did with this batch, ``(L,)`` a statistic:
    ``held_share``, the share of the positions whose expert is held here
    (``len(held) / n_experts`` under even routing), ``max_over_mean``, the
    most positions a held expert gets over their mean, ``held_counts``
    ``(L, n)``, and the loops' ``trips`` and ``tile_fill`` (``ops/moe.py``
    ``held_tile_stats``)."""
    chosen = chosen_experts(params, tokens, cfg)
    held = jnp.asarray(cfg.held, jnp.int32)
    counts = jnp.sum(chosen[:, None] == held[None, :, None, None], axis=(2, 3))  # (L, n)
    return {
        "held_counts": counts,
        "held_share": jnp.sum(counts, axis=1) / chosen.shape[1],
        "max_over_mean": jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts.astype(jnp.float32), axis=1), 1e-9),
        **held_tile_stats(counts, chosen.shape[1]),
    }


def _cross_entropy(h: jax.Array, embed: jax.Array, targets: jax.Array, cfg: CCAMoELMConfig, cs) -> jax.Array:
    """``kit.blocked_cross_entropy`` against the tied embedding."""
    return kit.blocked_cross_entropy(h, embed, targets, cfg.head_block, lambda h, e: _head(h, e, cfg, cs))


def _objective(cparams: Params, batch: Dict[str, jax.Array], cfg: CCAMoELMConfig, mesh: Optional[Mesh]):
    h, _ = _run_layers(cparams, batch["tokens"], cfg, mesh)
    return _cross_entropy(h, cparams["embed"], batch["targets"], cfg, constrainer(mesh))


def loss_fn(
    params: Params, batch: Dict[str, jax.Array], cfg: CCAMoELMConfig, *, mesh: Optional[Mesh] = None
) -> jax.Array:
    """Mean cross-entropy of ``batch["tokens"]``'s logits against
    ``batch["targets"]`` (the next tokens), over the vocabulary held here."""
    return _objective(compute_params(params, cfg), batch, cfg, mesh)


def state_specs(cfg: CCAMoELMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output (``kit.state_specs``)."""
    return kit.state_specs(param_specs(cfg), state)


def init_state(
    rng: jax.Array, cfg: CCAMoELMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Dict[str, Any]:
    """{params, opt_state, step}, the whole of it placed per ``state_specs``
    under a mesh."""
    return kit.init_state(init_params(rng, cfg), param_specs(cfg), tx, mesh)


def make_train_step(
    cfg: CCAMoELMConfig, tx: optax.GradientTransformation, *, mesh: Optional[Mesh] = None
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit
    (``kit.make_train_step``). Every leaf takes adamw's update, the selection
    bias too."""
    gauges = {
        "cca_moe_lm.layers": cfg.n_layers,
        "cca_moe_lm.experts_held": len(cfg.held),
        "cca_moe_lm.matmul_params_per_token": cfg.matmul_params_per_token,
    }
    return kit.make_train_step(
        _objective, lambda p: compute_params(p, cfg), lambda state, batch: (batch, cfg, mesh), tx, mesh,
        param_specs(cfg), gauges,
    )
