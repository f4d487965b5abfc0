"""Block-diffusion language model: a routed-expert decoder trained to
denoise blocks of a sequence, one network over the clean and the noised copy.

The ``sdar_moe`` stack (JetLM; the ``config.json`` of SDAR-30B-A3B-Chat is a
``qwen3_moe`` config under that model type), as a train state on the same
path as the other families (``init_state`` / ``make_train_step`` /
``CheckpointManager``). The objective is block diffusion (Arriola et al.,
"Block Diffusion", arXiv:2503.09573): masked diffusion inside a block of
``block_length`` positions, autoregressive between blocks. With
``rms(x; g) = x rsqrt(mean(x^2) + eps) g`` in float32, S the sequence length
and ``blk(i) = i // block_length``::

    per sequence and block:  t ~ U(t_min, 1);  a position of the block is masked with probability t
    x = [tokens ; where(masked, mask_token_id, tokens)]        P = 2 S positions, both copies carry ids 0 .. S-1
    h = E[x]
    per layer:
        a = rms(h; g1);  q = a Wq (H heads), k, v = a Wk, a Wv (H_kv heads), no bias
        q, k <- rms over the head's hd (g_q, g_k);  q, k <- rope(theta, pairs (i, i + hd/2)) at the position id
        h <- h + softmax(q k^T / sqrt(hd) + M) v Wo,   a KV head serving H / H_kv query heads
        b = rms(h; g2);  p = softmax(b Wr) over ALL experts (float32, full precision)
        the top_k by p;  w_e = p_e / (sum of the chosen p)
        h <- h + sum over the chosen experts HELD HERE of w_e (silu(b G_e) * (b U_e)) D_e
    logits = rms(h[noised half]; g_f) W_head^T (untied), at the noised positions only
    loss = 1 / (B S) * sum over masked positions of (1 / t_block) CE(logits there, the clean token there)

    M: clean query -> clean key iff blk(key) <= blk(query);  clean -> noised never
       noised query -> clean key iff blk(key) < blk(query);  noised -> noised iff blk(key) == blk(query)

A logit answers for its own position (no shift), so ``batch["targets"]`` is
not read. The noise is part of the state: ``state["rng"]`` is a saved leaf,
the step draws from ``fold_in(rng, step)`` and hands on a new ``rng``, so a
resumed job continues the uninterrupted one's masks bit for bit and one
that lost the key does not. No auxiliary balancing loss (the config has no
coefficient). **What is not here**: generation by iterated unmasking.

**The chip's share of the experts** is ``hybrid_lm``'s: the layer is told
the ids it holds (``cfg.held``), scores and chooses over all ``n_experts``,
normalises over all ``top_k`` chosen, and adds only its own experts' terms
(``ops/moe.py`` ``softmax_topk_routed``; no token is dropped).

How it is compiled: the layers are **stacked and scanned** (one compiled
block whatever the depth, as ``transformer.py`` and ``looped_lm.py``), each
a ``jax.checkpoint``; the held experts of all layers are three leaves
``(L, n, D, F)``. The mask goes through the one attention dispatch
(``ops/attention.py`` ``causal_attention_route(..., mask=)``): on the chip
the Pallas kernels visit only the tiles that hold a live score, 80 of 256
at S 4096 and tile 512 (``BlockDiffusionMask.live_tiles``), and no
``(2S)^2`` tensor exists. The matrices are cast to the compute dtype once
a step and the train step differentiates that tree, as ``looped_lm.py``
says; float32 stay the residual stream, the norms, the rotation, the
router, and every matmul result.

Sharding: the batch over 'data', ``embed`` and ``head`` over the vocabulary
on 'model', the layers' leaves replicated (as ``hybrid_lm``: no
tensor-parallel layout of the expert loops exists, and no multi-chip cell
runs this family).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..ops.attention import BlockDiffusionMask, causal_attention_route
from ..ops.moe import held_row_tile, held_tile_stats, softmax_topk_routed
from .transformer import make_optimizer  # noqa: F401  (the same optimizer)

Params = Dict[str, Any]
_ATTN_BLOCK = 512  # the tiling every route targets, as TransformerConfig's default


@dataclasses.dataclass(frozen=True)
class BlockDiffusionLMConfig:
    """Published sizes (defaults: SDAR-30B-A3B-Chat's ``config.json``) and
    the objective's two constants, which that file does not give. ``held``
    names the experts whose weights live here (all by default). The mask
    token is the vocabulary's last id."""

    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 128
    top_k: int = 8
    expert_ff: int = 768
    held: Tuple[int, ...] = tuple(range(128))
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    block_length: int = 4
    t_min: float = 1e-3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(set(self.held)) != len(self.held) or not all(0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"held expert ids {self.held} are not distinct ids below {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(f"{self.n_heads} query heads over {self.n_kv_heads} KV heads of {self.head_dim}")

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1

    @property
    def layer_matmul_params(self) -> int:
        """Parameters one position multiplies by in a layer: q, k, v, o, the
        router, and of the held experts their expected share under even
        routing (``top_k * len(held) / n_experts`` experts a position)."""
        D = self.d_model
        attn = 2 * D * self.n_heads * self.head_dim + 2 * D * self.n_kv_heads * self.head_dim
        experts = round(self.top_k * len(self.held) / self.n_experts * 3 * D * self.expert_ff)
        return attn + D * self.n_experts + experts

    @property
    def matmul_params_per_token(self) -> int:
        """Per token of the batch: the stack runs over its clean and its
        noised position, the head over the noised one."""
        return 2 * self.n_layers * self.layer_matmul_params + self.vocab_size * self.d_model


# What the init below sets so that random routers spread their load evenly
# over the experts (``init_params`` says why each).
_BRANCH_OUT_SCALE = 0.3  # o and expert_down, against fan_in^-0.5
_MASK_ROW_SCALE = 0.03  # the mask token's embedding, against the other rows'
_Q_NORM_INIT = 3.0  # the first layer's q-norm scale: its scores start at std 3


def init_params(rng: jax.Array, cfg: BlockDiffusionLMConfig) -> Params:
    """The parameter pytree, stacked over layers: matrices normal with std
    ``fan_in^-0.5``, the embedding with std 1, norm scales 1, but for three
    choices that **keep random routers level**. A deployment's routers are
    trained level (its balancing loss is absent here: the config has no
    coefficient); random ones are not, and the experts' loops cost what the
    routing sends them, so the step's time would move with the seed
    (PERF.md, PRs 32 and 34: at the plain init the held experts drew 0.1 to
    2.2 times their share by seed and layer). A router sees ``rms(h)``, and
    the loads are level where that differs from position to position:

    - the residual branches write small (``o`` and ``expert_down`` at 0.3
      of ``fan_in^-0.5``), so a clean position's stream stays its own
      token's embedding through the stack. An attention layer passes a
      component common to its keys unattenuated while it averages the rest
      away, layer after layer, until every position reads alike;
    - the mask token's embedding is 0.03 of the others', so a masked
      position's stream is what its attention brings it and not the one
      vector all masked positions share (a quarter of all positions would
      choose one set of experts);
    - the first layer's q-norm scale starts at 3: scores of std 3 pick a few
      keys by content and relative position where scores of std 1 average
      thousands, which is what tells one masked position from the next;
      what that layer brings stays a masked position's stream, because the
      soft layers after it add little. They start at 1: a sharp softmax
      multiplies what bfloat16 has rounded off in the stream it reads, and
      six of them in a row put 4 % between a masked position's logits and
      the float32 reference's where one puts 1 % (PERF.md, PR 34)."""
    c, dt = cfg, cfg.param_dtype
    L, D, n, F = c.n_layers, c.d_model, len(c.held), c.expert_ff
    A, Akv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    keys = iter(jax.random.split(rng, 10))

    def norm(shape, fan_in, scale=1.0):
        return jax.random.normal(next(keys), shape, dt) * (scale * fan_in**-0.5)

    def ones(*shape):
        return jnp.ones(shape, dt)

    layers = {
        "q": norm((L, D, A), D),
        "k": norm((L, D, Akv), D),
        "v": norm((L, D, Akv), D),
        "o": norm((L, A, D), A, _BRANCH_OUT_SCALE),
        "router": norm((L, D, c.n_experts), D),
        "expert_gate": norm((L, n, D, F), D),
        "expert_up": norm((L, n, D, F), D),
        "expert_down": norm((L, n, F, D), F, _BRANCH_OUT_SCALE),
        "ln1_scale": ones(L, D),
        "ln2_scale": ones(L, D),
        "q_norm_scale": ones(L, c.head_dim).at[0].set(_Q_NORM_INIT),
        "k_norm_scale": ones(L, c.head_dim),
    }
    embed = norm((c.vocab_size, D), 1)
    return {
        "embed": embed.at[c.mask_id].multiply(_MASK_ROW_SCALE),
        "head": norm((c.vocab_size, D), D),
        "layers": layers,
        "ln_f_scale": ones(D),
    }


def param_specs(cfg: BlockDiffusionLMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh: ``embed`` and ``head`` over
    the vocabulary, every layer leaf replicated (module docstring)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = jax.tree_util.tree_map(lambda x: P(*([None] * x.ndim)), shapes)
    return {**specs, "embed": P("model", None), "head": P("model", None)}


# Leaves that feed a matmul in the compute dtype; the router's product is
# float32 at full precision and stays as stored.
_MATRICES = {"q", "k", "v", "o", "expert_gate", "expert_up", "expert_down"}


def compute_params(params: Params, cfg: BlockDiffusionLMConfig) -> Params:
    """The tree the layers read: the matrices and the head in the compute
    dtype, cast once a step; the embedding, the scales and the router as
    stored."""
    layers = {k: v.astype(cfg.dtype) if k in _MATRICES else v for k, v in params["layers"].items()}
    return {**params, "layers": layers, "head": params["head"].astype(cfg.dtype)}


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Statistics, scaling and result in float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, ids: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on (B, P, H, hd) at the position ids ``(P,)``,
    half-split pairing (i, i + hd/2) as ``looped_lm._rope``; angles,
    rotation and result in float32."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = ids.astype(jnp.float32)[:, None] * inv_freq  # (P, hd/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(x, w):
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def expert_tile(cfg: BlockDiffusionLMConfig, positions: int) -> int:
    """Rows of a row tile of the held experts' list (``ops/moe.py``
    ``_held_experts``): what an expert's load is rounded up to, and the unit
    ``routing_stats`` counts ``trips`` in. It follows from ``positions``
    alone: the experts' widths choose the products' column blocks, not the
    row tile."""
    del cfg
    return held_row_tile(positions)


def attention_mask(cfg: BlockDiffusionLMConfig, S: int) -> BlockDiffusionMask:
    """The mask over the ``2 S`` positions of a sequence of ``S``."""
    return BlockDiffusionMask(half=S, block=cfg.block_length)


def _attention_route(cfg: BlockDiffusionLMConfig, mesh: Optional[Mesh], B: int, S: int):
    """The shared dispatch's "auto" under the block-diffusion mask: what the
    backend, the mesh and S allow."""
    return causal_attention_route("auto", _ATTN_BLOCK, cfg.n_heads, mesh, B, 2 * S, mask=attention_mask(cfg, S))


def select_attention(cfg: BlockDiffusionLMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the layers run for this mesh and
    sequences of ``S`` tokens (``2 S`` positions)."""
    return _attention_route(cfg, mesh, B, S)[0]


def _constrainer(mesh: Optional[Mesh]):
    if mesh is None:
        return lambda x, spec: x
    return lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def draw_noise(key: jax.Array, B: int, S: int, cfg: BlockDiffusionLMConfig) -> Tuple[jax.Array, jax.Array]:
    """One step's noise: ``masked (B, S)`` bool and each position's block
    level ``t (B, S)`` float32. ``t ~ U(t_min, 1)`` a sequence and block
    (the linear schedule: a position is masked with probability t)."""
    if S % cfg.block_length:
        raise ValueError(f"blocks of {cfg.block_length} do not tile a sequence of {S}")
    with jax.named_scope("bd_noise"):
        k_t, k_m = jax.random.split(key)
        t = jax.random.uniform(k_t, (B, S // cfg.block_length), jnp.float32, cfg.t_min, 1.0)
        t = jnp.repeat(t, cfg.block_length, axis=1)
        return jax.random.uniform(k_m, (B, S), jnp.float32) < t, t


def _run_layers(cparams: Params, tokens: jax.Array, masked: jax.Array, cfg: BlockDiffusionLMConfig, mesh: Optional[Mesh]):
    """The closed hidden state of the noised half ``rms(h[:, S:]; g_f)``
    ``(B, S, D)`` in float32 and the chosen expert ids ``(L, B * 2S, k)``.
    ``cparams`` is ``compute_params``' tree."""
    c = cfg
    B, S = tokens.shape
    cs = _constrainer(mesh)
    _, attend = _attention_route(c, mesh, B, S)
    ids = jnp.tile(jnp.arange(S), 2)  # both copies carry 0 .. S-1
    stream = P("data", None, None)

    @jax.checkpoint
    def layer(h, w):
        with jax.named_scope("attn_bd"):
            a = _rmsnorm(h, w["ln1_scale"], c.norm_eps).astype(c.dtype)

            def heads(t, n, scale):
                t = t.reshape(B, 2 * S, n, c.head_dim)
                if scale is not None:
                    t = _rope(_rmsnorm(t, scale, c.norm_eps), ids, c.rope_theta)
                return t.astype(c.dtype)

            out = attend(
                heads(_mm(a, w["q"]), c.n_heads, w["q_norm_scale"]),
                heads(_mm(a, w["k"]), c.n_kv_heads, w["k_norm_scale"]),
                heads(_mm(a, w["v"]), c.n_kv_heads, None),
            )
            h = cs(h + _mm(out.reshape(B, 2 * S, c.n_heads * c.head_dim), w["o"]), stream)
        b = _rmsnorm(h, w["ln2_scale"], c.norm_eps)
        y, chosen = softmax_topk_routed(w, b, top_k=c.top_k, held=c.held)
        return cs(h + y, stream), chosen

    x = jnp.concatenate([tokens, jnp.where(masked, c.mask_id, tokens)], axis=1)
    h = cs(cparams["embed"][x].astype(jnp.float32), stream)
    h, chosen = jax.lax.scan(layer, h, cparams["layers"])
    return _rmsnorm(h[:, S:], cparams["ln_f_scale"], c.norm_eps), chosen


def _head(h: jax.Array, head: jax.Array, cs) -> jax.Array:
    """(B, S, vocab) float32 logits."""
    with jax.named_scope("lm_head"):
        logits = jnp.matmul(h.astype(head.dtype), head.T, preferred_element_type=jnp.float32)
        return cs(logits, P("data", None, "model"))


def forward(
    params: Params, tokens: jax.Array, masked: jax.Array, cfg: BlockDiffusionLMConfig, mesh: Optional[Mesh] = None
) -> jax.Array:
    """(B, S) int32 tokens and the (B, S) bool of masked positions ->
    (B, S, vocab) float32 logits at the noised copy's positions."""
    p = compute_params(params, cfg)
    return _head(_run_layers(p, tokens, masked, cfg, mesh)[0], p["head"], _constrainer(mesh))


def chosen_experts(params: Params, tokens: jax.Array, masked: jax.Array, cfg: BlockDiffusionLMConfig) -> jax.Array:
    """The ids ``(L, B * 2S, top_k)`` each position chose in each layer,
    from the forward pass the train step runs."""
    return _run_layers(compute_params(params, cfg), tokens, masked, cfg, None)[1]


def routing_stats(params: Params, tokens: jax.Array, masked: jax.Array, cfg: BlockDiffusionLMConfig) -> Dict[str, jax.Array]:
    """What the routers did with this batch, ``(L,)`` a statistic:
    ``held_share``, the share of the ``top_k * positions`` assignments that
    fall on experts held here (``len(held) / n_experts`` under even
    routing), ``max_over_mean``, the most positions a held expert gets
    over their mean (the expert loops' longest trip over the average one),
    ``held_counts`` ``(L, n)``, the positions each held expert gets, and
    the loops' ``trips`` and ``tile_fill`` (``ops/moe.py``
    ``held_tile_stats``)."""
    chosen = chosen_experts(params, tokens, masked, cfg)
    held = jnp.asarray(cfg.held, jnp.int32)
    counts = jnp.sum(chosen[:, None] == held[None, :, None, None], axis=(2, 3))  # (L, n)
    return {
        "held_counts": counts,
        "held_share": jnp.sum(counts, axis=1) / (chosen.shape[1] * chosen.shape[2]),
        "max_over_mean": jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts.astype(jnp.float32), axis=1), 1e-9),
        **held_tile_stats(counts, chosen.shape[1]),
    }


def _objective(cparams: Params, tokens: jax.Array, key: jax.Array, cfg: BlockDiffusionLMConfig, mesh: Optional[Mesh]):
    cs = _constrainer(mesh)
    B, S = tokens.shape
    masked, t = draw_noise(key, B, S, cfg)

    @jax.checkpoint
    def head_and_ce(h, head):
        logits = _head(h, head, cs)
        at_token = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - at_token
        return jnp.sum(jnp.where(masked, ce / t, 0.0)) / (B * S)

    return head_and_ce(_run_layers(cparams, tokens, masked, cfg, mesh)[0], cparams["head"])


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: BlockDiffusionLMConfig,
    *,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """The block-diffusion loss of ``batch["tokens"]`` under the noise drawn
    from ``key``: cross-entropy at the masked positions, each weighted by
    ``1 / t`` of its block, over ``B S``, on the vocabulary held here."""
    return _objective(compute_params(params, cfg), batch["tokens"], key, cfg, mesh)


def noise_key(state: Dict[str, Any]) -> jax.Array:
    """The key a step on ``state`` draws its noise from."""
    return jax.random.fold_in(state["rng"], state["step"])


def state_specs(cfg: BlockDiffusionLMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output: adam moments
    inherit their parameter's spec, the scalars and the key replicated."""
    from ..parallel.mesh import optax_state_specs

    p_specs = param_specs(cfg)
    return {
        "params": p_specs,
        "opt_state": optax_state_specs(p_specs, state["opt_state"]),
        "step": P(),
        "rng": P(None),
    }


def init_state(
    rng: jax.Array,
    cfg: BlockDiffusionLMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Dict[str, Any]:
    """{params, opt_state, step, rng}, the whole of it placed per
    ``state_specs`` under a mesh. ``rng`` is a raw ``PRNGKey``; half of it
    becomes the noise key, raw key data (``uint32[2]``) and a leaf like
    any other."""
    k_params, k_noise = jax.random.split(rng)
    params = init_params(k_params, cfg)
    if mesh is not None:
        from ..parallel.mesh import shard_pytree

        params = shard_pytree(params, param_specs(cfg), mesh)
    state = {
        "params": params,
        "opt_state": tx.init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": k_noise,
    }
    if mesh is not None:
        state = shard_pytree(state, state_specs(cfg, state), mesh)
    return state


def make_train_step(
    cfg: BlockDiffusionLMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit.
    ``batch["tokens"]`` is read, ``batch["targets"]`` is not. Under a mesh
    the returned state is pinned to ``state_specs``."""
    # What is about to be compiled, on the bus for `stats -v` and the exporters.
    telemetry.gauge_set("block_diffusion_lm.layers", cfg.n_layers)
    telemetry.gauge_set("block_diffusion_lm.experts_held", len(cfg.held))
    telemetry.gauge_set("block_diffusion_lm.matmul_params_per_token", cfg.matmul_params_per_token)

    def train_step(state, batch):
        # Gradients are taken with respect to the tree the layers read, so
        # the matrices' come in the compute dtype (looped_lm.py says why);
        # adamw's moments and update are float32.
        params = state["params"]
        loss, grads = jax.value_and_grad(_objective)(
            compute_params(params, cfg), batch["tokens"], noise_key(state), cfg, mesh
        )
        updates, opt_state = tx.update(grads, state["opt_state"], params)
        new_state = {
            "params": optax.apply_updates(params, updates),
            "opt_state": opt_state,
            "step": state["step"] + 1,
            # Handed on, so that two jobs that reach one step number from
            # different keys, or from one key by another path, do not share masks.
            "rng": jax.random.split(state["rng"])[0],
        }
        if mesh is not None:
            new_state = jax.tree_util.tree_map(
                lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec)),
                new_state,
                state_specs(cfg, new_state),
            )
        return new_state, loss

    return train_step
