"""Looped language model: one weight-shared stack applied several times.

The published block of Ouro (ByteDance; Zhu et al., "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741), as a train state
on the same path as the transformer (``init_state`` / ``make_train_step`` /
``CheckpointManager``). With T = ``ut_steps`` and L layers::

    h0 = E[tokens]                                   (no positional term, no scale)
    for t in 1..T:   u = h(t-1)
        for l in 1..L (the SAME weights at every t):
            a = rms(u; g1);  q, k, v = a Wq, a Wk, a Wv;  q, k <- rope(q, k; theta)
            u <- u + rms( causal_softmax(q k^T / sqrt(hd)) v Wo ; g2 )
            m = rms(u; g3);  u <- u + rms( (silu(m Wgate) * (m Wup)) Wdown ; g4 )
        h(t) = rms(u; g_f)                 closes every pass; pass t+1 starts from it
        logits(t) = h(t) W_head^T          (untied)
        lambda_t = sigmoid(h(t) w_g + b_g) exit gate
    p_t = lambda_t prod_{j<t}(1 - lambda_j)  (t < T),   p_T = prod_{j<T}(1 - lambda_j)
    loss = mean over positions of [ sum_t p_t CE(logits(t), target) - beta H(p) ]

How it is compiled: an outer ``lax.scan`` over the passes whose body closes
over the stacked layer weights (so their gradient is the sum over the
passes) and runs an inner ``lax.scan`` over the layers. T x L layer
applications of activations do not fit beside 12 B/param of state, so each
layer application and each pass's head + cross-entropy is a
``jax.checkpoint``: the backward pass keeps one residual per application and
recomputes the rest. ``loss_fn`` never holds more than one pass's logits.
The matrices are cast to the compute dtype once a step (``compute_params``),
not once per application, and the train step differentiates that tree.

Sharding follows the transformer's ('data','model') layout: Megatron
column->row pairs, the residual stream sequence-sharded over 'model'
between sublayers, ``embed`` and ``head`` sharded over the vocabulary.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..ops.attention import causal_attention_route
from .transformer import make_optimizer  # noqa: F401  (the same optimizer)

Params = Dict[str, Any]
_ATTN_BLOCK = 512  # the tiling every route targets, as TransformerConfig's default


@dataclasses.dataclass(frozen=True)
class LoopedLMConfig:
    """Published sizes only (defaults: Ouro-2.6B's ``config.json``)."""

    vocab_size: int = 49152
    d_model: int = 2048
    n_heads: int = 16
    head_dim: int = 128
    n_layers: int = 48
    d_ff: int = 5632
    ut_steps: int = 4
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    exit_beta: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def layer_matmul_params(self) -> int:
        """Matrix parameters of one layer: q, k, v, o and gate, up, down."""
        return 4 * self.d_model * self.n_heads * self.head_dim + 3 * self.d_model * self.d_ff

    @property
    def matmul_params_per_token(self) -> int:
        """Parameters one token's training forward pass multiplies by: the
        stack and the head once per pass, and the gate's vector."""
        per_pass = self.n_layers * self.layer_matmul_params + self.vocab_size * self.d_model
        return self.ut_steps * per_pass + self.d_model


def init_params(rng: jax.Array, cfg: LoopedLMConfig) -> Params:
    """The parameter pytree: the published matrices, unfused, stacked over
    layers (one compiled block whatever the depth)."""
    c = cfg
    L, D, A, F = c.n_layers, c.d_model, c.n_heads * c.head_dim, c.d_ff
    keys = iter(jax.random.split(rng, 10))

    def norm(shape, fan_in):
        return jax.random.normal(next(keys), shape, c.param_dtype) * (fan_in**-0.5)

    def ones(*shape):
        return jnp.ones(shape, c.param_dtype)

    layers = {
        "q": norm((L, D, A), D),
        "k": norm((L, D, A), D),
        "v": norm((L, D, A), D),
        "o": norm((L, A, D), A),
        "gate": norm((L, D, F), D),
        "up": norm((L, D, F), D),
        "down": norm((L, F, D), F),
        "ln1_scale": ones(L, D),
        "ln2_scale": ones(L, D),
        "ln3_scale": ones(L, D),
        "ln4_scale": ones(L, D),
    }
    return {
        "embed": norm((c.vocab_size, D), D),
        "head": norm((c.vocab_size, D), D),
        "layers": layers,
        "ln_f_scale": ones(D),
        "exit_gate_w": norm((D,), D),
        "exit_gate_b": jnp.zeros((), c.param_dtype),
    }


def param_specs(cfg: LoopedLMConfig) -> Params:
    """PartitionSpecs on a ('data','model') mesh, as ``transformer.param_specs``:
    column-parallel q, k, v, gate, up; row-parallel o, down; ``embed`` and
    ``head`` over the vocabulary (local lookup and local head matmul onto
    vocabulary-sharded logits); scales and the gate replicated."""
    col, row, rep = P(None, None, "model"), P(None, "model", None), P(None, None)
    layers = {
        "q": col, "k": col, "v": col, "o": row,
        "gate": col, "up": col, "down": row,
        "ln1_scale": rep, "ln2_scale": rep, "ln3_scale": rep, "ln4_scale": rep,
    }
    return {
        "embed": P("model", None),
        "head": P("model", None),
        "layers": layers,
        "ln_f_scale": P(None),
        "exit_gate_w": P(None),
        "exit_gate_b": P(),
    }


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Statistics, scaling and result in float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on (B, S, H, hd), half-split pairing (i, i + hd/2),
    angles, rotation and result in float32."""
    S, hd = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq  # (S, hd/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """(T, ...) gate logits -> (T, ...) exit probabilities that sum to 1
    over T: ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``, the last pass
    taking what is left. Products in log space (``log_sigmoid``)."""
    g = gate_logits.astype(jnp.float32)
    log_stay = jax.nn.log_sigmoid(-g)  # log(1 - lambda_t)
    stayed = jnp.cumsum(log_stay, axis=0) - log_stay  # log prod_{j<t}(1 - lambda_j)
    log_p = jnp.concatenate([(stayed + jax.nn.log_sigmoid(g))[:-1], stayed[-1:]], axis=0)
    return jnp.exp(log_p)


def _attention_route(cfg: LoopedLMConfig, mesh: Optional[Mesh], B: int, S: int):
    """The shared dispatch's "auto": what the backend, the mesh and S allow."""
    return causal_attention_route("auto", _ATTN_BLOCK, cfg.n_heads, mesh, B, S)


def select_attention(cfg: LoopedLMConfig, mesh: Optional[Mesh], B: int, S: int) -> str:
    """The name of the attention route the block runs for this mesh and shape."""
    return _attention_route(cfg, mesh, B, S)[0]


def _constrainer(mesh: Optional[Mesh]):
    if mesh is None:
        return lambda x, spec: x
    return lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def compute_params(params: Params, cfg: LoopedLMConfig) -> Params:
    """The tree the passes read: every matrix that feeds a matmul (the
    layers' seven stacks and the head) in the compute dtype, cast once a
    step and not once per application; the embedding, the norm scales and
    the gate stay as stored."""
    layers = {
        k: v if k.startswith("ln") else v.astype(cfg.dtype) for k, v in params["layers"].items()
    }
    return {**params, "layers": layers, "head": params["head"].astype(cfg.dtype)}


def _run_passes(
    cparams: Params,
    tokens: jax.Array,
    cfg: LoopedLMConfig,
    mesh: Optional[Mesh],
    per_pass: Callable[[jax.Array], Any],
):
    """``per_pass(h(t))`` for every pass, stacked over the passes.
    ``cparams`` is ``compute_params``' tree.

    The residual stream is float32 between sublayers and between passes:
    only matmul operands are rounded to the compute dtype, and what a
    matmul feeds into a norm, the rotation or the gated product leaves it
    in float32 (the MXU accumulates in float32 anyway). Through T x L
    applications of the same weights rounding compounds: with a bfloat16
    stream, 4 x 8 applications differ from the float32 reference by 5 % of
    the logits' range at the 99th percentile (CPU, widths 256 and 512)."""
    c = cfg
    B, S = tokens.shape
    cs = _constrainer(mesh)
    _, attend = _attention_route(c, mesh, B, S)
    residual = P("data", "model", None)  # sequence-sharded between sublayers (Megatron sp)

    def mm(x, w):
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)

    @jax.checkpoint
    def block(u, w):
        with jax.named_scope("block"):
            u = cs(u, residual)
            a = cs(_rmsnorm(u, w["ln1_scale"], c.norm_eps).astype(c.dtype), P("data", None, None))

            def heads(t, rotate):
                t = cs(t, P("data", None, "model")).reshape(B, S, c.n_heads, c.head_dim)
                t = _rope(t, c.rope_theta) if rotate else t
                return cs(t.astype(c.dtype), P("data", None, "model", None))

            attn = attend(heads(mm(a, w["q"]), True), heads(mm(a, w["k"]), True), heads(mm(a, w["v"]), False))
            attn = cs(attn.reshape(B, S, c.n_heads * c.head_dim), P("data", None, "model"))
            u = u + _rmsnorm(cs(mm(attn, w["o"]), residual), w["ln2_scale"], c.norm_eps)

            m = cs(_rmsnorm(u, w["ln3_scale"], c.norm_eps).astype(c.dtype), P("data", None, None))
            ff = (jax.nn.silu(mm(m, w["gate"])) * mm(m, w["up"])).astype(c.dtype)
            ff = cs(ff, P("data", None, "model"))
            u = u + _rmsnorm(cs(mm(ff, w["down"]), residual), w["ln4_scale"], c.norm_eps)
            return u, None

    def one_pass(h, _):
        with jax.named_scope("pass"):
            u, _ = jax.lax.scan(block, h, cparams["layers"])
            h = _rmsnorm(cs(u, P("data", None, None)), cparams["ln_f_scale"], c.norm_eps)
            return h, per_pass(h)

    h0 = cs(cparams["embed"][tokens].astype(jnp.float32), residual)
    _, outs = jax.lax.scan(one_pass, h0, None, length=c.ut_steps)
    return outs


def _head(h: jax.Array, head: jax.Array, cfg: LoopedLMConfig, cs) -> jax.Array:
    """(B, S, vocab) float32 logits of one pass."""
    with jax.named_scope("lm_head"):
        logits = jnp.matmul(h.astype(cfg.dtype), head.T, preferred_element_type=jnp.float32)
        return cs(logits, P("data", None, "model"))


def _exit_gate(h: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """(B, S) gate logits, before the sigmoid, in float32."""
    with jax.named_scope("exit_gate"):
        return h @ w.astype(jnp.float32) + b.astype(jnp.float32)


def pass_outputs(
    params: Params, tokens: jax.Array, cfg: LoopedLMConfig, mesh: Optional[Mesh] = None
) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 -> every pass's logits (T, B, S, vocab) and gate logits
    (T, B, S), both float32."""
    cs = _constrainer(mesh)
    p = compute_params(params, cfg)
    return _run_passes(
        p, tokens, cfg, mesh,
        lambda h: (_head(h, p["head"], cfg, cs), _exit_gate(h, p["exit_gate_w"], p["exit_gate_b"])),
    )


def forward(
    params: Params, tokens: jax.Array, cfg: LoopedLMConfig, mesh: Optional[Mesh] = None
) -> Tuple[jax.Array, jax.Array]:
    """(B, S) int32 -> the T passes' logits (T, B, S, vocab) and the exit
    distribution (T, B, S), which sums to 1 over T."""
    logits, gate_logits = pass_outputs(params, tokens, cfg, mesh)
    return logits, exit_distribution(gate_logits)


def _objective(cparams: Params, batch: Dict[str, jax.Array], cfg: LoopedLMConfig, mesh: Optional[Mesh]):
    cs = _constrainer(mesh)
    targets = batch["targets"]

    @jax.checkpoint
    def head_and_ce(h, head, gate_w, gate_b):
        logits = _head(h, head, cfg, cs)
        at_target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - at_target, _exit_gate(h, gate_w, gate_b)

    ce, gate_logits = _run_passes(
        cparams, batch["tokens"], cfg, mesh,
        lambda h: head_and_ce(h, cparams["head"], cparams["exit_gate_w"], cparams["exit_gate_b"]),
    )
    p = exit_distribution(gate_logits)  # (T, B, S)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, jnp.finfo(jnp.float32).tiny)), axis=0)
    return jnp.mean(jnp.sum(p * ce, axis=0) - cfg.exit_beta * entropy)


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: LoopedLMConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Expected cross-entropy under the exit distribution, less
    ``exit_beta`` times its entropy; one pass's logits alive at a time."""
    return _objective(compute_params(params, cfg), batch, cfg, mesh)


def state_specs(cfg: LoopedLMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output: adam moments
    inherit their parameter's spec, scalars replicated."""
    from ..parallel.mesh import optax_state_specs

    p_specs = param_specs(cfg)
    return {
        "params": p_specs,
        "opt_state": optax_state_specs(p_specs, state["opt_state"]),
        "step": P(),
    }


def init_state(
    rng: jax.Array,
    cfg: LoopedLMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Dict[str, Any]:
    """{params, opt_state, step}, the whole of it placed per
    ``state_specs`` under a mesh (scalars too: see
    ``transformer.init_state``)."""
    params = init_params(rng, cfg)
    if mesh is not None:
        from ..parallel.mesh import shard_pytree

        params = shard_pytree(params, param_specs(cfg), mesh)
    state = {"params": params, "opt_state": tx.init(params), "step": jnp.zeros((), jnp.int32)}
    if mesh is not None:
        state = shard_pytree(state, state_specs(cfg, state), mesh)
    return state


def make_train_step(
    cfg: LoopedLMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit.
    Under a mesh the returned state is pinned to ``state_specs`` (see
    ``transformer.make_train_step``)."""
    # What is about to be compiled, on the bus for `stats -v` and the exporters.
    telemetry.gauge_set("looped_lm.ut_steps", cfg.ut_steps)
    telemetry.gauge_set("looped_lm.layer_applications", cfg.ut_steps * cfg.n_layers)
    telemetry.gauge_set("looped_lm.matmul_params_per_token", cfg.matmul_params_per_token)

    def train_step(state, batch):
        # Gradients are taken with respect to the tree the passes read, so
        # the matrices' come in the compute dtype, summed over the passes
        # in it: float32 copies of them (and float32 accumulators in both
        # backward scans) do not fit beside 12 B/param and the recompute
        # working set. adamw's moments and update are float32.
        loss, grads = jax.value_and_grad(_objective)(
            compute_params(state["params"], cfg), batch, cfg, mesh
        )
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        new_state = {
            "params": optax.apply_updates(state["params"], updates),
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        if mesh is not None:
            new_state = jax.tree_util.tree_map(
                lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec)),
                new_state,
                state_specs(cfg, new_state),
            )
        return new_state, loss

    return train_step
