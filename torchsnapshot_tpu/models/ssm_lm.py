"""SSM language model: linear-time sequence mixing instead of attention.

A decoder-only LM whose blocks mix the sequence with the diagonal
selective SSM (ops/ssm.py — ``lax.associative_scan`` recurrence) instead
of attention: O(S) compute and O(1) state per step, the long-context
model family complementing the attention transformer. Like the
transformer, the reference ships no model code (its benchmarks build
throwaway torch models); this exists to produce realistic trainable
distributed state for the snapshot layer.

Sharding: batch over 'data'; FFN weights over 'model' (tp); with a mesh
that has a 'seq' axis, the residual stream stays sequence-sharded
end-to-end and the scan's cross-chunk carry rides one tiny all_gather per
layer (``ssm_mix_sharded``) — the SSM analogue of context parallelism.

State (params + optax state + step) is the canonical AppState the
snapshot layer checkpoints and reshards.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.ssm import init_ssm_params, ssm_mix

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    vocab_size: int = 32768
    d_model: int = 512
    d_state: int = 16
    n_layers: int = 4
    d_ff: int = 2048
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32


def _norm_init(shape, dtype):
    return jnp.ones(shape, dtype)


def init_params(rng: jax.Array, cfg: SSMConfig) -> Params:
    c = cfg
    k_emb, k_layers = jax.random.split(rng)
    ks = jax.random.split(k_layers, 3)

    def stack(init_one):
        outs = [init_one(jax.random.fold_in(ks[0], i)) for i in range(c.n_layers)]
        return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *outs)

    layers = {
        "ssm": stack(lambda k: init_ssm_params(k, c.d_model, c.d_state, c.param_dtype)),
        "ln1_scale": _norm_init((c.n_layers, c.d_model), c.param_dtype),
        "ln2_scale": _norm_init((c.n_layers, c.d_model), c.param_dtype),
        "ff_in": jax.random.normal(
            ks[1], (c.n_layers, c.d_model, c.d_ff), c.param_dtype
        ) * (c.d_model**-0.5),
        "ff_out": jax.random.normal(
            ks[2], (c.n_layers, c.d_ff, c.d_model), c.param_dtype
        ) * (c.d_ff**-0.5),
    }
    return {
        "embed": jax.random.normal(
            k_emb, (c.vocab_size, c.d_model), c.param_dtype
        ) * (c.d_model**-0.5),
        "layers": layers,
        "ln_f_scale": _norm_init((c.d_model,), c.param_dtype),
    }


def param_specs(cfg: SSMConfig) -> Params:
    """PartitionSpecs for a ('data','model'[,'seq']) mesh: FFN tp-sharded,
    the tied embedding sharded over the vocabulary (as the transformer's:
    a local head matmul onto vocabulary-sharded logits), SSM params
    replicated (they are tiny: O(d_model * d_state))."""
    none2 = P(None, None)
    return {
        "embed": P("model", None),
        "layers": {
            "ssm": {
                "log_a": none2,
                "w_bc": P(None, None, None),
                "w_dt": P(None, None, None),
                "dt_bias": none2,
                "d_skip": none2,
            },
            "ln1_scale": none2,
            "ln2_scale": none2,
            "ff_in": P(None, None, "model"),
            "ff_out": P(None, "model", None),
        },
        "ln_f_scale": P(None),
    }


def _rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * scale.astype(x.dtype)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: SSMConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Causal LM forward: (B, S) int32 -> (B, S, vocab) logits.

    With a mesh carrying a 'seq' axis the residual stream is sequence
    sharded and each layer's scan runs sequence-parallel; otherwise the
    scan is local. Sharding constraints are no-ops with mesh=None.
    """
    c = cfg
    B, S = tokens.shape
    has_seq = mesh is not None and "seq" in mesh.axis_names
    seq_ax = "seq" if has_seq else None

    def cs(x, spec):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    x = params["embed"].astype(c.dtype)[tokens]  # (B, S, D)
    x = cs(x, P("data", seq_ax, None))

    def mix(params_l, h):
        if has_seq:
            from ..ops.ssm import ssm_mix_sharded

            y, _ = ssm_mix_sharded(params_l, h, mesh, seq_axis="seq")
            return y
        y, _ = ssm_mix(params_l, h)
        return y

    def block(x, layer):
        h = _rmsnorm(x, layer["ln1_scale"])
        h = cs(h, P("data", seq_ax, None))
        x = x + cs(mix(layer["ssm"], h), P("data", seq_ax, None))
        h = _rmsnorm(x, layer["ln2_scale"])
        h = jax.nn.gelu(h @ layer["ff_in"].astype(c.dtype))
        h = cs(h, P("data", seq_ax, "model"))
        x = x + cs(h @ layer["ff_out"].astype(c.dtype), P("data", seq_ax, None))
        return x, None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = x @ params["embed"].astype(c.dtype).T
    return cs(logits, P("data", seq_ax, "model"))


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: SSMConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    logits = forward(params, batch["tokens"], cfg, mesh=mesh)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def state_specs(cfg: SSMConfig, state: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching init_state's output: Adam moments
    inherit their param's spec; scalars replicated ON the mesh — a
    restored scalar comes back committed, and a single-device scalar next
    to mesh-committed params is an invalid jit input mix (same rationale
    as transformer.state_specs)."""
    from ..parallel.mesh import optax_state_specs

    p_specs = param_specs(cfg)
    opt_spec = optax_state_specs(p_specs, state["opt"])
    return {"params": p_specs, "opt": opt_spec, "step": P()}


def init_state(
    rng: jax.Array,
    cfg: SSMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Dict[str, Any]:
    params = init_params(rng, cfg)
    if mesh is not None:
        from ..parallel.mesh import shard_pytree

        params = shard_pytree(params, param_specs(cfg), mesh)
    state = {
        "params": params,
        "opt": tx.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if mesh is not None:
        from ..parallel.mesh import shard_pytree

        state = shard_pytree(state, state_specs(cfg, state), mesh)
    return state


def make_train_step(
    cfg: SSMConfig,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
) -> Callable:
    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=mesh)
        )(state["params"])
        updates, opt = tx.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt": opt, "step": state["step"] + 1}, loss

    return step
