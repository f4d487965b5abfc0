"""What the model zoo's train states share: the float32 norm, the compute
cast, the sharding constraint, the head, the attention dispatch, the state
and its layout, the optimizer and the train step's skeleton.

A model file keeps what makes it different (its config, its parameters and
their layout, its layers and its objective) and binds its public names
(``state_specs``, ``init_state``, ``make_train_step``, ``select_attention``,
``compute_params``, ``make_optimizer``) to these in a line each. The state
is ``{params, opt_state, step}`` and whatever leaves a model adds; under a
mesh the whole of it is placed per ``state_specs``, scalars too: a restored
scalar comes back committed to its destination's sharding, and a
single-device scalar next to mesh-committed parameters is an invalid jit
input mix.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..ops.attention import causal_attention_route
from ..parallel.mesh import optax_state_specs, shard_pytree

Params = Dict[str, Any]
ATTN_BLOCK = 512  # the tiling every route targets, as TransformerConfig's default


def make_optimizer(lr: float = 1e-3) -> optax.GradientTransformation:
    return optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.01)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Statistics, scaling and result in float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def mm(x, w):
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal convolution along axis 1: ``y_t = sum_j w[j]
    x_{t-(K-1)+j} + b`` with zeros before the sequence. float32."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * w[j].astype(jnp.float32) for j in range(K)) + b.astype(jnp.float32)


def constrainer(mesh: Optional[Mesh]):
    if mesh is None:
        return lambda x, spec: x
    return lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def head(h: jax.Array, w: jax.Array, cs) -> jax.Array:
    """(B, positions, vocab) float32 logits of an untied head ``w`` already
    in the compute dtype."""
    with jax.named_scope("lm_head"):
        logits = jnp.matmul(h.astype(w.dtype), w.T, preferred_element_type=jnp.float32)
        return cs(logits, P("data", None, "model"))


def blocked_cross_entropy(h: jax.Array, w: jax.Array, targets: jax.Array, head_block: int, logits: Callable) -> jax.Array:
    """Mean next-token cross-entropy of the closed hidden state ``h (B, S,
    D)``, head and loss in blocks of ``head_block`` positions of each
    sequence, each block recomputed in the backward pass: one block's
    ``(B, head_block, vocab)`` logits ``logits(h, w)`` alive at a time, and
    the gradient of the head ``w`` summed over the blocks in float32."""
    B, S, D = h.shape
    block = math.gcd(S, head_block)

    @jax.checkpoint
    def block_ce(w, h, targets):
        z = logits(h, w)
        at_target = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - at_target)

    if block == S:
        return block_ce(w, h, targets) / (B * S)
    blocks = lambda t: jnp.moveaxis(t.reshape(B, S // block, block, *t.shape[2:]), 1, 0)  # noqa: E731
    sums = jax.lax.map(lambda args: block_ce(w, *args), (blocks(h), blocks(targets)))
    return jnp.sum(sums) / (B * S)


def vocab_sharded(shapes: Params) -> Params:
    """PartitionSpecs on a ('data','model') mesh for a parameter tree of
    ``shapes``: ``embed`` and an untied ``head`` over the vocabulary, every
    other leaf replicated."""
    specs = jax.tree_util.tree_map(lambda x: P(*([None] * x.ndim)), shapes)
    return {**specs, **{k: P("model", None) for k in ("embed", "head") if k in shapes}}


def attention_route(n_heads: int, mesh: Optional[Mesh], B: int, S: int, **mask):
    """The shared dispatch's "auto": what the backend, the mesh and S allow."""
    return causal_attention_route("auto", ATTN_BLOCK, n_heads, mesh, B, S, **mask)


def route_name(n_heads: int, mesh: Optional[Mesh], B: int, S: int, **mask) -> str:
    """The name of the route ``attention_route`` takes."""
    return attention_route(n_heads, mesh, B, S, **mask)[0]


def cast_matrices(params: Params, dtype: Any, matrices: Iterable[str], head: bool = True) -> Params:
    """The tree the layers read: the ``layers`` leaves named in ``matrices``
    (at any depth) and, with ``head``, the head in the compute ``dtype``,
    cast once a step; every other leaf as stored."""

    def cast(w):
        return {k: cast(v) if isinstance(v, dict) else v.astype(dtype) if k in matrices else v for k, v in w.items()}

    out = {**params, "layers": cast(params["layers"])}
    if head:
        out["head"] = params["head"].astype(dtype)
    return out


def state_specs(
    param_specs: Params, state: Dict[str, Any], extra_specs: Optional[Mapping[str, Any]] = None
) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_state``'s output: adam moments
    inherit their parameter's spec, the scalars replicated."""
    return {
        "params": param_specs,
        "opt_state": optax_state_specs(param_specs, state["opt_state"]),
        "step": P(),
        **(extra_specs or {}),
    }


def init_state(
    params: Params,
    param_specs: Params,
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    extra: Optional[Mapping[str, Any]] = None,
    extra_specs: Optional[Mapping[str, Any]] = None,
) -> Dict[str, Any]:
    """{params, opt_state, step, **extra} from freshly made ``params``, the
    whole of it placed per ``state_specs`` under a mesh."""
    if mesh is not None:
        params = shard_pytree(params, param_specs, mesh)
    state = {"params": params, "opt_state": tx.init(params), "step": jnp.zeros((), jnp.int32), **(extra or {})}
    if mesh is not None:
        state = shard_pytree(state, state_specs(param_specs, state, extra_specs), mesh)
    return state


def make_train_step(
    objective: Callable,
    cast: Callable[[Params], Params],
    objective_args: Callable[[Dict[str, Any], Dict[str, jax.Array]], tuple],
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh],
    param_specs: Params,
    gauges: Mapping[str, Any],
    frozen: Iterable[str] = (),
    extra: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None,
    extra_specs: Optional[Mapping[str, Any]] = None,
) -> Callable:
    """Returns train_step(state, batch) -> (state, loss), ready to jit: the
    loss is ``objective(cast(params), *objective_args(state, batch))``.

    ``gauges`` go on the telemetry bus now, what is about to be compiled,
    for `stats -v` and the exporters. Parameter leaves named in ``frozen``
    come back as they went in; ``extra(state)`` gives the model's own
    leaves of the next state, laid out per ``extra_specs``. Under a mesh the
    returned state is pinned to ``state_specs``: left to GSPMD, replicated leaves come back sharded, a
    second step recompiles for the drifted input, and what gets saved is no
    longer the declared layout."""
    for name, value in gauges.items():
        telemetry.gauge_set(name, value)
    frozen = frozenset(frozen)

    def train_step(state, batch):
        # Gradients are taken with respect to the tree the layers read, so
        # the matrices' come in the compute dtype, summed over a scan's
        # trips in it: float32 copies of them (and float32 accumulators in
        # the backward scans) do not fit beside 12 B/param and the
        # recompute working set. adamw's moments and update are float32.
        params = state["params"]
        loss, grads = jax.value_and_grad(objective)(cast(params), *objective_args(state, batch))
        updates, opt_state = tx.update(grads, state["opt_state"], params)
        new_params = jax.tree_util.tree_map_with_path(
            lambda path, new, old: old if getattr(path[-1], "key", None) in frozen else new,
            optax.apply_updates(params, updates),
            params,
        )
        new_state = {"params": new_params, "opt_state": opt_state, "step": state["step"] + 1, **(extra(state) if extra else {})}
        if mesh is not None:
            new_state = jax.tree_util.tree_map(
                lambda x, spec: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec)),
                new_state,
                state_specs(param_specs, new_state, extra_specs),
            )
        return new_state, loss

    return train_step
