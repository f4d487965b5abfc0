"""GPipe-style SPMD pipeline parallelism over a mesh axis.

Layer-stacked parameters (leading dim = layers) shard over the ``pipe``
mesh axis so each device owns a contiguous stage of ``L/P`` layers. The
input batch is split into ``n_micro`` microbatches that flow through the
stages: at tick ``t`` stage ``s`` processes microbatch ``t - s``, then
hands its activation to stage ``s+1`` via a neighbor ``ppermute`` — the
cheapest collective on a TPU torus, and the schedule is a ``lax.scan``
(static length ``n_micro + P - 1``), so XLA sees one compiled tick body.

Bubble ticks (the pipeline fill/drain) run the same computation with a
validity mask instead of data-dependent control flow — standard SPMD
pipelining: every device executes the identical program every tick, which
is what keeps it one XLA computation with static shapes.

Backward is just ``jax.grad`` through the scan: autodiff reverses the
``ppermute`` s (activations forward, gradients backward) and produces the
standard 1F1B-free GPipe backward schedule automatically.

The reference framework has no pipeline parallelism (SURVEY.md §2's
parallelism table records the absence); at the state-dict level a
pipelined model's parameters are just layer-stacked arrays sharded over
``pipe`` — another sharded entry for the snapshot layer, restorable onto
any other stage count via overlap resharding.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LayerFn = Callable[[Any, jax.Array], jax.Array]


def _varying_over(axes):
    """Cast-to-device-varying over any of ``axes`` a value isn't already
    varying on — scan carry initializers must declare the vma their
    outputs will have (ppermute/axis_index make carries varying)."""

    def cast(v):
        vma = jax.typeof(v).vma
        missing = tuple(a for a in axes if a not in vma)
        if missing:
            return jax.lax.pcast(v, missing, to="varying")
        return v

    return cast


def _stage_apply(stage_params: Any, x: jax.Array, layer_fn: LayerFn) -> jax.Array:
    """Apply this stage's layers (leading dim = local layers) in order."""

    def body(h, layer_params):
        return layer_fn(layer_params, h), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def pipeline_spmd(
    stage_params: Any,
    x_micro: jax.Array,
    *,
    axis_name: str,
    layer_fn: LayerFn,
) -> jax.Array:
    """Pipeline body. Must run inside ``shard_map``.

    ``stage_params``: pytree whose leaves have leading dim ``L_local``
    (this stage's layers). ``x_micro: (M, Bm, ...)`` microbatched input
    (every stage receives it; only stage 0 reads it). Returns the
    pipelined output ``(M, Bm, ...)``, identical on every stage.
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    # Activations move stage s -> s+1; no wraparound edge — stage 0 feeds
    # from x_micro, so the last stage's activation is simply not sent
    # (ppermute zero-fills receivers with no incoming edge).
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        act, outs = carry
        mb = t - stage  # microbatch index this stage handles at tick t
        valid = (mb >= 0) & (mb < n_micro)
        y = _stage_apply(stage_params, act, layer_fn)
        # Last stage banks its result at microbatch slot mb. The masked
        # dynamic_update_slice keeps every stage's program identical.
        write = valid & (stage == n_stages - 1)
        slot = jnp.clip(mb, 0, n_micro - 1)
        upd = jnp.where(write, y, jax.lax.dynamic_index_in_dim(outs, slot, 0, False))
        outs = jax.lax.dynamic_update_index_in_dim(outs, upd, slot, 0)
        # Hand activations to the next stage; stage 0 ingests the next
        # microbatch instead of the (meaningless) wraparound receive.
        recv = jax.lax.ppermute(y, axis_name, fwd_perm)
        nxt = jnp.clip(t + 1, 0, n_micro - 1)
        act_next = jnp.where(
            stage == 0, jax.lax.dynamic_index_in_dim(x_micro, nxt, 0, False), recv
        )
        return (act_next, outs), None

    act0 = jnp.where(
        stage == 0, x_micro[0], jnp.zeros_like(x_micro[0])
    )
    outs0 = jnp.zeros_like(x_micro)
    act0, outs0 = map(_varying_over((axis_name,)), (act0, outs0))
    (_, outs), _ = jax.lax.scan(tick, (act0, outs0), jnp.arange(n_ticks))
    # Everyone needs the outputs (e.g. for a replicated loss): zero out all
    # but the last stage's banked copy and sum over the pipe axis.
    outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def _prep_pipeline(
    params: Any,
    x: jax.Array,
    mesh: Mesh,
    n_micro: int,
    pipe_axis: str,
    batch_axis: Optional[str],
):
    """Shared validation + microbatching for the pipeline entry points.

    Returns ``(batch_axis_or_None, x_micro, param_specs)``."""
    axes = set(mesh.axis_names)
    if pipe_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks pipe axis {pipe_axis!r}")
    n_stages = mesh.shape[pipe_axis]
    L = jax.tree_util.tree_leaves(params)[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible by {n_stages} stages")
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
    b = batch_axis if batch_axis in axes else None
    if b is not None and (B // n_micro) % mesh.shape[b]:
        raise ValueError(
            f"per-microbatch size {B // n_micro} not divisible by the "
            f"{b!r} axis size {mesh.shape[b]} (batch {B}, n_micro {n_micro})"
        )
    x_micro = x.reshape(n_micro, B // n_micro, *x.shape[1:])
    param_specs = jax.tree_util.tree_map(
        lambda leaf: P(pipe_axis, *([None] * (leaf.ndim - 1))), params
    )
    return b, x_micro, param_specs


def pipelined_apply(
    params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    layer_fn: LayerFn,
    n_micro: int,
    pipe_axis: str = "pipe",
    batch_axis: Optional[str] = "data",
) -> jax.Array:
    """Apply layer-stacked ``params`` to ``x: (B, ...)`` through a pipeline.

    ``params`` leaves have leading dim L (total layers), sharded over
    ``pipe_axis`` (L divisible by the axis size); the batch splits into
    ``n_micro`` microbatches (B divisible by ``n_micro`` and, when present,
    by the ``batch_axis`` size — dp composes with pp on an orthogonal mesh
    axis). Output matches ``x``'s leading shape.
    """
    b, x_micro, param_specs = _prep_pipeline(
        params, x, mesh, n_micro, pipe_axis, batch_axis
    )
    B = x.shape[0]
    fn = partial(pipeline_spmd, axis_name=pipe_axis, layer_fn=layer_fn)
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(param_specs, P(None, b)),
        out_specs=P(None, b),
    )(params, x_micro)
    return out.reshape(B, *x.shape[1:])


def pipeline_1f1b_spmd(
    stage_params: Any,
    x_micro: jax.Array,
    t_micro: jax.Array,
    *,
    axis_name: str,
    layer_fn: LayerFn,
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    varying_axes: Optional[tuple] = None,
):
    """1F1B pipeline train tick body. Must run inside ``shard_map``.

    One-forward-one-backward schedule: every tick, each stage runs one
    microbatch forward AND one microbatch backward (masked during
    fill/drain), so the activation stash is bounded by the pipeline
    DEPTH (2·stages slots here), not by ``n_micro`` — the memory
    property that separates 1F1B from GPipe, where autodiff through the
    forward scan stashes all ``n_micro`` microbatch activations before
    any backward runs.

    Timing (flush/PipeDream-style, non-interleaved): at tick ``t`` stage
    ``s`` forwards microbatch ``t - s`` and backwards microbatch
    ``t - (2·S - 2 - s)``. The last stage's backward for a microbatch
    fires the SAME tick as its forward — the loss gradient seeds it
    directly. Activation gradients ride the reverse ``ppermute`` edge
    (one-tick latency, exactly the schedule's stage offset). Each
    stage's backward re-runs its forward via ``jax.vjp`` on the stashed
    INPUT activation (per-stage rematerialization), so only stage inputs
    are stashed, never internals.

    Returns ``(loss_sum, grads)``: the summed per-microbatch loss
    (identical on every stage) and this stage's parameter gradients
    (leading dim = local layers — exactly the ``pipe``-sharded layout
    the snapshot layer sees).
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + 2 * n_stages - 2
    stash_size = 2 * n_stages
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    bwd_perm = [(i + 1, i) for i in range(n_stages - 1)]
    last = n_stages - 1

    def fwd(act):
        return _stage_apply(stage_params, act, layer_fn)

    def tick(carry, t):
        act_in, g_in_flight, stash, grads, loss_sum = carry
        fm = t - stage                      # fwd microbatch this tick
        bm = t - (2 * n_stages - 2 - stage)  # bwd microbatch this tick
        fwd_valid = (fm >= 0) & (fm < n_micro)
        bwd_valid = (bm >= 0) & (bm < n_micro)

        # ---- forward ------------------------------------------------
        y = fwd(act_in)
        fslot = jnp.clip(fm, 0, n_micro - 1) % stash_size
        stash = jax.lax.dynamic_update_index_in_dim(
            stash,
            jnp.where(fwd_valid, act_in, jax.lax.dynamic_index_in_dim(stash, fslot, 0, False)),
            fslot,
            0,
        )
        # Last stage: per-microbatch loss + seed gradient, this tick.
        tgt = jax.lax.dynamic_index_in_dim(
            t_micro, jnp.clip(fm, 0, n_micro - 1), 0, False
        )
        mb_loss, g_seed = jax.value_and_grad(loss_fn)(y, tgt)
        loss_sum = loss_sum + jnp.where(
            fwd_valid & (stage == last), mb_loss, 0.0
        )

        # ---- backward -----------------------------------------------
        # Gradient w.r.t. this stage's OUTPUT for microbatch bm: the
        # loss seed on the last stage (bm == fm there), else the
        # neighbor's activation gradient from the previous tick.
        g_out = jnp.where(stage == last, g_seed, g_in_flight)
        bslot = jnp.clip(bm, 0, n_micro - 1) % stash_size
        act_for_bwd = jax.lax.dynamic_index_in_dim(stash, bslot, 0, False)
        # One linearization yields both cotangents (per-stage remat).
        _, vjp_fn = jax.vjp(
            lambda p, a: _stage_apply(p, a, layer_fn), stage_params, act_for_bwd
        )
        g_params, g_act = vjp_fn(g_out)
        grads = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(bwd_valid, g, jnp.zeros_like(g)),
            grads,
            g_params,
        )

        # ---- comms --------------------------------------------------
        recv_act = jax.lax.ppermute(y, axis_name, fwd_perm)
        nxt = jnp.clip(t + 1, 0, n_micro - 1)
        act_next = jnp.where(
            stage == 0,
            jax.lax.dynamic_index_in_dim(x_micro, nxt, 0, False),
            recv_act,
        )
        g_next = jax.lax.ppermute(
            jnp.where(bwd_valid, g_act, jnp.zeros_like(g_act)),
            axis_name,
            bwd_perm,
        )
        return (act_next, g_next, stash, grads, loss_sum), None

    act0 = jnp.where(stage == 0, x_micro[0], jnp.zeros_like(x_micro[0]))
    g0 = jnp.zeros_like(x_micro[0])
    stash0 = jnp.zeros((stash_size,) + x_micro.shape[1:], x_micro.dtype)
    grads0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    loss0 = jnp.zeros((), jnp.float32)

    # The scan carry becomes device-varying over every manual mesh axis
    # the data touches (pipe always; the batch axis too under dp x pp —
    # microbatch activations and per-rank losses are data-sharded).
    # Initializers must declare the same. The GRADS accumulator is the
    # exception: the params are data-invariant, so vma-aware autodiff
    # psums their cotangent over the batch axis each tick — grads stay
    # varying over the PIPE axis only.
    want_axes = tuple(varying_axes or (axis_name,))
    carry0 = (
        *jax.tree_util.tree_map(_varying_over(want_axes), (act0, g0, stash0)),
        jax.tree_util.tree_map(_varying_over((axis_name,)), grads0),
        _varying_over(want_axes)(loss0),
    )
    (_, _, _, grads, loss_sum), _ = jax.lax.scan(
        tick, carry0, jnp.arange(n_ticks)
    )
    # Loss lives on the last stage only; share it (grads stay per-stage —
    # that IS the pipe-sharded layout).
    loss_sum = jax.lax.psum(
        jnp.where(stage == last, loss_sum, 0.0), axis_name
    )
    return loss_sum, grads


def pipelined_value_and_grad(
    params: Any,
    x: jax.Array,
    targets: jax.Array,
    mesh: Mesh,
    *,
    layer_fn: LayerFn,
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    n_micro: int,
    pipe_axis: str = "pipe",
    batch_axis: Optional[str] = "data",
):
    """(mean microbatch loss, param grads) via the 1F1B schedule.

    ``loss_fn(y_micro, t_micro) -> scalar`` is the per-microbatch mean
    loss. Grads come back layer-stacked and ``pipe``-sharded (same
    layout as ``pipeline_param_sharding``), averaged over microbatches
    and — when ``batch_axis`` is on the mesh — over data-parallel
    replicas.
    """
    b, x_micro, param_specs = _prep_pipeline(
        params, x, mesh, n_micro, pipe_axis, batch_axis
    )
    B = x.shape[0]
    t_micro = targets.reshape(n_micro, B // n_micro, *targets.shape[1:])

    def spmd(p, xm, tm):
        loss_sum, grads = pipeline_1f1b_spmd(
            p, xm, tm, axis_name=pipe_axis, layer_fn=layer_fn, loss_fn=loss_fn,
            varying_axes=(pipe_axis,) + ((b,) if b is not None else ()),
        )
        loss = loss_sum / n_micro
        if b is not None:
            loss = jax.lax.pmean(loss, b)
            # The params are data-INVARIANT, so the vjp already inserted
            # a psum over the data axis into their cotangent (vma-aware
            # autodiff): grads arrive as the SUM over data ranks. Divide
            # by the axis size for mean-over-the-full-microbatch
            # semantics — a pmean here would double-count.
            grads = jax.tree_util.tree_map(
                lambda g: g / mesh.shape[b], grads
            )
        grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
        return loss, grads

    return jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(param_specs, P(None, b), P(None, b)),
        out_specs=(P(), param_specs),
    )(params, x_micro, t_micro)


def pipeline_param_sharding(
    params: Any, mesh: Mesh, pipe_axis: str = "pipe"
) -> Any:
    """NamedShardings placing layer-stacked params on their pipeline stages
    (what ``init`` should ``device_put`` with, and exactly what the
    snapshot layer sees as sharded entries)."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(
            mesh, P(pipe_axis, *([None] * (leaf.ndim - 1)))
        ),
        params,
    )
