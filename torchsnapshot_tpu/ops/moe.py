"""Mixture-of-experts FFN with expert parallelism (ep).

GShard-style top-2 routing with static capacity: every shape is fixed at
trace time, so the whole layer jits cleanly and the expert dimension
shards over a mesh axis. Overflowing tokens are dropped (their FFN output
is zero and the residual carries them), the standard capacity trade-off.

Two dispatch strategies, same routing semantics:

- ``einsum``: the (T, E, capacity) one-hot dispatch/combine tensors of the
  GShard paper. All-matmul (MXU-friendly) but the dispatch tensor is
  O(T * E * cap) ~ O(T^2 * capacity_factor) memory — fine for small T*E,
  a blow-up at scale.
- ``sort``: tokens are stably argsorted by expert id; position-in-expert
  falls out of the sorted order (arange minus each expert's start offset),
  and dispatch/combine are a 1-D scatter-add / gather of rows. O(T*K)
  memory, no quadratic tensor. Priority matches the einsum path exactly
  (all top-1 claims fill capacity before any top-2 claim, in token order),
  so both paths route identically.

``moe_ffn`` picks per size (``dispatch="auto"``). ``moe_ffn_sharded`` is
the explicit expert-parallel path: tokens sharded over the expert mesh
axis, each device sort-dispatches its local tokens into per-expert
buffers, one ``lax.all_to_all`` swaps buffers so every device holds its
experts' tokens, local expert FFNs run, and the reverse all-to-all brings
outputs home for the gather-combine. Capacity is per sending device, so
buffer shapes stay static regardless of routing skew.

The expert-stacked weights (E, D, F)/(E, F, D) shard over the 'model' axis
by default — expert parallelism at the state-dict level is just another
sharded array for the snapshot layer (which is the point: SURVEY.md §2's
"Parallelism" table, extended to ep).

Auxiliary load-balancing loss follows Switch/GShard: mean(fraction of
tokens per expert * mean router prob per expert) * E.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_add_rows import add_rows
from .pallas_grouped import grouped_matmul, grouped_matmul_t, uninitialised

# Above this many elements in the (T, E, cap) dispatch tensor, "auto"
# switches to the sort-based dispatch (2**22 f32 elements = 16 MB).
_EINSUM_DISPATCH_MAX_ELEMENTS = 1 << 22


def init_moe_params(
    rng: jax.Array,
    d_model: int,
    d_ff: int,
    n_experts: int,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    k_r, k_in, k_out = jax.random.split(rng, 3)
    return {
        "router": jax.random.normal(k_r, (d_model, n_experts), dtype) * (d_model**-0.5),
        "w_in": jax.random.normal(k_in, (n_experts, d_model, d_ff), dtype)
        * (d_model**-0.5),
        "w_out": jax.random.normal(k_out, (n_experts, d_ff, d_model), dtype)
        * (d_ff**-0.5),
    }


def moe_param_specs(expert_axis: Optional[str] = "model") -> Dict[str, Any]:
    """PartitionSpecs: experts sharded over ``expert_axis``; router replicated."""
    from jax.sharding import PartitionSpec as P

    return {
        "router": P(None, None),
        "w_in": P(expert_axis, None, None),
        "w_out": P(expert_axis, None, None),
    }


def _top2_route(x2: jax.Array, router: jax.Array):
    """Top-2 routing. Returns (e1, e2 int32 (T,), g1, g2 f32 renormalized
    gates (T,), probs f32 (T, E))."""
    E = router.shape[1]
    logits = (x2 @ router.astype(x2.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    g1 = jnp.max(probs, axis=-1)
    e1 = jnp.argmax(probs, axis=-1)
    probs_wo1 = probs - jax.nn.one_hot(e1, E) * probs
    g2 = jnp.max(probs_wo1, axis=-1)
    e2 = jnp.argmax(probs_wo1, axis=-1)
    denom = g1 + g2 + 1e-9
    return e1, e2, g1 / denom, g2 / denom, probs


def _aux_loss(e1: jax.Array, probs: jax.Array) -> jax.Array:
    """Switch-style load-balancing loss from top-1 assignments."""
    E = probs.shape[-1]
    frac_tokens = jnp.mean(jax.nn.one_hot(e1, E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return (jnp.sum(frac_tokens * frac_probs) * E).astype(jnp.float32)


def _einsum_dispatch(x2, e1, e2, g1, g2, E, cap):
    """GShard one-hot dispatch: (E, cap, D) buffers + (T, E, cap) combine."""
    T = x2.shape[0]

    def dispatch(e, g, prior_load):
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)  # (T, E)
        pos = jnp.cumsum(onehot, axis=0) - 1 + prior_load[None, :]
        pos = jnp.sum(pos * onehot, axis=-1)  # (T,)
        keep = pos < cap
        # (T, E, cap) one-hot dispatch tensor
        disp = (
            jax.nn.one_hot(e, E)[:, :, None]
            * jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap)[:, None, :]
            * keep[:, None, None]
        )
        return disp, g * keep, prior_load + jnp.sum(onehot, axis=0)

    load0 = jnp.zeros((E,), jnp.int32)
    disp1, g1k, load1 = dispatch(e1, g1, load0)
    disp2, g2k, _ = dispatch(e2, g2, load1)
    combine = disp1 * g1k[:, None, None] + disp2 * g2k[:, None, None]  # (T,E,cap)
    dispatch_mask = (combine > 0).astype(x2.dtype)
    # precision=HIGHEST: the mask is 0/1, so this einsum is a permutation,
    # not arithmetic — default TPU bf16 matmul precision would round the
    # dispatched activations and make the two dispatch paths diverge.
    xe = jnp.einsum(
        "td,tec->ecd", x2, dispatch_mask, precision=jax.lax.Precision.HIGHEST
    )  # (E,cap,D)
    return xe, combine


def _sort_dispatch(x2, e1, e2, E, cap):
    """Sort-based dispatch: (E, cap, D) buffers + per-slot buffer rows.

    Tokens are stably argsorted by expert id in slot-major order (all top-1
    claims, by token id, then all top-2 claims), so position-in-expert is
    just ``arange - expert_start`` over the sorted sequence — identical
    priority to the einsum path's cumsum-with-prior-load, without the
    (T, E, cap) tensor. Returns ``(xe, dest)`` where ``dest: (T, 2)`` maps
    each (token, choice) slot to its row in the flattened (E*cap) buffer,
    or to E*cap (a zero pad row) when the slot overflowed capacity.
    """
    T, D = x2.shape
    flat_e = jnp.concatenate([e1, e2])  # (2T,) slot-major
    flat_t = jnp.tile(jnp.arange(T), 2)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_t[order]
    counts = jnp.bincount(flat_e, length=E)  # (E,)
    start = jnp.cumsum(counts) - counts  # exclusive prefix sum
    pos = jnp.arange(2 * T) - start[se]  # position within expert run
    dest_sorted = jnp.where(pos < cap, se * cap + pos, E * cap)
    # Scatter kept tokens into expert buffers; overflow rows (index E*cap)
    # fall off the end and are dropped.
    xe = (
        jnp.zeros((E * cap, D), x2.dtype)
        .at[dest_sorted]
        .add(x2[st], mode="drop")
        .reshape(E, cap, D)
    )
    # Invert the sort so each original slot knows its buffer row.
    dest = jnp.zeros((2 * T,), jnp.int32).at[order].set(dest_sorted)
    return xe, dest.reshape(2, T).T  # (T, 2)


def _sort_combine(ye, dest, g1, g2, dtype):
    """Gather each token's (up to) two expert outputs and gate-sum them."""
    E_cap, D = ye.shape[0] * ye.shape[1], ye.shape[2]
    # Pad row E*cap is zero — dropped slots contribute nothing.
    ye_pad = jnp.concatenate(
        [ye.reshape(E_cap, D), jnp.zeros((1, D), ye.dtype)], axis=0
    )
    y = (
        ye_pad[dest[:, 0]] * g1[:, None].astype(dtype)
        + ye_pad[dest[:, 1]] * g2[:, None].astype(dtype)
    )
    return y


def _expert_ffn(params, xe, activation, dtype):
    """(E, cap, D) -> (E, cap, D) through the per-expert FFNs."""
    h = activation(jnp.einsum("ecd,edf->ecf", xe, params["w_in"].astype(dtype)))
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(dtype))


def moe_ffn(
    params: Dict[str, Any],
    x: jax.Array,
    *,
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
    dispatch: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Top-2 MoE FFN. ``x: (..., T, D)`` -> (same shape, aux_loss scalar).

    Leading dims are flattened into one token axis for routing; capacity is
    per expert: ceil(2 * T / E * capacity_factor). ``dispatch`` is
    ``"einsum"`` (GShard one-hot, all-matmul), ``"sort"`` (argsort +
    scatter/gather, no (T, E, cap) tensor), or ``"auto"`` (einsum while the
    dispatch tensor stays small). Both dispatches route identically.
    """
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)  # (T, D)
    T = x2.shape[0]
    E = params["router"].shape[1]
    cap = int(max(1, math.ceil(2 * T * capacity_factor / E)))
    if dispatch == "auto":
        dispatch = (
            "einsum" if T * E * cap <= _EINSUM_DISPATCH_MAX_ELEMENTS else "sort"
        )
    if dispatch not in ("einsum", "sort"):
        raise ValueError(f"unknown dispatch {dispatch!r}")

    e1, e2, g1, g2, probs = _top2_route(x2, params["router"])

    if dispatch == "einsum":
        xe, combine = _einsum_dispatch(x2, e1, e2, g1, g2, E, cap)
        ye = _expert_ffn(params, xe, activation, x.dtype)
        # HIGHEST precision for the same reason as the dispatch einsum: the
        # combine tensor is a gated permutation, not a real matmul.
        y = jnp.einsum(
            "ecd,tec->td", ye, combine.astype(x.dtype),
            precision=jax.lax.Precision.HIGHEST,
        )  # (T, D)
    else:
        xe, dest = _sort_dispatch(x2, e1, e2, E, cap)
        ye = _expert_ffn(params, xe, activation, x.dtype)
        y = _sort_combine(ye, dest, g1, g2, x.dtype)

    return y.reshape(orig_shape), _aux_loss(e1, probs)


def moe_ffn_sharded(
    params: Dict[str, Any],
    x: jax.Array,
    mesh,
    *,
    expert_axis: str = "model",
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel top-2 MoE FFN with explicit all-to-all dispatch.

    ``x: (T, D)`` with T sharded over ``expert_axis``; expert-stacked
    weights sharded over the same axis (``moe_param_specs``). Each device
    routes its local tokens, sort-dispatches them into (E, cap_local, D)
    buffers, and one ``lax.all_to_all`` swaps buffers so each device holds
    the tokens bound for its E/n local experts; after the local expert
    FFNs, the reverse all-to-all brings outputs home for the combine.
    Capacity is per *sending* device (cap_local = ceil(2 * T_local * cf /
    E)), so buffer shapes are static and per-device memory is O(T_local) —
    routing skew costs drops, never memory.

    Semantically equivalent to ``moe_ffn`` except capacity is accounted
    per device rather than globally (with ample ``capacity_factor`` the
    outputs match exactly).
    """
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[expert_axis]
    E = params["router"].shape[1]
    if E % n_dev:
        raise ValueError(f"n_experts {E} not divisible by mesh axis {n_dev}")
    T, D = x.shape
    if T % n_dev:
        raise ValueError(f"token count {T} not divisible by mesh axis {n_dev}")
    cap_l = int(max(1, math.ceil(2 * (T // n_dev) * capacity_factor / E)))

    param_specs = {
        "router": P(None, None),
        "w_in": P(expert_axis, None, None),
        "w_out": P(expert_axis, None, None),
    }

    def block(params, x_l):
        # x_l: (T_l, D); w_in/w_out: (E_l, ...) local experts.
        e1, e2, g1, g2, probs = _top2_route(x_l, params["router"])
        xe, dest = _sort_dispatch(x_l, e1, e2, E, cap_l)  # (E, cap_l, D)
        # Swap: every device sends each destination device its tokens for
        # that device's experts; receives (E_l, n_dev * cap_l, D).
        xe = jax.lax.all_to_all(
            xe, expert_axis, split_axis=0, concat_axis=1, tiled=True
        )
        ye = _expert_ffn(params, xe, activation, x_l.dtype)
        ye = jax.lax.all_to_all(
            ye, expert_axis, split_axis=1, concat_axis=0, tiled=True
        )  # back to (E, cap_l, D), this device's tokens
        y_l = _sort_combine(ye, dest, g1, g2, x_l.dtype)
        # Aux loss over the global batch: the per-expert fractions are
        # means over ALL tokens, so pmean each factor before the product —
        # pmean of the per-device products would be a different statistic.
        frac_tokens = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(e1, E, dtype=jnp.float32), axis=0),
            expert_axis,
        )
        frac_probs = jax.lax.pmean(jnp.mean(probs, axis=0), expert_axis)
        aux = (jnp.sum(frac_tokens * frac_probs) * E).astype(jnp.float32)
        return y_l, aux

    y, aux = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(param_specs, P(expert_axis, None)),
        out_specs=(P(expert_axis, None), P()),
        check_vma=False,
    )(params, x)
    return y, aux


# ------------------------------- top-k of many, dropless, a share of the experts


def relu2_ffn(x: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``relu(x w_up)^2 w_down``: the two-matrix expert without a gate.
    Operands in the weights' dtype, both products accumulated in float32."""
    h = jnp.matmul(x.astype(w_up.dtype), w_up, preferred_element_type=jnp.float32)
    a = jnp.square(jax.nn.relu(h)).astype(w_down.dtype)
    return jnp.matmul(a, w_down, preferred_element_type=jnp.float32)


def gated_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``(silu(x w_gate) * (x w_up)) w_down``: the gated three-matrix expert.
    Operands in the weights' dtype, the three products accumulated in
    float32, the gate and the gated product in float32."""
    x = x.astype(w_gate.dtype)
    g = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    return jnp.matmul((jax.nn.silu(g) * u).astype(w_down.dtype), w_down, preferred_element_type=jnp.float32)


def sigmoid_topk_route(
    x2: jax.Array, router: jax.Array, bias: jax.Array, top_k: int, scale: float, n_group: int = 1, topk_group: int = 1
) -> Tuple[jax.Array, jax.Array]:
    """``(T, D)`` tokens -> the ids ``(T, k)`` of the ``top_k`` experts with
    the largest ``sigmoid(x router) + bias`` and their weights ``(T, k)``:
    ``scale * s_i / (sum of the chosen s + 1e-20)``, from ``s`` and not from
    ``s + bias``. The bias only selects and takes no gradient. All of it in
    float32, the product at full precision (a bfloat16 pass moves scores by
    1e-2, which is the distance between neighbours among 128 of them). ``n_group`` > 1: ``group_limited``."""
    f32 = jnp.float32
    logits = jnp.matmul(x2.astype(f32), router.astype(f32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(group_limited(s + jax.lax.stop_gradient(bias.astype(f32)), n_group, topk_group), top_k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def softmax_topk_route(x2: jax.Array, router: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """``(T, D)`` tokens -> the ids ``(T, k)`` of the ``top_k`` experts with
    the largest ``softmax(x router)`` over **all** experts, and their
    weights ``(T, k)``: the chosen probabilities over their own sum
    (``norm_topk_prob``). All of it in float32, the product at full
    precision, as the sigmoid sibling says."""
    f32 = jnp.float32
    logits = jnp.matmul(x2.astype(f32), router.astype(f32), precision=jax.lax.Precision.HIGHEST)
    chosen, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


# An expert is its matrices, the last one down: two of them are
# ``relu2_ffn``, three are ``gated_ffn``. What differs between the two is the
# hidden activation from the products ``x w`` of the matrices ahead of the
# last, and its derivative: per kind, ``hs -> (a, da -> the cotangent of each
# h)``, all float32.


def _relu2_hidden(hs):
    (r,) = (jax.nn.relu(h) for h in hs)
    return jnp.square(r), lambda da: (da * 2.0 * r,)


def _gated_hidden(hs):
    g, u = hs
    sig = jax.nn.sigmoid(g)
    act = g * sig  # silu(g)
    return act * u, lambda da: (da * u * (sig + act * (1.0 - sig)), da * act)


_HIDDEN = {2: _relu2_hidden, 3: _gated_hidden}

_ROW_TILE = 128  # rows of a row tile of the held experts' list: why, under sigmoid_topk_routed
_CHUNK_TILES = 8  # row tiles gathered, multiplied and combined at a time


def _cdiv(a, b):
    return -(-a // b)


def held_row_tile(T: int) -> int:
    """Rows of a row tile of the list ``_held_experts`` makes of ``T``
    tokens: one expert's rows, and the granule a load is rounded up to."""
    return min(_ROW_TILE, T)


def _tile_plan(counts, tile):
    """Where each expert's segment lies in the list of row tiles: ``(start,
    end)``, ``(n,)`` each, in tiles. An expert has the tiles that hold its
    ``counts`` rows, and one where it has none: its zero gradient is written
    from that tile like any other."""
    tiles = jnp.maximum(_cdiv(counts, tile), 1)
    end = jnp.cumsum(tiles)
    return end - tiles, end


def _tile_groups(plan, first, k):
    """The ``k`` row tiles of the list from tile ``first`` on: their numbers
    and each one's expert (the last expert's past the list's end)."""
    _, end = plan
    t = first + jnp.arange(k, dtype=jnp.int32)
    return t, jnp.minimum(jnp.sum(end[None, :] <= t[:, None], axis=1, dtype=jnp.int32), end.shape[0] - 1)


def _list_tiles(plan, counts, order, w_held, first, k, limit, tile):
    """The ``k`` row tiles of the list from tile ``first`` on: ``group (k,)``,
    each tile's expert; ``own (k,)``, how many of its leading rows are the
    expert's own (0 for a tile from ``limit`` on: past the list, or left to
    another pass); ``idx (k * tile,)``, each row's token, and ``w_rows``, its
    weight. A tile's rows past the expert's own are tokens of others, as
    ``order`` lists them behind the expert's own (the last token again where
    T is no multiple of the tile): real rows, at weight 0."""
    t, group = _tile_groups(plan, first, k)
    row0 = (t - plan[0][group]) * tile
    pos = row0[:, None] + jnp.arange(tile, dtype=jnp.int32)
    mine = pos < counts[group][:, None]
    idx = order[group[:, None], jnp.minimum(pos, order.shape[1] - 1)]
    w_rows = jnp.where(mine, w_held[group[:, None], idx], 0.0)
    own = jnp.where(t < limit, jnp.sum(mine, axis=1, dtype=jnp.int32), 0)
    return group, own, idx.reshape(-1), w_rows.reshape(-1)


def _lane_aligned(m):
    """``(matrix, swapped)``: a stack of matrices whose rows are no multiple
    of the chip's 128 lanes long (1856) but whose columns are (2688) is taken
    through its transpose, which the kernels can cut blocks from and copy;
    the products below read ``swapped`` and ask for the other form. Where
    the compiler keeps such a stack columns-first already, as it does a
    parameter of that shape, the transpose moves nothing."""
    swapped = m.shape[2] % 128 != 0 and m.shape[1] % 128 == 0
    return (jnp.swapaxes(m, 1, 2) if swapped else m), swapped


def _product(x, matrix, group, live, transpose=False, **kwargs):
    m, swapped = matrix
    return grouped_matmul(x, m, group, live, transpose_rhs=transpose != swapped, **kwargs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_experts(x2, w_held, order, counts, ws, tile, limit=0.0):
    """``sum_e w_held[e, t] f(x2[t]; ws[..][e])`` over the tokens that chose
    expert e: ``(T, D) -> (T, D)`` float32. ``ws`` are the experts' stacked
    matrices, ``(up, down)`` for ``relu2_ffn`` or ``(gate, up, down)`` for
    ``gated_ffn``: what is given chooses the expert (``limit``: its clamp).

    ``order[e]`` lists the tokens, those of expert e first (``counts[e]`` of
    them), and ``w_held[e]`` is 0 on everyone else. The own rows of all
    experts are **one list sorted by expert**, each expert's segment begun
    on a row tile of ``tile`` rows and its last tile filled from ``order[e]``
    (rows at weight 0), so a row tile is one expert's. The list is never
    held whole: ``_CHUNK_TILES`` row tiles at a time are gathered, multiplied
    by **grouped matrix products** (``pallas_grouped.py``: one product a
    matrix over the chunk, each row tile against its expert's matrix, the
    matrix fetched once while its tiles follow each other), passed through
    the hidden activation and combined, and the walk stops after the chunk
    that holds the list's last tile. A step costs what its routing sends
    here, rounded up to a tile an expert and a chunk a pass, and nothing of
    size ``E x T`` or ``T x top_k`` is ever held. The trip counts are data,
    which reverse-mode autodiff cannot transpose, so the backward pass is
    written out below over the same list.

    A chunk's own rows are added into the float32 accumulator (``y`` here,
    ``dx`` backward) where it lies, by ``add_rows``
    (``pallas_add_rows.py``), a row tile after the other: a token that
    several experts hold is in several tiles, never twice in one. The loops
    carry the accumulator as ``(T, 1, D)`` and one relayout to ``(T, D)``
    follows. ``held_tile_stats`` counts the row tiles and how full they
    are."""
    return _held_experts_fwd(x2, w_held, order, counts, ws, tile, limit)[0]


def _row_accumulator(x2):
    """Zeros for ``add_rows`` to add into, row by row: ``(T, 1, D)`` float32."""
    return jnp.zeros((x2.shape[0], 1, x2.shape[1]), jnp.float32)


def _held_experts_fwd(x2, w_held, order, counts, ws, tile, limit=0.0):
    hidden, (*ins, down), k = _expert_hidden(ws, limit), [_lane_aligned(m) for m in ws], _CHUNK_TILES
    plan = _tile_plan(counts, tile)
    total = plan[1][-1]

    def chunk(c, y):
        group, own, idx, w_rows = _list_tiles(plan, counts, order, w_held, c * k, k, total, tile)
        live = jnp.minimum(total - c * k, k)
        rows = x2[idx]
        act, _ = hidden([_product(rows, m, group, live) for m in ins])
        o = _product(act.astype(x2.dtype), down, group, live, row_scale=w_rows)
        return add_rows(y, idx, o, own)

    y = jax.lax.fori_loop(0, _cdiv(total, k), chunk, _row_accumulator(x2))
    return y.reshape(x2.shape), (x2, w_held, order, counts, ws)


def _held_experts_bwd(tile, limit, res, g):
    """The list again, in slabs of whole experts: a weight gradient is one
    transposed grouped product over all of an expert's rows, which adds its
    row tiles' products in VMEM and writes the expert's ``(D, F)`` once, in
    the weights' dtype. So a slab's chunks leave their operands of those
    products (the rows, the hidden activation, the two or three cotangents,
    in the weights' dtype) in buffers a slab long, and the transposed
    products follow the slab's last chunk. A slab is as many row tiles as
    the largest expert possible (all T tokens) and a tile an expert more,
    rounded up to chunks: the list of an even load is one slab, and of any
    load at most one an expert."""
    x2, w_held, order, counts, ws = res
    f32, (T, D), n = jnp.float32, x2.shape, counts.shape[0]
    hidden, (*ins, down), k = _expert_hidden(ws, limit), [_lane_aligned(m) for m in ws], _CHUNK_TILES
    F = ws[-1].shape[1]
    plan = start, end = _tile_plan(counts, tile)
    slab = _cdiv(_cdiv(T, tile) + n, k) * k  # tiles

    def one_slab(carry):
        e0, dx, dw, stacks = carry
        t0 = start[e0]
        e1 = jnp.sum(end <= t0 + slab, dtype=jnp.int32)  # experts [e0, e1) lie whole in this slab's tiles
        limit = end[e1 - 1]

        def chunk(c, carry):
            dx, dw, kept = carry
            first = t0 + c * k
            group, own, idx, w_rows = _list_tiles(plan, counts, order, w_held, first, k, limit, tile)
            live = jnp.minimum(limit - first, k)
            product = functools.partial(_product, group=group, live=live)
            rows, go = x2[idx], g[idx]
            act, d_hidden = hidden([product(rows, m) for m in ins])
            a = act.astype(x2.dtype)
            o = product(a, down)
            # dw only where an expert's own rows are: the rows past them are tokens of others
            mine = (jnp.arange(tile, dtype=jnp.int32) < own[:, None]).reshape(-1)
            at = jnp.where(mine, jnp.repeat(group, tile, total_repeat_length=k * tile) * T + idx, n * T + jnp.arange(k * tile))
            dw = dw.at[at].set(jnp.sum(o * go, axis=-1), mode="drop", unique_indices=True)
            do = (w_rows[:, None] * go).astype(x2.dtype)
            dhs = [dh.astype(x2.dtype) for dh in d_hidden(product(do, down, transpose=True))]
            drows = functools.reduce(operator.add, [product(dh, m, transpose=True) for dh, m in zip(dhs, ins)])
            kept = tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, new, c * k * tile, 0) for buf, new in zip(kept, (rows, a, do, *dhs))
            )
            return add_rows(dx, idx, drows, own), dw, kept

        kept = tuple(uninitialised((slab * tile, width), x2.dtype) for width in (D, F, D) + (F,) * len(ins))
        dx, dw, (rows, a, do, *dhs) = jax.lax.fori_loop(0, _cdiv(limit - t0, k), chunk, (dx, dw, kept))
        group = _tile_groups(plan, t0, slab)[1]
        stacks = tuple(
            grouped_matmul_t(*((rhs, lhs) if swapped else (lhs, rhs)), group, limit - t0, stack)
            for lhs, rhs, (_, swapped), stack in zip((rows,) * len(ins) + (a,), (*dhs, do), (*ins, down), stacks)
        )
        return e1, dx, dw, stacks

    stacks = tuple(uninitialised(m.shape, m.dtype) for m, _ in (*ins, down))
    _, dx, dw, dws = jax.lax.while_loop(
        lambda carry: carry[0] < n, one_slab, (jnp.int32(0), _row_accumulator(x2), jnp.zeros((n * T,), f32), stacks)
    )
    dws = tuple(jnp.swapaxes(d, 1, 2) if swapped else d for d, (_, swapped) in zip(dws, (*ins, down)))
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dx.reshape(x2.shape).astype(x2.dtype), dw.reshape(n, T), none(order), none(counts), dws


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _routed_to_held(x: jax.Array, route, held: Tuple[int, ...], ws: Tuple[jax.Array, ...], limit: float = 0.0):
    """What the experts held here add for ``x: (..., D)``: (float32 of the
    same shape, the chosen ids ``(T, k)``). ``route(x2) -> (ids, weights)``
    scores and chooses over all experts; ``ws`` are the held experts'
    stacked matrices (``_held_experts``)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    T, n = x2.shape[0], len(held)
    if ws[0].shape[0] != n:
        raise ValueError(f"{n} expert ids held, {ws[0].shape[0]} experts' weights given")
    with jax.named_scope("moe_route"):
        ids, weights = route(x2)
        hit = ids[None] == jnp.asarray(held, ids.dtype)[:, None, None]  # (n, T, k)
        member = jnp.any(hit, axis=-1)  # (n, T)
        w_held = jnp.sum(jnp.where(hit, weights[None], 0.0), axis=-1)  # (n, T) float32, 0 off the expert
        counts = jnp.sum(member, axis=-1, dtype=jnp.int32)
        # Each expert's list of all T tokens, its own first, in token order.
        order = jnp.argsort(~member, axis=-1, stable=True).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        y = _held_experts(x2.astype(ws[0].dtype), w_held, order, counts, ws, held_row_tile(T), limit)
    return y.reshape(orig_shape), ids


def held_tile_stats(held_counts: jax.Array, T: int) -> Dict[str, jax.Array]:
    """What ``_held_experts``' list makes of ``held_counts`` ``(..., n)``,
    each held expert's own rows among ``T``: ``trips``, the row tiles
    (``held_row_tile(T)`` rows) the grouped products multiply in a pass over
    the layer, an expert's own and no other's, one for an expert of no rows;
    and ``tile_fill``, own rows over the rows of those tiles: the share of
    the multiplied rows that is not padding."""
    tile = held_row_tile(T)
    trips = jnp.sum(jnp.maximum(_cdiv(held_counts, tile), 1), axis=-1)
    return {"trips": trips, "tile_fill": jnp.sum(held_counts, axis=-1) / (trips * tile)}


def sigmoid_topk_routed(
    params: Dict[str, Any],
    x: jax.Array,
    *,
    top_k: int,
    held: Tuple[int, ...],
    routed_scale: float,
) -> Tuple[jax.Array, jax.Array]:
    """The routed part of a sigmoid top-k expert layer, for the experts held
    here: ``x: (..., D)`` -> (float32 of the same shape, the chosen ids
    ``(T, k)``).

    ``params``: ``router (D, E)`` and ``router_bias (E,)`` over **all** E
    experts, ``expert_up (n, D, F)`` and ``expert_down (n, F, D)`` of the
    ``n = len(held)`` experts whose ids ``held`` names. Every token scores
    and chooses among all E and its weights are normalised over all
    ``top_k`` chosen; the result is ``sum of w_i f_i(x)`` over the chosen
    experts that are held, and what the others would add is left out
    (summing this over disjoint shares that cover E gives the whole layer).

    **No token is dropped and every shape is static**: each held expert has
    a list of all T tokens with its own sorted to the front (a token
    chooses an expert at most once), and the own rows of all held experts
    are walked as one list sorted by expert, a chunk of row tiles at a time
    (``_held_experts``). A layer that nobody chooses costs its routing and
    a row tile an expert; one that everybody chooses costs ``min(top_k, n)
    * T`` rows. What a row tile pays: an expert's load is rounded up to row
    tiles of 128 rows (``held_row_tile``), so at 512 tokens an expert a
    tenth of the multiplied rows is padding (``held_tile_stats``: 0.84 to
    0.94 full by layer on the chip, where the loops' tiles of 1024 rows
    were 0.44 to 0.53 full: PERF.md, PR 39); a row tile costs its 128 rows
    against the expert's matrix, and an expert's matrix is fetched once a
    product and chunk however many of its row tiles follow each other. 256
    rows a tile padded a fifth more rows and took the same time. What a
    chunk (eight row tiles) pays
    whatever its load: two gathers of scalars (the tokens' weights, 8 to 10
    us) and a kernel launch a product; what it pays by its own rows: the
    gathers of the rows, ``add_rows``' two 8 KB DMAs a row, 30 ns, and its
    pass over the chunk's products (11 us for 8 MB). The weight gradients
    are written once an expert, in the weights' dtype, from float32 blocks
    in VMEM: nothing of an expert's shape is zero-filled, sliced or updated
    in place (PERF.md, PR 39).
    """
    return _routed_to_held(
        x,
        lambda x2: sigmoid_topk_route(x2, params["router"], params["router_bias"], top_k, routed_scale),
        held, (params["expert_up"], params["expert_down"]),
    )


def softmax_topk_routed(
    params: Dict[str, Any], x: jax.Array, *, top_k: int, held: Tuple[int, ...]
) -> Tuple[jax.Array, jax.Array]:
    """The softmax sibling, with gated experts: ``router (D, E)`` over all
    E, ``expert_gate`` and ``expert_up (n, D, F)``, ``expert_down (n, F,
    D)`` of the held ones. Scores are ``softmax`` over all E, the weights
    the ``top_k`` chosen probabilities over their sum, the result ``sum of
    w_i (silu(x G_i) * (x U_i)) D_i`` over the chosen experts held here;
    dropless, and one list of rows sorted by expert as ``sigmoid_topk_routed``
    says, with three grouped products a chunk for two."""
    return _routed_to_held(
        x,
        lambda x2: softmax_topk_route(x2, params["router"], top_k),
        held, (params["expert_gate"], params["expert_up"], params["expert_down"]),
    )


# ------------------------------- top-1 of many, the route an MLP over a stream carried through the depth


def mlp_top1_route(
    x2: jax.Array, r_prev: jax.Array, router: Dict[str, Any], *, norm_eps: float
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A top-1 route that is not a matrix (the ZAYA1 router, Zyphra,
    arXiv:2511.17127, written from memory of the report): ``(T, D)`` tokens
    and the router's stream ``r_prev (T, R)`` from the layer before ->
    the id ``(T, 1)`` of each token's expert, its weight ``(T, 1)`` and the
    stream ``r (T, R)`` the next layer receives::

        r = x W_d + c_d + gamma * r_prev                    exponential depth averaging, gamma a learned vector
        u = rms(r; g_r)
        l = gelu(gelu(u W_1 + c_1) W_2 + c_2) W_3           R -> R -> R -> E, the exact (erf) gelu
        p = softmax(l);   e* = argmax(p + beta_sel);   w = p[e*]

    ``router``: ``down (D, R)``, ``down_b``, ``decay``, ``norm_scale
    (R,)``, ``w1``, ``w2 (R, R)``, ``b1``, ``b2 (R,)``, ``w3 (R, E)``,
    ``bias (E,)``. The bias only selects and takes no
    gradient. **The weight is the chosen probability itself, not
    renormalised**: one chosen weight over its own sum is 1, whatever the
    router says, and no gradient would reach it. All of it in float32, the
    products at full precision (a bfloat16 pass moves a probability by
    1e-2, the distance between neighbours among 16)."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    w = {k: v.astype(f32) for k, v in router.items()}
    r = jnp.matmul(x2.astype(f32), w["down"], precision=hi) + w["down_b"] + w["decay"] * r_prev.astype(f32)
    with jax.named_scope("router_mlp"):
        u = r * jax.lax.rsqrt(jnp.mean(jnp.square(r), axis=-1, keepdims=True) + norm_eps) * w["norm_scale"]
        h = jax.nn.gelu(jnp.matmul(u, w["w1"], precision=hi) + w["b1"], approximate=False)
        h = jax.nn.gelu(jnp.matmul(h, w["w2"], precision=hi) + w["b2"], approximate=False)
        p = jax.nn.softmax(jnp.matmul(h, w["w3"], precision=hi), axis=-1)
    ids = jnp.argmax(p + jax.lax.stop_gradient(w["bias"]), axis=-1, keepdims=True).astype(jnp.int32)
    return ids, jnp.take_along_axis(p, ids, axis=-1), r


def mlp_top1_routed(
    params: Dict[str, Any], x: jax.Array, r_prev: jax.Array, *, held: Tuple[int, ...], norm_eps: float
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed part of a top-1 layer whose router is ``mlp_top1_route``,
    for the gated experts held here: ``x: (..., D)`` and ``r_prev: (...,
    R)`` -> (float32 of ``x``'s shape, the chosen ids ``(T, 1)``, ``r`` of
    ``r_prev``'s shape). ``params``: the router's leaves as ``router_<name>``
    and ``expert_gate`` / ``expert_up (n, D, F)``, ``expert_down (n, F, D)``
    of the held experts. The result is ``w (silu(x G_e) * (x U_e)) D_e`` for
    a token whose expert e is held here and 0 for every other token;
    dropless, and one list of rows sorted by expert as ``sigmoid_topk_routed``
    says. Every chip that shares the layer computes the same route and the
    same ``r``."""
    router = {k[len("router_"):]: v for k, v in params.items() if k.startswith("router_")}
    carried = []

    def route(x2):
        ids, weights, r = mlp_top1_route(x2, r_prev.reshape(x2.shape[0], -1), router, norm_eps=norm_eps)
        carried.append(r)
        return ids, weights

    y, ids = _routed_to_held(x, route, held, (params["expert_gate"], params["expert_up"], params["expert_down"]))
    return y, ids, carried[0].reshape(r_prev.shape)


# ------------------------------- group-limited selection and clamped gated experts (both off by default)


def group_limited(scores: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """Selection scores ``(T, E)`` with every expert outside a token's
    ``topk_group`` best groups set to ``-inf``: the E experts are ``n_group``
    groups of ``E / n_group`` in id order, a group scores the sum of its two
    best, and the ``topk_group`` best groups are kept (DeepSeek-V3's
    ``noaux_tc``, arXiv:2412.19437, from memory). ``n_group`` 1 hands
    ``scores`` back as they are, so ``sigmoid_topk_route`` lowers as it
    did before the argument existed."""
    if n_group == 1:
        return scores
    T, E = scores.shape
    if E % n_group or not 0 < topk_group <= n_group:
        raise ValueError(f"{E} experts in {n_group} groups, {topk_group} of them kept")
    with jax.named_scope("group_select"):
        grouped = scores.reshape(T, n_group, E // n_group)
        _, keep = jax.lax.top_k(jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1), topk_group)  # (T, topk_group)
        kept = jnp.any(jnp.arange(n_group)[None, :, None] == keep[:, None, :], axis=-1)  # (T, n_group)
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(T, E)


def _clamped_gated_hidden(hs, limit):
    """``silu(min(g, l)) * clip(u, -l, l)``, the gated hidden with its
    products clamped at ``l``, and its derivative: nothing reaches a product
    past its clamp."""
    g, u = hs
    gc, uc = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    sig = jax.nn.sigmoid(gc)
    act = gc * sig  # silu(min(g, l))
    d_act = jnp.where(g < limit, sig + act * (1.0 - sig), 0.0)
    return act * uc, lambda da: (da * uc * d_act, jnp.where(jnp.abs(u) < limit, da * act, 0.0))


def _expert_hidden(ws, limit):
    """The hidden activation of an expert of ``ws``' matrices, clamped at
    ``limit`` where it is not 0."""
    if not limit:
        return _HIDDEN[len(ws)]
    if len(ws) != 3:
        raise ValueError("only the gated (three-matrix) expert takes a clamp")
    return functools.partial(_clamped_gated_hidden, limit=float(limit))


def clamped_gated_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, limit: float) -> jax.Array:
    """``gated_ffn`` with its two products clamped at ``limit`` as
    ``_clamped_gated_hidden`` says; ``limit`` 0 is ``gated_ffn`` itself."""
    if not limit:
        return gated_ffn(x, w_gate, w_up, w_down)
    x = x.astype(w_gate.dtype)
    g = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    a, _ = _clamped_gated_hidden((g, u), limit)
    return jnp.matmul(a.astype(w_down.dtype), w_down, preferred_element_type=jnp.float32)


def sigmoid_group_routed(
    params: Dict[str, Any],
    x: jax.Array,
    *,
    top_k: int,
    held: Tuple[int, ...],
    routed_scale: float,
    n_group: int,
    topk_group: int,
    limit: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """``sigmoid_topk_routed`` with gated experts, a group-limited choice
    and an optional clamp: ``router (D, E)`` and ``router_bias (E,)`` over
    all E, ``expert_gate`` / ``expert_up (n, D, F)``, ``expert_down (n, F,
    D)`` of the held ones. Every token keeps its ``topk_group`` best of
    ``n_group`` groups by ``s + bias`` (``group_limited``) and takes the
    ``top_k`` best experts among them; the weights are ``routed_scale *
    s_i / sum of the chosen s``, the result ``sum of w_i E_i(x)`` over the
    chosen experts held here, an expert ``(silu(min(x G_i, l)) * clip(x U_i,
    -l, l)) D_i`` (``limit`` l 0: no clamp). Dropless, one list of rows
    sorted by expert (``_held_experts``)."""
    return _routed_to_held(
        x,
        lambda x2: sigmoid_topk_route(
            x2, params["router"], params["router_bias"], top_k, routed_scale, n_group, topk_group
        ),
        held, (params["expert_gate"], params["expert_up"], params["expert_down"]), limit,
    )


# ------------------------------- a shared expert scaled a token by a sigmoid gate


def sigmoid_gated_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, w_scale: jax.Array) -> jax.Array:
    """``sigmoid(x w_scale) * gated_ffn(x, w_gate, w_up, w_down)``: a shared
    expert whose output each token scales by one gate of its own (``w_scale
    (D, 1)``; Qwen3-Next's ``shared_expert_gate``, from memory of its
    modelling code). The gate's product accumulates in float32, the gate and
    the scaled output are float32."""
    scale = jax.nn.sigmoid(jnp.matmul(x.astype(w_scale.dtype), w_scale, preferred_element_type=jnp.float32))
    return scale * gated_ffn(x, w_gate, w_up, w_down)
