"""Ring attention: context parallelism over a mesh axis.

The sequence dimension is sharded over a mesh axis (the "ring"). Each device
keeps its Q shard resident and its K/V shard rotates one hop per step around
the ring via ``jax.lax.ppermute`` — an ICI-neighbor exchange, the cheapest
collective pattern on a TPU torus. After ``ring_size`` steps every Q shard
has attended to every K/V shard; softmax statistics are merged online
(same accumulator as blockwise attention), so no (S, S) matrix and no
full-sequence gather ever materializes. Peak per-device memory is
O(S_local * D) and the K/V transfer fully overlaps with the block matmul
XLA schedules for the previous step.

``ring_self_attention`` is written to run *inside* ``jax.shard_map`` (it
uses ``axis_index``/``ppermute``); ``ring_attention_sharded`` is the
convenience wrapper that applies ``shard_map`` with the canonical specs.

The reference framework has no context parallelism (SURVEY.md §5.7 — its
checkpoint layer just reshards whatever state such schemes produce); this op
exists because long-context training is first-class in the TPU build.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .attention import NEG_INF, _finalize, attention_block_update


def _ring_acc_init(q: jax.Array, axis_name: str):
    """Zero (o, m, l) online-softmax accumulator shaped like ``q``.

    The scan carry is device-varying over every mesh axis q varies over
    plus the ring axis (masks depend on ``axis_index``); shard_map tracks
    this in the type system, so the initializers must declare it.
    """
    vma = jax.typeof(q).vma
    qv = q if axis_name in vma else jax.lax.pcast(q, (axis_name,), to="varying")
    qz = qv.astype(jnp.float32) * 0.0
    zrow = qz[..., 0].transpose(0, 2, 1)  # (B, H, S) of zeros
    return qz, zrow + NEG_INF, zrow


def _rotate(x: jax.Array, axis_name: str, ring: int) -> jax.Array:
    """One hop around the ring (device i -> i+1 mod ring)."""
    return jax.lax.ppermute(
        x, axis_name, [(i, (i + 1) % ring) for i in range(ring)]
    )


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Per-shard ring attention body. Must run inside ``shard_map``.

    ``q, k, v: (B, S_local, H, D)`` — the local sequence shard; the global
    sequence is ``ring_size * S_local`` laid out contiguously along the axis
    (device i owns positions [i*S_local, (i+1)*S_local)).
    """
    B, S_loc, H, D = q.shape
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = D**-0.5

    q_pos = me * S_loc + jnp.arange(S_loc)

    def step(carry, s):
        o, m, l, k_cur, v_cur = carry
        # After s hops device `me` holds the shard owned by (me - s) mod ring.
        owner = jax.lax.rem(me - s + ring, ring)
        k_pos = owner * S_loc + jnp.arange(S_loc)
        o, m, l = attention_block_update(
            q, k_cur, v_cur, q_pos, k_pos, scale, causal, (o, m, l)
        )
        # Rotate even on the last step (returns K/V to its owner); the
        # extra hop costs one neighbor exchange and keeps the scan uniform.
        k_nxt = _rotate(k_cur, axis_name, ring)
        v_nxt = _rotate(v_cur, axis_name, ring)
        return (o, m, l, k_nxt, v_nxt), None

    acc = _ring_acc_init(q, axis_name)
    # Step 0 processes the diagonal block (owner == me), which always
    # contains valid keys for causal masking — see attention_block_update.
    (o, m, l, _, _), _ = jax.lax.scan(step, (*acc, k, v), jnp.arange(ring))
    return _finalize((o, m, l), q.dtype)


def zigzag_ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causally load-balanced ring attention. Must run inside ``shard_map``.

    Plain ring attention with a causal mask wastes ~half its FLOPs: at ring
    step ``s`` every device computes a full (S_local x S_local) score block
    and masks it, even when the incoming K/V shard lies entirely above its
    queries' diagonal. The zigzag layout (striped/zigzag ring attention)
    folds the sequence: with ring size n, the global sequence is cut into
    2n chunks and device i owns chunks ``(i, 2n-1-i)`` concatenated —
    ``q[:, :half]`` is chunk i ("lo"), ``q[:, half:]`` is chunk 2n-1-i
    ("hi"). Then at every step exactly one of the four (q-half, kv-half)
    pairs is fully below the diagonal (q_hi x kv_lo — computed unmasked),
    one is fully above (skipped entirely), and the remaining work is one
    full block (off-diagonal steps) or two triangular blocks (the diagonal
    step) selected by ``lax.switch``. Every device does the same ~2
    half-blocks of matmul per step: ~2x the causal throughput of the plain
    ring, with identical numerics.

    ``q, k, v: (B, S_local, H, D)`` in zigzag layout (use
    ``zigzag_ring_attention_sharded`` to apply the layout from globally
    ordered arrays, or keep activations zigzag end-to-end in training).
    """
    B, S_loc, H, D = q.shape
    if S_loc % 2:
        raise ValueError(f"zigzag needs an even local seq length, got {S_loc}")
    half = S_loc // 2
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = D**-0.5

    pos = jnp.arange(half)
    q_lo, q_hi = q[:, :half], q[:, half:]
    pos_lo = me * half + pos  # global positions of chunk `me`
    pos_hi = (2 * ring - 1 - me) * half + pos  # chunk 2n-1-me

    def step(carry, s):
        acc_lo, acc_hi, k_cur, v_cur = carry
        j = jax.lax.rem(me - s + ring, ring)  # owner of the incoming shard
        k_lo, v_lo = k_cur[:, :half], v_cur[:, :half]
        k_hi, v_hi = k_cur[:, half:], v_cur[:, half:]
        kpos_lo = j * half + pos
        kpos_hi = (2 * ring - 1 - j) * half + pos

        # q_hi x kv_lo: chunk 2n-1-me is always strictly after chunk j<n,
        # so this block is always needed and never masked.
        acc_hi = attention_block_update(
            q_hi, k_lo, v_lo, pos_hi, kpos_lo, scale, False, acc_hi
        )

        def diagonal(acc_lo, acc_hi):  # j == me: two triangular blocks
            acc_lo = attention_block_update(
                q_lo, k_lo, v_lo, pos_lo, kpos_lo, scale, True, acc_lo
            )
            acc_hi = attention_block_update(
                q_hi, k_hi, v_hi, pos_hi, kpos_hi, scale, True, acc_hi
            )
            return acc_lo, acc_hi

        def below(acc_lo, acc_hi):  # j < me: q_lo x kv_lo, full
            acc_lo = attention_block_update(
                q_lo, k_lo, v_lo, pos_lo, kpos_lo, scale, False, acc_lo
            )
            return acc_lo, acc_hi

        def above(acc_lo, acc_hi):  # j > me: q_hi x kv_hi, full
            acc_hi = attention_block_update(
                q_hi, k_hi, v_hi, pos_hi, kpos_hi, scale, False, acc_hi
            )
            return acc_lo, acc_hi

        branch = jnp.where(j == me, 0, jnp.where(j < me, 1, 2))
        acc_lo, acc_hi = jax.lax.switch(
            branch, (diagonal, below, above), acc_lo, acc_hi
        )

        k_nxt = _rotate(k_cur, axis_name, ring)
        v_nxt = _rotate(v_cur, axis_name, ring)
        return (acc_lo, acc_hi, k_nxt, v_nxt), None

    # Step 0 is the diagonal (j == me): both accumulators fold in a block
    # containing their diagonal first, so the NEG_INF init never leaks.
    (acc_lo, acc_hi, _, _), _ = jax.lax.scan(
        step,
        (
            _ring_acc_init(q[:, :half], axis_name),
            _ring_acc_init(q[:, half:], axis_name),
            k,
            v,
        ),
        jnp.arange(ring),
    )
    out_lo = _finalize(acc_lo, q.dtype)
    out_hi = _finalize(acc_hi, q.dtype)
    return jnp.concatenate([out_lo, out_hi], axis=1)


def zigzag_layout_indices(seq_len: int, ring: int) -> "jnp.ndarray":
    """Permutation mapping a globally ordered sequence to zigzag layout.

    ``take(x, idx, axis=seq)`` then sharding over the ring axis gives
    device i chunks (i, 2n-1-i). Invert with ``argsort(idx)``.
    """
    if seq_len % (2 * ring):
        raise ValueError(f"seq {seq_len} not divisible by 2*ring={2 * ring}")
    chunk = seq_len // (2 * ring)
    order = []
    for i in range(ring):
        order.extend([i, 2 * ring - 1 - i])
    idx = jnp.concatenate(
        [jnp.arange(c * chunk, (c + 1) * chunk) for c in order]
    )
    return idx


def _zigzag_sharded(
    body_fn,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str,
    batch_axis: Optional[str],
    head_axis: Optional[str],
    in_layout: bool,
    check_vma: bool = True,
) -> jax.Array:
    """Shared zigzag shard_map wrapper: the layout permute contract lives
    here ONCE for both the pure-JAX and flash-kernel zigzag bodies."""
    axes = set(mesh.axis_names)
    if seq_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks seq axis {seq_axis!r}")
    ring = mesh.shape[seq_axis]
    b = batch_axis if batch_axis in axes else None
    h = head_axis if head_axis in axes else None
    spec = P(b, seq_axis, h, None)
    if not in_layout:
        idx = zigzag_layout_indices(q.shape[1], ring)
        inv = jnp.argsort(idx)
        q, k, v = (jnp.take(x, idx, axis=1) for x in (q, k, v))
    out = jax.shard_map(
        body_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=check_vma,
    )(q, k, v)
    if not in_layout:
        out = jnp.take(out, inv, axis=1)
    return out


def zigzag_ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = "model",
    scale: Optional[float] = None,
    in_layout: bool = False,
) -> jax.Array:
    """Zigzag ring attention on ``(B, S, H, D)`` arrays.

    With ``in_layout=False`` (default) the inputs are globally ordered:
    the wrapper permutes the sequence into zigzag layout (one resharding
    collective), runs the balanced ring, and permutes back. Training loops
    that keep activations in zigzag layout end-to-end pass
    ``in_layout=True`` and skip both permutes — every position-wise op
    commutes with the layout, so only attention needs to know about it
    (see models/transformer.py, which permutes once after the position
    encoding and inverts once at the logits).
    """
    fn = partial(zigzag_ring_self_attention, axis_name=seq_axis, scale=scale)
    return _zigzag_sharded(
        fn, q, k, v, mesh, seq_axis, batch_axis, head_axis, in_layout
    )


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = "model",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Apply ring attention to globally-shaped ``(B, S, H, D)`` arrays.

    Sequence is sharded over ``seq_axis`` (the ring); batch over
    ``batch_axis`` and heads over ``head_axis`` when those axes exist —
    heads are embarrassingly parallel in attention, so tensor parallelism
    composes with the ring at zero extra communication.
    """
    axes = set(mesh.axis_names)
    if seq_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks seq axis {seq_axis!r}")
    b = batch_axis if batch_axis in axes else None
    h = head_axis if head_axis in axes else None
    spec = P(b, seq_axis, h, None)
    fn = partial(
        ring_self_attention, axis_name=seq_axis, causal=causal, scale=scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
