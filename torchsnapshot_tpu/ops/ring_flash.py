"""Ring attention with the Pallas flash kernel as the inner compute.

``ring_attention.py`` rotates K/V shards around a mesh axis and merges
online-softmax statistics with a pure-JAX block update. That inner
compute is the hot loop of long-context training, and the Pallas flash
kernel is the TPU path for it (speed on the chip: not measured,
ROADMAP A5). This module fuses the
two: each ring hop runs the flash kernel on the resident Q shard against
the currently-held K/V shard, and hops are merged by their log-sum-exp
statistics — o = Σ exp(lse_i − m)·o_i / Σ exp(lse_i − m), the exact
associative combine for normalized partials.

Because causality across shards is coarse — the hop holding the device's
OWN shard is the only diagonal (causal mask inside the kernel); shards
owned by lower ring indices are entirely in the past (full attention);
higher indices entirely in the future (skipped) — hop 0 uses the causal
kernel once and every later hop uses the full kernel, no per-hop
branching.

The whole ring loop lives inside one ``jax.custom_vjp``: the backward
pass re-rotates K/V the same way and drives the flash backward kernels
with the GLOBAL lse/delta (exact FA2 gradients for any key subset),
accumulating dK/dV in tensors that rotate alongside their shards so each
arrives home after a full cycle. Like the plain ring, per-device memory
stays O(S_local · D) and each hop's ppermute is an ICI-neighbor
exchange.

No reference analogue (the reference has no attention code at all);
the pure-JAX ring remains the fallback for non-TPU backends and
non-divisible block shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .attention import NEG_INF, pick_block_size
from .pallas_attention import _make_flash_parts
from .ring_attention import _rotate  # shared ring-neighbor permutation


def _merge(o, lse, o_s, lse_s):
    """Associative combine of normalized attention partials (f32 o)."""
    m = jnp.maximum(lse, lse_s)
    w1 = jnp.exp(lse - m)
    w2 = jnp.exp(lse_s - m)
    denom = w1 + w2
    o_new = (w1 * o + w2 * o_s.astype(jnp.float32)) / denom
    return o_new, m + jnp.log(denom)


def _varying(x, axis_name: str):
    vma = jax.typeof(x).vma
    return x if axis_name in vma else lax.pcast(x, (axis_name,), to="varying")


@functools.lru_cache(maxsize=None)
def _make_ring_flash(axis_name, causal, scale, block_q, block_k, interpret):
    fwd_full, bwd_full = _make_flash_parts(
        False, scale, block_q, block_k, interpret
    )
    if causal:
        fwd_diag, bwd_diag = _make_flash_parts(
            True, scale, block_q, block_k, interpret
        )
    else:
        fwd_diag, bwd_diag = fwd_full, bwd_full

    def fwd_pass(q, k, v):
        ring = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        # Hop 0: the device's own shard — the causal diagonal.
        o0, lse0 = fwd_diag(q, k, v)
        carry0 = (
            o0.astype(jnp.float32),
            lse0,
            _rotate(_varying(k, axis_name), axis_name, ring),
            _rotate(_varying(v, axis_name), axis_name, ring),
        )

        def hop(carry, s):
            o, lse, k_cur, v_cur = carry
            o_s, lse_s = fwd_full(q, k_cur, v_cur)
            if causal:
                # After s hops we hold the shard of (me - s) mod ring;
                # owners ahead of us are entirely in the future.
                skip = ((me - s) % ring) > me
                o_s = jnp.where(skip, jnp.zeros_like(o_s), o_s)
                lse_s = jnp.where(skip, jnp.full_like(lse_s, NEG_INF), lse_s)
            o, lse = _merge(o, lse, o_s, lse_s)
            return (
                o,
                lse,
                _rotate(k_cur, axis_name, ring),
                _rotate(v_cur, axis_name, ring),
            ), None

        # axis_size is static inside shard_map, so the hop count is too.
        (o, lse, _, _), _ = lax.scan(hop, carry0, jnp.arange(1, ring))
        return o.astype(q.dtype), lse

    @jax.custom_vjp
    def ring_flash(q, k, v):
        return fwd_pass(q, k, v)[0]

    def ring_flash_fwd(q, k, v):
        o, lse = fwd_pass(q, k, v)
        return o, (q, k, v, o, lse)

    def ring_flash_bwd(res, g):
        q, k, v, o, lse = res
        ring = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        # delta from the full-precision cotangent, THEN downcast g for the
        # kernels — matching the non-ring flash_bwd exactly.
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )
        g = g.astype(q.dtype)

        # Hop 0 on the home shard (diagonal), then rotate; later hops use
        # the full kernel with the GLOBAL lse/delta. dK/dV accumulate in
        # tensors rotating WITH their shard: after `ring` rotations each
        # gradient lands back on its owner.
        dq0, dk0, dv0 = bwd_diag(q, k, v, g, lse, delta)
        carry0 = (
            dq0.astype(jnp.float32),
            _rotate(_varying(k, axis_name), axis_name, ring),
            _rotate(_varying(v, axis_name), axis_name, ring),
            _rotate(dk0.astype(jnp.float32), axis_name, ring),
            _rotate(dv0.astype(jnp.float32), axis_name, ring),
        )

        def hop(carry, s):
            dq, k_cur, v_cur, dk_cur, dv_cur = carry
            dq_s, dk_s, dv_s = bwd_full(q, k_cur, v_cur, g, lse, delta)
            if causal:
                skip = ((me - s) % ring) > me
                dq_s = jnp.where(skip, jnp.zeros_like(dq_s), dq_s)
                dk_s = jnp.where(skip, jnp.zeros_like(dk_s), dk_s)
                dv_s = jnp.where(skip, jnp.zeros_like(dv_s), dv_s)
            return (
                dq + dq_s.astype(jnp.float32),
                _rotate(k_cur, axis_name, ring),
                _rotate(v_cur, axis_name, ring),
                _rotate(dk_cur + dk_s.astype(jnp.float32), axis_name, ring),
                _rotate(dv_cur + dv_s.astype(jnp.float32), axis_name, ring),
            ), None

        (dq, _, _, dk, dv), _ = lax.scan(hop, carry0, jnp.arange(1, ring))
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    ring_flash.defvjp(ring_flash_fwd, ring_flash_bwd)
    return ring_flash


def ring_flash_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-shard ring-flash body on ``(B, S_local, H, D)``; must run
    inside ``shard_map`` over ``axis_name`` (same contract as
    ``ring_self_attention``, same layout: device i owns global positions
    [i·S_local, (i+1)·S_local))."""
    B, S_loc, H, D = q.shape
    if block_q is None:
        block_q = pick_block_size(S_loc, 512) or min(512, S_loc)
    if block_k is None:
        block_k = pick_block_size(S_loc, 512) or min(512, S_loc)
    block_q = min(block_q, S_loc)
    block_k = min(block_k, S_loc)
    if S_loc % block_q or S_loc % block_k:
        raise ValueError(
            f"local seq len {S_loc} must be divisible by block_q={block_q} "
            f"and block_k={block_k}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = D**-0.5

    fn = _make_ring_flash(axis_name, causal, scale, block_q, block_k, interpret)

    def flat(x):  # (B, S, H, D) -> (B*H, S, D)
        return x.transpose(0, 2, 1, 3).reshape(B * H, S_loc, D)

    out = fn(flat(q), flat(k), flat(v))
    return out.reshape(B, H, S_loc, D).transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _make_zigzag_flash(axis_name, scale, block_q, block_k, interpret):
    """Zigzag (causally load-balanced) ring with flash inner kernels.

    Same layout contract as ``zigzag_ring_self_attention`` (device i owns
    chunks (i, 2n-1-i) concatenated): per hop the always-needed
    q_hi x kv_lo block runs the full kernel, and a ``lax.switch`` picks
    the diagonal (two causal kernels), below (one full on the lo half),
    or above (one full on the hi half) — every device does the same ~2
    half-blocks of kernel work per hop.
    """
    fwd_full, bwd_full = _make_flash_parts(
        False, scale, block_q, block_k, interpret
    )
    fwd_diag, bwd_diag = _make_flash_parts(
        True, scale, block_q, block_k, interpret
    )

    def _neutral(like_o, like_lse):
        return (
            jnp.zeros_like(like_o),
            jnp.full_like(like_lse, NEG_INF),
        )

    def fwd_pass(q, k, v):
        ring = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        BH, S_loc, D = q.shape
        half = S_loc // 2
        q_lo, q_hi = q[:, :half], q[:, half:]
        # Scan carries must hold a stable vma type: fresh zeros are
        # replicated while kernel outputs vary over the ring axis, so
        # promote the inits (the TPU vma checker rejects the mismatch;
        # interpret mode does not — see tests' check_vma note).
        o0 = _varying(jnp.zeros((BH, half, D), jnp.float32), axis_name)
        l0 = _varying(jnp.full((BH, half, 1), NEG_INF, jnp.float32), axis_name)

        def hop(carry, s):
            o_lo, l_lo, o_hi, l_hi, k_cur, v_cur = carry
            j = lax.rem(me - s + ring, ring)
            k_lo, v_lo = k_cur[:, :half], v_cur[:, :half]
            k_hi, v_hi = k_cur[:, half:], v_cur[:, half:]

            # q_hi x kv_lo: chunk 2n-1-me is strictly after every lo
            # chunk — always needed, never masked.
            o_s, l_s = fwd_full(q_hi, k_lo, v_lo)
            o_hi, l_hi = _merge(o_hi, l_hi, o_s, l_s)

            def diagonal(_):
                a_o, a_l = fwd_diag(q_lo, k_lo, v_lo)
                b_o, b_l = fwd_diag(q_hi, k_hi, v_hi)
                return a_o, a_l, b_o, b_l

            def below(_):
                a_o, a_l = fwd_full(q_lo, k_lo, v_lo)
                n_o, n_l = _neutral(a_o, a_l)
                return a_o, a_l, n_o, n_l

            def above(_):
                b_o, b_l = fwd_full(q_hi, k_hi, v_hi)
                n_o, n_l = _neutral(b_o, b_l)
                return n_o, n_l, b_o, b_l

            branch = jnp.where(j == me, 0, jnp.where(j < me, 1, 2))
            a_o, a_l, b_o, b_l = lax.switch(
                branch, (diagonal, below, above), 0
            )
            o_lo, l_lo = _merge(o_lo, l_lo, a_o, a_l)
            o_hi, l_hi = _merge(o_hi, l_hi, b_o, b_l)
            return (
                o_lo, l_lo, o_hi, l_hi,
                _rotate(k_cur, axis_name, ring),
                _rotate(v_cur, axis_name, ring),
            ), None

        carry0 = (
            o0, l0, o0, l0,
            _varying(k, axis_name), _varying(v, axis_name),
        )
        (o_lo, l_lo, o_hi, l_hi, _, _), _ = lax.scan(
            hop, carry0, jnp.arange(ring)
        )
        o = jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)
        lse = jnp.concatenate([l_lo, l_hi], axis=1)
        return o, lse

    @jax.custom_vjp
    def zz_flash(q, k, v):
        return fwd_pass(q, k, v)[0]

    def zz_fwd(q, k, v):
        o, lse = fwd_pass(q, k, v)
        return o, (q, k, v, o, lse)

    def zz_bwd(res, g):
        q, k, v, o, lse = res
        ring = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        BH, S_loc, D = q.shape
        half = S_loc // 2
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )
        g = g.astype(q.dtype)
        q_lo, q_hi = q[:, :half], q[:, half:]
        g_lo, g_hi = g[:, :half], g[:, half:]
        lse_lo, lse_hi = lse[:, :half], lse[:, half:]
        d_lo, d_hi = delta[:, :half], delta[:, half:]
        # Varying like the kernel outputs: used both as scan-carry inits
        # and inside lax.switch branches, where all branches must agree.
        zero = _varying(jnp.zeros((BH, half, D), jnp.float32), axis_name)

        def hop(carry, s):
            dq_lo, dq_hi, k_cur, v_cur, dk_cur, dv_cur = carry
            j = lax.rem(me - s + ring, ring)
            k_lo, v_lo = k_cur[:, :half], v_cur[:, :half]
            k_hi, v_hi = k_cur[:, half:], v_cur[:, half:]

            a_dq, a_dk, a_dv = bwd_full(q_hi, k_lo, v_lo, g_hi, lse_hi, d_hi)

            def diagonal(_):
                dql, dkl, dvl = bwd_diag(q_lo, k_lo, v_lo, g_lo, lse_lo, d_lo)
                dqh, dkh, dvh = bwd_diag(q_hi, k_hi, v_hi, g_hi, lse_hi, d_hi)
                return tuple(
                    x.astype(jnp.float32) for x in (dql, dkl, dvl, dqh, dkh, dvh)
                )

            def below(_):
                dql, dkl, dvl = bwd_full(q_lo, k_lo, v_lo, g_lo, lse_lo, d_lo)
                return (
                    dql.astype(jnp.float32),
                    dkl.astype(jnp.float32),
                    dvl.astype(jnp.float32),
                    zero, zero, zero,
                )

            def above(_):
                dqh, dkh, dvh = bwd_full(q_hi, k_hi, v_hi, g_hi, lse_hi, d_hi)
                return (
                    zero, zero, zero,
                    dqh.astype(jnp.float32),
                    dkh.astype(jnp.float32),
                    dvh.astype(jnp.float32),
                )

            branch = jnp.where(j == me, 0, jnp.where(j < me, 1, 2))
            dql, dkl, dvl, dqh, dkh, dvh = lax.switch(
                branch, (diagonal, below, above), 0
            )
            dk_new = jnp.concatenate(
                [
                    dk_cur[:, :half]
                    + dkl + a_dk.astype(jnp.float32),
                    dk_cur[:, half:] + dkh,
                ],
                axis=1,
            )
            dv_new = jnp.concatenate(
                [
                    dv_cur[:, :half]
                    + dvl + a_dv.astype(jnp.float32),
                    dv_cur[:, half:] + dvh,
                ],
                axis=1,
            )
            return (
                dq_lo + dql,
                dq_hi + dqh + a_dq.astype(jnp.float32),
                _rotate(k_cur, axis_name, ring),
                _rotate(v_cur, axis_name, ring),
                _rotate(dk_new, axis_name, ring),
                _rotate(dv_new, axis_name, ring),
            ), None

        carry0 = (
            zero, zero,
            _varying(k, axis_name), _varying(v, axis_name),
            _varying(jnp.zeros((BH, S_loc, D), jnp.float32), axis_name),
            _varying(jnp.zeros((BH, S_loc, D), jnp.float32), axis_name),
        )
        (dq_lo, dq_hi, _, _, dk, dv), _ = lax.scan(
            hop, carry0, jnp.arange(ring)
        )
        dq = jnp.concatenate([dq_lo, dq_hi], axis=1)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    zz_flash.defvjp(zz_fwd, zz_bwd)
    return zz_flash


def zigzag_ring_flash_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Zigzag-flash body on ``(B, S_local, H, D)`` in zigzag layout; must
    run inside ``shard_map`` (same contract as
    ``zigzag_ring_self_attention``)."""
    B, S_loc, H, D = q.shape
    if S_loc % 2:
        raise ValueError(f"zigzag needs an even local seq length, got {S_loc}")
    half = S_loc // 2
    if block_q is None:
        block_q = pick_block_size(half, 512) or min(512, half)
    if block_k is None:
        block_k = pick_block_size(half, 512) or min(512, half)
    block_q = min(block_q, half)
    block_k = min(block_k, half)
    if half % block_q or half % block_k:
        raise ValueError(
            f"half-shard length {half} must be divisible by "
            f"block_q={block_q} and block_k={block_k}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = D**-0.5

    fn = _make_zigzag_flash(axis_name, scale, block_q, block_k, interpret)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S_loc, D)

    out = fn(flat(q), flat(k), flat(v))
    return out.reshape(B, H, S_loc, D).transpose(0, 2, 1, 3)


def zigzag_ring_flash_attention_sharded(
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
    """Zigzag-flash on ``(B, S, H, D)`` arrays — drop-in for
    ``zigzag_ring_attention_sharded`` with the Pallas inner kernel."""
    from .ring_attention import _zigzag_sharded

    fn = functools.partial(
        zigzag_ring_flash_self_attention, axis_name=seq_axis, scale=scale
    )
    return _zigzag_sharded(
        fn, q, k, v, mesh, seq_axis, batch_axis, head_axis, in_layout,
        # Pallas interpret mode trips the vma checker off-TPU (see
        # ring_flash_attention_sharded).
        check_vma=jax.default_backend() == "tpu",
    )


def ring_flash_attention_sharded(
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
    """Ring-flash attention on globally-shaped ``(B, S, H, D)`` arrays —
    drop-in for ``ring_attention_sharded`` with the Pallas inner kernel."""
    axes = set(mesh.axis_names)
    if seq_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks seq axis {seq_axis!r}")
    b = batch_axis if batch_axis in axes else None
    h = head_axis if head_axis in axes else None
    spec = P(b, seq_axis, h, None)
    fn = functools.partial(
        ring_flash_self_attention, axis_name=seq_axis, causal=causal, scale=scale
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # Pallas interpret mode (CPU tests) mixes empty-vma internals with
        # varying operands and trips the vma checker; on TPU the real
        # lowering type-checks fine (same workaround as
        # flash_attention_sharded / ulysses).
        check_vma=jax.default_backend() == "tpu",
    )(q, k, v)
