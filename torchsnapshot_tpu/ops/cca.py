"""Compressed convolutional attention (CCA): the front end that makes q, k, v.

Attention in a compressed latent (Zyphra, *Compressed Convolutional
Attention*, arXiv:2510.04476, as ``models/cca_moe_lm.py``'s docstring and
``benchmarks/chip/configs/zaya1_8b.json`` ``assumed`` say: written from
memory of that paper, no network). Every other model of the repository
hands ``causal_attention_route``'s ``attend(q, k, v)`` three plain
projections of the normed stream; here, on ``a: (B, S, D)`` with H query
heads and H_kv KV heads of ``hd``, a group of ``G = H / H_kv`` query heads
a KV head::

    q~ = a Wq  (H x hd),   k~ = a Wk  (H_kv x hd),   no bias
    v  = [a Wv1 ; shift(a) Wv2]             the first half of the KV heads reads the position itself,
                                            the second half the one before (value shift)
    m_q = (q~ + rep(k~)) / 2,   m_k = mean of m_q over each group's G heads            (q-k mean)
    y = sum_j w0[:, j] * shift^(K0-1-j)([q~ ; k~]) + b0          depthwise over the H + H_kv heads' channels
    z_h = sum_j shift^(K1-1-j)(y_h) W1[h, j] + b1[h]             a (hd x hd) matrix a head and tap
    q = z_q + m_q,   k = z_k + m_k
    q <- sqrt(hd) q / |q|,   k <- sqrt(hd) exp(tau) k / |k|      over each head's hd, tau one scalar a KV head
    q, k <- rope on the first ``rotary_dim`` of each head's hd, pairs (i, i + rotary_dim / 2)

``shift`` moves a sequence one position later and fills position 0 with
zeros (``causal_shift``), so both convolutions and the value shift are
causal: position t's q, k, v do not change when a later position does.
The projections take their operands in the weights' dtype and accumulate
in float32; the convolutions (the per-head one at full precision), the
mean, the L2 norm, the temperature and the rotation are float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

L2_FLOOR = 1e-12  # under the root of a head's sum of squares, which is about hd


def causal_shift(x: jax.Array, axis: int = 1) -> jax.Array:
    """``y[t] = x[t - 1]`` along ``axis``, ``y[0] = 0``: one position later,
    zero-filled. What a causal convolution of kernel K is K - 1 of."""
    axis = axis % x.ndim
    head = jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=axis))
    return jnp.concatenate([head, jax.lax.slice_in_dim(x, 0, x.shape[axis] - 1, axis=axis)], axis=axis)


def _taps(x: jax.Array, n: int):
    """``x`` shifted ``n - 1, ..., 1, 0`` positions: what taps 0 .. n-1 of a
    causal kernel of ``n`` multiply."""
    out = [x]
    for _ in range(n - 1):
        out.append(causal_shift(out[-1]))
    return out[::-1]


def depthwise_causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """``y[t] = sum_j w[:, j] * x[t - (K-1) + j] + b`` on ``x: (B, S, C)``
    with ``w: (C, K)``, zeros ahead of the sequence. float32."""
    f32 = jnp.float32
    return sum(t * w[:, j].astype(f32) for j, t in enumerate(_taps(x.astype(f32), w.shape[1]))) + b.astype(f32)


def headwise_causal_conv(y: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """``z_h[t] = sum_j y_h[t - (K-1) + j] W[h, j] + b[h]`` on ``y: (B, S,
    n, hd)`` with ``w: (n, K, hd, hd)`` and ``b: (n, hd)``: the channels of
    a head mix, heads do not. float32 at full precision."""
    f32 = jnp.float32
    return sum(
        jnp.einsum("bshd,hde->bshe", t, w[:, j].astype(f32), precision=jax.lax.Precision.HIGHEST)
        for j, t in enumerate(_taps(y.astype(f32), w.shape[1]))
    ) + b.astype(f32)


def l2_normalize(x: jax.Array) -> jax.Array:
    """``sqrt(hd) x / |x|`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_FLOOR) * x.shape[-1] ** 0.5)


def partial_rope(x: jax.Array, positions: jax.Array, theta: float, rotary_dim: int) -> jax.Array:
    """Rotary positions on the first ``rotary_dim`` of ``x: (B, S, H, hd)``
    at ``positions (S,)``, half-split pairing ``(i, i + rotary_dim / 2)``
    inside the rotated part; the rest passes. float32."""
    x = x.astype(jnp.float32)
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # (S, rotary_dim / 2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = jnp.split(x, [rotary_dim // 2, rotary_dim], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _mm(x, w):
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def cca_qkv(
    params: Dict[str, Any],
    a: jax.Array,
    *,
    rope_theta: float,
    rotary_dim: int,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The normed stream ``a: (B, S, D)`` -> ``q (B, S, H, hd)``, ``k`` and
    ``v (B, S, H_kv, hd)`` float32, in the layout ``attend`` takes (module
    docstring). ``params``: ``q (D, H hd)``, ``k (D, H_kv hd)``, ``v1`` and
    ``v2 (D, H_kv hd / 2)``, ``conv0_w (C, K0)`` and ``conv0_b (C,)`` over
    the ``C = (H + H_kv) hd`` channels ``[q ; k]``, ``conv1_w (H + H_kv,
    K1, hd, hd)`` and ``conv1_b (H + H_kv, hd)``, ``temp (H_kv,)``. The
    head counts are read off the shapes."""
    B, S, _ = a.shape
    n_kv = params["temp"].shape[-1]
    hd = params["k"].shape[-1] // n_kv
    n_q = params["q"].shape[-1] // hd
    if n_q % n_kv or n_kv % 2 or params["conv1_w"].shape[0] != n_q + n_kv:
        raise ValueError(f"{n_q} query heads, {n_kv} KV heads of {hd}, conv1_w {params['conv1_w'].shape}")
    group, A = n_q // n_kv, n_q * hd
    with jax.named_scope("cca_proj"):
        q0, k0 = _mm(a, params["q"]), _mm(a, params["k"])  # (B, S, A), (B, S, A_kv) float32
        # a[t-1] Wv2 = (a Wv2)[t-1]: the projection has no bias, so the
        # shift moves hd channels a head and not the stream's D.
        v = jnp.concatenate([_mm(a, params["v1"]), causal_shift(_mm(a, params["v2"]))], axis=-1)
        v = v.reshape(B, S, n_kv, hd)
    with jax.named_scope("cca_qkmean"):
        qg, kg = q0.reshape(B, S, n_kv, group, hd), k0.reshape(B, S, n_kv, 1, hd)
        m_q = 0.5 * (qg + kg)
        m_k = jnp.mean(m_q, axis=3)
    with jax.named_scope("cca_conv"):
        w0, b0, w1, b1 = params["conv0_w"], params["conv0_b"], params["conv1_w"], params["conv1_b"]
        # depthwise, then a matrix a head: [q ; k] packed is q and k apart,
        # each with its own slice of the kernels
        z_q = headwise_causal_conv(depthwise_causal_conv(q0, w0[:A], b0[:A]).reshape(B, S, n_q, hd), w1[:n_q], b1[:n_q])
        z_k = headwise_causal_conv(depthwise_causal_conv(k0, w0[A:], b0[A:]).reshape(B, S, n_kv, hd), w1[n_q:], b1[n_q:])
        q = z_q + m_q.reshape(B, S, n_q, hd)
        k = z_k + m_k
    with jax.named_scope("cca_norm_rope"):
        if positions is None:
            positions = jnp.arange(S)
        q = partial_rope(l2_normalize(q), positions, rope_theta, rotary_dim)
        k = l2_normalize(k) * jnp.exp(params["temp"].astype(jnp.float32))[:, None]
        k = partial_rope(k, positions, rope_theta, rotary_dim)
    return q, k, v
