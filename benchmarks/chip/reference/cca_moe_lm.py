"""Plain reference of the ``zaya`` stack (compressed convolutional attention,
an MLP top-1 router with a depth-averaged carry, residual scaling, a tied
sliced vocabulary), in float32 ``jax.numpy``.

No kernel, no tiles, no sort, no scan, no checkpoint, no sharding: the
convolutions as explicit sums over shifted copies, the value shift as a
projection of the shifted stream, softmax attention over the whole causal
score matrix (one query head at a time, so that 8192 x 8192 scores fit), a
dense loop over the held experts with a weight that is 0 where a position
chose another. It follows the config's keys and the layer equations of
``models/cca_moe_lm.py``'s docstring and is independent of that module and
of ``ops/``: only the layout of the parameter tree is shared (a layer is
an index into the stacked leaves). With H query and H_kv KV heads of hd,
G = H / H_kv::

    x = embed[tokens];  r = 0
    per layer:
      a = rms(x; g1);  a_prev[t] = a[t-1], a_prev[0] = 0
      q~ = a Wq;  k~ = a Wk;  v = [a Wv1 ; a_prev Wv2]               half the KV heads read the position before
      m_q = (q~ + rep(k~)) / 2;  m_k = mean of m_q over a group's G heads
      c = [q~ ; k~];  y[t] = sum_j w0[:, j] c[t-(K0-1)+j] + b0;  z_h[t] = sum_j y_h[t-(K1-1)+j] W1[h, j] + b1[h]
      q = z_q + m_q;  k = z_k + m_k;  q <- sqrt(hd) q / |q|;  k <- sqrt(hd) exp(tau) k / |k|
      q, k <- rope on the first rotary_dim of hd, pairs (i, i + rotary_dim / 2)
      x <- (x + beta_r) sigma_r + (softmax(q k^T / sqrt(hd) + causal) v Wo + beta_h) sigma_h
      b = rms(x; g2);  r <- b W_d + c_d + gamma r;  u = rms(r; g_r)
      p = softmax(gelu(gelu(u W1 + c1) W2 + c2) W3);  e* = argmax(p + beta_sel);  w = p[e*]
      x <- (x + beta_r') sigma_r' + (w (silu(b G_e*) * (b U_e*)) D_e* [e* HELD, else 0] + beta_h') sigma_h'
    logits = rms(x; g_f) embed^T;   loss = mean CE(logits[t], targets[t])

``held`` are the ids of the experts whose weights ``expert_gate`` /
``expert_up`` / ``expert_down`` hold, in that order; the router is as wide
as the model's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def shifted(x, n):
    """``y[t] = x[t - n]`` along axis 1, zeros for ``t < n``."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :n]), x[:, : x.shape[1] - n]], axis=1)


def _rope(x, theta, rotary_dim):
    """(B, S, H, hd): the first ``rotary_dim`` rotated at positions 0 .. S-1."""
    half = rotary_dim // 2
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * theta ** (-jnp.arange(0, rotary_dim, 2, dtype=F32) / rotary_dim)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], axis=-1)


def cca_qkv(w, a, *, n_heads, n_kv_heads, rope_theta, rotary_dim, **_):
    """q (B, S, H, hd), k and v (B, S, H_kv, hd)."""
    B, S, _ = a.shape
    hd = w["k"].shape[1] // n_kv_heads
    group = n_heads // n_kv_heads
    q0, k0 = (a @ w["q"]).reshape(B, S, n_heads, hd), (a @ w["k"]).reshape(B, S, n_kv_heads, hd)
    v = jnp.concatenate([a @ w["v1"], shifted(a, 1) @ w["v2"]], axis=-1).reshape(B, S, n_kv_heads, hd)
    m_q = 0.5 * (q0 + jnp.repeat(k0, group, axis=2))
    m_k = jnp.mean(m_q.reshape(B, S, n_kv_heads, group, hd), axis=3)
    c = jnp.concatenate([q0, k0], axis=2)  # (B, S, H + H_kv, hd): the packed channels, a head a row
    K0, K1 = w["conv0_w"].shape[1], w["conv1_w"].shape[1]
    w0 = w["conv0_w"].reshape(n_heads + n_kv_heads, hd, K0)
    y = sum(shifted(c, K0 - 1 - j) * w0[..., j] for j in range(K0)) + w["conv0_b"].reshape(-1, hd)
    z = sum(jnp.einsum("bshd,hde->bshe", shifted(y, K1 - 1 - j), w["conv1_w"][:, j]) for j in range(K1)) + w["conv1_b"]
    q, k = z[:, :, :n_heads] + m_q, z[:, :, n_heads:] + m_k
    unit = lambda t: math.sqrt(hd) * t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-12)  # noqa: E731
    q, k = unit(q), unit(k) * jnp.exp(w["temp"])[:, None]
    return _rope(q, rope_theta, rotary_dim), _rope(k, rope_theta, rotary_dim), v


def attention(w, a, *, n_heads, n_kv_heads, **args):
    B, S, _ = a.shape
    q, k, v = (jnp.moveaxis(t, 2, 0) for t in cca_qkv(w, a, n_heads=n_heads, n_kv_heads=n_kv_heads, **args))
    hd = q.shape[-1]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(args):  # one (S, S) score matrix at a time
        q_h, kv = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k[kv]) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v[kv])

    out = jax.lax.map(one_head, (q, jnp.arange(n_heads) // (n_heads // n_kv_heads)))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, n_heads * hd) @ w["o"]


def route(w, b, r_prev, *, norm_eps, **_):
    """The chosen expert's id (...,), its weight (...,) and the carry r."""
    r = b @ w["router_down"] + w["router_down_b"] + w["router_decay"] * r_prev
    u = _rms(r, w["router_norm_scale"], norm_eps)
    gelu = lambda t: jax.nn.gelu(t, approximate=False)  # noqa: E731
    h = gelu(gelu(u @ w["router_w1"] + w["router_b1"]) @ w["router_w2"] + w["router_b2"])
    p = jax.nn.softmax(h @ w["router_w3"], axis=-1)
    ids = jnp.argmax(p + w["router_bias"], axis=-1)
    return ids, jnp.take_along_axis(p, ids[..., None], axis=-1)[..., 0], r


def moe(w, b, r_prev, *, held, **args):
    """What the held experts add, the carry and the chosen ids: every held
    expert over every position, times the position's weight for it (0
    where the position chose another)."""
    ids, weight, r = route(w, b, r_prev, **args)
    out = jnp.zeros_like(b)
    for slot, expert in enumerate(held):
        ffn = (jax.nn.silu(b @ w["expert_gate"][slot]) * (b @ w["expert_up"][slot])) @ w["expert_down"][slot]
        out = out + jnp.where(ids == expert, weight, 0.0)[..., None] * ffn
    return out, r, ids


def res_scale(w, sub, x, branch):
    return (x + w[f"{sub}_res_bias"]) * w[f"{sub}_res_scale"] + (branch + w[f"{sub}_out_bias"]) * w[f"{sub}_out_scale"]


def layer(w, x, r, **args):
    """One layer: the new stream, the new carry, the chosen ids."""
    x = res_scale(w, "attn", x, attention(w, _rms(x, w["ln1_scale"], args["norm_eps"]), **args))
    b = _rms(x, w["ln2_scale"], args["norm_eps"])
    y, r, ids = moe(w, b, r, **args)
    return res_scale(w, "moe", x, y), r, ids


def _layers(p):
    n = p["layers"]["q"].shape[0]
    return [{k: v[i] for k, v in p["layers"].items()} for i in range(n)]


def hidden(params, tokens, **args):
    """The closed hidden state ``rms(x; g_f)``, the float32 tree, and per
    layer the chosen expert ids."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    x, chosen = p["embed"][tokens], []
    r = jnp.zeros(x.shape[:2] + (p["layers"]["router_down"].shape[-1],), F32)
    for w in _layers(p):
        x, r, ids = layer(w, x, r, **args)
        chosen.append(ids)
    return _rms(x, p["ln_f_scale"], args["norm_eps"]), p, chosen


def forward(params, tokens, **args):
    """(B, S) int32 -> (B, S, vocab) float32 logits. ``args``: ``n_heads``,
    ``n_kv_heads``, ``held``, ``norm_eps``, ``rope_theta``, ``rotary_dim``."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, tokens, **args)
        return h @ p["embed"].T


def loss(params, tokens, targets, **args):
    """Mean next-token cross-entropy over the vocabulary held."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, tokens, **args)
        logp = jax.nn.log_softmax(h @ p["embed"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def chosen_experts(params, tokens, **args):
    """Per layer, in order, the id (B, S) each position chose."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, **args)[2]
