"""Plain reference of the block the program runs, in float32 ``jax.numpy``.

No kernel, no sharding, no cache, no dispatch: one Python loop over the
layers, dense causal attention, and in an expert layer every token goes
through every expert in turn with a gate that is zero where it was not
routed (a ``lax.scan`` over the experts, so that 64 of them compile as one).
It follows the program's block as ``models/transformer.py`` and
``ops/moe.py`` describe it (the configuration files list how that block
departs from the published architectures), and is independent of their
code: only the layout of the parameter tree is shared.

    x = embed[tokens] + sinusoid(positions)
    per layer:  x += attn_out( causal_softmax(q k^T / sqrt(hd)) v ),  q,k,v = split(rmsnorm(x) W_qkv)
                x += gelu(rmsnorm(x) W_in) W_out                       (dense)
                x += sum over the token's top-2 experts of gate * expert(rmsnorm(x))   (MoE)
    logits = rmsnorm(x) embed^T

MoE routing: softmax over the router's logits, the two largest
probabilities renormalised to sum to 1; every expert takes at most
``capacity = ceil(2 T cf / E)`` claims, first choices before second
choices and each in token order; a claim past the capacity is dropped.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6
F32 = jnp.float32


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _attention(x, w_qkv, w_out, n_heads):
    B, S, D = x.shape
    hd = D // n_heads
    q, k, v = (t.reshape(B, S, n_heads, hd) for t in jnp.split(x @ w_qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, D) @ w_out


def _moe(x, router, w_in, w_out, capacity_factor):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    T, E = x.shape[0], router.shape[1]
    cap = max(1, math.ceil(2 * T * capacity_factor / E))
    probs = jax.nn.softmax(x @ router, axis=-1)
    e1 = jnp.argmax(probs, axis=-1)
    g1 = jnp.take_along_axis(probs, e1[:, None], axis=-1)[:, 0]
    rest = probs.at[jnp.arange(T), e1].set(0.0)
    e2 = jnp.argmax(rest, axis=-1)
    g2 = jnp.take_along_axis(rest, e2[:, None], axis=-1)[:, 0]
    g1, g2 = g1 / (g1 + g2 + 1e-9), g2 / (g1 + g2 + 1e-9)
    first = jax.nn.one_hot(e1, E, dtype=jnp.int32)
    second = jax.nn.one_hot(e2, E, dtype=jnp.int32)
    # A claim's place in its expert's queue: first choices in token order,
    # then second choices in token order behind all of them.
    place1 = jnp.sum((jnp.cumsum(first, axis=0) - 1) * first, axis=-1)
    place2 = jnp.sum((jnp.cumsum(second, axis=0) - 1 + jnp.sum(first, axis=0)) * second, axis=-1)
    keep1, keep2 = place1 < cap, place2 < cap

    def add_expert(y, expert):  # every token through every expert, gated: plain, not fast
        e, w_i, w_o = expert
        gate = jnp.where((e1 == e) & keep1, g1, 0.0) + jnp.where((e2 == e) & keep2, g2, 0.0)
        return y + gate[:, None] * (jax.nn.gelu(x @ w_i) @ w_o), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(E), w_in, w_out))
    return y.reshape(shape)


def forward(params, tokens, n_heads: int, capacity_factor: float = 1.25):
    """(B, S) int32 -> (B, S, vocab) float32 logits. ``params`` is the
    program's parameter tree (layers stacked on a leading axis)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
        embed, layers = p["embed"], p["layers"]
        D = embed.shape[1]
        S = tokens.shape[1]
        angles = jnp.arange(S, dtype=F32)[:, None] * 10000.0 ** (-2.0 * jnp.arange(D // 2, dtype=F32) / D)
        x = embed[tokens] + jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)[None]
        for i in range(layers["attn_qkv"].shape[0]):
            h = _rmsnorm(x, layers["ln1_scale"][i])
            x = x + _attention(h, layers["attn_qkv"][i], layers["attn_out"][i], n_heads)
            h = _rmsnorm(x, layers["ln2_scale"][i])
            if "moe_router" in layers:
                x = x + _moe(h, layers["moe_router"][i], layers["moe_w_in"][i], layers["moe_w_out"][i], capacity_factor)
            else:
                x = x + jax.nn.gelu(h @ layers["ff_in"][i]) @ layers["ff_out"][i]
        return _rmsnorm(x, p["ln_f_scale"]) @ embed.T
