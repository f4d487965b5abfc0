"""Plain reference of the hybrid stack (``nemotron_h``), in float32 ``jax.numpy``.

No chunks, no sort, no kernel, no checkpoint, no sharding: the Mamba-2
recurrence step by step (a ``lax.scan`` over positions), a dense loop over
the held experts with a mask, softmax attention over the whole score matrix
(one query head at a time, so that 8192 x 8192 scores fit). It follows the
config's keys and the layer equations of ``models/hybrid_lm.py``'s docstring
and is independent of that module and of ``ops/``: only the layout of the
parameter tree is shared (a layer's kind is the end of its key). The second
tower that the release describes is not here either: it has no equation.

    x = embed[tokens]
    per layer:   a = rms(x; w);   x += mixer(a)
        mamba:   [z, xBC, dt] = a W_in;  xBC = silu(conv(xBC) + b);  [u, B, C] = xBC
                 delta = softplus(dt + dt_bias);  A = -exp(A_log)
                 h_t = exp(delta_t A) h_(t-1) + delta_t u_t B_t^T;  y_t = h_t C_t + D u_t
                 (group_rms(y silu(z)) w) W_out
        moe:     s = sigmoid(a W_r);  the top_k by s + b;  w_i = scale s_i / (sum chosen s + 1e-20)
                 sum over HELD chosen experts of w_i relu(a U_i)^2 V_i  +  relu(a U_s)^2 V_s
        attn:    softmax(causal(q k^T / sqrt(hd))) v W_o, a KV head a group of query heads
    logits = rms(x; w_f) head^T

``held`` are the ids of the experts whose weights ``expert_up`` /
``expert_down`` hold, in that order; the router is as wide as the model's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def mamba(w, a, *, mamba_heads, ssm_groups, ssm_state, norm_eps, **_):
    B, S, _ = a.shape
    H, G, N = mamba_heads, ssm_groups, ssm_state
    inner = w["out_proj"].shape[0]
    P = inner // H
    zxd = a @ w["in_proj"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + inner + 2 * G * N], zxd[..., inner + inner + 2 * G * N:]
    K = w["conv_w"].shape[0]
    conv = jnp.zeros_like(xbc)
    for j in range(K):  # y_t = sum_j w[j] x_(t - (K-1) + j)
        shift = K - 1 - j
        conv = conv + jnp.pad(xbc, ((0, 0), (shift, 0), (0, 0)))[:, :S] * w["conv_w"][j]
    xbc = jax.nn.silu(conv + w["conv_b"])
    u = xbc[..., :inner].reshape(B, S, H, P)
    bmat = jnp.repeat(xbc[..., inner:inner + G * N].reshape(B, S, G, N), H // G, axis=2)  # a group's B for each of its heads
    cmat = jnp.repeat(xbc[..., inner + G * N:].reshape(B, S, G, N), H // G, axis=2)
    delta = jax.nn.softplus(dt + w["dt_bias"])  # (B, S, H)
    A = -jnp.exp(w["A_log"])

    def step(h, xs):  # h: (B, H, P, N)
        d, u_t, b_t, c_t = xs
        h = jnp.exp(d * A)[..., None, None] * h + (d[..., None] * u_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (delta, u, bmat, cmat))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32), xs)
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * u
    y = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(B, S, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + norm_eps)
    return (y.reshape(B, S, inner) * w["gnorm_scale"]) @ w["out_proj"]


def route(w, a, *, top_k, routed_scale, **_):
    """Chosen expert ids (..., k) and their weights (..., k)."""
    s = jax.nn.sigmoid(a @ w["router"])
    ids = jnp.argsort(-(s + w["router_bias"]), axis=-1)[..., :top_k]
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, routed_scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _ffn(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def moe_routed(w, a, *, held, **args):
    """What the held experts add: every one over every token, times the
    token's weight for it (0 where the token did not choose it)."""
    ids, weights = route(w, a, **args)
    out = jnp.zeros_like(a)
    for slot, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        out = out + weight[..., None] * _ffn(a, w["expert_up"][slot], w["expert_down"][slot])
    return out


def moe(w, a, **args):
    return moe_routed(w, a, **args) + _ffn(a, w["shared_up"], w["shared_down"])


def attention(w, a, *, n_heads, n_kv_heads, **_):
    B, S, _ = a.shape
    hd = w["q"].shape[1] // n_heads
    q = jnp.moveaxis((a @ w["q"]).reshape(B, S, n_heads, hd), 2, 0)  # (H, B, S, hd)
    k = jnp.moveaxis((a @ w["k"]).reshape(B, S, n_kv_heads, hd), 2, 0)
    v = jnp.moveaxis((a @ w["v"]).reshape(B, S, n_kv_heads, hd), 2, 0)
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = n_heads // n_kv_heads

    def one_head(args):  # one (S, S) score matrix at a time
        q_h, kv = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k[kv]) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v[kv])

    out = jax.lax.map(one_head, (q, jnp.arange(n_heads) // group))
    return jnp.moveaxis(out, 0, 2).reshape(B, S, n_heads * hd) @ w["o"]


MIXERS = {"mamba": mamba, "moe": moe, "attn": attention}


def hidden(params, tokens, **args):
    """The closed hidden state ``rms(x; w_f)`` and the float32 tree."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    x = p["embed"][tokens]
    for name in sorted(p["layers"]):
        w = p["layers"][name]
        x = x + MIXERS[name.rsplit("_", 1)[1]](w, _rms(x, w["norm_scale"], args["norm_eps"]), **args)
    return _rms(x, p["ln_f_scale"], args["norm_eps"]), p


def forward(params, tokens, **args):
    """(B, S) int32 -> (B, S, vocab) float32 logits. ``args``: ``n_heads``,
    ``n_kv_heads``, ``mamba_heads``, ``ssm_groups``, ``ssm_state``,
    ``top_k``, ``routed_scale``, ``held``, ``norm_eps``."""
    with jax.default_matmul_precision("highest"):
        h, p = hidden(params, tokens, **args)
        return h @ p["head"].T


def loss(params, batch, **args):
    """Mean next-token cross-entropy, over every position."""
    with jax.default_matmul_precision("highest"):
        h, p = hidden(params, batch["tokens"], **args)
        logp = jax.nn.log_softmax(h @ p["head"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))


def chosen_experts(params, tokens, **args):
    """Per ``moe`` layer, in order, the ids (B, S, k) each token chose."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
        x, out = p["embed"][tokens], []
        for name in sorted(p["layers"]):
            w, kind = p["layers"][name], name.rsplit("_", 1)[1]
            a = _rms(x, w["norm_scale"], args["norm_eps"])
            if kind == "moe":
                out.append(route(w, a, **args)[0])
            x = x + MIXERS[kind](w, a, **args)
        return out
