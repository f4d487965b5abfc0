"""Plain reference of the ``qwen3_next`` stack (Gated DeltaNet, gated
attention at a wide head, softmax top-k routing with a gated shared
expert), in float32 ``jax.numpy``.

No chunks, no kernel, no sort of rows, no checkpoint, no sharding: the
delta rule position by position (a ``lax.scan`` over positions carrying
each value head's ``(d_k, d_v)`` state), the convolution as a sum of
shifted copies, softmax attention over the whole causal score matrix (one
head at a time, so that 8192 x 8192 scores fit), the route by sorting each
token's probabilities, a dense loop over the held experts with a weight
that is 0 where a position did not choose one. Layers run one after the
other, in published order. It follows the config's keys and the layer
equations of ``models/gdn_lm.py``'s docstring and is independent of that
module and of ``ops/``: only the layout of the parameter tree is shared (a
stack per kind of layer, ``gdn`` and ``attn``, its layers in order; GDN's
``qkvz`` and ``ba`` columns grouped by key head, its convolution's channels
q, k, v)::

    x = embed[tokens]
    per layer p (published id):  a = rms0(x; g1);  x += mixer(a);  b = rms0(x; g2);  x += moe(b)
      rms0(x; w) = x / sqrt(mean x^2 + eps) * (1 + w)
      GDN  ((p + 1) % interval):  per key head j: [q_j | k_j | v_j | z_j] = a W_qkvz ;  [b_j | a_j] = a W_ba
                  q, k, v = silu(conv(q, k, v)) ;  q <- q / |q| / sqrt(d_k) ;  k <- k / |k| ;  value head i reads key head i // r
                  beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias)
                  S_t = exp(g_t) S_(t-1) + beta_t k_t (v_t - exp(g_t) S_(t-1)^T k_t)^T ;  o_t = S_t^T q_t
                  (rms(o; w_n) * silu(z)) W_o
      attn (else):  [q_h | gate_h] = a W_q ;  k, v = a W_k, a W_v ;  q, k <- rope(rms0(.)) on the first rot dims
                  (softmax(q k^T / sqrt(d) + causal) v * sigmoid(gate)) W_o,  KV head h // (H / H_kv) for head h
      moe:  p = softmax(b W_r) ;  the top_k of p, weights p / sum chosen p
            sum over HELD chosen of w E_i(b) + sigmoid(b w_s) E_shared(b),  E = (silu(b G) * (b U)) D
    logits = rms0(x; g_f) head^T

``held`` are the ids of the experts whose weights the expert stacks hold,
in that order; the router is as wide as the model's.
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


def kinds(layers, full_attention_interval):
    return ["attn" if (p + 1) % full_attention_interval == 0 else "gdn" for p in layers]


def gdn(w, a, *, n_key_heads, n_value_heads, norm_eps, **_):
    B, S, _ = a.shape
    Hk, Hv = n_key_heads, n_value_heads
    r = Hv // Hk
    dv = w["o_norm"].shape[-1]
    dk = (w["qkvz"].shape[1] // Hk - 2 * r * dv) // 2
    mixed = (a @ w["qkvz"]).reshape(B, S, Hk, -1)
    q, k = mixed[..., :dk], mixed[..., dk:2 * dk]
    v, z = mixed[..., 2 * dk:2 * dk + r * dv], mixed[..., 2 * dk + r * dv:]
    ba = (a @ w["ba"]).reshape(B, S, Hk, 2 * r)
    b, a_ = ba[..., :r].reshape(B, S, Hv), ba[..., r:].reshape(B, S, Hv)
    qkv = jnp.concatenate([q.reshape(B, S, -1), k.reshape(B, S, -1), v.reshape(B, S, -1)], axis=-1)
    taps = w["conv"]  # (K, C): y_t = sum_j taps[j] x_(t - (K-1) + j)
    K = taps.shape[0]
    qkv = jax.nn.silu(sum(shifted(qkv, K - 1 - j) * taps[j] for j in range(K)))
    q = qkv[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = qkv[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = qkv[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    kv_of = jnp.arange(Hv) // r  # the key head each value head reads
    q, k = q[:, :, kv_of], k[:, :, kv_of]
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a_ + w["dt_bias"])

    def step(state, xs):  # state (B, Hv, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + b_t[..., None, None] * k_t[..., None] * err[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, Hv, dk, dv), F32), xs)
    o = _rms(jnp.moveaxis(o, 0, 1), w["o_norm"], norm_eps)
    return (o.reshape(B, S, Hv * dv) * jax.nn.silu(z.reshape(B, S, Hv * dv))) @ w["o"]


def _rope_lead(x, rot, theta):
    """Positions 0 .. S-1 on the first ``rot`` of (B, S, H, d), pairs (i, i + rot / 2)."""
    half = rot // 2
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attn(w, a, *, n_heads, n_kv_heads, rotary_dim, rope_theta, norm_eps, **_):
    B, S, _ = a.shape
    H, Hkv = n_heads, n_kv_heads
    d = w["q_norm"].shape[-1]
    qg = (a @ w["q"]).reshape(B, S, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k, v = (a @ w["k"]).reshape(B, S, Hkv, d), (a @ w["v"]).reshape(B, S, Hkv, d)
    q = _rope_lead(_rms(q, 1.0 + w["q_norm"], norm_eps), rotary_dim, rope_theta)
    k = _rope_lead(_rms(k, 1.0 + w["k_norm"], norm_eps), rotary_dim, rope_theta)
    kv_of = jnp.arange(H) // (H // Hkv)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(args):  # one (S, S) score matrix at a time
        q_h, k_h, v_h = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(d)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1), v_h)

    out = jnp.moveaxis(jax.lax.map(one_head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v))), 0, 2)  # (B, S, H, d)
    return (out * jax.nn.sigmoid(gate)).reshape(B, S, H * d) @ w["o"]


def route(w, b, *, top_k, **_):
    """Chosen expert ids (..., k) and their weights (..., k)."""
    p = jax.nn.softmax(b @ w["router"], axis=-1)
    ids = jnp.argsort(-p, axis=-1)[..., :top_k]
    chosen = jnp.take_along_axis(p, ids, axis=-1)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _swiglu(b, gate, up, down):
    return (jax.nn.silu(b @ gate) * (b @ up)) @ down


def moe(w, b, *, held, **args):
    ids, weights = route(w, b, **args)
    out = jax.nn.sigmoid(b @ w["shared_expert_gate"]) * _swiglu(b, w["shared_gate"], w["shared_up"], w["shared_down"])
    for slot, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        out = out + weight[..., None] * _swiglu(b, w["expert_gate"][slot], w["expert_up"][slot], w["expert_down"][slot])
    return out, ids


def hidden(params, tokens, **args):
    """The closed hidden state ``rms0(x; g_f)``, the float32 tree, and per
    layer the chosen ids."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    eps = args["norm_eps"]
    x, chosen, seen = p["embed"][tokens], [], {}
    for kind in kinds(args["layers"], args["full_attention_interval"]):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        w = {k: v[i] for k, v in p["layers"][kind].items()}
        a = _rms(x, 1.0 + w["ln1_scale"], eps)
        x = x + (gdn(w, a, **args) if kind == "gdn" else attn(w, a, **args))
        y, ids = moe(w, _rms(x, 1.0 + w["ln2_scale"], eps), **args)
        x, chosen = x + y, chosen + [ids]
    return _rms(x, 1.0 + p["ln_f_scale"], eps), p, chosen


def forward(params, tokens, **args):
    """(B, S) int32 -> (B, S, vocab) float32 logits. ``args``: ``layers``,
    ``full_attention_interval``, ``n_key_heads``, ``n_value_heads``,
    ``n_heads``, ``n_kv_heads``, ``rotary_dim``, ``rope_theta``, ``top_k``,
    ``held``, ``norm_eps``."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, tokens, **args)
        return h @ p["head"].T


def loss(params, batch, **args):
    """Mean next-token cross-entropy over the vocabulary held."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, batch["tokens"], **args)
        logp = jax.nn.log_softmax(h @ p["head"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))


def chosen_experts(params, tokens, **args):
    """Per layer, in order, the ids (B, S, k) each position chose."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, **args)[2]
