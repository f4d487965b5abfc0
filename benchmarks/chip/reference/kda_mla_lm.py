"""Plain reference of the ``bailing_hybrid`` stack (Kimi Delta Attention,
latent attention with a head-wise gate, group-limited sigmoid routing), in
float32 ``jax.numpy``.

No chunks, no kernel, no sort of rows, no checkpoint, no sharding: the
delta rule position by position (a ``lax.scan`` over positions carrying
each head's ``(d_k, d_v)`` state), the convolutions as sums of shifted
copies, softmax attention over the whole causal score matrix (one head at a
time, so that 8192 x 8192 scores fit), the route by sorting each token's
scores, a dense loop over the held experts with a weight that is 0 where a
position did not choose one. Layers run one after the other, in published
order. It follows the config's keys and the layer equations of
``models/kda_mla_lm.py``'s docstring and is independent of that module and
of ``ops/``: only the layout of the parameter tree is shared (a stack per
kind of layer, ``<mixer>_<mlp>``, its layers in order)::

    x = embed[tokens]
    per layer p (published id):  a = rms(x; g1);  x += mixer(a);  b = rms(x; g2);  x += mlp(b)
      KDA  ((p + 1) % group):  q, k, v = silu(conv(a W)) ;  q <- q / |q| / sqrt(d_k) ;  k <- k / |k|
                  beta = sigmoid(a W_b) ;  g = L sigmoid(exp(A_h) (a W_f + b_dt))
                  S_t = (I - beta k k^T) Diag(exp g) S_(t-1) + beta k v^T ;  o_t = S_t^T q_t
                  (rms_head(o; g_o) * sigmoid(a W_g)) W_o
      MLA  (else):  q = a W_q ;  [c ; k_r] = a W_kva ;  c <- rms(c) ;  [k_nope ; v] = c W_kvb
                  k_h = [k_nope,h ; k_r] ;  q, k <- rope(rms(.)) on the last rope dims
                  (softmax(q k^T / sqrt(d_qk) + causal) v * sigmoid(a w_g,h)) W_o
      dense (p < first_k_dense):  (silu(b G) * (b U)) D
      moe:  s = sigmoid(b W_r) ;  keep the topk_group groups with the largest sum of their two best s + bias,
            the top_k of s + bias among them ;  w = scale s / sum chosen s
            sum over HELD chosen of w E_i(b) + E_shared(b),  E = (silu(min(b G, l)) * clip(b U, -l, l)) D
    logits = rms(x; g_f) head^T

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


def kinds(layers, layer_group_size, first_k_dense):
    return [("mla" if (p + 1) % layer_group_size == 0 else "kda") + ("_dense" if p < first_k_dense else "_moe")
            for p in layers]


def kda(w, a, *, n_heads, lower_bound, norm_eps, **_):
    B, S, _ = a.shape
    hd = w["q"].shape[1] // n_heads

    def conv(x, taps):  # taps (K, C): y_t = sum_j taps[j] x_(t - (K-1) + j)
        K = taps.shape[0]
        return sum(shifted(x, K - 1 - j) * taps[j] for j in range(K))

    q, k, v = (jax.nn.silu(conv(a @ w[n], w[f"{n}_conv"])).reshape(B, S, n_heads, hd) for n in ("q", "k", "v"))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12) / math.sqrt(hd)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12)
    beta = jax.nn.sigmoid(a @ w["b"])  # (B, S, H)
    rate = jnp.exp(w["A_log"])[:, None]  # (H, 1): a head's rate over its channels
    g = lower_bound * jax.nn.sigmoid(rate * ((a @ w["f"]) + w["dt_bias"]).reshape(B, S, n_heads, hd))

    def step(state, xs):  # state (B, H, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        state = state - b_t[..., None, None] * k_t[..., None] * jnp.einsum("bhk,bhkv->bhv", k_t, state)[..., None, :]
        state = state + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, n_heads, hd, hd), F32), xs)
    o = _rms(jnp.moveaxis(o, 0, 1), w["o_norm"], norm_eps).reshape(B, S, n_heads * hd)
    return (o * jax.nn.sigmoid(a @ w["g"])) @ w["o"]


def _rope_tail(x, rot, theta):
    """Positions 0 .. S-1 on the last ``rot`` of (B, S, H, d), pairs (i, i + rot / 2)."""
    half, d = rot // 2, x.shape[-1]
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., d - rot:d - half], x[..., d - half:]
    return jnp.concatenate([x[..., :d - rot], x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(w, a, *, n_heads, kv_lora_rank, qk_rope_dim, v_head_dim, rope_theta, norm_eps, **_):
    B, S, _ = a.shape
    H, R, rope, dv = n_heads, kv_lora_rank, qk_rope_dim, v_head_dim
    q = (a @ w["q"]).reshape(B, S, H, -1)
    dqk = q.shape[-1]
    c = _rms(a @ w["kv_a"][:, :R], w["kv_a_norm"], norm_eps)
    k_r = a @ w["kv_a"][:, R:]
    kv = (c @ w["kv_b"]).reshape(B, S, H, -1)
    k = jnp.concatenate([kv[..., : dqk - rope], jnp.repeat(k_r[:, :, None], H, axis=2)], axis=-1)
    v = kv[..., dqk - rope:]
    q = _rope_tail(_rms(q, w["q_norm"], norm_eps), rope, rope_theta)
    k = _rope_tail(_rms(k, w["k_norm"], norm_eps), rope, rope_theta)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(args):  # one (S, S) score matrix at a time
        q_h, k_h, v_h = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(dqk)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1), v_h)

    out = jnp.moveaxis(jax.lax.map(one_head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v))), 0, 2)  # (B, S, H, dv)
    out = out * jax.nn.sigmoid(a @ w["gate"])[..., None]
    return out.reshape(B, S, H * dv) @ w["o"]


def route(w, b, *, top_k, routed_scale, n_group, topk_group, **_):
    """Chosen expert ids (..., k) and their weights (..., k)."""
    s = jax.nn.sigmoid(b @ w["router"])
    sel = s + w["router_bias"]
    E = sel.shape[-1]
    grouped = sel.reshape(*sel.shape[:-1], n_group, E // n_group)
    best_two = -jnp.sort(-grouped, axis=-1)[..., :2].sum(-1)  # (..., n_group)
    kept_groups = jnp.argsort(-best_two, axis=-1)[..., :topk_group]
    kept = jnp.zeros(best_two.shape, bool)
    for j in range(topk_group):
        kept = kept | (jnp.arange(n_group) == kept_groups[..., j : j + 1])
    masked = jnp.where(jnp.repeat(kept, E // n_group, axis=-1), sel, -jnp.inf)
    ids = jnp.argsort(-masked, axis=-1)[..., :top_k]
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, routed_scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _swiglu(b, gate, up, down, limit):
    h, u = b @ gate, b @ up
    if limit:
        h, u = jnp.minimum(h, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(h) * u) @ down


def moe(w, b, *, held, limits, **args):
    ids, weights = route(w, b, **args)
    out = _swiglu(b, w["shared_gate"], w["shared_up"], w["shared_down"], limits[1])
    for slot, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        out = out + weight[..., None] * _swiglu(b, w["expert_gate"][slot], w["expert_up"][slot],
                                                w["expert_down"][slot], limits[0])
    return out, ids


def _plan(args):
    """Each held layer's stack and index in it, and its clamps."""
    seen, out = {}, []
    for i, kind in enumerate(kinds(args["layers"], args["layer_group_size"], args["first_k_dense"])):
        limits = tuple(float(lim[i]) if lim else 0.0 for lim in (args["expert_limits"], args["shared_limits"]))
        out.append((kind, seen.get(kind, 0), limits))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def hidden(params, tokens, **args):
    """The closed hidden state ``rms(x; g_f)``, the float32 tree, and per
    expert layer the chosen ids."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    eps = args["norm_eps"]
    x, chosen = p["embed"][tokens], []
    for kind, i, limits in _plan(args):
        w = {k: v[i] for k, v in p["layers"][kind].items()}
        a = _rms(x, w["ln1_scale"], eps)
        x = x + (kda(w, a, **args) if kind.startswith("kda") else mla(w, a, **args))
        b = _rms(x, w["ln2_scale"], eps)
        if kind.endswith("_dense"):
            x = x + _swiglu(b, w["mlp_gate"], w["mlp_up"], w["mlp_down"], 0.0)
        else:
            y, ids = moe(w, b, limits=limits, **args)
            x, chosen = x + y, chosen + [ids]
    return _rms(x, p["ln_f_scale"], eps), p, chosen


def forward(params, tokens, **args):
    """(B, S) int32 -> (B, S, vocab) float32 logits. ``args``: ``layers``,
    ``layer_group_size``, ``first_k_dense``, ``n_heads``, ``lower_bound``,
    ``kv_lora_rank``, ``qk_rope_dim``, ``v_head_dim``, ``rope_theta``,
    ``top_k``, ``routed_scale``, ``n_group``, ``topk_group``, ``held``,
    ``expert_limits``, ``shared_limits``, ``norm_eps``."""
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
    """Per expert layer, in order, the ids (B, S, k) each position chose."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, **args)[2]
