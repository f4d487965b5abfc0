"""Plain reference of the block-diffusion stack (``sdar_moe``), in float32 ``jax.numpy``.

No kernel, no tiles, no sort, no scan, no checkpoint, no sharding: the
four-rule mask built densely as a ``(2S, 2S)`` boolean, softmax attention
over the whole score matrix (one query head at a time, so that 8192 x 8192
scores fit), a dense loop over the held experts with a weight that is 0
where a position did not choose the expert. It follows the config's keys
and the layer equations of ``models/block_diffusion_lm.py``'s docstring and
is independent of that module and of ``ops/``: only the layout of the
parameter tree is shared (a layer is an index into the stacked leaves).

    x = [tokens ; where(masked, mask_token_id, tokens)];  h = embed[x];  ids = [0..S-1 ; 0..S-1]
    per layer:   a = rms(h; g1);  q, k, v = a Wq, a Wk, a Wv;  q, k <- rope(rms over hd (g_q, g_k); ids)
                 h += softmax(q k^T / sqrt(hd) + M) v Wo,  a KV head a group of query heads
                 b = rms(h; g2);  p = softmax(b Wr);  the top_k by p;  w_e = p_e / sum of the chosen p
                 h += sum over HELD chosen experts of w_e (silu(b G_e) * (b U_e)) D_e
    logits = rms(h[noised half]; g_f) head^T
    loss = 1 / (B S) * sum over masked positions of CE(logits there, the clean token there) / t there

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


def _rope(x, ids, theta):
    """(B, P, H, hd) rotated at the position ids (P,), pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    angles = ids.astype(F32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)  # (P, hd/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mask(S, block_length):
    """The (2S, 2S) boolean, quadrant by quadrant: rows are queries."""
    blk = jnp.arange(S) // block_length
    key, query = blk[None, :], blk[:, None]
    clean_clean, clean_noised = key <= query, jnp.zeros((S, S), bool)
    noised_clean, noised_noised = key < query, key == query
    return jnp.block([[clean_clean, clean_noised], [noised_clean, noised_noised]])


def attention(w, a, allowed, ids, *, n_heads, n_kv_heads, norm_eps, rope_theta, **_):
    B, P, _ = a.shape
    hd = w["q"].shape[1] // n_heads

    def heads(m, n, scale=None):
        t = (a @ m).reshape(B, P, n, hd)
        if scale is not None:
            t = _rope(_rms(t, scale, norm_eps), ids, rope_theta)
        return jnp.moveaxis(t, 2, 0)  # (n, B, P, hd)

    q = heads(w["q"], n_heads, w["q_norm_scale"])
    k, v = heads(w["k"], n_kv_heads, w["k_norm_scale"]), heads(w["v"], n_kv_heads)
    group = n_heads // n_kv_heads

    def one_head(args):  # one (P, P) score matrix at a time
        q_h, kv = args
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k[kv]) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", probs, v[kv])

    out = jax.lax.map(one_head, (q, jnp.arange(n_heads) // group))
    return jnp.moveaxis(out, 0, 2).reshape(B, P, n_heads * hd) @ w["o"]


def route(w, b, *, top_k, **_):
    """Chosen expert ids (..., k) and their weights (..., k)."""
    p = jax.nn.softmax(b @ w["router"], axis=-1)
    ids = jnp.argsort(-p, axis=-1)[..., :top_k]
    chosen = jnp.take_along_axis(p, ids, axis=-1)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def moe(w, b, *, held, **args):
    """What the held experts add: every one over every position, times the
    position's weight for it (0 where the position did not choose it)."""
    ids, weights = route(w, b, **args)
    out = jnp.zeros_like(b)
    for slot, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        ffn = (jax.nn.silu(b @ w["expert_gate"][slot]) * (b @ w["expert_up"][slot])) @ w["expert_down"][slot]
        out = out + weight[..., None] * ffn
    return out


def _layers(p):
    n = p["layers"]["q"].shape[0]
    return [{k: v[i] for k, v in p["layers"].items()} for i in range(n)]


def hidden(params, tokens, masked, *, block_length, mask_token_id, **args):
    """The closed hidden state of the noised half ``rms(h[:, S:]; g_f)``,
    the float32 tree, and per layer the chosen expert ids."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    S = tokens.shape[1]
    x = jnp.concatenate([tokens, jnp.where(masked, mask_token_id, tokens)], axis=1)
    ids = jnp.concatenate([jnp.arange(S), jnp.arange(S)])
    allowed = mask(S, block_length)
    h, chosen = p["embed"][x], []
    for w in _layers(p):
        h = h + attention(w, _rms(h, w["ln1_scale"], args["norm_eps"]), allowed, ids, **args)
        b = _rms(h, w["ln2_scale"], args["norm_eps"])
        chosen.append(route(w, b, **args)[0])
        h = h + moe(w, b, **args)
    return _rms(h[:, S:], p["ln_f_scale"], args["norm_eps"]), p, chosen


def forward(params, tokens, *, masked, **args):
    """(B, S) int32 and the (B, S) bool of masked positions (more rows of
    it than of tokens are cut to fit) -> (B, S, vocab) float32 logits at
    the noised positions. ``args``: ``n_heads``, ``n_kv_heads``, ``top_k``,
    ``held``, ``norm_eps``, ``rope_theta``, ``block_length``,
    ``mask_token_id``."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, tokens, jnp.asarray(masked)[: tokens.shape[0]], **args)
        return h @ p["head"].T


def loss(params, tokens, masked, t, **args):
    """The block-diffusion loss under the given noise: ``masked`` and each
    position's level ``t``, both (B, S)."""
    with jax.default_matmul_precision("highest"):
        h, p, _ = hidden(params, tokens, masked, **args)
        logp = jax.nn.log_softmax(h @ p["head"].T, axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(masked, ce / t, 0.0)) / tokens.size


def chosen_experts(params, tokens, *, masked, **args):
    """Per layer, in order, the ids (B, 2S, k) each position chose."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, jnp.asarray(masked)[: tokens.shape[0]], **args)[2]
