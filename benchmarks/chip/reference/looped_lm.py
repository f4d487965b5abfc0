"""Plain reference of the looped LM's block (Ouro), in float32 ``jax.numpy``.

No scan, no checkpoint, no kernel, no sharding: Python loops over the passes
and over the layers (an unrolled T x L stack that reads the same weights),
dense causal attention. It follows the published description (Zhu et al.,
arXiv:2510.25741, and the model's ``config.json``) and is independent of
``models/looped_lm.py``: only the layout of the parameter tree is shared.

    h = embed[tokens]
    T times:   u = h
        per layer:  u += rms( attn(rope(rms(u) Wq), rope(rms(u) Wk), rms(u) Wv) Wo )
                    u += rms( (silu(rms(u) Wgate) * rms(u) Wup) Wdown )
        h = rms(u);  logits_t = h head^T;  gate_t = h . w_g + b_g
    exit distribution: p_t = sigmoid(gate_t) prod_{j<t} (1 - sigmoid(gate_j)); the last pass takes the rest
    loss = mean[ sum_t p_t CE(logits_t, target) - beta H(p) ]

``forward`` returns one array ``(B, positions, T, vocab + 1)``: each pass's
logits and, as the last column, that pass's gate logit before the sigmoid
(the scale of the logits, so one relative tolerance holds both).
**Only the last ``positions`` positions of each sequence are returned**
when that argument is given (they depend on every earlier one): four
passes of 2 x 2048 x 49153 float32 are 3.2 GB a side, and two such arrays do
not fit beside a 7.35 GB train state on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _attention(a, wq, wk, wv, wo, n_heads, theta):
    B, S, _ = a.shape
    hd = wq.shape[1] // n_heads
    q, k, v = ((a @ w).reshape(B, S, n_heads, hd) for w in (wq, wk, wv))
    freqs = jnp.arange(S, dtype=F32)[:, None] / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)[None, :]
    cos = jnp.cos(jnp.concatenate([freqs, freqs], axis=-1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([freqs, freqs], axis=-1))[None, :, None, :]
    q, k = q * cos + _rotate_half(q) * sin, k * cos + _rotate_half(k) * sin
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, n_heads * hd) @ wo


def _passes(params, tokens, n_heads, ut_steps, rope_theta, norm_eps):
    """Yields h(t), the closed hidden state of each pass, in float32."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    w = p["layers"]
    h = p["embed"][tokens]
    for _ in range(ut_steps):
        u = h
        for i in range(w["q"].shape[0]):
            a = _rms(u, w["ln1_scale"][i], norm_eps)
            attn = _attention(a, w["q"][i], w["k"][i], w["v"][i], w["o"][i], n_heads, rope_theta)
            u = u + _rms(attn, w["ln2_scale"][i], norm_eps)
            m = _rms(u, w["ln3_scale"][i], norm_eps)
            ff = (jax.nn.silu(m @ w["gate"][i]) * (m @ w["up"][i])) @ w["down"][i]
            u = u + _rms(ff, w["ln4_scale"][i], norm_eps)
        h = _rms(u, p["ln_f_scale"], norm_eps)
        yield p, h


def forward(params, tokens, n_heads: int, ut_steps: int, rope_theta: float, norm_eps: float,
            positions: int | None = None):
    """(B, S) int32 -> (B, positions or S, T, vocab + 1) float32."""
    with jax.default_matmul_precision("highest"):
        out = []
        for p, h in _passes(params, tokens, n_heads, ut_steps, rope_theta, norm_eps):
            h = h if positions is None else h[:, -positions:]  # one pass's logits at a time
            gate = h @ p["exit_gate_w"] + p["exit_gate_b"]
            out.append(jnp.concatenate([h @ p["head"].T, gate[..., None]], axis=-1))
        return jnp.stack(out, axis=2)


def exit_distribution(gate_logits):
    """[T] list of gate logits -> [T] list of exit probabilities."""
    stayed, out = 1.0, []
    for t, g in enumerate(gate_logits):
        lam = jax.nn.sigmoid(g)
        out.append(stayed if t == len(gate_logits) - 1 else stayed * lam)
        stayed = stayed * (1.0 - lam)
    return out


def loss(params, batch, n_heads: int, ut_steps: int, rope_theta: float, norm_eps: float, exit_beta: float):
    """The training objective, over every position."""
    with jax.default_matmul_precision("highest"):
        ce, gates = [], []
        for p, h in _passes(params, batch["tokens"], n_heads, ut_steps, rope_theta, norm_eps):
            logp = jax.nn.log_softmax(h @ p["head"].T, axis=-1)
            ce.append(-jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0])
            gates.append(h @ p["exit_gate_w"] + p["exit_gate_b"])
        probs = exit_distribution(gates)
        expected = sum(q * c for q, c in zip(probs, ce))
        entropy = -sum(q * jnp.log(jnp.maximum(q, 1e-30)) for q in probs)
        return jnp.mean(expected - exit_beta * entropy)
