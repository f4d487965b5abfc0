"""Kimi Delta Attention (KDA): a gated delta rule with a decay a key channel.

The linear-attention mixer of *Kimi Linear* (arXiv:2510.26692), written
from memory of the paper and of the flash-linear-attention code, no
network (``benchmarks/chip/configs/ling3flash.json`` ``assumed`` lists each
form). Per head, a state ``S: (d_k, d_v)`` from ``S_0 = 0``, and at each
position ``t`` a query and a key ``q_t, k_t: (d_k,)``, a value ``v_t:
(d_v,)``, a log-decay ``g_t: (d_k,)`` (every entry below 0) and a rate
``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

Mamba-2 (``ops/ssm.py``) decays a head's state by one scalar a position and
adds without correcting; here every key channel decays at its own rate and
the rule subtracts what the state already says about ``k_t``.
``kda_recurrent`` computes the equations position by position (the
reference form). ``kda_chunked`` is the training form, on chunks of C
positions, exact in float32 algebra:

    gamma_r = sum_(i <= r) g_i, within a chunk;   S_0 the state at its start
    w_r = beta_r (v_r - S_(r-1)^T exp(g_r) k_r)        so that  S_r = Diag(exp(g_r)) S_(r-1) + k_r w_r^T
    A_ri = beta_r (k_r exp(gamma_r))^T (k_i exp(-gamma_i))   (i < r)
    W = (I + A)^-1 (beta V - beta K~ S_0),   K~_r = k_r exp(gamma_r)       the WY / UT form
      = U - W_k S_0,   U = (I + A)^-1 beta V,   W_k = (I + A)^-1 beta K~
    O = Q~ S_0 + P W,   Q~_r = q_r exp(gamma_r),   P_ri = (q_r exp(gamma_r))^T (k_i exp(-gamma_i)) (i <= r)
    S_C = Diag(exp(gamma_C)) S_0 + K-^T W,   K-_i = k_i exp(gamma_C - gamma_i)

**The float32 range.** ``exp(gamma_r - gamma_i)`` lies in (0, 1] for
``i <= r``, but its factors do not: over 64 positions at the bound of -5
a position ``exp(-gamma_i)`` reaches ``e^320``, and float32 ends at
``e^88.7``. So the products ``A`` and ``P`` are taken a sub-block of at most
16 rows at a time, each re-based at its own first position ``n``: row
``r`` of the sub-block is scaled by ``exp(gamma_r - gamma_n)`` (at most 1)
and column ``i`` by ``exp(gamma_n - gamma_i)``, which is at most 1 for a
column of an earlier sub-block and at most ``e^75`` for one of the same;
a sub-block's product takes the columns up to its own end and no further.
Every other factor above is at most 1. All of it is float32
at ``HIGHEST``; what is computed once for all chunks at a time
(``kda_intra``) is everything but the state, and the scan over chunks
(``kda_inter``) carries ``S`` alone, two products a chunk.

The backward pass is the algebra's own derivative (the model checkpoints
the layer around it): no term of it is hand-written. ``kda`` runs either
this form or the same algebra as a pair of Pallas kernels
(``ops/pallas_kda.py``, with a hand-written backward pass), as
``kda_route`` decides from the backend and the shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB_BLOCK = 16  # rows re-based together: 16 x 5 = 80 < 88.7, float32's largest exponent


def kda_recurrent(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array):
    """The equations position by position: ``q, k, g: (B, S, H, d_k)``, ``v:
    (B, S, H, d_v)``, ``beta: (B, S, H)`` -> (``o: (B, S, H, d_v)``, the last
    state ``(B, H, d_k, d_v)``), float32 at ``HIGHEST``."""
    B, _, H, K = q.shape
    state = jnp.zeros((B, H, K, v.shape[-1]), F32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        k_s = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - k_s)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)

    xs = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def kda_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = 64
) -> jax.Array:
    """``kda_recurrent``'s output, ``(B, S, H, d_v)`` float32, by chunks of
    ``chunk`` positions (the module docstring), re-based every
    ``gcd(chunk, SUB_BLOCK)`` of them. S must be a multiple of the chunk
    (or shorter than it: then the whole sequence is one chunk)."""
    S = q.shape[1]
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"{S} positions in chunks of {C}")
    return _chunked(q, k, v, g, beta, C, math.gcd(C, SUB_BLOCK))


def _chunked(q, k, v, g, beta, C, sb):
    B, S, H, K = q.shape
    V = v.shape[-1]
    n, m = S // C, C // sb

    def chunks(t):  # (B, S, H, X) -> (B, H, n, C, X) float32
        return jnp.moveaxis(t.astype(F32).reshape(B, n, C, H, -1), 3, 1)

    q, k, v, g = map(chunks, (q, k, v, g))
    beta = chunks(beta[..., None])
    with jax.named_scope("kda_intra"):
        pos = jnp.arange(C)
        lower = pos[:, None] >= pos[None, :]
        gamma = jnp.einsum("rj,bhnjk->bhnrk", lower.astype(F32), g, precision=HI)  # cumulative log-decay
        base = jnp.repeat(gamma[..., ::sb, :], sb, axis=3)  # each row's sub-block's first position
        row = jnp.exp(gamma - base)  # <= 1

        def scores(left, right):  # (B, H, n, C, K) rows x (B, H, n, C, K) keys -> (B, H, n, C, C)
            out = []
            for a in range(m):  # sub-block a's rows against the columns up to its end, re-based at its start
                end = (a + 1) * sb
                col = right[:, :, :, :end] * jnp.exp(gamma[:, :, :, a * sb:a * sb + 1] - gamma[:, :, :, :end])
                rows = (left * row)[:, :, :, a * sb:end]
                out.append(jnp.pad(jnp.einsum("bhnrk,bhnjk->bhnrj", rows, col, precision=HI),
                                   ((0, 0),) * 4 + ((0, C - end),)))
            return jnp.concatenate(out, axis=3)

        a = jnp.where(pos[:, None] > pos[None, :], beta * scores(k, k), 0.0)
        p = jnp.where(lower, scores(q, k), 0.0)
        decay = jnp.exp(gamma)
        u, w_k, p1, p2 = wy_solve(q, k, v, beta, decay, a, p)
        last = gamma[..., -1:, :]
        k_bar = k * jnp.exp(last - gamma)  # (B, H, n, C, K)
    with jax.named_scope("kda_inter"):
        o = chunk_scan(u, w_k, k_bar, jnp.exp(last), p1, p2)
    return jnp.moveaxis(o, 1, 3).reshape(B, S, H, V)


def wy_solve(q, k, v, beta, decay, a, p):
    """The WY / UT solve of a chunked delta rule, all chunks at once, on
    ``(B, H, n, C, X)`` float32 chunks: ``decay`` is ``exp(gamma)`` (a key
    channel's or, broadcast, a head's), ``a`` the strictly lower ``A``, ``p``
    the lower ``P`` (the module docstring) -> ``U``, ``W_k`` and the two
    factors of the output, ``P U`` and ``Q~ - P W_k``."""
    solve = lambda rhs: jax.lax.linalg.triangular_solve(  # noqa: E731
        a, rhs, left_side=True, lower=True, unit_diagonal=True
    )
    u = solve(beta * v)  # (B, H, n, C, V)
    w_k = solve(beta * k * decay)  # (B, H, n, C, K)
    p1 = jnp.einsum("bhnrj,bhnjv->bhnrv", p, u, precision=HI)
    p2 = q * decay - jnp.einsum("bhnrj,bhnjk->bhnrk", p, w_k, precision=HI)
    return u, w_k, p1, p2


def chunk_scan(u, w_k, k_bar, d_last, p1, p2):
    """The scan over chunks, carrying the state ``(B, H, K, V)`` from 0 and
    nothing else: ``k_bar`` is ``K-``, ``d_last`` ``exp(gamma_C)`` ``(B, H,
    n, 1, K or 1)`` -> the output ``(B, H, n, C, V)``, ``P U + (Q~ - P W_k)
    S_0`` a chunk."""
    B, H, _, _, K = w_k.shape

    def step(s, xs):  # s: (B, H, K, V), the state at the chunk's start
        u_c, w_c, k_c, d_c = xs
        w = u_c - jnp.einsum("bhrk,bhkv->bhrv", w_c, s, precision=HI)
        return d_c[..., 0, :, None] * s + jnp.einsum("bhrk,bhrv->bhkv", k_c, w, precision=HI), s

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (u, w_k, k_bar, d_last))
    _, starts = jax.lax.scan(step, jnp.zeros((B, H, K, u.shape[-1]), F32), xs)
    return p1 + jnp.einsum("bhnrk,nbhkv->bhnrv", p2, starts, precision=HI)


def kda_route(S: int, d_k: int, d_v: int, chunk: int) -> str:
    """Which form ``kda`` runs: ``pallas`` (``ops/pallas_kda.py``) on a TPU
    where both widths tile the lanes (multiples of 128) and S is a whole
    number of chunks, else ``xla`` (``kda_chunked``: the CPU, other
    widths, a sequence shorter than a chunk)."""
    if jax.default_backend() == "tpu" and d_k % 128 == 0 and d_v % 128 == 0 and S % chunk == 0:
        return "pallas"
    return "xla"


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = 64) -> jax.Array:
    """``kda_chunked``'s output by the form ``kda_route`` picks."""
    if kda_route(q.shape[1], q.shape[-1], v.shape[-1], chunk) == "pallas":
        from .pallas_kda import kda_pallas

        return kda_pallas(q, k, v, g, beta, chunk=chunk)
    return kda_chunked(q, k, v, g, beta, chunk=chunk)
