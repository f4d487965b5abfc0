"""Gated DeltaNet (GDN): the delta rule with one scalar decay a head.

The linear-attention mixer of Qwen3-Next (Gated Delta Networks,
arXiv:2412.06464), written from memory of the paper and of the release's
modelling code, no network (``benchmarks/chip/configs/qwen3next80b.json``
``assumed`` lists each form). Per head, a state ``S: (d_k, d_v)`` from
``S_0 = 0``, and at each position ``t`` a query and a key ``q_t, k_t:
(d_k,)``, a value ``v_t: (d_v,)``, a log-decay ``g_t`` (one scalar, below 0
and **not bounded below**) and a rate ``beta_t`` in (0, 1)::

    S_t = exp(g_t) S_(t-1) + beta_t k_t (v_t - exp(g_t) S_(t-1)^T k_t)^T
    o_t = S_t^T q_t

``ops/kda.py``'s rule with ``Diag(exp(g_t))`` a multiple of the identity.
``gdn_recurrent`` computes the equations position by position (the
reference form). ``gdn_chunked`` is the training form, on chunks of C
positions, the WY / UT algebra of ``ops/kda.py`` with a scalar decay::

    gamma_r = sum_(i <= r) g_i,  within a chunk
    G_ri = exp(gamma_r - gamma_i)  (i <= r),  0 above the diagonal      in [0, 1]
    A = beta (K K^T) * G  (strictly lower),   P = (Q K^T) * G  (lower)
    then U, W_k, O and the state as ops/kda.py (``wy_solve``, ``chunk_scan``)

**Why KDA's form does not do here.** KDA splits ``exp(gamma_r -
gamma_i)`` into a row and a column factor and keeps both in float32 by
re-basing every 16 rows, which holds only because its decay is bounded at
-5 a position (16 x 5 = 80 < 88.7). GDN's decay has no bound: a position
of -60 is common at initialisation, so any factor of ``exp(-gamma_i)``
overflows. A scalar decay needs no factors: ``G`` is one ``(C, C)`` matrix
a head, and its exponent is masked to ``-inf`` above the diagonal
**before** the exponential, so no value or gradient meets an ``inf``. The
exponent is the sum of ``g`` over ``(i, r]`` taken directly (a product of
the lower triangle with ``g`` masked past ``i``), not the difference of two
running sums: where ``gamma`` reaches -3000 the difference would lose the
4th digit of a small decay. ``exp(gamma_r)`` and ``exp(gamma_C -
gamma_i)``, the factors the state meets, are at most 1. All of it is
float32 at ``HIGHEST``; the backward pass is the algebra's own
derivative.

``gdn`` runs this form wherever ``gdn_route`` sends it, which today is
everywhere (``ROUTES``): it never takes ``ops/pallas_kda.py``'s kernels,
whose sub-blocks overflow on these decays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kda import F32, HI, chunk_scan, wy_solve

ROUTES = ("xla",)  # the forms ``gdn`` can run, by the index a gauge reports


def gdn_recurrent(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array):
    """The equations position by position: ``q, k: (B, S, H, d_k)``, ``v:
    (B, S, H, d_v)``, ``g, beta: (B, S, H)`` -> (``o: (B, S, H, d_v)``, the
    last state ``(B, H, d_k, d_v)``), float32 at ``HIGHEST``."""
    B, _, H, K = q.shape
    state = jnp.zeros((B, H, K, v.shape[-1]), F32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        k_s = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - k_s)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)

    xs = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def gdn_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = 64
) -> jax.Array:
    """``gdn_recurrent``'s output, ``(B, S, H, d_v)`` float32, by chunks of
    ``chunk`` positions (the module docstring). S must be a multiple of the
    chunk (or shorter than it: then the whole sequence is one chunk)."""
    B, S, H, _ = q.shape
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"{S} positions in chunks of {C}")
    n = S // C

    def chunks(t):  # (B, S, H, X) -> (B, H, n, C, X) float32
        return jnp.moveaxis(t.astype(F32).reshape(B, n, C, H, -1), 3, 1)

    q, k, v = map(chunks, (q, k, v))
    g, beta = chunks(g[..., None]), chunks(beta[..., None])  # (B, H, n, C, 1)
    with jax.named_scope("gdn_intra"):
        pos = jnp.arange(C)
        lower = (pos[:, None] >= pos[None, :]).astype(F32)
        gamma = jnp.einsum("rj,bhnjx->bhnrx", lower, g, precision=HI)  # cumulative log-decay (B, H, n, C, 1)
        past = jnp.where(pos[:, None] > pos[None, :], g, 0.0)  # (j, i): g_j where j > i
        span = jnp.einsum("rj,bhnji->bhnri", lower, past, precision=HI)  # sum of g over (i, r]
        decays = jnp.exp(jnp.where(lower > 0, span, -jnp.inf))  # G (B, H, n, C, C)
        kk = jnp.einsum("bhnrk,bhnik->bhnri", k, k, precision=HI)
        a = jnp.where(pos[:, None] > pos[None, :], beta * kk * decays, 0.0)
        p = jnp.einsum("bhnrk,bhnik->bhnri", q, k, precision=HI) * decays
        u, w_k, p1, p2 = wy_solve(q, k, v, beta, jnp.exp(gamma), a, p)
        k_bar = k * decays[..., -1, :, None]  # k_i exp(gamma_C - gamma_i)
    with jax.named_scope("gdn_inter"):
        o = chunk_scan(u, w_k, k_bar, jnp.exp(gamma[..., -1:, :]), p1, p2)
    return jnp.moveaxis(o, 1, 3).reshape(B, S, H, -1)


def gdn_route(S: int, d_k: int, d_v: int, chunk: int) -> str:
    """Which form ``gdn`` runs: ``xla`` (``gdn_chunked``) for every backend
    and shape. No kernel pair of this rule exists yet, and KDA's
    (``ops/pallas_kda.py``) factors the decay in a way these decays
    overflow."""
    return "xla"


def gdn(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = 64) -> jax.Array:
    """``gdn_chunked``'s output by the form ``gdn_route`` picks: the one
    there is."""
    return gdn_chunked(q, k, v, g, beta, chunk=chunk)
