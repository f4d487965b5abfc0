"""Pallas TPU kernels for the chunked gated delta rule of ``ops/kda.py``.

The arithmetic is ``ops/kda.py``'s module docstring, chunk for chunk; only
where it lives changes. ``kda_fwd`` walks the chunks in order, one grid
step a chunk of every head, with the heads' states in VMEM, and
``kda_bwd`` walks them in reverse with the states' gradients in VMEM. A
chunk's ``(C, C)`` products, its triangular solve and both states never
reach HBM. The kernels take the operands as the model lays them out,
``(B, S, H, d)`` and ``beta: (B, S, H)``: a block ``(1, C, H, d)`` is a
whole number of Mosaic's (8, 128) tiles where H is 8 and d 128, and a
head's ``(C, d)`` is one sublane of each (a strided load), so no
transpose runs outside.

**The state is held transposed**, ``S^T: (d_v, d_k)``: a key channel's
decay then scales a column of it, and every product below is one that
contracts the last dimension of both operands, or the first, or the last
of one with the first of the other. Within a chunk, with ``gamma``, ``A``
(``beta`` times ``A'``), ``P``, ``K~ = k exp(gamma)``, ``Q~ = q
exp(gamma)``, ``K- = k exp(gamma_C - gamma)`` as ``ops/kda.py`` has them::

    T = (I + A)^-1,   U = T (beta V),   W_k = T (beta K~),   W = U - W_k S_0
    O = Q~ S_0 + P W,   S_C = Diag(exp(gamma_C)) S_0 + K-^T W

``T`` is built by doubling, exact block back-substitution and no series:
with ``T`` the inverse of the diagonal blocks of size ``2^l`` of ``I + A``
and ``E_l`` the part of ``A`` that joins two such blocks into one of
``2^(l+1)``, the inverse of those is ``T - T E_l T``, from ``T = I`` at
blocks of 1. A, P are taken by ``ops/kda.py``'s sub-block rule
(``SUB_BLOCK`` rows re-based at their first position), so no factor leaves
float32.

**The backward pass**, for a chunk, from ``dO`` and the gradient ``dS_C``
of the state it hands on (zero after the last chunk)::

    dQ~ = dO S_0^T                  dS_0 = Diag(exp(gamma_C)) dS_C + Q~^T dO - W_k^T dW
    dP = lower(dO W^T)              dW = P^T dO + K- dS_C
    dK- = W dS_C^T                  dW_k = -dW S_0^T,   dU = dW
    dR_v = T^T dU,  dR_k = T^T dW_k                    (X = T R, so d(R) = T^T dX)
    dA = -strict_lower(dR_v U^T + dR_k W_k^T)          (dA = -T^T dX X^T, A's free entries)
    dv = beta dR_v,   dK~ = beta dR_k,   dA' = beta dA
    dbeta = rowsum(dA A') + rowsum(dR_v V) + rowsum(dR_k K~)

and through the re-based products, ``A'_ri = sum_k k_rk k_ik exp(gamma_rk
- gamma_ik)`` and ``P_ri`` the same with ``q_r``: with ``G`` = ``dA'`` or
the strictly lower part of ``dP``, ``Drow_r = sum_i G_ri k_i exp(gamma_r -
gamma_i)`` and ``Dcol_i = sum_r G_ri x_r exp(gamma_r - gamma_i)`` (x the
rows' k or q), each taken a sub-block of rows at a time at the same bases
as the forward's::

    dq = dQ~ exp(gamma) + Drow(dP) + diag(dP) k
    dk = dK~ exp(gamma) + dK- exp(gamma_C - gamma) + Drow(dA') + Dcol(dA') + Dcol(dP) + diag(dP) q
    dgamma = dQ~ Q~ + dK~ K~ + k Drow(dA') + q Drow(dP) - k (Dcol(dA') + Dcol(dP))
    dgamma_C += colsum(S_0 dS_C) exp(gamma_C)                          (the last row's)
    dg = L^T dgamma + L_< (dK- K-)                                     (L_< strictly lower)

P's diagonal, ``q_r . k_r``, depends on no decay: through ``dgamma`` it
would add and take away terms of order 1 whose difference is the whole
gradient, which at decays near the bound is of order ``e^-5``; so do the
``K-`` terms (``gamma_C - gamma_r`` is the sum of the decays after ``r``).
Both go around the reverse sum, as does ``gamma`` itself around the
rounding of a cumulative sum (``_cumulative``). The kernels' gradient of
``g`` at a decay of -5 throughout is within 4e-7 of the float64
recurrence's largest where ``kda_chunked``'s, which takes the plain
derivative, misses it by 3e-4.

Every product is float32 at ``HIGHEST`` (``contract_precision<fp32>`` in
Mosaic), nothing is bfloat16. ``kda_fwd`` also writes, for ``kda_bwd`` to
read in reverse, the state at each chunk's start, ``(B, n, H, d_v, d_k)``
(recomputing them would take a forward walk over the chunks ahead of the
backward one), and each chunk's ``T``, ``A'`` and ``P``, ``(B, n, H, 3, C,
C)``, which the backward step would otherwise recompute (a fifth of its
products: 4.7 ms of 41.7 for a layer's 32 heads forward and backward, on a
TPU v5e): 64 and 48 MiB for 8 heads of 128 over 8192 positions. Off the TPU the kernels run in Pallas interpret mode, as the other kernels
of ``ops/`` do, so the CPU's tests run the same code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .kda import F32, HI, SUB_BLOCK


def _dot(a, b, ca=1, cb=0):
    """``a`` by ``b`` over ``a``'s dimension ``ca`` and ``b``'s ``cb``,
    float32 at ``HIGHEST``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())), precision=HI, preferred_element_type=F32)


def _iotas(n, m):
    return jax.lax.broadcasted_iota(jnp.int32, (n, m), 0), jax.lax.broadcasted_iota(jnp.int32, (n, m), 1)


def _sub_blocks(q, k, gamma, sb):
    """Per sub-block of ``sb`` rows from ``lo``, re-based at ``gamma_lo``:
    its rows of k and q, ``(2 sb, K)``, scaled by ``exp(gamma_r - gamma_lo)``
    (at most 1); the columns of k, ``(C, K)``, scaled by ``exp(gamma_lo -
    gamma_i)`` up to the sub-block's end and 0 after it; and both factors.
    ``gamma`` is a ``_cumulative`` pair."""
    C = k.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
    out = []
    for lo in range(0, C, sb):
        hi = lo + sb
        base = tuple(t[lo:lo + 1] for t in gamma)
        row = jnp.exp(_minus(tuple(t[lo:hi] for t in gamma), base))
        col = jnp.where(i < hi, jnp.exp(_minus(base, gamma)), 0.0)
        out.append((jnp.concatenate([k[lo:hi] * row, q[lo:hi] * row]), k * col, row, col))
    return out


def _scores(q, k, gamma, sb):
    """``A'`` (strictly lower) and ``P`` (lower), ``(C, C)`` each."""
    pieces = [_dot(rows, cols, 1, 1) for rows, cols, _, _ in _sub_blocks(q, k, gamma, sb)]  # (2 sb, C)
    r, i = _iotas(k.shape[0], k.shape[0])
    a = jnp.concatenate([p[:sb] for p in pieces])
    p = jnp.concatenate([p[sb:] for p in pieces])
    return jnp.where(r > i, a, 0.0), jnp.where(r >= i, p, 0.0)


def _scores_vjp(da, dp, q, k, gamma, sb):
    """Through ``_scores``: ``Drow`` of ``dA'`` and of ``dP`` and the two
    ``Dcol`` summed (the module docstring), ``(C, K)`` each."""
    rows_k, rows_q, dcol = [], [], 0.0
    for n, (rows, cols, row, col) in enumerate(_sub_blocks(q, k, gamma, sb)):
        lo, hi = n * sb, (n + 1) * sb
        grad = jnp.concatenate([da[lo:hi], dp[lo:hi]])  # (2 sb, C)
        d = _dot(grad, cols)
        rows_k.append(d[:sb] * row)
        rows_q.append(d[sb:] * row)
        dcol = dcol + col * _dot(grad, rows, 0, 0)
    return jnp.concatenate(rows_k), jnp.concatenate(rows_q), dcol


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for ``A`` strictly lower, ``(C, C)``, by doubling (the
    module docstring)."""
    C = a.shape[0]
    r, i = _iotas(C, C)
    t = (r == i).astype(F32) - jnp.where((r >> 1) == (i >> 1), a, 0.0)
    level = 1
    while (1 << level) < C:
        joins = ((r >> (level + 1)) == (i >> (level + 1))) & ((r >> level) != (i >> level))
        t = t - _dot(_dot(t, jnp.where(joins, a, 0.0)), t)
        level += 1
    return t


def _prefix(x, reverse=False):
    """The inclusive cumulative sum of ``x`` over its rows, from the first
    row on or, with ``reverse``, from the last back: a product with a
    triangle of ones (on the chip, faster than ``log2(C)`` shifted adds)."""
    r, i = _iotas(x.shape[0], x.shape[0])
    return _dot(((r <= i) if reverse else (r >= i)).astype(F32), x)


def _cumulative(g):
    """The cumulative log-decay ``gamma`` as a pair whose sum it is: the
    sums of ``g`` floored to a multiple of ``2^-10``, exact in float32
    (``|gamma| <= 320 < 2^13``), and the sums of what the floor left, at
    most ``2^-10`` a term. A difference ``gamma_r - gamma_i`` taken part by
    part is then exact to float32's precision of the small part, where one
    of the sums themselves would carry an error of ``|gamma|`` times
    ``2^-24`` (up to ``2^-15``) into every decay factor."""
    whole = jnp.floor(g * 1024.0) * (1.0 / 1024.0)
    return _prefix(whole), _prefix(g - whole)


def _minus(x, y):
    """``x - y`` of two ``_cumulative`` pairs, part by part."""
    return (x[0] - y[0]) + (x[1] - y[1])


def _decays(q, k, g):
    """A chunk's cumulative log-decay and what it scales."""
    C = k.shape[0]
    gamma = _cumulative(g)
    decay = jnp.exp(gamma[0] + gamma[1])
    last = tuple(t[C - 1:C] for t in gamma)
    to_end = jnp.exp(_minus(last, gamma))  # exp(gamma_C - gamma_r)
    return dict(gamma=gamma, decay=decay, last=jnp.exp(last[0] + last[1]), to_end=to_end, kd=k * decay,
                qd=q * decay, kbar=k * to_end)


def _solve(c, t, v, b, s0):
    """``U``, ``W_k`` and ``W`` of a chunk from ``T``, into ``c``."""
    u, wk = _dot(t, b * v), _dot(t, b * c["kd"])
    return dict(c, t=t, u=u, wk=wk, w=u - _dot(wk, s0, 1, 1))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, m_ref, state, *, sb):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    for h in range(q_ref.shape[2]):
        s0 = state[h]  # (V, K): the state at the chunk's start, transposed
        q, k, b = q_ref[0, :, h], k_ref[0, :, h], b_ref[0, :, h:h + 1]
        c = _decays(q, k, g_ref[0, :, h])
        a, p = _scores(q, k, c["gamma"], sb)
        c = _solve(c, _unit_lower_inverse(b * a), v_ref[0, :, h], b, s0)
        o_ref[0, :, h] = _dot(c["qd"], s0, 1, 1) + _dot(p, c["w"])
        state[h] = s0 * c["last"] + _dot(c["w"], c["kbar"], 0, 0)
        s_ref[0, 0, h] = s0
        m_ref[0, 0, h, 0], m_ref[0, 0, h, 1], m_ref[0, 0, h, 2] = c["t"], a, p


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, m_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dstate, *, sb):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, F32)

    for h in range(q_ref.shape[2]):
        q, k, v, b = q_ref[0, :, h], k_ref[0, :, h], v_ref[0, :, h], b_ref[0, :, h:h + 1]
        s0, do, ds = s_ref[0, 0, h], do_ref[0, :, h], dstate[h]
        t, a, p = m_ref[0, 0, h, 0], m_ref[0, 0, h, 1], m_ref[0, 0, h, 2]
        c = _solve(_decays(q, k, g_ref[0, :, h]), t, v, b, s0)
        C, V = k.shape[0], v.shape[1]
        r, i = _iotas(C, C)
        d_qd = _dot(do, s0)
        dp = _dot(do, c["w"], 1, 1)
        dp_diag = jnp.sum(jnp.where(r == i, dp, 0.0), axis=1, keepdims=True)
        dp = jnp.where(r > i, dp, 0.0)
        dw = _dot(p, do, 0, 0) + _dot(c["kbar"], ds, 1, 1)
        d_kbar = _dot(c["w"], ds)
        dwk = -_dot(dw, s0)
        ds0 = ds * c["last"] + _dot(do, c["qd"], 0, 0) - _dot(dw, c["wk"], 0, 0)
        dr = _dot(t, jnp.concatenate([dw, dwk], axis=1), 0, 0)  # T^T [dU, dW_k]
        dr_v, dr_k = dr[:, :V], dr[:, V:]
        da = -jnp.where(r > i, _dot(dr, jnp.concatenate([c["u"], c["wk"]], axis=1), 1, 1), 0.0)
        d_kd = b * dr_k
        rows_k, rows_q, dcol = _scores_vjp(b * da, dp, q, k, c["gamma"], sb)
        dgamma = d_qd * c["qd"] + d_kd * c["kd"] + k * rows_k + q * rows_q - k * dcol
        d_last = jnp.sum(s0 * ds, axis=0, keepdims=True) * c["last"]
        dgamma = dgamma + jnp.where(jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == C - 1, d_last, 0.0)
        dq_ref[0, :, h] = d_qd * c["decay"] + rows_q + dp_diag * k
        dk_ref[0, :, h] = d_kd * c["decay"] + d_kbar * c["to_end"] + rows_k + dcol + dp_diag * q
        dv_ref[0, :, h] = b * dr_v
        dg_ref[0, :, h] = _prefix(dgamma, reverse=True) + _dot((r > i).astype(F32), d_kbar * c["kbar"])
        db_ref[0, :, h:h + 1] = (jnp.sum(da * a, axis=1, keepdims=True) + jnp.sum(dr_v * v, axis=1, keepdims=True)
                                 + jnp.sum(dr_k * c["kd"], axis=1, keepdims=True))
        dstate[h] = ds0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params(interpret: bool) -> dict:
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                                    vmem_limit_bytes=_VMEM_BYTES)}


# The backward step's blocks, held twice for the pipeline, and each head's
# working set overrun Mosaic's default 16 MiB at 8 heads of 128 (16.4 MiB,
# compiled for a described v5e); a v5e core has 128 MiB.
_VMEM_BYTES = 48 << 20


def _specs(B, S, H, K, V, chunk, reverse):
    """Blocks of one chunk of every head, the chunks in order or reversed:
    q, k, v, g, beta; the state at each chunk's start; its ``T``, ``A'``
    and ``P``. A grid step takes one chunk of every head of the call: a
    block ``(1, C, H, d)`` is a whole number of (8, 128) tiles where H is 8
    (the model's head block), and the heads' chains of products are
    independent, for the scheduler to interleave."""
    from jax.experimental import pallas as pl

    n = S // chunk
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)

    def tile(d):
        return pl.BlockSpec((1, chunk, H, d), lambda b, c: (b, at(c), 0, 0))

    return ([tile(K), tile(K), tile(V), tile(K), pl.BlockSpec((1, chunk, H), lambda b, c: (b, at(c), 0))],
            [pl.BlockSpec((1, 1, H, V, K), lambda b, c: (b, at(c), 0, 0, 0)),
             pl.BlockSpec((1, 1, H, 3, chunk, chunk), lambda b, c: (b, at(c), 0, 0, 0, 0))], tile)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _fwd(q, k, v, g, beta, *, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, K = q.shape
    V, n = v.shape[-1], S // chunk
    inputs, kept, tile = _specs(B, S, H, K, V, chunk, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sb=math.gcd(chunk, SUB_BLOCK)),
        grid=(B, n),
        in_specs=inputs,
        out_specs=[tile(V)] + kept,
        out_shape=[jax.ShapeDtypeStruct(v.shape, F32), jax.ShapeDtypeStruct((B, n, H, V, K), F32),
                   jax.ShapeDtypeStruct((B, n, H, 3, chunk, chunk), F32)],
        scratch_shapes=[pltpu.VMEM((H, V, K), F32)],
        interpret=interpret,
        name="kda_fwd",
        **_params(interpret),
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _bwd(q, k, v, g, beta, states, mats, do, *, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, K = q.shape
    V = v.shape[-1]
    inputs, kept, tile = _specs(B, S, H, K, V, chunk, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sb=math.gcd(chunk, SUB_BLOCK)),
        grid=(B, S // chunk),
        in_specs=inputs + kept + [tile(V)],
        out_specs=inputs,
        out_shape=[jax.ShapeDtypeStruct(t.shape, F32) for t in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((H, V, K), F32)],
        # each gradient takes its operand's place: a grid step reads a chunk
        # of the operand before it writes that chunk of the gradient
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3, 4: 4},
        interpret=interpret,
        name="kda_bwd",
        **_params(interpret),
    )(q, k, v, g, beta, states, mats, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, chunk):
    return _fwd(q, k, v, g, beta, chunk=chunk, interpret=_interpret())[0]


def _kda_fwd(q, k, v, g, beta, chunk):
    o, states, mats = _fwd(q, k, v, g, beta, chunk=chunk, interpret=_interpret())
    return o, (q, k, v, g, beta, states, mats)


def _kda_bwd(chunk, res, do):
    return tuple(_bwd(*res, do, chunk=chunk, interpret=_interpret()))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_pallas(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = 64):
    """``kda_chunked``'s output, ``(B, S, H, d_v)`` float32, of the same
    operands (``q, k, g: (B, S, H, d_k)``, ``v: (B, S, H, d_v)``, ``beta:
    (B, S, H)``) through the kernels, which read and write them as they lie.
    A sequence shorter than the chunk is one chunk, as there."""
    S = q.shape[1]
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"{S} positions in chunks of {C}")
    return _kda(*(t.astype(F32) for t in (q, k, v, g, beta)), C)
