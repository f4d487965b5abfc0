"""Pallas TPU flash-attention kernel.

The blockwise op (ops/attention.py) expresses the online-softmax scan in
pure JAX and lets XLA schedule it; this kernel hand-places the same
algorithm on the TPU memory hierarchy with Pallas: each grid program owns
one (batch*head, q-block) tile, streams K/V blocks through VMEM next to the
MXU, and carries the (acc, m, l) softmax state in registers — the score
matrix never touches HBM. Causal programs skip K blocks entirely above the
diagonal (not just mask them), so the causal kernel does ~half the FLOPs.

Backward is also a pair of Pallas kernels (flash-attention backward with
the standard recompute-p-blocks-in-VMEM scheme): the forward additionally
emits the per-row log-sum-exp, and the backward recomputes each softmax
block from (q, k, lse) next to the MXU — dq in a kernel gridded over
q-blocks streaming K/V, dk/dv in a kernel gridded over k-blocks streaming
Q/dO. Like the forward, the causal variants skip fully-masked blocks
rather than masking them, and so do all three under a block-diffusion
mask (ops/attention.py BlockDiffusionMask), whose live tiles they walk.
In training, backward is ~2/3 of attention FLOPs. On non-TPU backends
the kernels run in Pallas interpret mode (tests), or callers can just
use blockwise_attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _apply_causal_mask(s, q_off, k_off, block_q, block_k):
    """Mask scores above the causal diagonal to NEG_INF. Shared by the
    forward and both backward kernels so the mask semantics (tie at
    q_pos == k_pos attends) can never desynchronize between fwd and bwd."""
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k, causal, scale, seq_len):
    """o and lse for one (batch*head, q-block) tile, streaming K/V blocks.
    ``causal``, here and in the backward kernels, is the mask: True for the
    causal rule, False for none, or a ``BlockDiffusionMask``."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)  # q-block index within the sequence
    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, D)

    # K blocks that hold no live score (strictly above the causal diagonal,
    # or dead under the mask) contribute nothing — skip them (the loop's
    # trip count), don't just mask them.
    n_k, k_tile = _k_tiles(causal, qi, block_q, block_k, seq_len)

    def body(j, carry):
        acc, m, l = carry
        kb = k_tile(j)
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        s = _apply_mask(causal, s, qi * block_q, kb * block_k, block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha + pv
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc, m, l))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # Per-row log-sum-exp (of the scaled scores): the backward kernels
    # recompute softmax blocks as exp(s - lse) without re-running the
    # online max/sum scan.
    lse_ref[0] = m + jnp.log(l)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
    *, block_q, block_k, causal, scale, seq_len,
):
    """dq for one (batch*head, q-block) tile, streaming K/V blocks.

    ds = p * (dp - delta) with p = exp(s - lse), dp = dO @ V^T,
    delta = rowsum(dO * O); dq = scale * sum_blocks ds @ K.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    g = g_ref[0].astype(jnp.float32)  # (block_q, D)
    lse = lse_ref[0]  # (block_q, 1)
    delta = delta_ref[0]  # (block_q, 1)

    n_k, k_tile = _k_tiles(causal, qi, block_q, block_k, seq_len)

    def body(j, dq):
        kb = k_tile(j)
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        s = _apply_mask(causal, s, qi * block_q, kb * block_k, block_q, block_k)
        p = jnp.exp(s - lse)  # masked entries underflow to 0
        dp = jax.lax.dot_general(
            g, v_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    dq = jax.lax.fori_loop(0, n_k, body, dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q, block_k, causal, scale, seq_len,
):
    """dk, dv for one (batch*head, k-block) tile, streaming Q/dO blocks.

    dv = sum_blocks p^T @ dO; dk = scale * sum_blocks ds^T @ Q. Causal
    programs start at the first q-block that can see this k-block; under a
    mask the loop walks the q-blocks that can.
    """
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)  # (block_k, D)
    v = v_ref[0].astype(jnp.float32)  # (block_k, D)

    first, last, q_tile = _q_tiles(causal, ki, block_q, block_k, seq_len)

    def body(j, carry):
        dk, dv = carry
        qb = q_tile(j)
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        g_blk = g_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]  # (block_q, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = scale * jax.lax.dot_general(
            q_blk, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        s = _apply_mask(causal, s, qb * block_q, ki * block_k, block_q, block_k)
        p = jnp.exp(s - lse)
        dv_new = dv + jax.lax.dot_general(
            p, g_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)
        dp = jax.lax.dot_general(
            g_blk, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)
        return dk_new, dv_new

    dk = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, last, body, (dk, dv))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

# Everything above and down to ``flash_attention`` keeps its lines: the Mosaic
# modules' location tables hold the line of ``_apply_causal_mask``'s ``where``
# (under shard_map), of the three ``pallas_call``s and of ``flash_attention``'s
# call of the kernel, so a line more or fewer ahead of any of them changes the
# lowered train step, and with it the compile-cache key, of every model. What
# the block-diffusion mask adds (``_apply_mask``, the tile walks) is at the end.


def _shape(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-mesh-axes of ``like``: inside
    shard_map pallas_call output types must declare their vma; outside it
    the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


@functools.lru_cache(maxsize=None)
def _make_flash_parts(causal, scale, block_q, block_k, interpret):
    """Raw (fwd_impl, bwd_impl) on (BH, S, D) operands.

    ``fwd_impl`` returns (normalized o, lse); ``bwd_impl`` consumes the
    GLOBAL lse/delta, which is what lets ring attention drive these same
    kernels per hop and still produce exact gradients (FA2 math: p =
    exp(s - lse_global) is correct for any subset of keys).
    """
    from jax.experimental import pallas as pl

    def kern_opts(D, S):
        return dict(
            block_q=block_q,
            block_k=block_k,
            causal=causal,
            scale=scale if scale is not None else D**-0.5,
            seq_len=S,
        )

    def fwd_impl(q, k, v):
        # q, k, v: (BH, S, D) -> (o, lse)
        BH, S, D = q.shape
        kern = functools.partial(_kernel, **kern_opts(D, S))
        return pl.pallas_call(
            kern,
            # lse rides as (BH, S, 1): TPU Mosaic requires the last two
            # block dims divisible by (8, 128) or equal to the array dims —
            # a trailing singleton satisfies that where (1, block_q) cannot.
            out_shape=(
                _shape((BH, S, D), q.dtype, q),
                _shape((BH, S, 1), jnp.float32, q),
            ),
            grid=(BH, S // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ),
            interpret=interpret, **_call_opts(causal, "fwd", S, D, q.dtype),
        )(q, k, v)

    def bwd_impl(q, k, v, g, lse, delta):
        BH, S, D = q.shape
        full = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))
        full_row = pl.BlockSpec((1, S, 1), lambda b, i: (b, 0, 0))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **kern_opts(D, S)),
            out_shape=_shape((BH, S, D), q.dtype, q),
            grid=(BH, S // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # q
                full,  # k
                full,  # v
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # dO
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # lse
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # delta
            ],
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            interpret=interpret, **_call_opts(causal, "dq", S, D, q.dtype),
        )(q, k, v, g, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, **kern_opts(D, S)),
            out_shape=(
                _shape((BH, S, D), k.dtype, q),
                _shape((BH, S, D), v.dtype, q),
            ),
            grid=(BH, S // block_k),
            in_specs=[
                full,  # q
                pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),  # k
                pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),  # v
                full,  # dO
                full_row,  # lse
                full_row,  # delta
            ],
            out_specs=(
                pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            ),
            interpret=interpret, **_call_opts(causal, "dkv", S, D, q.dtype),
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    return fwd_impl, bwd_impl


@functools.lru_cache(maxsize=None)
def _make_flash(causal, scale, block_q, block_k, interpret):
    fwd_impl, bwd_impl = _make_flash_parts(
        causal, scale, block_q, block_k, interpret
    )

    @jax.custom_vjp
    def flash(q, k, v):
        return fwd_impl(q, k, v)[0]

    def flash_fwd(q, k, v):
        o, lse = fwd_impl(q, k, v)
        return o, (q, k, v, o, lse)

    def flash_bwd(res, g):
        q, k, v, o, lse = res
        # delta = rowsum(dO * O): tiny elementwise reduce; XLA fuses it, no
        # kernel needed.
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )
        return bwd_impl(q, k, v, g.astype(q.dtype), lse, delta)

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    mask=None,
) -> jax.Array:
    """Flash attention on ``(B, S, H, D)`` via a Pallas TPU kernel.

    ``block_q``/``block_k`` default to the largest divisor of S up to 512;
    explicitly passed blocks must divide S (callers pad or pick divisors;
    static shapes keep the kernel MXU-tiled). ``interpret=None``
    auto-enables interpret mode off-TPU so tests run on CPU. A ``mask``
    (``attention.BlockDiffusionMask``) takes the causal rule's place: one
    tile for both blocks, and only tiles with a live score are visited.

    Bigger tiles amortize the grid and keep the MXU fed; at D=128 a
    512-block program uses well under VMEM (q/acc tiles 256 KB, score
    tile 1 MB). Which block size is fastest on a directly attached chip:
    not measured (ROADMAP A5).
    """
    from .attention import pick_block_size

    B, S, H, D = q.shape
    if mask is not None:
        block_q = block_k = _mask_tile(mask, S, block_q, block_k)
    default = pick_block_size(S, 512) or min(512, S)
    block_q, block_k = min(block_q or default, S), min(block_k or default, S)
    if S % block_q or S % block_k:
        raise ValueError(f"seq len {S} must be divisible by block_q={block_q} and block_k={block_k}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    flash = _make_flash(causal if mask is None else mask, scale, block_q, block_k, interpret)
    # (B, S, H, D) -> (B*H, S, D)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    # This call's line is in the Mosaic modules' location tables (the note
    # ahead of ``_shape``).
    out = flash(qt, kt, vt)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = "model", mask=None,
) -> jax.Array:
    """Flash attention under a ('data','model') mesh via ``shard_map``.

    A bare ``pallas_call`` has no GSPMD partitioning rule, so calling
    :func:`flash_attention` on sharded operands would make XLA gather
    them. Attention is embarrassingly parallel over batch and heads, so
    this wrapper shard_maps the kernel with batch over ``batch_axis`` and
    heads over ``head_axis`` — each device runs the kernel on its local
    (B_l, S, H_l, D) block, zero communication. Heads must divide the
    head-axis size (callers fall back to blockwise otherwise).
    """
    from jax.sharding import PartitionSpec as P

    axes = set(mesh.axis_names)
    b = batch_axis if batch_axis in axes else None
    h = head_axis if head_axis in axes else None
    if h is not None and q.shape[2] % mesh.shape[h]:
        raise ValueError(
            f"flash_attention_sharded needs heads ({q.shape[2]}) divisible "
            f"by the {h!r} axis size ({mesh.shape[h]})"
        )
    if b is not None and q.shape[0] % mesh.shape[b]:
        raise ValueError(
            f"flash_attention_sharded needs batch ({q.shape[0]}) divisible "
            f"by the {b!r} axis size ({mesh.shape[b]})"
        )
    spec = P(b, None, h, None)

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale, mask=mask)

    # Interpret mode (off-TPU testing) trips shard_map's varying-axes
    # checker with a jax-internal false positive (see ulysses.py); the
    # checker stays on for real TPU compiles.
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=jax.default_backend() == "tpu",
    )(q, k, v)


# The kernels keep one (batch*head)'s whole K and V (forward, dq) or Q, dO,
# lse and delta (dk/dv) in VMEM, double-buffered; the two (S, 1) float32
# rows are padded to 128 lanes there. Mosaic's scoped default is 16 MiB,
# which S = 2048 at D = 128 uses 6 MiB of and S = 8192 overruns (21 MiB
# asked for, compiled for a described v5e). A v5e core has 128 MiB.
_VMEM_DEFAULT_ROOM = 12 << 20
_VMEM_MOST = 96 << 20


def _vmem_room(S: int, D: int, dtype) -> dict:
    """``compiler_params`` for a ``pallas_call`` of these kernels: nothing
    where the default limit holds the dk/dv kernel's operands (the largest
    of the three), else a limit of twice what they take."""
    need = 2 * (2 * S * D * jnp.dtype(dtype).itemsize + 2 * S * 128 * 4)
    if need <= _VMEM_DEFAULT_ROOM:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=min(2 * need, _VMEM_MOST))}


def _call_opts(mask, kernel: str, S: int, D: int, dtype) -> dict:
    """What a ``pallas_call`` of these kernels takes beside its specs: the
    VMEM room, and under a block-diffusion mask a name (``attn_bd_fwd``,
    ``attn_bd_dq``, ``attn_bd_dkv``) by which a trace can find the kernel.
    The causal and unmasked calls keep the name they always had."""
    named = {} if isinstance(mask, bool) else {"name": f"attn_bd_{kernel}"}
    return {**named, **_vmem_room(S, D, dtype)}


def _apply_mask(mask, s, q_off, k_off, block_q, block_k):
    """Scores the mask rules out set to NEG_INF: the causal rule, none, or a
    ``BlockDiffusionMask``'s, for the forward and both backward kernels."""
    if isinstance(mask, bool):
        return _apply_causal_mask(s, q_off, k_off, block_q, block_k) if mask else s
    # One column of query positions against one row of key positions.
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return jnp.where(mask.allowed(q_pos, k_pos), s, NEG_INF)


def _mask_tile(mask, S: int, block_q: Optional[int], block_k: Optional[int]) -> int:
    """The one tile the kernels use under ``mask``: what the caller named
    for both blocks, else the mask's own 512-target pick."""
    tile = block_q or block_k or mask.tile(512)
    if S != 2 * mask.half or not tile or (block_k or tile) != tile or mask.half % tile or tile % mask.block:
        raise ValueError(
            f"{mask} over seq len {S}: needs S = 2 * half and one tile of "
            f"whole blocks that divides half, got {block_q} x {block_k}"
        )
    return tile


def _k_tiles(mask, qi, block_q: int, block_k: int, seq_len: int):
    """The K tiles the forward and dq kernels visit for q tile ``qi``:
    ``(count, j -> tile index)``."""
    n_k_blocks = seq_len // block_k
    if mask is False:
        return n_k_blocks, lambda j: j
    if mask is True:
        q_end = (qi + 1) * block_q
        n_k = jax.lax.div(q_end + block_k - 1, block_k)
        return jnp.minimum(n_k, n_k_blocks), lambda j: j
    return mask.k_tiles(qi, block_q)


def _q_tiles(mask, ki, block_q: int, block_k: int, seq_len: int):
    """The Q tiles the dk/dv kernel visits for k tile ``ki``: the loop's
    ``(first, last, j -> tile index)``."""
    n_q_blocks = seq_len // block_q
    if isinstance(mask, bool):
        return (jax.lax.div(ki * block_k, block_q) if mask else 0), n_q_blocks, lambda j: j
    count, tile = mask.q_tiles(ki, block_k)
    return 0, count, tile
