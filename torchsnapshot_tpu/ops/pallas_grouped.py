"""Pallas TPU grouped matrix products over a list of rows sorted by group:
``rows[i] @ rhs[group of i]`` and, transposed, ``sum over a group's rows of
lhs[i]^T rhs[i]`` a group.

The list is cut into row tiles of ``tile`` rows and **a row tile belongs to
one group**: the caller (``ops/moe.py`` ``_held_experts``) starts each
group's segment at a multiple of the row tile and fills its last tile with
rows at weight 0, so no kernel masks a row and no row tile is multiplied
twice. ``tile_group[t]`` names tile t's group; the tiles from ``n_live`` on
are not computed and not fetched (their block indices repeat the last live
tile's, which the pipeline does not fetch again), and their rows of the
result are left as they were: uninitialised. Nothing of theirs may reach a
value. A matrix's last dimension is a multiple of 128 (the caller hands
1856 x 2688 over as its transpose: Mosaic cuts no copy out of an array
whose rows are 14.5 lanes long).

Both kernels take the whole contraction dimension as one block, so a
group's matrix is read once a product and column block, whatever the number
of its row tiles: the grid walks the column blocks outside and the row
tiles inside. ``grouped_matmul`` copies a group's block into VMEM itself,
while the row tiles of the group before it are multiplied
(``_matmul_kernel``); ``grouped_matmul_t`` writes a group's block once, when
its last row tile has been added. Operands in the matrices' dtype, every
product accumulated in float32.

Off the TPU the kernels run in Pallas interpret mode, as
``ops/pallas_attention.py`` and ``ops/pallas_add_rows.py`` do, so the CPU's
tests run the same code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .pallas_attention import _VMEM_MOST

# What one block of a matrix may take of VMEM (it is held twice, one copy
# being fetched while the other is multiplied). A call asks for twice what its
# blocks take, up to ``_VMEM_MOST``: the compiler plans 16 MiB by default.
_BLOCK_BYTES = 4 << 20


def _column_block(n: int, k: int, itemsize: int, most: int = _BLOCK_BYTES) -> int:
    """Columns of a ``(k, n)`` matrix a block takes: the largest multiple of
    128 that divides ``n`` and keeps the block under ``most`` bytes; all of
    ``n`` where it is no multiple of 128 (a block is a multiple of 128 wide
    or the whole width)."""
    if n % 128:
        return n
    fits = [c for c in range(128, n + 1, 128) if n % c == 0 and k * c * itemsize <= most]
    return max(fits, default=128)


def _params(vmem_bytes: int, grid_rank: int) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * grid_rank,
            vmem_limit_bytes=int(min(max(2 * vmem_bytes, 32 << 20), _VMEM_MOST)),
        )
    }


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Each kernel is a jitted function of its operands, with what else decides
# the program (interpret mode among it) static: a step calls one kernel at
# one set of shapes from many places (three products a chunk, the forward
# pass twice under ``jax.checkpoint``, a layer after the other where a stack
# is not scanned), and a call that hits jit's cache is lowered to Mosaic once
# a step and not once a place (65 to 80 ms each: 5 s of ``jit.lower`` in
# ``nemotron2t30b.save``, PERF.md, PR 39).


def uninitialised(shape, dtype) -> jax.Array:
    """An array nobody has written: a buffer for kernels to fill, without
    the pass over it that ``jnp.zeros`` costs (``jax.lax.empty`` lowers to
    one too)."""
    return _uninitialised(tuple(shape), jnp.dtype(dtype), _interpret())


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _uninitialised(shape, dtype, interpret):
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda out: None,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=interpret,
        name="uninitialised",
    )()


def _live(t, n_live_ref):
    """Tile t, or the last live tile where t is past it."""
    return jnp.minimum(t, jnp.maximum(n_live_ref[0] - 1, 0))


def _matmul_kernel(group_ref, n_live_ref, lhs_ref, rhs_hbm, *rest, tn, transpose_rhs, scaled):
    """One row tile against a column block of its group's matrix. The block
    lies in one of two slots of ``wbuf``, copied there while the run of row
    tiles of the group before it was multiplied: a run's first tile waits
    for its block, then starts the copy of the next run's (the next group
    on this column block, or the first group on the next). The pipeline's
    own prefetch looks one grid step ahead, and a block of some MB does not
    arrive within one row tile's product."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale_ref, out_ref, wbuf, sem, runs = rest if scaled else (None,) + rest
    j, t, n_live, tiles = pl.program_id(0), pl.program_id(1), n_live_ref[0], pl.num_programs(1)

    def copy(g, col, slot):
        block = rhs_hbm.at[g, pl.ds(col * tn, tn), :] if transpose_rhs else rhs_hbm.at[g, :, pl.ds(col * tn, tn)]
        return pltpu.make_async_copy(block, wbuf.at[slot], sem.at[slot])

    @pl.when(t < n_live)
    def _():
        g = group_ref[t]

        @pl.when((t == 0) | (group_ref[jnp.maximum(t - 1, 0)] != g))
        def _():
            @pl.when((j == 0) & (t == 0))
            def _():
                runs[0] = 0
                copy(g, j, 0).start()

            slot = runs[0] % 2
            copy(g, j, slot).wait()
            # the run after this one: the first live tile behind t of another group, or tile 0 of the next column block
            after = jax.lax.fori_loop(
                0, tiles, lambda i, found: jnp.where((i > t) & (i < n_live) & (found == tiles) & (group_ref[i] != g), i, found), tiles
            )

            @pl.when(after < tiles)
            def _():
                copy(group_ref[jnp.minimum(after, tiles - 1)], j, 1 - slot).start()

            @pl.when((after == tiles) & (j + 1 < pl.num_programs(0)))
            def _():
                copy(group_ref[0], j + 1, 1 - slot).start()

            runs[0] = runs[0] + 1

        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc = jax.lax.dot_general(lhs_ref[...], wbuf[(runs[0] - 1) % 2], contract, preferred_element_type=jnp.float32)
        if scaled:
            acc = acc * scale_ref[...]
        out_ref[...] = acc.astype(out_ref.dtype)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    tile_group: jax.Array,
    n_live: jax.Array,
    *,
    transpose_rhs: bool = False,
    row_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """``lhs[rows of tile t] @ rhs[tile_group[t]]`` for the tiles ``t <
    n_live``: ``lhs (S, K)``, ``rhs (n, K, N)``, or ``(n, N, K)`` with
    ``transpose_rhs``, -> ``(S, N)`` float32. ``tile_group (S / tile,)``
    int32 gives the row tile its size; ``n_live`` is an int32 scalar, 1 at
    least. ``row_scale (S,)`` float32 multiplies each row of the float32
    product before it is written. The rows of the tiles from ``n_live`` on
    come back uninitialised."""
    return _grouped_matmul(lhs, rhs, tile_group, n_live, row_scale, transpose_rhs=transpose_rhs, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _grouped_matmul(lhs, rhs, tile_group, n_live, row_scale, *, transpose_rhs, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = tile_group.shape[0]
    tile = S // tiles
    tn = _column_block(N, K, rhs.dtype.itemsize)
    rows = lambda j, t, group, n_live: (_live(t, n_live), 0)  # noqa: E731
    in_specs, args = [pl.BlockSpec((tile, K), rows), pl.BlockSpec(memory_space=pl.ANY)], [lhs, rhs]
    if row_scale is not None:
        in_specs.append(pl.BlockSpec((tile, 1), rows))
        args.append(row_scale.reshape(S, 1).astype(jnp.float32))
    vmem = 2 * (K * tn * rhs.dtype.itemsize + tile * K * lhs.dtype.itemsize + tile * tn * 4) + tile * tn * 4
    return pl.pallas_call(
        functools.partial(_matmul_kernel, tn=tn, transpose_rhs=transpose_rhs, scaled=row_scale is not None),
        out_shape=jax.ShapeDtypeStruct((S, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, tn), lambda j, t, group, n_live: (_live(t, n_live), j)),
            scratch_shapes=[
                pltpu.VMEM((2, tn, K) if transpose_rhs else (2, K, tn), rhs.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        name="grouped_matmul",
        interpret=interpret,
        **_params(vmem, 2),
    )(tile_group, jnp.reshape(n_live, (1,)).astype(jnp.int32), *args)


def _matmul_t_kernel(group_ref, n_live_ref, stack_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    """Tile t of group g adds ``lhs^T rhs`` to the group's float32 block in
    VMEM: zeroed at the group's first tile, written out, in the result's
    dtype, at its last."""
    from jax.experimental import pallas as pl

    del stack_ref  # the result, aliased: groups of other calls stay as they are
    t, last = pl.program_id(2), n_live_ref[0] - 1

    @pl.when(t <= last)
    def _():
        g = group_ref[t]

        @pl.when((t == 0) | (group_ref[jnp.maximum(t - 1, 0)] != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        @pl.when((t == last) | (group_ref[jnp.minimum(t + 1, last)] != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def grouped_matmul_t(
    lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array, n_live: jax.Array, stack: jax.Array
) -> jax.Array:
    """``stack[g] = sum over the rows of g's tiles of lhs[row]^T rhs[row]``
    for every group g among the tiles ``t < n_live``, in place: ``lhs (S,
    K)``, ``rhs (S, N)``, ``stack (n, K, N)``, donated to the result, in
    whose dtype a group's block is written once, from a float32 block in
    VMEM that its row tiles add to. **A group's tiles are consecutive and
    all in this call**; the groups that have no tile here keep what
    ``stack`` held."""
    return _grouped_matmul_t(lhs, rhs, tile_group, n_live, stack, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_matmul_t(lhs, rhs, tile_group, n_live, stack, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, K = lhs.shape
    N = rhs.shape[1]
    tiles = tile_group.shape[0]
    tile = S // tiles
    tn = _column_block(N, K, 4, 2 * _BLOCK_BYTES)
    tk = K if K * tn * 4 <= 2 * _BLOCK_BYTES else _column_block(K, tn, 4, 2 * _BLOCK_BYTES)
    if K % tk or (tk != K and tk % 128):
        raise ValueError(f"no block of the {K} rows of a ({K}, {N}) result fits VMEM")
    vmem = tk * tn * (4 + 2 * stack.dtype.itemsize) + 2 * tile * (tk * lhs.dtype.itemsize + tn * rhs.dtype.itemsize)
    return pl.pallas_call(
        _matmul_t_kernel,
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, K // tk, tiles),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((tile, tk), lambda j, k, t, group, n_live: (_live(t, n_live), k)),
                pl.BlockSpec((tile, tn), lambda j, k, t, group, n_live: (_live(t, n_live), j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda j, k, t, group, n_live: (group[_live(t, n_live)], k, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        input_output_aliases={2: 0},  # stack, after the two prefetched scalars
        name="grouped_matmul_t",
        interpret=interpret,
        **_params(vmem, 3),
    )(tile_group, jnp.reshape(n_live, (1,)).astype(jnp.int32), stack, lhs, rhs)
