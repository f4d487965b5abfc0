"""Pallas TPU row-accumulate kernel: ``acc[idx[r]] += rows[r]`` for the
first ``n_own`` rows of a tile, in place, wherever the accumulator lies.

XLA's scatter-add into a loop-carried ``(T, D)`` float32 accumulator wants
its operand in VMEM: every trip copies the whole accumulator in and out
around a serial scatter (``ops/moe.py`` ``_held_experts``; PERF.md, PR 35).
This kernel leaves the accumulator where it lies (``pl.ANY``, aliased onto
the result: in HBM, or in VMEM where the compiler holds it there across a
loop) and moves only the rows it adds to: one DMA a row into VMEM, the
addition, one DMA a row back, a group of rows at a time with the reads of
one group under way while the last group's writes drain. On a v5e a tile of
1024 rows of 8 KB costs 11 us to pass its products through and 30 ns an own
row, near what HBM allows for 16 KB (PERF.md, PR 35).

The accumulator is ``(T, 1, D)``, not ``(T, D)``: Mosaic refuses a one-row
DMA out of a ``(T, D)`` array, in HBM and in VMEM alike ("Slice shape along
dimension 0 must be aligned to tiling (8)": the rows of a ``T(8,128)`` array
are interleaved), and ``(T, 1, D)`` gets ``T(1,128)``, each row ``4 D``
contiguous bytes. ``rows`` stays ``(tile, D)`` as XLA's products leave it;
the kernel regroups a group of them in VMEM (a reshape outside costs a pass
over the tile a trip).

Off the TPU the kernel runs in Pallas interpret mode, as
``ops/pallas_attention.py`` does, so the CPU's tests run the same code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# Rows a grid step reads, adds and writes back: 64 rows of 8 KB are 512 KB
# of VMEM a slot. Two slots, so one group's reads overlap the last one's
# writes. 32 to 256 rows and three or four slots read the same on the chip.
_GROUP = 64
_SLOTS = 2


def _kernel(idx_ref, n_ref, acc_in, rows_ref, acc_out, buf, read_sem, write_sem, *, group):
    """One group of ``group`` rows a grid step, tile by tile (the grid's first
    dimension). ``acc_in`` is ``acc_out`` (aliased): every DMA names
    ``acc_out``. Group g owns slot ``g % 2`` of ``buf`` from the start of
    its reads (a step early) to the end of its writes (waited for a step
    late, before group g + 2's reads, or at the tile's last step: a tile
    leaves no copy in flight). A group past the tile's last own row moves
    and adds nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del acc_in
    g, last = pl.program_id(1), pl.num_programs(1) - 1
    n_own, base = n_ref[pl.program_id(0)], pl.program_id(0) * (pl.num_programs(1) * group)

    def own(grp):  # own rows of group grp: 0 past the expert's last row
        return jnp.clip(n_own - grp * group, 0, group)

    def copies(grp, back, start):
        """Start, or wait for, group grp's row copies: in (``back`` False)
        or back out."""
        slot, n = grp % _SLOTS, own(grp)
        sem = (write_sem if back else read_sem).at[slot]

        def one(r, _):
            row = acc_out.at[idx_ref[base + grp * group + r]]
            src, dst = (buf.at[slot, r], row) if back else (row, buf.at[slot, r])
            copy = pltpu.make_async_copy(src, dst, sem)
            copy.start() if start else copy.wait()

        if start:
            jax.lax.fori_loop(0, n, one, None)
            return

        # A semaphore counts bytes: a whole group's copies are waited for
        # in one wait the size of the slot, a part-filled group's row by row.
        @pl.when(n == group)
        def _():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem).wait()

        @pl.when(n < group)
        def _():
            jax.lax.fori_loop(0, n, one, None)

    @pl.when(g == 0)
    def _():
        copies(g, back=False, start=True)

    @pl.when(g < last)
    def _():
        @pl.when(g >= 1)
        def _():
            copies(g - 1, back=True, start=False)  # the slot's last writes have left it

        copies(g + 1, back=False, start=True)

    @pl.when(own(g) > 0)
    def _():
        copies(g, back=False, start=False)
        slot = g % _SLOTS
        # The whole group is added; of a part-filled one the tail is
        # written nowhere. (group, D) in (8, 128) tiles -> row by row.
        buf[slot] = buf[slot] + rows_ref[...].reshape(buf.shape[1:])
        copies(g, back=True, start=True)

    @pl.when(g == last)
    def _():
        @pl.when(g >= 1)
        def _():
            copies(g - 1, back=True, start=False)

        copies(g, back=True, start=False)


def add_rows(acc: jax.Array, idx: jax.Array, rows: jax.Array, n_own: jax.Array) -> jax.Array:
    """``acc.at[idx[:n_own], 0].add(rows[:n_own])``, in place, a tile of the
    rows at a time: tile t is ``idx`` and ``rows`` from ``t * tile`` on,
    ``tile = len(idx) / len(n_own)``, and ``n_own[t]`` of its leading rows
    are added.

    ``acc: (T, 1, D)`` float32, donated to the result; ``idx: (S,)`` int32 in
    ``[0, T)``, **unique within a tile's own rows**; ``rows: (S, D)``
    float32; ``n_own``: int32, a scalar (one tile) or ``(tiles,)``. The rows
    past a tile's ``n_own`` are neither read nor written, and their groups
    not fetched. A tile's last writes have landed before the next tile's
    first read, so two tiles may hold the same row of ``acc`` (a token that
    two experts hold)."""
    n_own = jnp.reshape(n_own, (-1,)).astype(jnp.int32)
    return _add_rows(acc, idx, rows, n_own, interpret=jax.default_backend() != "tpu")


# Jitted, so that the calls of one step (three loops a layer) are lowered to
# Mosaic once: ``pallas_grouped.py`` says why.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _add_rows(acc, idx, rows, n_own, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D, tiles = acc.shape[-1], n_own.shape[0]
    tile = idx.shape[0] // tiles
    group = math.gcd(tile, _GROUP)  # groups cover the tile exactly

    def rows_block(t, g, idx_ref, n_ref):
        # Past the last group that holds own rows the block stays where it
        # is, and the pipeline does not fetch it again.
        return t * (tile // group) + jnp.minimum(g, jnp.maximum(n_ref[t] - 1, 0) // group), 0

    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, tile // group),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec((group, D), rows_block)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, group, 1, D), acc.dtype),
                pltpu.SemaphoreType.DMA((_SLOTS,)),
                pltpu.SemaphoreType.DMA((_SLOTS,)),
            ],
        ),
        input_output_aliases={2: 0},  # acc, after the two prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="add_rows",
    )(idx, n_own, acc, rows)
