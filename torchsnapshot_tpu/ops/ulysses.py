"""Ulysses-style all-to-all sequence parallelism.

The second canonical context-parallel scheme (alongside ring attention,
ops/ring_attention.py): activations arrive sequence-sharded over a mesh
axis; one ``all_to_all`` re-shards them to *head*-sharded with the full
sequence per device, attention runs locally per head group (zero
communication inside), and a second ``all_to_all`` restores the
sequence-sharded layout. Two collectives per attention call, each moving
activations once over ICI — cheaper than the ring's per-step exchanges
when head count >= ring size, at the cost of O(S) per-device memory during
attention (the ring stays O(S/p)).

Trade-off guide: ring for the longest sequences (memory-bound), Ulysses
when heads are plentiful and S_local fits comfortably.

The reference framework has neither scheme (SURVEY.md §5.7) — its
checkpoint layer just reshards whatever state these produce; the ops exist
because long-context training is first-class in the TPU build.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from .attention import blockwise_attention, dense_attention, pick_block_size


def _resolve_inner(inner: str) -> str:
    """inner="auto" picks the Pallas flash kernel on TPU and the pure-JAX
    blockwise scan elsewhere (flash would run in slow interpret mode
    off-TPU)."""
    if inner != "auto":
        return inner
    return "flash" if jax.default_backend() == "tpu" else "blockwise"


def ulysses_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    inner: str = "auto",
    inner_block_size: int = 512,
) -> jax.Array:
    """Per-shard Ulysses body. Must run inside ``shard_map``.

    ``q, k, v: (B, S_local, H_local, D)`` with ``H_local`` divisible by the
    axis size. Returns the same layout.
    """
    inner = _resolve_inner(inner)
    p = jax.lax.axis_size(axis_name)
    if q.shape[2] % p != 0:
        raise ValueError(
            f"Ulysses needs heads per shard ({q.shape[2]}) divisible by the "
            f"sequence-parallel axis size ({p}); use ring attention for "
            f"head-starved configurations."
        )

    def to_head_sharded(t):
        # (B, S/p, H, D) -> (B, S, H/p, D): split heads across the axis,
        # concatenate the sequence shards.
        return jax.lax.all_to_all(
            t, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq_sharded(t):
        return jax.lax.all_to_all(
            t, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = to_head_sharded(q), to_head_sharded(k), to_head_sharded(v)
    S = qh.shape[1]
    bs = pick_block_size(S, inner_block_size)
    # Gate flash on the KERNEL's own tiling pick (512 target), not the
    # blockwise knob: the kernel chooses its tuned tiles itself, so the
    # gate must agree with what it will actually pick or an S the gate
    # accepts could fail the kernel's divisibility check.
    if inner == "flash" and pick_block_size(S, 512) is not None:
        from .pallas_attention import flash_attention

        # inner_block_size is the blockwise scan's memory knob; inheriting
        # it here would hand the MXU badly-undersized tiles.
        out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    elif inner == "blockwise" and bs is not None and S > inner_block_size:
        out = blockwise_attention(qh, kh, vh, block_size=bs, causal=causal, scale=scale)
    else:
        out = dense_attention(qh, kh, vh, causal=causal, scale=scale)
    return to_seq_sharded(out)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = "model",
    causal: bool = True,
    scale: Optional[float] = None,
    inner: str = "auto",
    inner_block_size: int = 512,
) -> jax.Array:
    """Apply Ulysses attention to globally-shaped ``(B, S, H, D)`` arrays.

    Same canonical specs as ``ring_attention_sharded``: sequence over
    ``seq_axis``, batch over ``batch_axis``, heads over ``head_axis`` (tensor
    parallelism composes — the all_to_all further splits the local heads).
    """
    inner = _resolve_inner(inner)
    axes = set(mesh.axis_names)
    if seq_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks seq axis {seq_axis!r}")
    b = batch_axis if batch_axis in axes else None
    h = head_axis if head_axis in axes else None
    spec = P(b, seq_axis, h, None)
    fn = partial(
        ulysses_self_attention,
        axis_name=seq_axis,
        causal=causal,
        scale=scale,
        inner=inner,
        inner_block_size=inner_block_size,
    )
    # Pallas interpret mode (CPU testing of inner="flash") emits
    # dynamic_slices whose index operands are unvarying, which trips
    # shard_map's varying-axes checker — a jax-internal false positive the
    # error message itself says to silence with check_vma=False. On TPU
    # the kernel compiles for real, so keep the checker ON there.
    check_vma = not (inner == "flash" and jax.default_backend() != "tpu")
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=check_vma,
    )(q, k, v)
