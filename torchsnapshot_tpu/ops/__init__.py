"""TPU-native hot ops: attention kernels, context/expert parallelism, SSM.

The reference (torchsnapshot) contains no model or attention code — it is a
checkpointing library (SURVEY.md §5.7 records the absence). This package
exists because the TPU framework treats long-context and distributed
execution as first-class, so the checkpointing layer has real parallel
state to snapshot:

- blockwise (flash-style) attention in pure JAX, and Pallas TPU flash
  kernels for forward AND backward (plus a shard_mapped variant for tp
  meshes);
- ring attention (K/V rotating on the ICI ring via ``ppermute``) and its
  causally load-balanced zigzag variant; Ulysses all-to-all sequence
  parallelism;
- ring-flash and zigzag-flash attention: the Pallas kernel as the ring's
  inner compute (zigzag keeps the causal load balance with two half-block
  kernels per hop), hops merged by log-sum-exp under one custom VJP;
- GShard-style top-2 MoE with einsum and sort-based dispatch, and an
  explicit all-to-all expert-parallel path;
- the front end of compressed convolutional attention (``cca``): q and k
  through two causal convolutions, a mean shared between them, an L2 norm
  and a temperature, v shifted by a position for half its heads;
- selective-SSM sequence mixing via associative scan, with a
  sequence-parallel cross-chunk carry.
"""

from .attention import blockwise_attention, dense_attention
from .moe import moe_ffn, moe_ffn_sharded
from .pallas_attention import flash_attention, flash_attention_sharded
from .ring_attention import (
    ring_attention_sharded,
    ring_self_attention,
    zigzag_ring_attention_sharded,
    zigzag_ring_self_attention,
)
from .ring_flash import (
    ring_flash_attention_sharded,
    ring_flash_self_attention,
    zigzag_ring_flash_attention_sharded,
    zigzag_ring_flash_self_attention,
)
from .ssm import ssm_mix, ssm_mix_sharded, ssm_scan, ssm_scan_sharded
from .ulysses import ulysses_attention_sharded, ulysses_self_attention

__all__ = [
    "blockwise_attention",
    "dense_attention",
    "flash_attention",
    "flash_attention_sharded",
    "moe_ffn",
    "moe_ffn_sharded",
    "ring_attention_sharded",
    "ring_flash_attention_sharded",
    "ring_flash_self_attention",
    "ring_self_attention",
    "ssm_mix",
    "ssm_mix_sharded",
    "ssm_scan",
    "ssm_scan_sharded",
    "ulysses_attention_sharded",
    "ulysses_self_attention",
    "zigzag_ring_attention_sharded",
    "zigzag_ring_flash_attention_sharded",
    "zigzag_ring_flash_self_attention",
    "zigzag_ring_self_attention",
]
