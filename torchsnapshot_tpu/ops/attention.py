"""Blockwise (flash-style) causal attention for a single device/shard.

Layout convention throughout: ``q, k, v: (batch, seq, heads, head_dim)``.
Softmax statistics are carried in float32 regardless of input dtype; the
output is cast back to the query dtype.

Why blockwise: materializing the (S, S) score matrix is O(S^2) HBM — the
usual long-context killer. Scanning over K/V blocks with an online softmax
keeps peak memory at O(S * block) while XLA still sees large static-shape
matmuls it can tile onto the MXU. ``lax.scan`` (not a Python loop) keeps the
compiled program size flat as sequence length grows.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

# Effectively -inf for masking without producing NaNs in exp()/max() chains.
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The block-diffusion training mask over ``2 * half`` positions: the
    clean copy of a sequence (positions ``0 .. half-1``) followed by its
    noised copy, both carrying the ids ``0 .. half-1``, in blocks of
    ``block`` ids (``blk = id // block``)::

        clean query  -> clean key   iff blk(key) <= blk(query)
        clean query  -> noised key  never
        noised query -> clean key   iff blk(key) <  blk(query)
        noised query -> noised key  iff blk(key) == blk(query)

    ``allowed`` is the one statement of the rule: the dense and blockwise
    routes and the three Pallas kernels all call it. The kernels work in
    square tiles of ``tile`` positions that divide ``half``, so a tile lies
    in one copy, and visit only tiles that hold a live score: ``k_tiles``
    and ``q_tiles`` enumerate them, for a tile index that may be traced.
    Every query sees its own block, so no row is empty."""

    half: int
    block: int

    def __post_init__(self):
        if self.half <= 0 or self.block <= 0 or self.half % self.block:
            raise ValueError(f"blocks of {self.block} do not tile {self.half} positions")

    def allowed(self, q_pos, k_pos):
        """Whether the query at ``q_pos`` sees the key at ``k_pos``
        (positions in ``0 .. 2 * half - 1``, any shapes that broadcast).
        Integer arithmetic and two comparisons, which is what the kernels'
        compiler takes on vectors: a noised position's block is numbered
        ``half`` higher than its clean twin's, past every clean block, so
        "the same block of the same copy" is one equality and "an earlier
        clean block" one inequality."""
        q_copy, k_copy = (jnp.where(pos >= self.half, 1, 0) for pos in (q_pos, k_pos))
        q_blk = jax.lax.div(q_pos - q_copy * self.half, self.block)
        k_blk = jax.lax.div(k_pos - k_copy * self.half, self.block) + k_copy * self.half
        return (k_blk == q_blk + q_copy * self.half) | (k_blk < q_blk)

    def tile(self, configured: int) -> Optional[int]:
        """The kernels' tile for a target of ``configured`` positions: a
        divisor of ``half`` that whole blocks fill, or None."""
        bs = pick_block_size(self.half, configured)
        return bs if bs is not None and bs % self.block == 0 else None

    def k_tiles(self, qi, tile: int):
        """The key tiles that hold a live score for query tile ``qi``:
        ``(count, j -> tile index)``. A clean query tile sees the clean
        tiles up to its own; a noised one its own noised tile (first: every
        row has a live key there, so the online softmax starts from a real
        maximum) and then the clean tiles up to its clean twin, the twin
        itself unless the tile is a single block."""
        n, twin = self.half // tile, int(tile > self.block)
        noised = qi >= n
        return (
            jnp.where(noised, qi - n + 1 + twin, qi + 1),
            lambda j: jnp.where(noised, jnp.where(j == 0, qi, j - 1), j),
        )

    def q_tiles(self, ki, tile: int):
        """The query tiles that hold a live score for key tile ``ki``: a
        clean key tile is seen from its own clean tile on and from its
        noised twin on (from the one after, where the tile is a single
        block), a noised one by itself alone."""
        n, past = self.half // tile, int(tile == self.block)
        clean = ki < n
        return (
            jnp.where(clean, 2 * (n - ki) - past, 1),
            lambda j: jnp.where(clean, jnp.where(j < n - ki, ki + j, 2 * ki + j + past), ki),
        )

    def live_tiles(self, tile: int) -> int:
        """Tiles of ``tile x tile`` scores that the kernels compute, of the
        ``(2 * half / tile)^2`` a masked-dense pass would."""
        return sum(int(self.k_tiles(qi, tile)[0]) for qi in range(2 * self.half // tile))


def _allowed(causal: bool, mask: Optional[BlockDiffusionMask], q_pos, k_pos):
    """The (Sq, Sk) boolean of scores that count, or None for all of them."""
    if mask is not None:
        return mask.allowed(q_pos[:, None], k_pos[None, :])
    return q_pos[:, None] >= k_pos[None, :] if causal else None


def pick_block_size(seq_len: int, configured: int) -> Optional[int]:
    """Largest divisor of ``seq_len`` within ``configured`` — the tiled
    kernels (blockwise, flash) require ``seq_len % block == 0``. Returns
    None when only tiny divisors exist (e.g. prime lengths): below a
    quarter of the configured size the O(S^2) dense path beats S/bs tiny
    blocks, so callers should fall back to dense."""
    bs = min(configured, seq_len)
    while seq_len % bs:
        bs -= 1
    if bs < max(1, min(configured, seq_len) // 4):
        return None
    return bs


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    k_offset: int = 0,
    mask: Optional[BlockDiffusionMask] = None,
) -> jax.Array:
    """Reference O(S^2)-memory attention. ``q, k, v: (B, S, H, D)``.

    ``q_offset``/``k_offset`` are the global positions of the first query /
    key — used when q and k are shards of a longer sequence. A ``mask``
    takes the causal rule's place.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    allowed = _allowed(causal, mask, q_offset + jnp.arange(q.shape[1]), k_offset + jnp.arange(k.shape[1]))
    if allowed is not None:
        s = jnp.where(allowed[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if allowed is not None:
        # A query row with no valid key (reachable via k_offset > q_offset
        # on sharded calls) must attend to nothing, not uniformly to
        # everything — softmax of an all-NEG_INF row is uniform.
        row_valid = allowed.any(axis=-1)  # (Sq, Sk) -> (Sq,)
        p = jnp.where(row_valid[None, None, :, None], p, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_block_update(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    scale: float,
    causal: bool,
    acc: Tuple[jax.Array, jax.Array, jax.Array],
    mask: Optional[BlockDiffusionMask] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax update of accumulator ``acc = (o, m, l)`` with a
    (q-block, kv-block) pair.

    o: (B, Sq, H, D) float32 unnormalized output;
    m: (B, H, Sq) float32 running max; l: (B, H, Sq) float32 running sum.
    ``q_pos``/``k_pos`` are int32 global positions, shapes (Sq,), (Sk,).

    Masked-out blocks are numerically inert: their scores sit at NEG_INF, so
    as long as the first block processed for every query row contains at
    least one valid key (true for causal self-attention, where the diagonal
    block is always processed first), ``exp(score - m)`` underflows to 0.
    A row whose first blocks are wholly masked (a ``mask``'s noised
    queries) collects weight-1 rows there and drops them, ``alpha = 0``,
    at its first live score; NEG_INF is finite, so nothing overflows.
    """
    o, m, l = acc
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    allowed = _allowed(causal, mask, q_pos, k_pos)
    if allowed is not None:
        s = jnp.where(allowed[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)  # (B, H, Sq)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def _finalize(acc: Tuple[jax.Array, jax.Array, jax.Array], dtype) -> jax.Array:
    o, _, l = acc
    return (o / l.transpose(0, 2, 1)[..., None]).astype(dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_size: int = 512,
    causal: bool = True,
    scale: Optional[float] = None,
    mask: Optional[BlockDiffusionMask] = None,
) -> jax.Array:
    """Flash-style attention: scan over K/V blocks with an online softmax.

    ``q, k, v: (B, S, H, D)`` with S divisible by ``block_size`` (callers pad;
    a static check enforces it so XLA never sees dynamic shapes). Every
    block is computed and masked, under the causal rule or a ``mask``.
    """
    B, S, H, D = q.shape
    if scale is None:
        scale = D**-0.5
    block_size = min(block_size, S)
    if S % block_size != 0:
        raise ValueError(f"seq len {S} not divisible by block_size {block_size}")
    n_blocks = S // block_size

    kb = k.reshape(B, n_blocks, block_size, H, D)
    vb = v.reshape(B, n_blocks, block_size, H, D)
    q_pos = jnp.arange(S)

    def scan_kv(acc, blk):
        k_blk, v_blk, j = blk
        k_pos = j * block_size + jnp.arange(block_size)
        acc = attention_block_update(
            q, k_blk, v_blk, q_pos, k_pos, scale, causal, acc, mask
        )
        return acc, None

    acc = (
        jnp.zeros((B, S, H, D), jnp.float32),
        jnp.full((B, H, S), NEG_INF, jnp.float32),
        jnp.zeros((B, H, S), jnp.float32),
    )
    # Scan from block 0 so the diagonal (always-valid) block is folded in
    # before any fully-masked block — see attention_block_update.
    acc, _ = jax.lax.scan(
        scan_kv,
        acc,
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), jnp.arange(n_blocks)),
    )
    return _finalize(acc, q.dtype)


# ------------------------------------------------- which path a model runs

ATTN_IMPLS = ("auto", "dense", "blockwise", "flash", "ring", "zigzag", "ulysses")
CP_ROUTES = ("ring", "ring_flash", "zigzag", "zigzag_flash", "ulysses")


def flash_mesh_ok(n_heads: int, mesh, B: int, S: int) -> bool:
    """Preconditions for routing attention through the shard_mapped flash
    kernel under a mesh: heads divide the 'model' axis when one exists,
    batch divides the 'data' axis, and S (the kernel's local sequence
    length — pass S_local for ring-flash) has a kernel-viable tile
    divisor (the kernel picks its own 512-target tiling, so the gate must
    agree with that pick). Shared by the flash and ring-flash routes."""
    if "model" in mesh.axis_names and n_heads % mesh.shape["model"]:
        return False
    if "data" in mesh.axis_names and B % mesh.shape["data"]:
        return False
    return S > 0 and pick_block_size(S, 512) is not None


def _tile(S: int, block_size: int, mask: Optional[BlockDiffusionMask]) -> Optional[int]:
    """The tile the blockwise and flash routes cut S into, or None."""
    return pick_block_size(S, block_size) if mask is None else mask.tile(block_size)


def _select_route(
    attn_impl: str, block_size: int, n_heads: int, mesh, B: int, S: int, mask: Optional[BlockDiffusionMask] = None
) -> str:
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    if mask is not None and (S != 2 * mask.half or attn_impl in ("ring", "zigzag", "ulysses")):
        raise ValueError(f"a block-diffusion mask over {2 * mask.half} positions: S is {S}, attn_impl {attn_impl!r}")
    on_tpu = jax.default_backend() == "tpu"
    tiled = S if mask is None else mask.half  # what the kernels' tiles divide
    impl = attn_impl
    if impl == "auto":
        # Backend-aware kernel choice: the Pallas flash kernel on TPU —
        # bare on a single device, shard_mapped over batch/heads under a
        # mesh when the preconditions hold (flash_mesh_ok; a bare
        # pallas_call has no partitioning rule, so it must never see
        # sharded operands); blockwise once S outgrows one block
        # (O(S*block) memory); dense for short sequences. Never selects a
        # cp impl — ring/zigzag/ulysses are mesh topology decisions for
        # the caller.
        if on_tpu and (mesh is None or flash_mesh_ok(n_heads, mesh, B, tiled)):
            impl = "flash"
        elif S > block_size:
            impl = "blockwise"
        else:
            impl = "dense"
    if impl in ("ring", "zigzag", "ulysses"):
        if mesh is None:
            # Single-device run of a cp-configured model: same math, no
            # axis to communicate over.
            return "dense"
        if "seq" not in mesh.axis_names:
            raise ValueError(
                f"attn_impl={impl!r} needs a mesh with a 'seq' axis; got "
                f"{mesh.axis_names}. Build one via make_mesh({{'data': ..., "
                f"'seq': ..., 'model': ...}})."
            )
        if impl == "ulysses":
            return impl
        # The ring's inner compute dominates long-context cost; run it
        # through the Pallas kernel when the LOCAL shard (the half-shard
        # for zigzag) satisfies the flash preconditions.
        parts = mesh.shape["seq"] * (2 if impl == "zigzag" else 1)
        if on_tpu and S % parts == 0 and flash_mesh_ok(n_heads, mesh, B, S // parts):
            return impl + "_flash"
        return impl
    if impl in ("blockwise", "flash"):
        if _tile(S, block_size, mask) is None:
            return "dense"
        if impl == "flash" and mesh is not None:
            # Under a mesh the bare pallas_call would make GSPMD gather
            # the sharded operands; shard_map the kernel instead, or give
            # way to blockwise when the preconditions don't hold.
            ok = flash_mesh_ok(n_heads, mesh, B, tiled) and _tile(S, 512, mask)
            return "flash_sharded" if ok else "blockwise"
    return impl


def causal_attention_route(
    attn_impl: str, block_size: int, n_heads: int, mesh, B: int, S: int,
    mask: Optional[BlockDiffusionMask] = None,
) -> Tuple[str, Callable[..., jax.Array]]:
    """The causal attention a model runs for this request, mesh and shape:
    the route's name and ``attend(q, k, v, in_layout=False)`` on logical
    ``(B, S, H, hd)`` operands (sharding via the caller's constraints);
    ``k`` and ``v`` may come with fewer heads, one a group of query heads.
    With a ``mask`` (``BlockDiffusionMask``; S is then the clean and the
    noised copy together) its rule takes the causal one's place on the
    dense, blockwise and flash routes, whose tiles then divide one copy;
    the context-parallel routes do not take one.

    ``attn_impl`` is a request; what runs also depends on things the code
    observes (backend, mesh axes, whether S tiles), and a request that
    cannot be met gives way to the next-best path. This function is that
    whole decision and the only dispatch: every model's block calls it, so
    callers (chip_smoke.py, tests, the benchmark) read what was selected
    instead of inferring it from a config string. The name is one of
    ``dense``, ``blockwise``, ``flash`` (bare Pallas kernel),
    ``flash_sharded`` (the kernel shard_mapped over batch/heads),
    ``ring``, ``ring_flash``, ``zigzag``, ``zigzag_flash``, ``ulysses``.
    ``in_layout`` tells the zigzag routes that the caller already applied
    the folded layout.
    """
    route = _select_route(attn_impl, block_size, n_heads, mesh, B, S, mask)

    def attend(q, k, v, in_layout: bool = False):
        if k.shape[2] != q.shape[2]:
            # Grouped-query attention: ``k, v: (B, S, H_kv, hd)`` with H_kv
            # dividing H. Each KV head is repeated over its group's query
            # heads ahead of whichever route runs, so every route (the
            # flash kernel among them) sees one KV head a query head, and
            # the repeat's transpose sums a group's gradients.
            group = q.shape[2] // k.shape[2]
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if route == "ulysses":
            from .ulysses import ulysses_attention_sharded

            return ulysses_attention_sharded(
                q, k, v, mesh, causal=True, inner_block_size=block_size
            )
        if route == "zigzag_flash":
            from .ring_flash import zigzag_ring_flash_attention_sharded

            return zigzag_ring_flash_attention_sharded(q, k, v, mesh, in_layout=in_layout)
        if route == "zigzag":
            from .ring_attention import zigzag_ring_attention_sharded

            return zigzag_ring_attention_sharded(q, k, v, mesh, in_layout=in_layout)
        if route == "ring_flash":
            from .ring_flash import ring_flash_attention_sharded

            return ring_flash_attention_sharded(q, k, v, mesh, causal=True)
        if route == "ring":
            from .ring_attention import ring_attention_sharded

            return ring_attention_sharded(q, k, v, mesh, causal=True)
        if route == "flash_sharded":
            from .pallas_attention import flash_attention_sharded

            return flash_attention_sharded(q, k, v, mesh, causal=True, mask=mask)
        if route == "flash":
            from .pallas_attention import flash_attention

            bs = _tile(S, block_size, mask)
            return flash_attention(q, k, v, causal=True, block_q=bs, block_k=bs, mask=mask)
        if route == "blockwise":
            bs = _tile(S, block_size, mask)
            return blockwise_attention(q, k, v, block_size=bs, causal=True, mask=mask)
        return dense_attention(q, k, v, causal=True, mask=mask)

    return route, attend
