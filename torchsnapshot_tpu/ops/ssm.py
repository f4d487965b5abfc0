"""Selective state-space (SSM) sequence mixing via associative scan.

Linear-time, constant-state sequence mixing — the long-context complement
to attention. The recurrence

    h_t = a_t * h_{t-1} + b_t,        y_t = h_t

is a first-order linear recurrence, and ``(a, b) ∘ (a', b') =
(a*a', a'*b + b')`` is associative, so the whole sequence solves in
O(log S) depth with ``jax.lax.associative_scan`` — the canonical way to
put a recurrence on the MXU/VPU instead of a sequential loop. Gates and
projections follow the diagonal-selective-SSM recipe (Mamba-style): the
per-step decay ``a_t = exp(-softplus(delta_t) * A)`` and input ``b_t =
delta_t * B_t * x_t`` are data-dependent, computed with dense matmuls
that XLA tiles onto the MXU. The decay rides at ``(B, S, 1, N)`` through
the scan — the combine broadcasts against the ``(B, S, D, N)`` state, so
materializing it per-channel would inflate the scan d_model-fold for
nothing.

Sequence parallelism: ``ssm_mix_sharded`` runs the same math over a
sequence-sharded mesh axis. One local scan produces both the local states
and the per-chunk (decay product, final state) summary; an all_gather of
the summaries — O(ring * state) bytes, independent of S — feeds a
static-length prefix fold that yields each chunk's incoming state AND the
global final state, and one elementwise fix-up folds the carry in. Same
contract as the single-device path: accepts ``h0``, returns
``(y, h_last)``, so mid-sequence checkpoints resume identically under
sequence parallelism.

The reference has no sequence-mixing code at all (SURVEY.md §5.7); this
op exists because the framework treats long-context as first-class, and
its parameters and recurrent state are ordinary (shardable, reshardable)
snapshot entries.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _scan_combine(left, right):
    a_l, b_l = left
    a_r, b_r = right
    return a_r * a_l, a_r * b_l + b_r


def ssm_scan(a: jax.Array, b: jax.Array, h0: Optional[jax.Array] = None):
    """Solve ``h_t = a_t * h_{t-1} + b_t`` along axis 1.

    ``a, b: (B, S, ...)`` broadcastable against each other; returns ``h``
    with ``b``'s shape. ``h0`` (``(B, ...)``, optional) is the incoming
    state.
    """
    a_cum, h = jax.lax.associative_scan(_scan_combine, (a, b), axis=1)
    if h0 is not None:
        # h_t = (prod a_1..t) * h0 + h_t^(zero-init): one elementwise fixup.
        h = a_cum * h0[:, None] + h
    return h


def ssm_scan_sharded(
    a: jax.Array,
    b: jax.Array,
    *,
    axis_name: str,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sequence-parallel ``ssm_scan``. Must run inside ``shard_map``.

    ``a, b: (B, S_local, ...)`` — the local chunk of a sequence sharded
    over ``axis_name`` (device i owns positions [i*S_local, (i+1)*S_local)).
    Returns ``(h, h_final)`` where ``h_final`` (identical on every device)
    is the state after the LAST position of the global sequence.

    One local scan yields both the zero-init local states and this chunk's
    (cumulative decay, final state) summary; the summaries are
    all_gathered and folded with a static-length ``lax.scan`` (reverse-
    differentiable, unlike a fori_loop with a traced bound) to produce the
    incoming state per chunk and the global final state.
    """
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    a_cum, h_local = jax.lax.associative_scan(_scan_combine, (a, b), axis=1)
    prod = a_cum[:, -1]  # (B, ..., N) cumulative decay of this chunk
    last = h_local[:, -1]  # zero-init final state of this chunk
    # One collective: all_gather takes a pytree.
    prods, lasts = jax.lax.all_gather((prod, last), axis_name)  # (ring, B, ..., N)

    zeros = jnp.zeros_like(last)
    h_start = zeros if h0 is None else h0 + zeros

    # Single pass over the chunk chain: state entering chunk i is the fold
    # of chunks < i (seeded with h0); capture it at i == me and keep
    # folding to the global final state.
    def fold(carry, i):
        h, h_in = carry
        h_in = jnp.where(i == me, h, h_in)
        h = prods[i] * h + lasts[i]
        return (h, h_in), None

    (h_final, h_in), _ = jax.lax.scan(
        fold, (h_start, zeros), jnp.arange(ring)
    )
    h = a_cum * h_in[:, None] + h_local
    return h, h_final


def init_ssm_params(
    rng: jax.Array, d_model: int, d_state: int = 16, dtype=jnp.float32
) -> Dict[str, Any]:
    k_in, k_dt = jax.random.split(rng, 2)
    return {
        # log-spaced stable decay rates, the standard S4/Mamba init
        "log_a": jnp.log(
            jnp.linspace(1.0, float(d_state), d_state, dtype=jnp.float32)
        ).astype(dtype),
        "w_bc": jax.random.normal(k_in, (d_model, 2 * d_state), dtype)
        * (d_model**-0.5),
        "w_dt": jax.random.normal(k_dt, (d_model, 1), dtype) * (d_model**-0.5),
        "dt_bias": jnp.zeros((1,), dtype),
        "d_skip": jnp.ones((d_model,), dtype),
    }


def _discretize(params: Dict[str, Any], xf: jax.Array):
    """Position-wise projections shared by the single-device and sharded
    paths: x -> (decay a (B,S,1,N), input b (B,S,D,N), readout c (B,S,N))."""
    bc = xf @ params["w_bc"].astype(jnp.float32)  # (B, S, 2N)
    b_in, c_out = jnp.split(bc, 2, axis=-1)
    delta = jax.nn.softplus(
        xf @ params["w_dt"].astype(jnp.float32) + params["dt_bias"]
    )  # (B, S, 1)
    a_rate = jnp.exp(params["log_a"].astype(jnp.float32))  # (N,)
    a = jnp.exp(-delta[..., None] * a_rate)  # (B, S, 1, N) — broadcasts
    b = (delta * xf)[..., None] * b_in[:, :, None, :]  # (B, S, D, N)
    return a, b, c_out


def _readout(params: Dict[str, Any], xf: jax.Array, h: jax.Array, c_out):
    y = jnp.einsum("bsdn,bsn->bsd", h, c_out) + xf * params["d_skip"].astype(
        jnp.float32
    )
    return y


def ssm_mix(
    params: Dict[str, Any], x: jax.Array, h0: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """Diagonal selective SSM over ``x: (B, S, D)``.

    Returns ``(y, h_last)`` where ``y: (B, S, D)`` and ``h_last:
    (B, D, N)`` is the final state (the recurrent "KV cache" analogue —
    exactly what checkpoints for sequence-chunked training).
    """
    xf = x.astype(jnp.float32)
    a, b, c_out = _discretize(params, xf)
    h = ssm_scan(a, b, h0)  # (B, S, D, N)
    y = _readout(params, xf, h, c_out)
    return y.astype(x.dtype), h[:, -1]


def ssm_mix_sharded(
    params: Dict[str, Any],
    x: jax.Array,
    mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sequence-parallel ``ssm_mix`` on globally shaped ``x: (B, S, D)``.

    Same contract as :func:`ssm_mix` — accepts an incoming state, returns
    ``(y, h_last)`` — so sequence-chunked training checkpoints/resumes
    identically whether or not the sequence is sharded. The projections
    are position-wise (free under sequence sharding); only the scan needs
    the cross-chunk carry.
    """
    from jax.sharding import PartitionSpec as P

    axes = set(mesh.axis_names)
    if seq_axis not in axes:
        raise ValueError(f"mesh {mesh.axis_names} lacks seq axis {seq_axis!r}")
    bspec = batch_axis if batch_axis in axes else None
    spec = P(bspec, seq_axis, None)
    state_spec = P(bspec, None, None)

    def block(params, x_l, h0_l):
        xf = x_l.astype(jnp.float32)
        a, b, c_out = _discretize(params, xf)
        h, h_final = ssm_scan_sharded(
            a, b, axis_name=seq_axis, h0=h0_l.astype(jnp.float32)
        )
        y = _readout(params, xf, h, c_out)
        # State stays f32 like ssm_mix's h_last: the carried state is the
        # precision-critical cursor; downcasting it per chunk boundary
        # would degrade low-precision (bf16) runs on the sharded path only.
        return y.astype(x_l.dtype), h_final

    if h0 is None:
        N = params["log_a"].shape[0]
        h0 = jnp.zeros((x.shape[0], x.shape[2], N), x.dtype)
    param_specs = jax.tree_util.tree_map(lambda _: P(), params)
    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(param_specs, spec, state_spec),
        out_specs=(spec, state_spec),
        check_vma=False,
    )(params, x, h0)


# ------------------------------------------------ Mamba-2, chunked (SSD)


def _within_chunk_sum(log_decay: jax.Array) -> jax.Array:
    """Inclusive sum along the last axis (the chunk position, on the lanes),
    float32. A product with a lower-triangular matrix of ones at
    ``HIGHEST``: the ones are exact in any operand format and the
    accumulation is float32, so this is a float32 sum in another order, and
    it runs on the MXU where a windowed sum along the lanes does not."""
    L = log_decay.shape[-1]
    ones = jnp.triu(jnp.ones((L, L), jnp.float32))  # [s, t]: s <= t
    return jnp.matmul(log_decay, ones, precision=jax.lax.Precision.HIGHEST)


def mamba2_chunked(
    u: jax.Array,
    delta: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int,
) -> jax.Array:
    """The Mamba-2 recurrence by its state-space duality, in chunks.

    Per head, with a state ``h`` of ``(P, N)`` and a scalar decay::

        h_t = exp(delta_t a) h_{t-1} + delta_t u_t b_t^T,      y_t = h_t c_t

    ``u: (B, S, H, P)``, ``delta: (B, S, H)`` float32 (after the softplus),
    ``a: (H,)`` float32 and negative, ``b, c: (B, S, G, N)`` with a group
    serving ``H / G`` heads. Returns ``y: (B, S, H, P)`` float32; the skip
    term ``D u`` is the caller's.

    Inside a chunk of ``chunk`` positions the recurrence is one masked
    matrix product (``(c_t . b_s) exp(sum_{s<r<=t} delta_r a)`` for
    ``s <= t``, against ``delta_s u_s``), between chunks a ``lax.scan``
    carries the ``(H, P, N)`` state, so nothing of size ``S x P x N`` is
    ever held (``ssm_scan`` holds exactly that). The backward pass is
    autodiff's of this form: chunked as well. Matmul operands keep the dtype
    ``u``, ``b`` and ``c`` come in; decays, their cumulative sums and every
    accumulation are float32. ``S`` must be a multiple of ``chunk``.

    **The layout**, with ``n`` chunks of ``L`` positions and ``H = G x Hg``:
    every intermediate has the batch axes of the products, ``(B, n, G, Hg)``,
    first and 128 or more elements on its last axis, because the chip holds
    the last two axes of an array in tiles of (8, 128) and a short last axis
    leaves the rest of each tile empty (``(..., 8, 8)`` fills 64 of 1024
    places; ``P`` = 64 half the lanes).

    - the decay side, ``(B, n, G, Hg, L)``: ``delta``, ``delta a``, its
      within-chunk sum ``cs`` (along the last axis), ``to_end``, ``exp(cs)``;
      the ``[t, s]`` segment differences are ``cs[..., :, None] -
      cs[..., None, :]`` of that;
    - the ``u`` side transposed, ``(B, n, G, Hg, P, L)``: ``x = delta u`` and
      ``y``, so a decay multiplies them along the same last axis and the
      three products read and write them as they lie (``x mix^T``,
      ``x_to_end b``, ``h_in c^T``); ``b`` and ``c`` are ``(B, n, G, L, N)``,
      the chunks' states and the carry ``(B, [n,] G, Hg, P, N)``.

    One transpose in (``u``, ``delta``, ``b``, ``c``) and one out (``y``);
    none around a product. (On a v5e the compiler makes the one in a copy of
    the float32 ``u`` and the one out two copies of ``y``, 0.4-0.5 ms each at
    the published sizes: ``PERF.md``, PR 33.)
    """
    B, S, H, P = u.shape
    G, N = b.shape[2], b.shape[3]
    if S % chunk or H % G:
        raise ValueError(f"mamba2_chunked needs S ({S}) % chunk ({chunk}) == 0 and H ({H}) % G ({G}) == 0")
    n, L, Hg, f32 = S // chunk, chunk, H // G, jnp.float32

    dt = jnp.swapaxes(delta.astype(f32).reshape(B, n, L, H), 2, 3).reshape(B, n, G, Hg, L)
    cs = _within_chunk_sum(dt * a.astype(f32).reshape(G, Hg, 1))  # inclusive, within the chunk; <= 0
    x = jnp.transpose(u.reshape(B, n, L, G, Hg, P), (0, 1, 3, 4, 5, 2))  # (B, n, G, Hg, P, L)
    x = (x.astype(f32) * dt[..., None, :]).astype(u.dtype)
    bc = jnp.transpose(b.reshape(B, n, L, G, N), (0, 1, 3, 2, 4))  # (B, n, G, L, N)
    cc = jnp.transpose(c.reshape(B, n, L, G, N), (0, 1, 3, 2, 4))

    # Within a chunk: y_t += sum_{s<=t} (c_t . b_s) exp(cs_t - cs_s) x_s.
    # The mask goes on before the exp: above the diagonal cs_t - cs_s > 0.
    cb = jnp.einsum("bnglk,bngsk->bngls", cc, bc, preferred_element_type=f32)
    seg = cs[..., :, None] - cs[..., None, :]  # (B, n, G, Hg, L, L): [t, s]
    causal = jnp.tril(jnp.ones((L, L), bool))
    mix = (cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))).astype(u.dtype)
    y = jnp.einsum("bnghps,bnghls->bnghpl", x, mix, preferred_element_type=f32)

    # What each chunk adds to the state by its end, and the state each
    # chunk starts from: h_in[j+1] = exp(sum of chunk j) h_in[j] + states[j].
    to_end = jnp.exp(cs[..., -1:] - cs)  # (B, n, G, Hg, L)
    states = jnp.einsum(
        "bnghps,bngsk->bnghpk", (x.astype(f32) * to_end[..., None, :]).astype(u.dtype), bc,
        preferred_element_type=f32,
    )
    chunk_decay = jnp.exp(cs[..., -1])  # (B, n, G, Hg)

    def carry(h, xs):  # float32 carried; handed on as the operand the last product reads
        decay, add = xs
        return decay[..., None, None] * h + add, h.astype(u.dtype)

    _, h_in = jax.lax.scan(
        carry, jnp.zeros((B, G, Hg, P, N), f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # (B, n, G, Hg, P, N)
    y_off = jnp.einsum("bnghpk,bnglk->bnghpl", h_in, cc, preferred_element_type=f32)
    y = y + y_off * jnp.exp(cs)[..., None, :]
    return jnp.transpose(y, (0, 1, 5, 2, 3, 4)).reshape(B, S, H, P)
