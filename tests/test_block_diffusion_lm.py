"""The block-diffusion LM (``models/block_diffusion_lm.py``: a stacked and
scanned routed-expert decoder over the clean and the noised copy of a
sequence, softmax top-k gated experts with a share of them held, the
four-quadrant mask through the one attention dispatch) against its plain
reference (``benchmarks/chip/reference/block_diffusion_lm.py``: float32,
``highest`` precision, the mask built densely, no kernel), and through the
train step and ``CheckpointManager`` as the other families go.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import CheckpointManager, StateDict, telemetry
from torchsnapshot_tpu.models import block_diffusion_lm as M
from torchsnapshot_tpu.ops.attention import BlockDiffusionMask, causal_attention_route
from torchsnapshot_tpu.ops import moe
from torchsnapshot_tpu.ops.moe import _held_experts, gated_ffn, softmax_topk_route, softmax_topk_routed
from torchsnapshot_tpu.parallel import make_mesh

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "chip", "reference", "block_diffusion_lm.py")
_spec = importlib.util.spec_from_file_location("block_diffusion_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, B, S = 96, 2, 32
# The toy size keeps every ratio of the published config that a layer's code
# depends on: several query heads a KV head, more experts than are held,
# top_k below the count held and above 1, several blocks a tile.
CFG = M.BlockDiffusionLMConfig(
    vocab_size=V, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8, n_experts=16, top_k=4,
    expert_ff=24, held=(0, 1, 2, 3), block_length=4, dtype=jnp.float32,
)


def _ref_args(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads, "top_k": cfg.top_k, "held": cfg.held,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta, "block_length": cfg.block_length,
            "mask_token_id": cfg.mask_id}


def _params(cfg, seed=0):
    """Seeded weights with every scale off its initial value, so that one
    applied in the wrong place shows."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return x + 0.2 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(jitter, params)


def _batch(mesh=None, seed=7, batch=B, seq=S):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, V, jnp.int32)
    out = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        out = jax.device_put(out, NamedSharding(mesh, P("data", None)))
    return out


def _noise(cfg=CFG, seed=5, batch=B, seq=S):
    return M.draw_noise(jax.random.PRNGKey(seed), batch, seq, cfg)


# ------------------------------------------------------------------ the mask


def _dense_rule(S_, block):
    """The four rules written out position by position, with numpy."""
    pos = np.arange(2 * S_)
    noised, blk = pos >= S_, (pos % S_) // block
    q_n, k_n, q_b, k_b = noised[:, None], noised[None, :], blk[:, None], blk[None, :]
    return np.where(k_n, q_n & (k_b == q_b), np.where(q_n, k_b < q_b, k_b <= q_b))


@pytest.mark.parametrize("seq,block", [(32, 4), (24, 8), (16, 1), (8, 8)])
def test_the_mask_function_and_the_references_quadrants_are_the_four_rules(seq, block):
    want = _dense_rule(seq, block)
    pos = jnp.arange(2 * seq)
    np.testing.assert_array_equal(np.asarray(BlockDiffusionMask(seq, block).allowed(pos[:, None], pos[None, :])), want)
    np.testing.assert_array_equal(np.asarray(R.mask(seq, block)), want)
    assert want.any(axis=1).all()  # every query sees its own block


@pytest.mark.parametrize("seq,block,tile,live", [(4096, 4, 512, 80), (64, 4, 16, 24), (64, 16, 16, 20), (32, 4, 32, 3)])
def test_the_kernels_visit_exactly_the_tiles_that_hold_a_live_score(seq, block, tile, live):
    """``k_tiles`` / ``q_tiles`` against a brute-force count over the dense
    rule: at S 4096 and tile 512, 80 of 256 (36 clean -> clean, 36 noised ->
    clean, 8 noised -> noised, none clean -> noised)."""
    m = BlockDiffusionMask(seq, block)
    n = 2 * seq // tile
    tiles = _dense_rule(seq, block).reshape(n, tile, n, tile).any(axis=(1, 3))
    assert int(tiles.sum()) == live == m.live_tiles(tile)
    for i in range(n if n <= 8 else 0):  # the walks themselves, at the small sizes and at 4096 below
        count, at = m.k_tiles(i, tile)
        assert sorted(int(at(j)) for j in range(int(count))) == list(np.flatnonzero(tiles[i]))
        count, at = m.q_tiles(i, tile)
        assert sorted(int(at(j)) for j in range(int(count))) == list(np.flatnonzero(tiles[:, i]))
    # a noised query tile starts at its own noised tile, where every row has a live key
    assert int(m.k_tiles(n - 1, tile)[1](0)) == n - 1
    assert sum(int(m.q_tiles(i, tile)[0]) for i in range(n)) == live


def _dense_attention_reference(q, k, v, seq, block):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.asarray(_dense_rule(seq, block))[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("impl,tile", [("dense", 16), ("blockwise", 16), ("flash", 16), ("flash", 32), ("auto", 16)])
def test_every_attention_route_computes_the_masked_softmax_and_its_gradient(impl, tile):
    """Through the one dispatch, with grouped KV heads, against a softmax
    over the dense four-rule mask; the flash route is the Pallas kernels in
    interpret mode, forward and both backward kernels. float32: 1e-5 is
    the online softmax's reassociation."""
    seq, block = 64, 4
    m = BlockDiffusionMask(seq, block)
    route, attend = causal_attention_route(impl, tile, 4, None, B, 2 * seq, mask=m)
    assert route == ("blockwise" if impl == "auto" else impl)
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, 2 * seq, 4, 16), jnp.float32)
    k, v = (jax.random.normal(key, (B, 2 * seq, 2, 16), jnp.float32) for key in (kk, kv))
    w = jax.random.normal(kw, q.shape, jnp.float32)
    want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(_dense_attention_reference(*a, seq, block) * w), (0, 1, 2))(q, k, v)
    got, got_grads = jax.value_and_grad(lambda *a: jnp.sum(attend(*a) * w), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_the_mask_goes_through_the_sharded_flash_route():
    """Under a ('data','model') mesh the kernel is shard_mapped over batch
    and heads; the mask rides along (here in interpret mode)."""
    seq, block = 32, 4
    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    route, attend = causal_attention_route("flash", 16, 4, mesh, B, 2 * seq, mask=BlockDiffusionMask(seq, block))
    assert route == "flash_sharded"
    q, k, v = (jax.random.normal(key, (B, 2 * seq, 4, 16), jnp.float32) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    np.testing.assert_allclose(jax.jit(attend)(q, k, v), _dense_attention_reference(q, k, v, seq, block), rtol=1e-5, atol=1e-6)


def test_the_dispatch_refuses_a_mask_it_cannot_tile_or_route():
    m = BlockDiffusionMask(64, 4)
    with pytest.raises(ValueError, match="block-diffusion mask"):
        causal_attention_route("auto", 16, 4, None, B, 64, mask=m)  # S must be both copies
    with pytest.raises(ValueError, match="block-diffusion mask"):
        causal_attention_route("ring", 16, 4, None, B, 128, mask=m)
    with pytest.raises(ValueError, match="do not tile"):
        BlockDiffusionMask(30, 4)
    assert BlockDiffusionMask(96, 32).tile(64) is None  # 48 divides 96 but holds one and a half blocks
    assert causal_attention_route("flash", 64, 4, None, B, 192, mask=BlockDiffusionMask(96, 32))[0] == "dense"
    from torchsnapshot_tpu.ops.pallas_attention import flash_attention

    x = jnp.zeros((1, 128, 1, 8))
    with pytest.raises(ValueError, match="one tile"):
        flash_attention(x, x, x, mask=m, block_q=16, block_k=32)


# --------------------------------------------------------------- the experts


def _stream(seed=3, rows=64, width=CFG.d_model):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, width), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _expert_layer(cfg, seed=0, held=None):
    w = {k: v[0] for k, v in _params(cfg, seed)["layers"].items()}
    if held is not None:  # all 16 experts' weights, to cut shares from
        keys = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
        D, F, E = cfg.d_model, cfg.expert_ff, cfg.n_experts
        w["expert_gate"] = jax.random.normal(keys[0], (E, D, F)) * D**-0.5
        w["expert_up"] = jax.random.normal(keys[1], (E, D, F)) * D**-0.5
        w["expert_down"] = jax.random.normal(keys[2], (E, F, D)) * F**-0.5
    return w


def test_softmax_routing_takes_the_top_k_of_all_experts_and_renormalises():
    w, x = _expert_layer(CFG), _stream()
    ids, weights = softmax_topk_route(x, w["router"], CFG.top_k)
    p = np.asarray(jax.nn.softmax(x @ w["router"], axis=-1))
    want_ids = np.argsort(-p, axis=-1)[:, : CFG.top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), axis=-1), np.sort(want_ids, axis=-1))
    chosen = np.take_along_axis(p, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    assert np.asarray(weights).std() > 0.05  # routers at full size here: the weights are not all an eighth


def test_the_eight_disjoint_shares_add_up_to_the_uncut_layer(monkeypatch):
    """Eight chips hold two experts each of sixteen; what they add is the
    layer with all sixteen held, and the reference's dense loop. Row tiles
    of 16, so an expert has several."""
    monkeypatch.setattr(moe, "_ROW_TILE", 16)
    w, x = _expert_layer(CFG, held="all"), _stream()
    whole, ids = softmax_topk_routed(w, x, top_k=CFG.top_k, held=tuple(range(16)))
    parts = []
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        part = {**w, **{k: w[k][jnp.asarray(held)] for k in ("expert_gate", "expert_up", "expert_down")}}
        out, ids_part = softmax_topk_routed(part, x, top_k=CFG.top_k, held=held)
        np.testing.assert_array_equal(ids_part, ids)  # every share scores and chooses over all sixteen
        parts.append(out)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6)
    want = R.moe(w, x, held=tuple(range(16)), top_k=CFG.top_k)
    np.testing.assert_allclose(whole, want, atol=2e-6)
    assert float(jnp.abs(parts[0]).max()) > 0 and not np.allclose(parts[0], whole)


def _dense_held(x, w_held, ws):
    """``sum_e w_held[e, t] gated_ffn(x[t]; e)``: every expert over every row."""
    return sum(w_held[e][:, None] * gated_ffn(x, *(m[e] for m in ws)) for e in range(w_held.shape[0]))


@pytest.mark.parametrize("tile", [8, 16, 64])
def test_the_gated_experts_backward_pass_is_autodiffs_of_the_dense_form(tile):
    """``_held_experts`` given three matrices: its hand-written backward
    (data-dependent chunk and slab counts, part-filled last tiles, an expert
    nobody chose) against ``jax.grad`` of a dense loop, for the rows, the
    routing weights and the three stacks. float32: 2e-5 is the order of the
    sums."""
    T, n = 64, 4
    x = _stream(rows=T)
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    ws = tuple(jax.random.normal(k, s) * s[1] ** -0.5
               for k, s in zip(keys, [(n, 32, 24), (n, 32, 24), (n, 24, 32)]))
    member = jax.random.uniform(keys[3], (n, T)) < jnp.asarray([0.5, 0.2, 0.0, 0.9])[:, None]
    w_held = jnp.where(member, jax.random.uniform(keys[4], (n, T)), 0.0)
    order = jnp.argsort(~member, axis=-1, stable=True).astype(jnp.int32)
    counts = jnp.sum(member, axis=-1, dtype=jnp.int32)
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    got_out = _held_experts(x, w_held, order, counts, ws, tile)
    np.testing.assert_allclose(got_out, _dense_held(x, w_held, ws), atol=2e-5)
    got = jax.grad(lambda x, w, ws: jnp.sum(_held_experts(x, w, order, counts, ws, tile) * g), (0, 1, 2))(x, w_held, ws)
    want = jax.grad(lambda x, w, ws: jnp.sum(_dense_held(x, w, ws) * g), (0, 1, 2))(x, w_held, ws)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(np.where(member, got[1], 0.0), np.where(member, want[1], 0.0), atol=2e-5)
    for a, b in zip(got[2], want[2]):
        np.testing.assert_allclose(a, b, atol=2e-5)
    assert float(jnp.abs(got[2][0][2]).max()) == 0.0  # the expert nobody chose takes no gradient


def test_routing_stats_count_what_the_routers_chose():
    params, batch = _params(CFG), _batch()
    masked, _ = _noise()
    stats = M.routing_stats(params, batch["tokens"], masked, CFG)
    chosen = np.asarray(M.chosen_experts(params, batch["tokens"], masked, CFG))
    assert chosen.shape == (CFG.n_layers, B * 2 * S, CFG.top_k)
    counts = np.stack([[np.sum(layer == e) for e in CFG.held] for layer in chosen])
    np.testing.assert_array_equal(stats["held_counts"], counts)
    np.testing.assert_allclose(stats["held_share"], counts.sum(1) / chosen[0].size, rtol=1e-6)
    np.testing.assert_allclose(stats["max_over_mean"], counts.max(1) / counts.mean(1), rtol=1e-6)
    # the list's row tiles: an expert's own rows rounded up to tiles, one tile for an expert of no rows
    tile = M.expert_tile(CFG, B * 2 * S)
    assert tile == min(128, B * 2 * S)
    trips = np.sum(np.maximum(-(-counts // tile), 1), axis=1)
    np.testing.assert_array_equal(stats["trips"], trips)
    np.testing.assert_allclose(stats["tile_fill"], counts.sum(1) / (trips * tile), rtol=1e-6)
    assert (trips >= len(CFG.held)).all()
    want = R.chosen_experts(params, batch["tokens"], masked=masked, **_ref_args(CFG))
    for got_layer, want_layer in zip(chosen, want):
        np.testing.assert_array_equal(np.sort(got_layer, -1), np.sort(np.asarray(want_layer).reshape(got_layer.shape), -1))


# ----------------------------------------------------------------- the stack


@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 1.5e-2, 0.3)])
def test_the_stack_agrees_with_the_reference(dtype, median_tol, max_tol):
    """Logits at the noised positions, relative to the reference's largest.
    float32: reassociation only. bfloat16 operands: 2^-9 a rounding over
    some twenty matmuls at the median; the maximum is a position whose
    chosen set flipped at a near-tie and is held only loosely."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params, tokens = _params(cfg), _batch()["tokens"]
    masked, _ = _noise()
    got = M.forward(params, tokens, masked, cfg)
    want = R.forward(params, tokens, masked=masked, **_ref_args(cfg))
    assert got.shape == (B, S, V) and got.dtype == jnp.float32
    err = np.max(np.abs(np.asarray(got) - np.asarray(want)), axis=-1) / np.max(np.abs(want))
    assert np.median(err) <= median_tol and err.max() <= max_tol, (np.median(err), err.max())


def test_the_noise_masks_by_block_level_and_the_noised_copy_reads_the_mask_token():
    masked, t = M.draw_noise(jax.random.PRNGKey(0), 64, 128, CFG)
    t = np.asarray(t).reshape(64, 32, 4)
    assert (t == t[..., :1]).all() and CFG.t_min <= t.min() and t.max() < 1.0  # one level a block
    assert abs(float(masked.mean()) - 0.5) < 0.03 and abs(float(t.mean()) - 0.5) < 0.03
    lo, hi = np.asarray(masked).reshape(64, 32, 4)[t[..., 0] < 0.2].mean(), np.asarray(masked).reshape(64, 32, 4)[t[..., 0] > 0.8].mean()
    assert lo < 0.2 and hi > 0.8
    # a masked position's own token does not reach its logit: only the mask token's embedding does
    params, tokens = _params(CFG), _batch()["tokens"]
    masked, _ = _noise()
    b, s = map(int, np.argwhere(np.asarray(masked))[0])
    other = tokens.at[b, s].set((tokens[b, s] + 1) % (V - 1))
    got, moved = (M.forward(params, tok, masked, CFG)[b, s] for tok in (tokens, other))
    # its clean twin is seen by later blocks only, so its own noised position reads the same
    np.testing.assert_allclose(got, moved, atol=1e-6)
    with pytest.raises(ValueError, match="do not tile"):
        M.draw_noise(jax.random.PRNGKey(0), 1, 30, CFG)


@pytest.mark.parametrize("n_layers", [1, 3])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(n_layers):
    """The program's loss under a key against the reference's under the
    noise that key draws, and ``jax.grad`` of both, float32: the scan, the
    checkpoints, the custom backward of the experts' loops and of nothing
    else (the dense attention route on the CPU). 1e-4 of a leaf's largest
    gradient is the order of float32 sums over 64 positions."""
    cfg = dataclasses.replace(CFG, n_layers=n_layers)
    params, batch = _params(cfg), _batch()
    key = jax.random.PRNGKey(11)
    masked, t = M.draw_noise(key, B, S, cfg)
    got, got_grads = jax.value_and_grad(lambda p: M.loss_fn(p, batch, cfg, key=key))(params)
    want, want_grads = jax.value_and_grad(lambda p: R.loss(p, batch["tokens"], masked, t, **_ref_args(cfg)))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        w = np.asarray(flat_want[path])
        assert np.abs(w).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-4 * np.abs(w).max(), err_msg=jax.tree_util.keystr(path))


def test_the_loss_weighs_masked_positions_by_their_blocks_level_and_no_others():
    params, batch = _params(CFG), _batch()
    key = jax.random.PRNGKey(3)
    masked, t = M.draw_noise(key, B, S, CFG)
    logits = M.forward(params, batch["tokens"], masked, CFG)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), batch["tokens"][..., None], axis=-1)[..., 0]
    want = float(jnp.sum(jnp.where(masked, ce / t, 0.0)) / (B * S))
    assert abs(float(M.loss_fn(params, batch, CFG, key=key)) - want) <= 2e-6 * want
    moved = {**batch, "targets": batch["targets"] * 0}
    assert float(M.loss_fn(params, moved, CFG, key=key)) == float(M.loss_fn(params, batch, CFG, key=key))


def test_the_published_sizes_count_to_the_cells_state():
    """The cell's cut of SDAR-30B-A3B: 6 layers, 16 of 128 experts, an
    eighth of the vocabulary; 645.6 M parameters in 15 leaves, 48 with the
    moments, the count, the step and the key; three expert stacks over
    512 MiB."""
    cfg = M.BlockDiffusionLMConfig(vocab_size=18992, n_layers=6, held=tuple(range(16)))
    shapes = jax.eval_shape(lambda k: M.init_state(k, cfg, M.make_optimizer()), jax.random.PRNGKey(0))
    params = jax.tree_util.tree_leaves(shapes["params"])
    assert sum(int(np.prod(x.shape)) for x in params) == 645_623_296 and len(params) == 15
    assert len(jax.tree_util.tree_leaves(shapes)) == 48
    assert shapes["rng"].shape == (2,) and shapes["rng"].dtype == jnp.uint32
    layer = sum(int(np.prod(x.shape[1:])) for x in jax.tree_util.tree_leaves(shapes["params"]["layers"]))
    assert layer == 18_874_368 + 262_144 + 4_352 + 75_497_472
    big = [x for x in jax.tree_util.tree_leaves(shapes) if x.size * x.dtype.itemsize > 512 << 20]
    assert len(big) == 9 and {x.shape for x in big} == {(6, 16, 2048, 768), (6, 16, 768, 2048)}
    assert cfg.layer_matmul_params == 18_874_368 + 262_144 + 3 * 2048 * 768
    assert cfg.matmul_params_per_token == 2 * 6 * 23_855_104 + 18992 * 2048 == 325_156_864
    assert M.attention_mask(cfg, 4096).live_tiles(512) == 80


def _route_loads(init, seed):
    """Held share over the even share and the fullest held expert over the
    mean, per layer, of a toy model of 32 experts, 8 held, top-4."""
    cfg = M.BlockDiffusionLMConfig(vocab_size=4096, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
                                   n_experts=32, top_k=4, expert_ff=32, held=tuple(range(8)), dtype=jnp.float32)
    key = jax.random.PRNGKey(seed)
    params = init(M.init_params(key, cfg), cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, 512), 0, 4096)
    masked, _ = M.draw_noise(jax.random.fold_in(key, 2), 1, 512, cfg)
    stats = jax.jit(lambda p, t, m: M.routing_stats(p, t, m, cfg))(params, tokens, masked)
    return np.asarray(stats["held_share"]) / 0.25, np.asarray(stats["max_over_mean"])


def _plain_init(params, cfg):
    """The init without its three levelling choices: every matrix at
    ``fan_in^-0.5``, every embedding row alike, the q-norm's scale 1."""
    layers = dict(params["layers"])
    layers["o"], layers["expert_down"] = layers["o"] / M._BRANCH_OUT_SCALE, layers["expert_down"] / M._BRANCH_OUT_SCALE
    layers["q_norm_scale"] = jnp.ones_like(layers["q_norm_scale"])
    embed = params["embed"].at[cfg.mask_id].multiply(1 / M._MASK_ROW_SCALE) * cfg.d_model**-0.5
    return {**params, "layers": layers, "embed": embed}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_init_keeps_random_routers_near_even_loads(seed):
    """What ``init_params`` says it does, at a toy size where the even load
    is 128 positions a held expert: the held experts get 0.65 to 1.3 of
    their share in every layer (sampling alone gives 0.9 to 1.1) and the
    deepest layer's fullest expert under twice the mean; without the three
    choices the deeper layers collapse onto a few experts (0 to 1.6 of the
    share, 4 to 8 times the mean: measured over four seeds)."""
    share, fullest = _route_loads(lambda p, cfg: p, seed)
    assert 0.65 <= share.min() and share.max() <= 1.3, share
    assert fullest[-1] < 2.0 and fullest.max() < 2.6, fullest
    plain_share, plain_fullest = _route_loads(_plain_init, seed)
    assert plain_fullest[-1] > 4.0 and np.abs(plain_share - 1).max() > 0.4, (plain_share, plain_fullest)


@pytest.mark.parametrize("positions,top_k,n_experts,tile", [(8192, 8, 128, 128), (16384, 8, 128, 128), (256, 4, 16, 128), (96, 6, 128, 96)])
def test_the_experts_row_tile_follows_the_positions_alone(positions, top_k, n_experts, tile):
    """128 rows, whatever the even load (512 positions an expert in
    ``sdar30b.save``, 1024 at twice its batch): the list rounds an expert's
    load up to a row tile, so a smaller tile is less padding, and 128 is the
    MXU's own."""
    cfg = dataclasses.replace(CFG, top_k=top_k, n_experts=n_experts, held=(0,))
    assert M.expert_tile(cfg, positions) == tile


def test_a_bad_share_or_head_grouping_is_refused():
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(0, 0))
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(16,))
    with pytest.raises(ValueError, match="KV heads"):
        dataclasses.replace(CFG, n_kv_heads=3)
    with pytest.raises(ValueError, match="experts' weights given"):
        softmax_topk_routed(_expert_layer(CFG), _stream(), top_k=4, held=(0, 1))


# ------------------------------------------------------------ the train step


def test_the_step_puts_what_it_compiles_on_the_telemetry_bus():
    telemetry.set_enabled(True)
    try:
        M.make_train_step(CFG, M.make_optimizer())
        gauges = telemetry.gauges()
    finally:
        telemetry.set_enabled(False)
    assert gauges["block_diffusion_lm.layers"] == 3 and gauges["block_diffusion_lm.experts_held"] == 4
    assert gauges["block_diffusion_lm.matmul_params_per_token"] == CFG.matmul_params_per_token


def test_the_named_scopes_reach_the_lowered_step():
    tx = M.make_optimizer()
    state = jax.eval_shape(lambda k: M.init_state(k, CFG, tx), jax.random.PRNGKey(0))
    text = jax.jit(M.make_train_step(CFG, tx)).lower(state, jax.eval_shape(_batch)).as_text(debug_info=True)
    for scope in ("attn_bd", "moe_route", "moe_experts", "bd_noise", "lm_head"):
        assert scope in text, scope


def test_the_train_steps_gradient_is_the_losss_and_the_key_moves_on():
    tx = M.make_optimizer(1e-2)
    state = M.init_state(jax.random.PRNGKey(0), CFG, tx)
    batch = _batch()
    new, loss = jax.jit(M.make_train_step(CFG, tx))(state, batch)
    want = M.loss_fn(state["params"], batch, CFG, key=M.noise_key(state))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert int(new["step"]) == 1 and not np.array_equal(new["rng"], state["rng"])
    np.testing.assert_array_equal(new["rng"], jax.random.split(state["rng"])[0])
    moved = [k for k, (a, b) in
             {jax.tree_util.keystr(p): (a, b) for (p, a), b in
              zip(jax.tree_util.tree_flatten_with_path(state["params"])[0], jax.tree_util.tree_leaves(new["params"]))}.items()
             if not np.array_equal(a, b)]
    assert len(moved) == 15  # every parameter leaf takes a gradient, the q- and k-norm scales among them
    # another key, other masks, another loss; the same key, the same
    other = {**state, "rng": state["rng"] + jnp.uint32(1)}
    assert float(jax.jit(M.make_train_step(CFG, tx))(other, batch)[1]) != float(loss)


@pytest.mark.parametrize("mesh_axes", [None, {"data": 2, "model": 2}])
def test_the_step_reports_a_finite_loss_and_keeps_its_layout(mesh_axes):
    mesh = make_mesh(mesh_axes, devices=jax.devices()[:4]) if mesh_axes else None
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    tx = M.make_optimizer()
    state = M.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    before = [(x.shape, x.dtype, x.sharding) for x in jax.tree_util.tree_leaves(state)]
    batch = _batch(mesh)
    step = jax.jit(M.make_train_step(cfg, tx, mesh=mesh), donate_argnums=0).lower(state, batch).compile()
    losses = []
    for _ in range(3):  # compiled once: a drifted layout would be an error, not a recompile
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and int(state["step"]) == 3
    for (path, x), (shape, dtype, sharding) in zip(jax.tree_util.tree_flatten_with_path(state)[0], before):
        name = jax.tree_util.keystr(path)
        assert (x.shape, x.dtype) == (shape, dtype), name
        if mesh is not None:
            assert x.sharding.is_equivalent_to(sharding, x.ndim), name


def test_the_sharded_loss_equals_the_one_device_loss():
    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    key = jax.random.PRNGKey(4)
    params = M.init_params(jax.random.split(jax.random.PRNGKey(0))[0], CFG)
    want = jax.jit(lambda p, b: M.loss_fn(p, b, CFG, key=key))(params, _batch())
    sharded = M.init_state(jax.random.PRNGKey(0), CFG, M.make_optimizer(), mesh=mesh)["params"]
    assert sharded["embed"].sharding.spec == sharded["head"].sharding.spec == P("model", None)
    got = jax.jit(lambda p, b: M.loss_fn(p, b, CFG, key=key, mesh=mesh))(sharded, _batch(mesh))
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


@functools.lru_cache(maxsize=None)
def _jitted_step(cfg):
    return jax.jit(M.make_train_step(cfg, M.make_optimizer()), donate_argnums=0)


def _train(cfg, state, steps, first=1):
    step = _jitted_step(cfg)  # compiled once for the cases below
    loss = None
    for n in range(first, first + steps):
        state, loss = step(state, _batch(seed=n))
    return state, float(loss)


@pytest.mark.parametrize("async_save", [True, False])
def test_the_train_state_goes_through_the_checkpoint_manager_bit_exact(tmp_path, async_save):
    """Save, restore into a destination from another seed, leaf for leaf
    equal (48 leaves, the noise key among them), and the next step's loss
    equal to the uninterrupted one: the resumed job draws the same masks."""
    cfg, tx = dataclasses.replace(CFG, dtype=jnp.bfloat16), M.make_optimizer()
    state, _ = _train(cfg, M.init_state(jax.random.PRNGKey(0), cfg, tx), 2)
    saved = jax.tree_util.tree_map(np.asarray, state)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1, keep_last=1, async_save=async_save)
    assert mgr.save(2, {"train": StateDict(**state)})
    mgr.wait()
    _, want_loss = _train(cfg, state, 1, first=3)

    dst = StateDict(**M.init_state(jax.random.PRNGKey(1), cfg, tx))
    assert not np.array_equal(dst["rng"], saved["rng"])
    assert mgr.restore({"train": dst}) == 2
    restored = dict(dst)
    leaves = jax.tree_util.tree_flatten_with_path(saved)[0]
    assert len(leaves) == 48
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=jax.tree_util.keystr(path))
    _, got_loss = _train(cfg, restored, 1, first=3)
    assert got_loss == want_loss


def test_a_resumed_job_that_lost_the_noise_key_does_not_continue_the_run(tmp_path):
    """Everything restored but ``rng``: the parameters and the step are the
    saved ones and the next step's loss is another, because its masks are."""
    cfg, tx = dataclasses.replace(CFG, dtype=jnp.bfloat16), M.make_optimizer()
    state, _ = _train(cfg, M.init_state(jax.random.PRNGKey(0), cfg, tx), 2)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1, keep_last=1, async_save=False)
    assert mgr.save(2, {"train": StateDict(**state)})
    _, want_loss = _train(cfg, state, 1, first=3)
    fresh = M.init_state(jax.random.PRNGKey(1), cfg, tx)
    dst = StateDict(**fresh)
    assert mgr.restore({"train": dst}) == 2
    lost = {**dict(dst), "rng": fresh["rng"]}
    assert int(lost["step"]) == 2
    _, got_loss = _train(cfg, lost, 1, first=3)
    assert np.isfinite(got_loss) and got_loss != want_loss
