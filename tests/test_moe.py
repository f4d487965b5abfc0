"""MoE FFN (expert parallelism) correctness.

Oracle for routing: a per-token numpy reimplementation of top-2
capacity-bounded dispatch. Model-level: the MoE transformer trains,
checkpoints with expert weights sharded over the mesh, restores, resumes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu.ops import moe, pallas_grouped
from torchsnapshot_tpu.ops.moe import init_moe_params, moe_ffn
from torchsnapshot_tpu.ops.pallas_add_rows import add_rows


def reference_moe_no_drops(params, x):
    """Per-token numpy top-2 MoE assuming ample capacity (no drops): each
    token's output is g1*FFN_e1(x) + g2*FFN_e2(x) with renormalized gates."""
    x = np.asarray(jnp.asarray(x, jnp.float32))
    router = np.asarray(params["router"], np.float32)
    w_in = np.asarray(params["w_in"], np.float32)
    w_out = np.asarray(params["w_out"], np.float32)

    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)

    out = np.zeros_like(x)
    gelu = lambda z: np.asarray(jax.nn.gelu(jnp.asarray(z)))
    for t in range(x.shape[0]):
        e1 = int(np.argmax(probs[t]))
        p = probs[t].copy()
        p[e1] = -1
        e2 = int(np.argmax(p))
        g1, g2 = probs[t, e1], probs[t, e2]
        s = g1 + g2 + 1e-9
        out[t] = (g1 / s) * (gelu(x[t] @ w_in[e1]) @ w_out[e1]) + (g2 / s) * (
            gelu(x[t] @ w_in[e2]) @ w_out[e2]
        )
    return out


@pytest.mark.slow
def test_moe_shapes_and_finiteness() -> None:
    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, aux = moe_ffn(params, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0


@pytest.mark.slow
def test_moe_matches_reference_routing() -> None:
    params = init_moe_params(jax.random.PRNGKey(2), 8, 16, 2)
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 8))
    y, _ = moe_ffn(params, x, capacity_factor=8.0)  # ample capacity, no drops
    ref = reference_moe_no_drops(params, x)
    np.testing.assert_allclose(np.asarray(y), ref, atol=2e-3)


def test_moe_capacity_drops_bounded() -> None:
    """With tiny capacity most tokens drop; outputs must stay finite and
    dropped tokens produce exactly zero."""
    params = init_moe_params(jax.random.PRNGKey(4), 8, 16, 2)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 8))
    y, _ = moe_ffn(params, x, capacity_factor=0.05)
    y = np.asarray(y)
    assert np.isfinite(y).all()
    zero_rows = (np.abs(y).sum(-1) == 0).sum()
    assert zero_rows > 0  # some tokens overflowed and were dropped


@pytest.mark.parametrize("dispatch", ["einsum", "sort"])
@pytest.mark.slow
def test_moe_gradients_flow(dispatch: str) -> None:
    params = init_moe_params(jax.random.PRNGKey(6), 8, 16, 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 8))

    def loss(params):
        y, aux = moe_ffn(params, x, dispatch=dispatch)
        return jnp.sum(y**2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(grads):
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
        assert np.abs(arr).sum() > 0  # every param receives gradient


@pytest.mark.parametrize("capacity_factor", [8.0, 1.25, 0.25])
def test_moe_sort_dispatch_matches_einsum(capacity_factor: float) -> None:
    """The two dispatch strategies must route identically — including which
    tokens drop under tight capacity (same slot-major priority order)."""
    params = init_moe_params(jax.random.PRNGKey(8), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(9), (96, 16))
    y_e, aux_e = moe_ffn(params, x, capacity_factor=capacity_factor, dispatch="einsum")
    y_s, aux_s = moe_ffn(params, x, capacity_factor=capacity_factor, dispatch="sort")
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e), atol=1e-5)
    np.testing.assert_allclose(float(aux_s), float(aux_e), atol=1e-6)


@pytest.mark.slow
def test_moe_sort_dispatch_gradients_match_einsum() -> None:
    params = init_moe_params(jax.random.PRNGKey(10), 8, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(11), (32, 8))

    def loss(params, dispatch):
        y, aux = moe_ffn(params, x, dispatch=dispatch)
        return jnp.sum(y**2) + 0.01 * aux

    g_e = jax.grad(lambda p: loss(p, "einsum"))(params)
    g_s = jax.grad(lambda p: loss(p, "sort"))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_e), jax.tree_util.tree_leaves(g_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_moe_sharded_all_to_all_matches_unsharded() -> None:
    """Explicit-EP (shard_map + lax.all_to_all) output matches the GSPMD
    single-call path when capacity is ample (per-device vs global capacity
    accounting only differs when tokens drop)."""
    from torchsnapshot_tpu.ops import moe_ffn_sharded

    n_dev = 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(n_dev), ("model",))
    params = init_moe_params(jax.random.PRNGKey(12), 16, 32, 8)
    x = jax.random.normal(jax.random.PRNGKey(13), (64, 16))
    x_sharded = jax.device_put(x, NamedSharding(mesh, P("model", None)))
    params_sharded = jax.device_put(
        params,
        {
            "router": NamedSharding(mesh, P(None, None)),
            "w_in": NamedSharding(mesh, P("model", None, None)),
            "w_out": NamedSharding(mesh, P("model", None, None)),
        },
    )
    y, aux = jax.jit(
        lambda p, x: moe_ffn_sharded(p, x, mesh, capacity_factor=8.0)
    )(params_sharded, x_sharded)
    y_ref, aux_ref = moe_ffn(params, x, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), atol=1e-6)


def test_moe_sharded_gradients_flow() -> None:
    from torchsnapshot_tpu.ops import moe_ffn_sharded

    n_dev = 2
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(n_dev), ("model",))
    params = init_moe_params(jax.random.PRNGKey(14), 8, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(15), (16, 8))

    def loss(params):
        y, aux = moe_ffn_sharded(params, x, mesh)
        return jnp.sum(y**2) + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(params)
    for leaf in jax.tree_util.tree_leaves(grads):
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
        assert np.abs(arr).sum() > 0


@pytest.mark.slow
def test_moe_transformer_trains_and_checkpoints(tmp_path) -> None:
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.models import transformer as T

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("data", "seq", "model"))
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=16, dtype=jnp.float32, attn_impl="ring", n_experts=2,
    )
    tx = T.make_optimizer()
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    # expert-stacked weights are sharded over 'model'
    w_in_sharding = state["params"]["layers"]["moe_w_in"].sharding
    assert "model" in w_in_sharding.spec

    step = jax.jit(T.make_train_step(cfg, tx, mesh=mesh))
    batch = {
        "tokens": jnp.zeros((4, 16), jnp.int32),
        "targets": jnp.zeros((4, 16), jnp.int32),
    }
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", "seq")))
    state, loss = step(state, batch)
    assert np.isfinite(float(loss))

    Snapshot.take(str(tmp_path / "s"), {"train": StateDict(state=state)})
    dst = {"train": StateDict(state=T.init_state(jax.random.PRNGKey(9), cfg, tx, mesh=mesh))}
    Snapshot(str(tmp_path / "s")).restore(dst)
    for a, b in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(dst["train"]["state"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state2, loss2 = step(dst["train"]["state"], batch)
    assert int(state2["step"]) == 2 and np.isfinite(float(loss2))


def test_dense_transformer_unchanged() -> None:
    """n_experts=0 keeps the original dense-FFN param tree."""
    from torchsnapshot_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=16, dtype=jnp.float32,
    )
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    assert "ff_in" in params["layers"] and "moe_router" not in params["layers"]
    logits = T.forward(params, jnp.zeros((2, 16), jnp.int32), cfg)
    assert logits.shape == (2, 16, 64)


# ----------------------------------------- the held experts' row-accumulate kernel


# A tile of 128 rows is two groups of the kernel's 64, one of 16 is a group
# of 16: no row, one, part of a group, a group and part of the next, all.
@pytest.mark.parametrize(
    "D,tile,n_own",
    [(128, 128, n) for n in (0, 1, 13, 64, 77, 128)] + [(256, 16, n) for n in (0, 1, 5, 16)] + [(96, 24, 7)],
)
def test_add_rows_is_xlas_scatter_add_of_the_own_rows(D, tile, n_own):
    """The kernel in interpret mode, the code the chip compiles: exactly
    ``acc.at[idx[:n_own], 0].add(rows[:n_own])``, the rows past ``n_own``
    untouched, and again on its own result inside a ``fori_loop`` with the
    tile's rows shifted by one, so that the second call adds to rows the
    first has written (the loops' use of it)."""
    T = 4 * tile
    k_acc, k_idx, k_rows = jax.random.split(jax.random.PRNGKey(0), 3)
    acc = jax.random.normal(k_acc, (T, 1, D), jnp.float32)
    idx = jax.random.permutation(k_idx, T)[:tile].astype(jnp.int32)
    idxs, rows = jnp.stack([idx, jnp.roll(idx, 1)]), jax.random.normal(k_rows, (2, tile, D), jnp.float32)
    n = jnp.int32(n_own)
    want = acc.at[idxs[0, :n_own], 0].add(rows[0, :n_own])
    np.testing.assert_array_equal(jax.jit(add_rows)(acc, idxs[0], rows[0], n), want)
    twice = jax.jit(lambda a: jax.lax.fori_loop(0, 2, lambda i, a: add_rows(a, idxs[i], rows[i], n), a))(acc)
    np.testing.assert_array_equal(twice, want.at[idxs[1, :n_own], 0].add(rows[1, :n_own]))


@pytest.mark.parametrize("n_own", [(5, 0, 16), (16, 16, 16), (0, 0, 0), (1, 9, 3)])
def test_add_rows_adds_a_tile_after_the_other(n_own):
    """Three tiles of 16 rows in one call, each with its own count of own
    rows, and every tile holding the same tokens as the tile before it in
    another order (a token that several experts hold): exactly what three
    scatter-adds, one a tile, leave."""
    T, D, tile = 64, 128, 16
    k_acc, k_idx, k_rows = jax.random.split(jax.random.PRNGKey(1), 3)
    acc = jax.random.normal(k_acc, (T, 1, D), jnp.float32)
    first = jax.random.permutation(k_idx, T)[:tile].astype(jnp.int32)
    idx = jnp.concatenate([first, first[::-1], jnp.roll(first, 3)])
    rows = jax.random.normal(k_rows, (3 * tile, D), jnp.float32)
    want = acc
    for t, n in enumerate(n_own):
        want = want.at[idx[t * tile:t * tile + n], 0].add(rows[t * tile:t * tile + n])
    np.testing.assert_array_equal(jax.jit(add_rows)(acc, idx, rows, jnp.asarray(n_own, jnp.int32)), want)


# ----------------------------------------- the grouped products' kernels


def _grouped_case(groups, live, tile=16, K=32, N=256, n=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (len(groups) * tile, K))
    return lhs, jax.random.normal(keys[1], (n, K, N)), jnp.asarray(groups, jnp.int32), jnp.int32(live), keys[2]


# Column blocks of 128 (two of them: a run's block is copied ahead across the
# change of column block too) and of all 256; one group, a group a tile, runs
# of several tiles, a group that comes back, dead tiles behind the live ones.
@pytest.mark.parametrize("block_bytes", [128 * 32 * 4, 1 << 20])
@pytest.mark.parametrize("transpose_rhs,scaled", [(False, False), (True, False), (False, True)])
@pytest.mark.parametrize("groups,live", [((1, 1, 1, 1), 4), ((0, 1, 2, 0), 4), ((0, 0, 2, 2, 2, 1), 6), ((2, 2, 0, 1, 1, 1), 3), ((1, 0, 0, 0), 1)])
def test_grouped_matmul_is_each_live_tile_against_its_groups_matrix(monkeypatch, block_bytes, transpose_rhs, scaled, groups, live):
    monkeypatch.setattr(pallas_grouped, "_BLOCK_BYTES", block_bytes)
    jax.clear_caches()  # the kernels are jitted: a trace at the other block size would answer
    lhs, rhs, group, n_live, key = _grouped_case(groups, live)
    scale = jax.random.uniform(key, (lhs.shape[0],)) if scaled else None
    got = pallas_grouped.grouped_matmul(
        lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs, group, n_live, transpose_rhs=transpose_rhs, row_scale=scale
    )
    tile = lhs.shape[0] // len(groups)
    for t in range(live):
        want = jnp.matmul(lhs[t * tile:(t + 1) * tile], rhs[groups[t]], precision="highest") * (1.0 if scale is None else scale[t * tile:(t + 1) * tile, None])
        np.testing.assert_allclose(got[t * tile:(t + 1) * tile], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_bytes", [128 * 32 * 4, 1 << 20])
@pytest.mark.parametrize("groups,live", [((1, 1, 1, 1), 4), ((0, 1, 2, 2), 4), ((0, 0, 2, 2, 2, 1), 6), ((2, 2, 0, 1, 1, 1), 3)])
def test_grouped_matmul_t_writes_a_groups_sum_once_and_leaves_the_other_groups(monkeypatch, block_bytes, groups, live):
    monkeypatch.setattr(pallas_grouped, "_BLOCK_BYTES", block_bytes // 2)  # the transposed product takes blocks of twice it
    jax.clear_caches()
    lhs, _, group, n_live, key = _grouped_case(groups, live)
    rhs = jax.random.normal(key, (lhs.shape[0], 256))
    stack = jnp.full((3, lhs.shape[1], 256), 7.0)
    got = pallas_grouped.grouped_matmul_t(lhs, rhs, group, n_live, stack)
    tile = lhs.shape[0] // len(groups)
    for g in range(3):
        rows = np.concatenate([np.arange(t * tile, (t + 1) * tile) for t in range(live) if groups[t] == g] or [np.zeros(0, int)])
        want = jnp.matmul(lhs[rows].T, rhs[rows], precision="highest") if len(rows) else stack[g]
        np.testing.assert_allclose(got[g], want, rtol=1e-5, atol=1e-5)


# ----------------------------------------- the held experts' list and its grouped products


def _held_case(n_matrices, T, D, F, member, seed=3):
    """Rows, weights and matrices for ``_held_experts`` with ``member (n,
    T)``, and the cotangent."""
    n = member.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (T, D))
    ws = tuple(jax.random.normal(k, (n,) + s) * s[0] ** -0.5 for k, s in zip(keys[1:4], [(D, F)] * (n_matrices - 1) + [(F, D)]))
    w_held = jnp.where(member, jax.random.uniform(keys[4], (n, T)) + 0.5, 0.0)
    order = jnp.argsort(~member, axis=-1, stable=True).astype(jnp.int32)
    counts = jnp.sum(member, axis=-1, dtype=jnp.int32)
    return x, w_held, order, counts, ws, jax.random.normal(keys[5], (T, D))


def _value_and_grads(f, x, w_held, ws, g):
    return jax.jit(lambda x, w, ws: (f(x, w, ws), jax.grad(lambda *a: jnp.sum(f(*a) * g), (0, 1, 2))(x, w, ws)))(x, w_held, ws)


def _members(case, n, T, key):
    if case == "mixed":  # nobody, a tile exactly, a tile and a row, part of a tile
        counts = [0, 16, 17, 5]
    elif case == "everyone":  # one expert holds every token, the others some
        counts = [T, 3, 0, 40]
    elif case == "worst":  # every token on every held expert at once: the list at its longest, n * T rows
        counts = [T] * n
    else:
        raise AssertionError(case)
    perm = jnp.stack([jax.random.permutation(k, T) for k in jax.random.split(key, n)])
    return jnp.zeros((n, T), bool).at[jnp.arange(n)[:, None], perm].set(jnp.arange(T)[None] < jnp.asarray(counts)[:, None])


# The row tile is 16 and a chunk two of them, so the lists below take one
# chunk to sixteen, and the backward pass one slab to four. T = 100 is no
# multiple of the row tile; F = 116 beside D = 128 is a width of 1856's kind
# beside 2688: no multiple of 128 where the other is, so the matrices into
# the hidden width go through their transposes (``_lane_aligned``).
@pytest.mark.parametrize("n_matrices", [2, 3])
@pytest.mark.parametrize(
    "case,T,D,F",
    [("mixed", 64, 32, 24), ("everyone", 64, 32, 24), ("worst", 64, 32, 24), ("everyone", 100, 32, 24), ("mixed", 64, 128, 116)],
)
def test_held_experts_are_the_dense_sum_over_the_experts(monkeypatch, n_matrices, case, T, D, F):
    """``_held_experts``, value and every gradient (the rows, the routing
    weights, each stack of matrices), against every expert over every row
    in float32: no row dropped at any load, none counted twice, and an
    expert nobody chose takes a zero gradient. The routing weights'
    gradient is held where an expert's own rows are: ``w_held`` is 0 by
    construction elsewhere, and what flows there is dropped by the routing's
    ``where``."""
    monkeypatch.setattr(moe, "_CHUNK_TILES", 2)
    n, tile = 4, 16
    member = _members(case, n, T, jax.random.PRNGKey(5))
    x, w_held, order, counts, ws, g = _held_case(n_matrices, T, D, F, member)
    ffn = {2: moe.relu2_ffn, 3: moe.gated_ffn}[n_matrices]
    dense = lambda x, w, ws: sum(w[e][:, None] * ffn(x, *(m[e] for m in ws)) for e in range(n))  # noqa: E731
    got = _value_and_grads(lambda x, w, ws: moe._held_experts(x, w, order, counts, ws, tile), x, w_held, ws, g)
    want_value, (want_dx, want_dw, want_dws) = _value_and_grads(dense, x, w_held, ws, g)
    want = (want_value, (want_dx, jnp.where(member, want_dw, 0.0), want_dws))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())))
    value, (dx, dw, dws) = got
    assert float(jnp.abs(value).max()) > 0 and float(jnp.abs(dx).max()) > 0 and float(jnp.abs(dw).max()) > 0
    for e in range(n):
        assert all((float(jnp.abs(d[e]).max()) > 0) == (int(counts[e]) > 0) for d in dws)


@pytest.mark.parametrize("n_matrices", [2, 3])
def test_no_row_past_the_lists_end_reaches_the_value_or_a_gradient(monkeypatch, n_matrices):
    """The products leave the rows of the tiles past the list's last
    uninitialised, and the slabs' buffers and the gradient stacks start so.
    With NaN wherever the chip would leave what it found, value and
    gradients are finite and the same."""
    monkeypatch.setattr(moe, "_CHUNK_TILES", 4)
    n, T, D, F, tile = 4, 64, 32, 24, 16
    member = _members("mixed", n, T, jax.random.PRNGKey(5))  # 5 tiles: the second chunk holds one of four
    x, w_held, order, counts, ws, g = _held_case(n_matrices, T, D, F, member)
    f = lambda x, w, ws: moe._held_experts(x, w, order, counts, ws, tile)  # noqa: E731
    want = _value_and_grads(f, x, w_held, ws, g)
    product = moe.grouped_matmul

    def poisoned(lhs, rhs, tile_group, n_live, **kwargs):
        dead = jnp.arange(lhs.shape[0]) // (lhs.shape[0] // tile_group.shape[0]) >= n_live
        return jnp.where(dead[:, None], jnp.nan, product(lhs, rhs, tile_group, n_live, **kwargs))

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    monkeypatch.setattr(moe, "uninitialised", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    got = _value_and_grads(f, x, w_held, ws, g)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_array_equal(a, b)
