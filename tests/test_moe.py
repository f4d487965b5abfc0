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

from torchsnapshot_tpu.ops import moe
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


def _xla_scatter(acc, idx, rows, n_own):
    """How the parent added a tile: XLA's scatter-add of every row of it,
    those past ``n_own`` at weight 0."""
    del n_own
    return acc.at[idx, 0].add(rows, unique_indices=True)


@pytest.mark.parametrize("n_matrices", [2, 3])
def test_held_experts_with_the_kernel_equal_the_parents_scatter(monkeypatch, n_matrices):
    """``_held_experts``, value and every gradient, against the same loops
    with XLA's scatter in the kernel's place: equal, not close (the same
    float32 additions in the same order of trips). An expert of no rows,
    one of exactly a tile, one of a tile and a row, one of part of a tile.
    The routing weights are powers of two, so a row's product with its
    weight is exact: the CPU's compiler contracts that product and the
    interpreted kernel's addition into one fused multiply-add, which rounds
    once where XLA's scatter, and the chip either way, round twice."""
    T, D, F, tile = 64, 32, 24, 16
    counts = jnp.asarray([0, tile, tile + 1, 5], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (T, D))
    ws = tuple(jax.random.normal(k, (4,) + s) * s[0] ** -0.5 for k, s in zip(keys[1:], [(D, F)] * (n_matrices - 1) + [(F, D)]))
    order = jnp.stack([jax.random.permutation(k, T) for k in jax.random.split(keys[4], 4)]).astype(jnp.int32)
    member = jnp.zeros((4, T), bool).at[jnp.arange(4)[:, None], order].set(jnp.arange(T)[None] < counts[:, None])
    w_held = jnp.where(member, 2.0 ** jax.random.randint(keys[5], (4, T), -2, 2), 0.0)
    g = jax.random.normal(jax.random.PRNGKey(4), (T, D))

    def value_and_grads():
        # a fresh trace each time: add_rows is looked up when the loops are traced
        f = lambda x, w, ws: moe._held_experts(x, w, order, counts, ws, tile)  # noqa: E731
        return jax.jit(lambda x, w, ws: (f(x, w, ws), jax.grad(lambda *a: jnp.sum(f(*a) * g), (0, 1, 2))(x, w, ws)))(x, w_held, ws)

    got = value_and_grads()
    monkeypatch.setattr(moe, "add_rows", _xla_scatter)
    want = value_and_grads()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    value, (dx, dw, dws) = got
    assert float(jnp.abs(value).max()) > 0 and float(jnp.abs(dx).max()) > 0
    assert all(float(jnp.abs(d[0]).max()) == 0.0 and float(jnp.abs(d[2]).max()) > 0 for d in dws)  # nobody chose expert 0
