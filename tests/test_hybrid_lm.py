"""The hybrid LM (``models/hybrid_lm.py``: Mamba-2, sigmoid top-k experts
with a share of them held, grouped-query attention, in a published order)
against its plain reference (``benchmarks/chip/reference/hybrid_lm.py``:
float32, the recurrence step by step, a dense loop over the held experts,
whole score matrices), and through the train step and ``CheckpointManager``
as the other families go.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu.models import hybrid_lm as M
from torchsnapshot_tpu.ops.attention import causal_attention_route, dense_attention
from torchsnapshot_tpu.ops import moe
from torchsnapshot_tpu.ops.moe import relu2_ffn, sigmoid_topk_routed
from torchsnapshot_tpu.ops.ssm import _within_chunk_sum, mamba2_chunked
from model_kit_cases import (  # noqa: F401  (the cases this file runs)
    Model,
    test_routing_stats_count_what_the_routers_chose,
    test_the_named_scopes_reach_the_lowered_step,
    test_the_sharded_loss_equals_the_one_device_loss,
    test_the_step_puts_what_it_compiles_on_the_telemetry_bus,
    test_the_step_reports_a_finite_loss_and_keeps_its_layout,
    test_the_train_state_goes_through_the_checkpoint_manager_bit_exact,
    test_the_train_steps_gradient_is_the_losss,
)

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "chip", "reference", "hybrid_lm.py")
_spec = importlib.util.spec_from_file_location("hybrid_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, B, S = 96, 2, 32
# The toy size keeps every ratio of the published config that a layer's code
# depends on: several heads a group, several query heads a KV head, more
# experts than are held, top_k below the count held, chunks shorter than S.
CFG = M.HybridLMConfig(
    vocab_size=V, d_model=32, n_layers=9, mamba_heads=8, mamba_head_dim=4, ssm_groups=2, ssm_state=8,
    chunk=8, n_heads=4, n_kv_heads=2, head_dim=8, n_experts=16, top_k=6, expert_ff=24, shared_ff=48,
    held=(0, 1, 2, 3), dtype=jnp.float32,
)


def _ref_args(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads, "mamba_heads": cfg.mamba_heads,
            "ssm_groups": cfg.ssm_groups, "ssm_state": cfg.ssm_state, "top_k": cfg.top_k,
            "routed_scale": cfg.routed_scale, "held": cfg.held, "norm_eps": cfg.norm_eps}


def _params(cfg, seed=0):
    """Seeded weights with every scale and the skip term off their initial
    1, so that one applied in the wrong place shows."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name or name.endswith("['D']"):
            return x + 0.2 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(jitter, params)


def _batch(mesh=None, seed=7, batch=B, seq=S):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, V, jnp.int32)
    out = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        out = jax.device_put(out, NamedSharding(mesh, P("data", None)))
    return out


def _stream(seed=3, batch=B, seq=S, width=CFG.d_model):
    """A normed residual stream, as a mixer receives it."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, seq, width), jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _layer(kind, cfg=CFG, seed=0):
    index = cfg.kinds.index(kind)
    return _params(cfg, seed)["layers"][cfg.layer_names[index]]


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# ------------------------------------------------------- each mixer alone


# float32 compute differs from the reference by the order of additions
# only (chunks against steps, sorted rows against a masked loop, blocks
# against whole score matrices): 1e-6 of the output's largest entry read,
# held to 2e-5. A bfloat16 pass reads 1e-2.
@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_each_mixer_alone_agrees_with_the_reference(kind):
    w, a = _layer(kind), _stream()
    attend = causal_attention_route("auto", 512, CFG.n_heads, None, B, S)[1]
    program = {"M": lambda w, a: M.mamba_mixer(w, a, CFG), "E": lambda w, a: M.moe_mixer(w, a, CFG)[0],
               "*": lambda w, a: M.attention_mixer(w, a, CFG, attend)}[kind]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(program)(w, a)
        want = jax.jit(lambda w, a: R.MIXERS[M.KINDS[kind]](w, a, **_ref_args(CFG)))(w, a)
    assert got.shape == want.shape == a.shape and got.dtype == jnp.float32
    assert _rel(got, want) <= 2e-5


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_each_mixers_gradient_agrees_with_the_reference(kind):
    """Of a scalar of the output, with respect to the input and every leaf
    the mixer reads; the selection bias takes none on either side."""
    w, a = _layer(kind), _stream()
    attend = causal_attention_route("auto", 512, CFG.n_heads, None, B, S)[1]
    probe = jax.random.normal(jax.random.PRNGKey(9), a.shape)
    program = {"M": lambda w, a: M.mamba_mixer(w, a, CFG), "E": lambda w, a: M.moe_mixer(w, a, CFG)[0],
               "*": lambda w, a: M.attention_mixer(w, a, CFG, attend)}[kind]
    reference = lambda w, a: R.MIXERS[M.KINDS[kind]](w, a, **_ref_args(CFG))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda w, a: jnp.sum(program(w, a) * probe), argnums=(0, 1)))(w, a)
        want = jax.jit(jax.grad(lambda w, a: jnp.sum(reference(w, a) * probe), argnums=(0, 1)))(w, a)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name or name.endswith("['norm_scale']"):  # no gradient; not read by the mixer
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(r))), name
            continue
        assert float(jnp.max(jnp.abs(r))) > 0, name
        assert _rel(g, r) <= 5e-5, (name, _rel(g, r))


# ------------------------------------------------- the chunked recurrence


def _recurrence(u, delta, a, b, c):
    """The definition, one position at a time, in float64 on the host."""
    u, delta, a, b, c = (np.asarray(t, np.float64) for t in (u, delta, a, b, c))
    Bn, Sn, H, Pd = u.shape
    G, N = b.shape[2], b.shape[3]
    h = np.zeros((Bn, H, Pd, N))
    y = np.zeros((Bn, Sn, H, Pd))
    for t in range(Sn):
        bt, ct = np.repeat(b[:, t], H // G, axis=1), np.repeat(c[:, t], H // G, axis=1)  # (B, H, N)
        h = np.exp(delta[:, t] * a)[..., None, None] * h + (delta[:, t, :, None] * u[:, t])[..., None] * bt[:, :, None]
        y[:, t] = np.einsum("bhpn,bhn->bhp", h, ct)
    return y


def _stepwise(u, delta, a, b, c):
    """The definition as a ``lax.scan`` over positions, for autodiff."""
    H, G = u.shape[2], b.shape[2]

    def step(h, xs):
        d, u_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, H // G, axis=1), jnp.repeat(c_t, H // G, axis=1)
        h = jnp.exp(d * a)[..., None, None] * h + (d[..., None] * u_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (delta, u, b, c))
    h0 = jnp.zeros((u.shape[0], H, u.shape[3], b.shape[3]))
    return jnp.moveaxis(jax.lax.scan(step, h0, xs)[1], 0, 1)


def _assert_the_gradients_are_the_recurrences(args, chunk, probe):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *t: jnp.sum(mamba2_chunked(*t, chunk=chunk) * probe), argnums=range(5)))(*args)
        want = jax.jit(jax.grad(lambda *t: jnp.sum(_stepwise(*t) * probe), argnums=range(5)))(*args)
    for name, g, r in zip("u delta a b c".split(), got, want):
        assert np.isfinite(np.asarray(g)).all() and _rel(g, r) <= 5e-5, (name, _rel(g, r))


def _ssm_inputs(seq, seed=0, heads=6, groups=3, head_dim=4, state=5):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 5))
    u = jax.random.normal(next(k), (2, seq, heads, head_dim))
    delta = jax.nn.softplus(jax.random.normal(next(k), (2, seq, heads)))
    a = -jnp.exp(jax.random.uniform(next(k), (heads,), minval=0.0, maxval=2.5))  # decays down to e^-12 a step
    b = jax.random.normal(next(k), (2, seq, groups, state))
    c = jax.random.normal(next(k), (2, seq, groups, state))
    return u, delta, a, b, c


# S of one, two and five chunks: within a chunk, across one boundary, and
# states carried over several. 3e-7 read against float64, held to 1e-5.
@pytest.mark.parametrize("chunks", [1, 2, 5])
@pytest.mark.parametrize("chunk", [4, 16])
def test_the_chunked_scan_is_the_recurrence_step_by_step(chunks, chunk):
    args = _ssm_inputs(chunks * chunk, seed=chunks)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *t: mamba2_chunked(*t, chunk=chunk))(*args)
    want = _recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)) <= 1e-5


@pytest.mark.parametrize("chunks", [2, 5])
def test_the_chunked_scans_backward_pass_is_the_recurrences(chunks):
    """Gradients of a scalar of y with respect to all five inputs, against
    autodiff of the reference's ``lax.scan`` over positions."""
    chunk = 8
    args = _ssm_inputs(chunks * chunk, seed=10 + chunks)
    probe = jax.random.normal(jax.random.PRNGKey(5), args[0].shape)
    _assert_the_gradients_are_the_recurrences(args, chunk, probe)


# The layout must not lean on the cell's 8 groups x 8 heads x 64: one group,
# a head a group, a head wider than the chunk, a state wider and narrower
# than the head, each against the recurrence and its gradients (3 chunks of 8).
_GEOMETRIES = {
    "one_group": dict(heads=4, groups=1, head_dim=3, state=5),
    "a_head_a_group": dict(heads=3, groups=3, head_dim=4, state=6),
    "head_wider_than_chunk": dict(heads=4, groups=2, head_dim=16, state=3),
    "state_wider_than_head": dict(heads=2, groups=2, head_dim=2, state=16),
}


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_the_chunked_scan_is_the_recurrence_at_other_head_geometries(geometry):
    args = _ssm_inputs(24, seed=20, **_GEOMETRIES[geometry])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *t: mamba2_chunked(*t, chunk=8))(*args)
    want = _recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)) <= 1e-5


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_the_chunked_scans_backward_pass_at_other_head_geometries(geometry):
    args = _ssm_inputs(24, seed=30, **_GEOMETRIES[geometry])
    _assert_the_gradients_are_the_recurrences(args, 8, jax.random.normal(jax.random.PRNGKey(6), args[0].shape))


# A chunk of 128 steps at both ends of what delta * a takes (time_step_floor
# x A = 1 up to a step of 0.3 x A = 16). A float32 sum in any order is within
# 128 roundings of the float64 one (6e-7 of the last sum read); one pass over
# the decays in bfloat16 reads 2e-3.
@pytest.mark.parametrize("size", [1e-4, 5.0])
def test_the_within_chunk_sum_is_a_float32_sum(size):
    log_decay = -size * jax.random.uniform(jax.random.PRNGKey(1), (2, 3, 8, 128), jnp.float32, 0.5, 1.5)
    got = jax.jit(_within_chunk_sum)(log_decay)
    want = np.cumsum(np.asarray(log_decay, np.float64), axis=-1)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert np.max(np.abs(np.asarray(got, np.float64) - want)) <= 4e-6 * np.max(np.abs(want))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _is_float32_product(eqn):
    """A product of two float32 arrays: in the mixer, a sum that stands in
    for a reduction (the matrices' products have bfloat16 operands). It has
    to be at full precision whatever the ambient one."""
    if eqn.primitive.name != "dot_general" or any(v.aval.dtype != jnp.float32 for v in eqn.invars):
        return False
    assert eqn.params["precision"] is not None and set(eqn.params["precision"]) == {jax.lax.Precision.HIGHEST}
    return True


def _last_axis(shape):
    """The axis the lanes hold: the last one, trailing axes of 1 (a
    broadcast about to happen, never an array of its own) set aside."""
    shape = list(shape)
    while len(shape) > 1 and shape[-1] == 1:
        shape.pop()
    return shape[-1] if shape else 1


@pytest.mark.parametrize("backward", [False, True])
def test_the_chunked_scan_holds_its_tensors_in_the_chips_tiles(backward):
    """At the cell's head geometry (two chunks): the chip holds an array's
    last two axes in (8, 128) tiles, so an array of ``L x H`` elements or
    more whose last axis is short wastes most of every tile (``(..., 8, 8)``
    fills 64 of 1024 places), and a running sum along any other axis than
    the last walks across tiles. ``P`` = 64 comes in and goes out on the last
    axis (``u`` and ``y``); nothing shorter may. The sums over the decays are
    float32 at full precision, whatever the ambient matmul precision."""
    H, Pd, G, N, L = 64, 64, 8, 128, 128
    S = 2 * L
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, S, H, Pd), jnp.bfloat16), ((1, S, H), jnp.float32), ((H,), jnp.float32),
        ((1, S, G, N), jnp.bfloat16), ((1, S, G, N), jnp.bfloat16))]
    fn = lambda *t: mamba2_chunked(*t, chunk=L)  # noqa: E731
    if backward:
        fn = jax.grad(lambda *t: jnp.sum(mamba2_chunked(*t, chunk=L)), argnums=(0, 1, 2, 3, 4))
    running = {"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}
    seen = {"arrays": 0, "sums": 0}
    for eqn in _equations(jax.make_jaxpr(fn)(*shapes).jaxpr):
        name = eqn.primitive.name
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if int(np.prod(shape)) >= L * H:
                seen["arrays"] += 1
                assert _last_axis(shape) >= 64, (name, shape)
        if name in running:
            assert eqn.params["axis"] == len(eqn.invars[0].aval.shape) - 1, (name, eqn.params)
        if name.startswith("reduce_window"):
            assert all(w == 1 for w in eqn.params["window_dimensions"][:-1]), (name, eqn.params)
        seen["sums"] += _is_float32_product(eqn)
    assert seen["arrays"] > 20 and seen["sums"] >= 1


def test_the_chunked_scan_refuses_a_ragged_sequence():
    with pytest.raises(ValueError, match="chunk"):
        mamba2_chunked(*_ssm_inputs(12), chunk=8)


# ------------------------------------------------------------ the experts


def _routed(w, a, held, cfg=CFG):
    part = {**w, "expert_up": w["expert_up"][jnp.asarray(held)], "expert_down": w["expert_down"][jnp.asarray(held)]}
    return sigmoid_topk_routed(part, a, top_k=cfg.top_k, held=tuple(held), routed_scale=cfg.routed_scale)


def _whole_layer(seed=0):
    """An ``E`` layer with all 16 experts' weights, and a stream."""
    cfg = dataclasses.replace(CFG, held=tuple(range(16)))
    return cfg, _layer("E", cfg, seed), _stream(seed + 1)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: the routed parts of all the shares,
    plus the shared expert once, are the reference's layer with all 16
    experts held. Every share scores and chooses over all 16 and
    normalises over all six chosen, or this would not hold."""
    cfg, w, a = _whole_layer()
    with jax.default_matmul_precision("highest"):
        parts = [_routed(w, a, range(lo, lo + 4))[0] for lo in range(0, 16, 4)]
        got = sum(parts) + relu2_ffn(a, w["shared_up"], w["shared_down"])
        want = R.moe(w, a, **_ref_args(cfg))
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)
    assert _rel(got, want) <= 2e-5
    # and one share alone is the reference told the same ids
    with jax.default_matmul_precision("highest"):
        part = {**w, "expert_up": w["expert_up"][4:8], "expert_down": w["expert_down"][4:8]}
        alone = R.moe_routed(part, a, **{**_ref_args(cfg), "held": (4, 5, 6, 7)})
    assert _rel(parts[1], alone) <= 2e-5


# The selection bias decides who is chosen: +10 on six experts puts every
# token's six there, -10 keeps every token off them. No token may be
# dropped in the first case (each held expert then serves all B x S tokens,
# the buffers' whole room), and the second costs nothing and adds nothing.
@pytest.mark.parametrize("case", ["all_on_held", "none_on_held", "as_routed"])
@pytest.mark.parametrize("tile", [8, 256])  # many row tiles an expert, in several chunks; one ragged tile
def test_no_token_is_dropped_whatever_the_routing(monkeypatch, case, tile):
    monkeypatch.setattr(moe, "_ROW_TILE", tile)
    monkeypatch.setattr(moe, "_CHUNK_TILES", 4)
    cfg, w, a = _whole_layer(seed=2)
    held = (0, 1, 2, 3, 4, 5)
    push = {"all_on_held": 10.0, "none_on_held": -10.0, "as_routed": 0.0}[case]
    w = {**w, "router_bias": w["router_bias"].at[jnp.asarray(held)].add(push)}
    with jax.default_matmul_precision("highest"):
        got, ids = _routed(w, a, held, cfg)
        part = {**w, "expert_up": w["expert_up"][:6], "expert_down": w["expert_down"][:6]}
        want = R.moe_routed(part, a, **{**_ref_args(cfg), "held": held})
    on_held = np.isin(np.asarray(ids), held).sum(axis=-1)
    assert ids.shape == (B * S, cfg.top_k)
    if case == "all_on_held":
        assert (on_held == 6).all()
        # all six weights arrive: they sum to the scaling factor for every token
        with jax.default_matmul_precision("highest"):
            unit = {**part, "expert_up": jnp.ones_like(part["expert_up"]), "expert_down": jnp.ones_like(part["expert_down"])}
            one = _routed({**w, **unit}, jnp.abs(a), held, cfg)[0]
        per_token = relu2_ffn(jnp.abs(a), unit["expert_up"][0], unit["expert_down"][0])
        np.testing.assert_allclose(np.asarray(one), cfg.routed_scale * np.asarray(per_token), rtol=1e-5)
    elif case == "none_on_held":
        assert (on_held == 0).all() and float(jnp.max(jnp.abs(got))) == 0.0
    else:
        assert 0 < on_held.mean() < 6
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(float(jnp.max(jnp.abs(want))), 1.0)


def test_routing_weights_come_from_the_scores_not_from_the_biased_scores():
    cfg, w, a = _whole_layer(seed=4)
    from torchsnapshot_tpu.ops.moe import sigmoid_topk_route

    x2 = a.reshape(-1, a.shape[-1])
    ids, weights = sigmoid_topk_route(x2, w["router"], w["router_bias"], cfg.top_k, cfg.routed_scale)
    s = np.asarray(jax.nn.sigmoid(jnp.matmul(x2, w["router"], precision="highest")), np.float64)
    biased = s + np.asarray(w["router_bias"], np.float64)
    want_ids = np.argsort(-biased, axis=-1)[:, : cfg.top_k]
    assert (np.sort(np.asarray(ids), axis=-1) == np.sort(want_ids, axis=-1)).all()
    chosen = np.take_along_axis(s, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(np.asarray(weights), cfg.routed_scale * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), cfg.routed_scale, rtol=1e-5)
    assert (np.sort(want_ids, -1) != np.sort(np.argsort(-s, axis=-1)[:, : cfg.top_k], -1)).any()  # the bias matters here


# -------------------------------------------------------------- attention


@pytest.mark.parametrize("seq,route", [(32, "dense"), (1024, "blockwise")])
def test_grouped_query_attention_repeats_a_kv_head_over_its_group(seq, route):
    """Through ``causal_attention_route``: k and v with 2 heads for 4 query
    heads give what the same route gives for the repeated heads, and
    query heads 0, 1 read KV head 0, heads 2, 3 read KV head 1."""
    name, attend = causal_attention_route("auto", 512, 4, None, 1, seq)
    assert name == route
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, seq, 4, 8))
    k, v = jax.random.normal(kk, (1, seq, 2, 8)), jax.random.normal(kv, (1, seq, 2, 8))
    got = jax.jit(attend)(q, k, v)
    want = dense_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True)
    assert got.shape == q.shape and _rel(got, want) <= 2e-5
    # the gradient of a KV head is the sum over its group's query heads
    dk = jax.jit(jax.grad(lambda k: jnp.sum(attend(q, k, v))))(k)
    dk_rep = jax.jit(jax.grad(lambda kr: jnp.sum(dense_attention(q, kr, jnp.repeat(v, 2, axis=2), causal=True))))(
        jnp.repeat(k, 2, axis=2))
    assert _rel(dk, dk_rep.reshape(1, seq, 2, 2, 8).sum(axis=3)) <= 5e-5


def test_the_flash_kernel_computes_the_grouped_layer():
    """Interpret mode: the kernel the chip runs, on 2 KV heads for 4 query heads."""
    from torchsnapshot_tpu.ops.pallas_attention import _vmem_room, flash_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (1, 256, 4, 128), jnp.float32)
    k, v = jax.random.normal(kk, (1, 256, 2, 128)), jax.random.normal(kv, (1, 256, 2, 128))
    kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    assert _rel(flash_attention(q, kr, vr, block_q=128, block_k=128), dense_attention(q, kr, vr)) <= 2e-5
    # The cells that were there keep Mosaic's default limit; S 8192 asks for room.
    assert _vmem_room(2048, 128, jnp.bfloat16) == {}
    assert _vmem_room(8192, 128, jnp.bfloat16)["compiler_params"].vmem_limit_bytes >= 24 << 20


# ---------------------------------------------------- the nine-layer stack


# As for the mixers: float32 held to 2e-5 of the largest reference logit at
# every position (1e-6 read). bfloat16 operands through nine layers at width
# 32 read 0.4e-2 at the median position, held to 2e-2 (the harness holds
# 3e-2 at full width); where a router's near-tie goes the other way a single
# position is off by more, so the maximum is not held there.
@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 2e-2, None)])
def test_the_stack_agrees_with_the_reference(dtype, median_tol, max_tol):
    cfg = dataclasses.replace(CFG, dtype=dtype)
    assert cfg.kinds == "MEMEM*EME" and cfg.layer_names[5] == "layer05_attn"
    params, tokens = _params(cfg), _batch()["tokens"]
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        logits = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, tokens)
    want = R.forward(params, tokens, **_ref_args(cfg))
    assert logits.shape == want.shape == (B, S, V) and logits.dtype == jnp.float32
    err = np.asarray(jnp.max(jnp.abs(logits - want), axis=-1)) / float(jnp.max(jnp.abs(want)))
    assert np.median(err) <= median_tol and (max_tol is None or err.max() <= max_tol), (np.median(err), err.max())


# Loss and every gradient leaf against jax.grad of the reference's loss, in
# float32, through jax.checkpoint a layer, the chunked scan, the experts'
# hand-written backward loops and the repeated KV heads. 5e-6 of a leaf's
# largest entry read, held to 5e-5.
@pytest.mark.parametrize("n_layers", [9, 6, 2])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(n_layers):
    cfg = dataclasses.replace(CFG, n_layers=n_layers)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p, b: M.loss_fn(p, b, cfg)))(params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p, b: R.loss(p, b, **_ref_args(cfg))))(params, batch)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    assert len(got) == len(jax.tree_util.tree_leaves(want))
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # it selects and takes no gradient
            assert float(jnp.max(jnp.abs(got[path]))) == 0.0 == float(jnp.max(jnp.abs(w))), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _rel(got[path], w) <= 5e-5, (name, _rel(got[path], w))


def test_the_published_sizes_count_to_the_cells_state():
    """The defaults are the published config; cut as the cell cuts it (nine
    layers, experts 0-7 of 128, an eighth of the vocabulary) the tree is the
    667.0 M parameters in 72 leaves the issue reckoned, the largest the
    embedding's 176 MB, nothing stacked over layers."""
    cfg = dataclasses.replace(M.HybridLMConfig(), n_layers=9, held=tuple(range(8)), vocab_size=16384)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape)) for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    per_layer = {n: sum(v for k, v in sizes.items() if n in k) for n in cfg.layer_names}
    assert per_layer["layer00_mamba"] == 38_744_896 and per_layer["layer05_attn"] == 23_399_040
    assert per_layer["layer01_moe"] == 100_125_440  # 79.82 M routed + 19.96 M shared + 0.34 M router, its bias and the scale
    assert sum(sizes.values()) == 666_963_456 and len(sizes) == 72
    assert max(sizes.values()) * 4 == 16384 * 2688 * 4 < 512 << 20
    assert shapes["layers"]["layer01_moe"]["router"].shape == (2688, 128)  # the router keeps its width
    assert shapes["layers"]["layer00_mamba"]["A_log"].shape == (64,)
    assert cfg.matmul_params_per_token == 318_431_232
    whole = M.HybridLMConfig()
    assert whole.kinds.count("M") == whole.kinds.count("E") == 23 and whole.kinds.count("*") == 6
    # What is saved: each parameter leaf, adamw's two moments of it, its count
    # and the step; the Mamba-2 input projection one leaf of the published
    # width, however the mixer cuts its product.
    state = jax.eval_shape(lambda k: M.init_state(k, cfg, M.make_optimizer()), jax.random.PRNGKey(0))
    saved = {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(state)[0]}
    assert len(saved) == 218 == 3 * 72 + 2
    in_proj = [x for name, x in saved.items() if name.endswith("['layer00_mamba']['in_proj']")]
    assert [(x.shape, x.dtype) for x in in_proj] == [((2688, 10304), jnp.float32)] * 3


def test_the_mixer_cuts_the_weight_and_not_the_projection():
    """``z``, ``x``, ``B C`` and ``dt`` are four products against column
    slices of the one ``in_proj`` leaf: no array of the projection's whole
    width, nor of the convolution's, exists for a slice to copy from, forward
    or backward, but the leaf and its one gradient."""
    w, a = _layer("M"), _stream()
    width = w["in_proj"].shape[1]
    assert width == 2 * CFG.mamba_inner + 2 * CFG.ssm_groups * CFG.ssm_state + CFG.mamba_heads
    grad = jax.grad(lambda w, a: jnp.sum(M.mamba_mixer(w, a, CFG)), argnums=(0, 1))
    assert jax.eval_shape(grad, w, a)[0]["in_proj"].shape == w["in_proj"].shape
    made = [(eqn.primitive.name, var.aval.shape) for eqn in _equations(jax.make_jaxpr(grad)(w, a).jaxpr)
            for var in eqn.outvars if hasattr(var.aval, "shape")]
    wide = [(name, shape) for name, shape in made if shape[-1:] == (width,)]
    assert wide == [("concatenate", w["in_proj"].shape)]  # the four slices' gradients, put together once
    assert not [(name, shape) for name, shape in made if shape[1:] == (S, CFG.conv_width)]


# The gated output's norm takes its statistics over a group's 512 columns of
# 4096. Against a float64 mean square, at entries from 1e-3 to 1e3 in one
# group: a float32 sum in any order is within 512 roundings (3e-5, 2e-7
# read); a bfloat16 pass over the squares reads 2e-3.
def test_the_group_norm_is_a_float32_mean_square():
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 4096), jnp.float32)
    y = y * 10.0 ** jax.random.uniform(jax.random.PRNGKey(3), y.shape, jnp.float32, -3.0, 3.0)
    got = jax.jit(lambda t: M._group_rms(t, 8, 1e-5))(y)
    grouped = np.asarray(y, np.float64).reshape(2, 16, 8, 512)
    want = (grouped / np.sqrt(np.mean(grouped ** 2, axis=-1, keepdims=True) + 1e-5)).reshape(y.shape)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert np.max(np.abs(np.asarray(got, np.float64) - want) / np.abs(want)) <= 2e-6


def test_the_mixer_keeps_its_width_and_its_float32_sums_whole():
    """The gate and the norm hold ``(B, S, inner)`` as the matmuls on both
    sides do: nothing is reshaped to ``(..., groups, inner / groups)``, a copy
    into other tiles on the chip, forward or backward; and every product of
    two float32 arrays (the sums that stand in for reductions) is at full
    precision whatever the ambient one."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)  # as the cell runs it: the matrices' products are not float32 ones
    w = {k: v.astype(cfg.dtype) if k in M._MATRICES else v for k, v in _layer("M").items()}
    a = _stream()
    grad = jax.grad(lambda w, a: jnp.sum(M.mamba_mixer(w, a, cfg)), argnums=(0, 1))
    grouped = (CFG.ssm_groups, CFG.mamba_inner // CFG.ssm_groups)
    sums = 0
    for eqn in _equations(jax.make_jaxpr(grad)(w, a).jaxpr):
        assert all(getattr(var.aval, "shape", ())[-2:] != grouped for var in eqn.outvars), (eqn.primitive.name, grouped)
        sums += _is_float32_product(eqn)
    assert sums >= 4  # the group sums and their hand-back, forward and backward, and the decays' sums


def test_the_init_keeps_random_routers_near_even_loads():
    """What ``assumed.init`` says: the second matrix of every relu^2 FFN has
    no mean over its hidden units (so the positive mean of relu^2 puts no
    vector common to all tokens into the residual stream), the convolution's
    bias and the selection bias are small, and they are not zero."""
    params = M.init_params(jax.random.PRNGKey(3), CFG)
    moe, mamba = params["layers"]["layer01_moe"], params["layers"]["layer00_mamba"]
    for name in ("expert_down", "shared_down"):
        w = np.asarray(moe[name])
        assert np.abs(w.mean(axis=-2)).max() <= 1e-7 and w.std() > 0
    a = _stream(batch=8, seq=64)

    def common_share(down):
        """Of the FFN output's power, the part that is the same for every token."""
        out = relu2_ffn(a, moe["shared_up"], down)
        return float(jnp.sum(jnp.mean(out, axis=(0, 1)) ** 2) / jnp.mean(jnp.sum(out**2, axis=-1)))

    raw = jax.random.normal(jax.random.PRNGKey(4), moe["shared_down"].shape) * jnp.std(moe["shared_down"])
    assert common_share(moe["shared_down"]) < 0.04 < 0.10 < common_share(raw)  # 0.01-0.02 against a sixth
    assert 0 < float(jnp.max(jnp.abs(mamba["conv_b"]))) <= 0.1
    assert 0 < float(jnp.std(moe["router_bias"])) < 0.03


def test_a_bad_pattern_or_share_is_refused():
    with pytest.raises(ValueError, match="pattern"):
        dataclasses.replace(CFG, pattern="MEX")
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(CFG, held=(0, 0, 1))
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(CFG, held=(3, 16))


# ------------------------------------------- the train step and the state


def _routes():
    params, tokens = _params(CFG), _batch()["tokens"]
    stats, chosen = jax.jit(lambda p, t: (M.routing_stats(p, t, CFG), M.chosen_experts(p, t, CFG)))(params, tokens)
    names = [n for n in CFG.layer_names if n.endswith("moe")]
    assert list(stats) == list(chosen) == names
    stats = {k: np.stack([np.asarray(stats[n][k]) for n in names]) for k in stats[names[0]]}
    want = jax.jit(lambda p, t: R.chosen_experts(p, t, **_ref_args(CFG)))(params, tokens)
    assert len(want) == len(names) == 4
    chosen = np.stack([np.asarray(chosen[n]) for n in names])
    assert chosen.shape == (len(names), B * S, CFG.top_k)
    return stats, chosen, np.stack([np.asarray(w) for w in want])


@pytest.fixture(scope="module")
def model():
    return Model(
        M, CFG, _batch,
        gauges={"hybrid_lm.layers": 9, "hybrid_lm.experts_held": 4,
                "hybrid_lm.matmul_params_per_token": CFG.matmul_params_per_token},
        scopes=("layer0/mamba2", "layer1/moe_route", "layer1/moe_experts", "layer1/moe_shared", "layer5/gqa",
                "layer8/moe_experts", "lm_head"),
        # the per-head 8-float leaves and the routers' biases among them
        leaves=(218, 8),
        still=tuple(f"['layers']['{n}']['router_bias']" for n in CFG.layer_names if n.endswith("moe")),
        routes=_routes,
        route_agreement=0.98,  # float32 on both sides: the same sets, bar an exact tie
        ratio_rtol=1e-5,
    )


def test_the_train_steps_gradient_is_the_losss_and_the_bias_stays(model):
    """The selection bias, which the step holds fixed against adamw's weight
    decay: it comes back as it went in, where adamw alone would move it."""
    old, opt_state = model.state["params"], model.state["opt_state"]
    new = model.stepped[0]["params"]
    decay, _ = jax.jit(model.tx.update)(jax.tree_util.tree_map(jnp.zeros_like, old), opt_state, old)
    seen = 0
    for name, w in old["layers"].items():
        if "router_bias" in w:
            seen += 1
            np.testing.assert_array_equal(np.asarray(new["layers"][name]["router_bias"]), np.asarray(w["router_bias"]))
            assert float(jnp.max(jnp.abs(decay["layers"][name]["router_bias"]))) > 0, name
    assert seen == 4
