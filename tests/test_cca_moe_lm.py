"""The CCA / MLP-routed LM (``models/cca_moe_lm.py``: attention in a
compressed, convolved latent from ``ops/cca.py``, a top-1 router that is an
MLP over a carry scanned through the depth from ``ops/moe.py``, residual
scaling, a tied sliced vocabulary) against its plain reference
(``benchmarks/chip/reference/cca_moe_lm.py``: float32, ``highest``
precision, explicit shifts, dense softmax, dense routing), piece by piece
against loops over positions, and through the train step and
``CheckpointManager`` as the other families go.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot
from torchsnapshot_tpu.io_preparers import chunked
from torchsnapshot_tpu.manifest import ChunkedArrayEntry
from torchsnapshot_tpu.models import cca_moe_lm as M
from torchsnapshot_tpu.ops import cca, moe
from torchsnapshot_tpu.ops.attention import causal_attention_route
from torchsnapshot_tpu.ops.moe import mlp_top1_route, mlp_top1_routed
from model_kit_cases import (  # noqa: F401  (the cases this file runs)
    Model,
    checkpoint_round_trip,
    test_routing_stats_count_what_the_routers_chose,
    test_the_named_scopes_reach_the_lowered_step,
    test_the_sharded_loss_equals_the_one_device_loss,
    test_the_step_puts_what_it_compiles_on_the_telemetry_bus,
    test_the_step_reports_a_finite_loss_and_keeps_its_layout,
    test_the_train_steps_gradient_is_the_losss,
)

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "chip", "reference", "cca_moe_lm.py")
_spec = importlib.util.spec_from_file_location("cca_moe_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, B, S = 96, 2, 32
# The toy size keeps every ratio of the published config that a layer's code
# depends on: four query heads a KV head, two KV heads (one shifted), a
# latent narrower than the stream (H hd = 32 < D = 48), more experts than
# are held, half of each head rotated, several head blocks a sequence.
CFG = M.CCAMoELMConfig(
    vocab_size=V, d_model=48, n_layers=3, n_heads=8, n_kv_heads=2, head_dim=4, n_experts=8, expert_ff=24,
    held=(0, 1, 2, 3), router_dim=16, head_block=16, dtype=jnp.float32,
)


def _ref_args(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads, "held": cfg.held, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "rotary_dim": cfg.rotary_dim}


def _params(cfg, seed=0):
    """Seeded weights with every scale, bias, kernel and temperature off
    its initial value, so that one applied in the wrong place shows: the
    convolutions leave the identity, the residual scaling the identity,
    the selection bias 0. The router's leaves move little, so that it
    still spreads the positions over the experts."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, x):
        name = jax.tree_util.keystr(path).rsplit("'", 2)[-2]
        if name in M._MATRICES or name == "embed":
            return x
        size = 0.03 if name.startswith("router_") else 0.2
        return x + size * jax.random.normal(next(keys), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(jitter, params)


def _layer_params(cfg, seed=0, index=0):
    return {k: v[index] for k, v in _params(cfg, seed)["layers"].items()}


def _batch(mesh=None, seed=7, batch=B, seq=S):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, V, jnp.int32)
    out = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        out = jax.device_put(out, NamedSharding(mesh, P("data", None)))
    return out


def _normed(seed=3, shape=(B, S, CFG.d_model)):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _qkv(w, a, cfg=CFG):
    return cca.cca_qkv(w, a, rope_theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim)


# ------------------------------------------------------------ the CCA front end


def test_causal_shift_moves_one_position_later_and_fills_with_zeros():
    x = jnp.arange(1, 25, dtype=jnp.float32).reshape(2, 4, 3)
    got = np.asarray(cca.causal_shift(x))
    np.testing.assert_array_equal(got[:, 0], 0.0)
    np.testing.assert_array_equal(got[:, 1:], np.asarray(x)[:, :-1])
    np.testing.assert_array_equal(cca.causal_shift(x, axis=-1)[..., 1:], x[..., :-1])
    # its transpose moves the cotangent one position earlier
    g = jax.grad(lambda t: jnp.sum(cca.causal_shift(t) * x))(x)
    np.testing.assert_array_equal(np.asarray(g)[:, :-1], np.asarray(x)[:, 1:])
    np.testing.assert_array_equal(np.asarray(g)[:, -1], 0.0)


@pytest.mark.parametrize("kernel", [1, 2, 4])
def test_both_convolutions_are_the_loops_over_positions_and_taps(kernel):
    """``y[t] = sum_j w[:, j] x[t-(K-1)+j] + b`` and the per-head ``z_h[t] =
    sum_j y_h[t-(K-1)+j] W[h, j] + b[h]``, position by position in numpy;
    float32 sums of K terms: 1e-6."""
    keys = jax.random.split(jax.random.PRNGKey(kernel), 6)
    n, hd, S_ = 3, 4, 9
    x = np.asarray(jax.random.normal(keys[0], (2, S_, n * hd)))
    w0, b0 = np.asarray(jax.random.normal(keys[1], (n * hd, kernel))), np.asarray(jax.random.normal(keys[2], (n * hd,)))
    want = np.zeros_like(x)
    for t in range(S_):
        for j in range(kernel):
            if t - (kernel - 1) + j >= 0:
                want[:, t] += w0[:, j] * x[:, t - (kernel - 1) + j]
    np.testing.assert_allclose(cca.depthwise_causal_conv(jnp.asarray(x), jnp.asarray(w0), jnp.asarray(b0)), want + b0, atol=1e-6)
    w1, b1 = np.asarray(jax.random.normal(keys[3], (n, kernel, hd, hd))), np.asarray(jax.random.normal(keys[4], (n, hd)))
    y = x.reshape(2, S_, n, hd)
    want = np.zeros_like(y)
    for t in range(S_):
        for j in range(kernel):
            if t - (kernel - 1) + j >= 0:
                for h in range(n):
                    want[:, t, h] += y[:, t - (kernel - 1) + j, h] @ w1[h, j]
    np.testing.assert_allclose(cca.headwise_causal_conv(jnp.asarray(y), jnp.asarray(w1), jnp.asarray(b1)), want + b1, atol=2e-6)


def _qkv_by_loops(w, a, cfg):
    """q, k, v of ``ops/cca.py``'s docstring with numpy loops over
    positions, heads and taps, float64."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    a = np.asarray(a, np.float64)
    Bn, Sn, _ = a.shape
    H, Hkv, hd, rot = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim
    G = H // Hkv
    q0, k0 = (a @ w["q"]).reshape(Bn, Sn, H, hd), (a @ w["k"]).reshape(Bn, Sn, Hkv, hd)
    v = np.zeros((Bn, Sn, Hkv, hd))
    for t in range(Sn):
        v[:, t, : Hkv // 2] = (a[:, t] @ w["v1"]).reshape(Bn, Hkv // 2, hd)  # the position itself
        if t > 0:
            v[:, t, Hkv // 2:] = (a[:, t - 1] @ w["v2"]).reshape(Bn, Hkv // 2, hd)  # the one before
    m_q, m_k = np.zeros_like(q0), np.zeros_like(k0)
    for h in range(H):
        m_q[:, :, h] = 0.5 * (q0[:, :, h] + k0[:, :, h // G])
    for g in range(Hkv):
        m_k[:, :, g] = m_q[:, :, g * G:(g + 1) * G].mean(axis=2)
    c = np.concatenate([q0, k0], axis=2)  # (B, S, H + Hkv, hd)
    K0, K1 = w["conv0_w"].shape[1], w["conv1_w"].shape[1]
    w0, b0 = w["conv0_w"].reshape(H + Hkv, hd, K0), w["conv0_b"].reshape(H + Hkv, hd)
    y, z = np.zeros_like(c), np.zeros_like(c)
    for t in range(Sn):
        y[:, t] = b0 + sum(w0[..., j] * c[:, t - (K0 - 1) + j] for j in range(K0) if t - (K0 - 1) + j >= 0)
    for t in range(Sn):
        for h in range(H + Hkv):
            z[:, t, h] = w["conv1_b"][h] + sum(
                y[:, t - (K1 - 1) + j, h] @ w["conv1_w"][h, j] for j in range(K1) if t - (K1 - 1) + j >= 0)
    q, k = z[:, :, :H] + m_q, z[:, :, H:] + m_k
    q = math.sqrt(hd) * q / np.linalg.norm(q, axis=-1, keepdims=True)
    k = math.sqrt(hd) * np.exp(w["temp"])[:, None] * k / np.linalg.norm(k, axis=-1, keepdims=True)

    def rope(x):
        out = x.copy()
        for t in range(Sn):
            for i in range(rot // 2):
                angle = t * cfg.rope_theta ** (-2 * i / rot)
                x1, x2 = x[:, t, :, i], x[:, t, :, i + rot // 2]
                out[:, t, :, i] = x1 * math.cos(angle) - x2 * math.sin(angle)
                out[:, t, :, i + rot // 2] = x2 * math.cos(angle) + x1 * math.sin(angle)
        return out

    return rope(q), rope(k), v


def test_cca_qkv_is_the_published_pieces_written_as_loops_over_positions():
    """Projections, the value shift, the q-k mean under a 4 : 1 group, both
    convolutions off the identity, the L2 norm with a temperature, the
    partial rotation: against float64 loops, and the reference's
    ``cca_qkv``. float32 sums over 48 channels: 2e-5 of values of order 1."""
    w, a = _layer_params(CFG), _normed()
    assert abs(float(w["temp"][0])) > 0.01 and float(jnp.abs(w["conv1_w"][0, 0]).max()) > 0.01
    got = _qkv(w, a)
    assert [t.shape for t in got] == [(B, S, 8, 4), (B, S, 2, 4), (B, S, 2, 4)] and all(t.dtype == jnp.float32 for t in got)
    for g, want, ref in zip(got, _qkv_by_loops(w, a, CFG), R.cca_qkv(w, a, **_ref_args(CFG))):
        np.testing.assert_allclose(g, want, atol=2e-5)
        np.testing.assert_allclose(g, ref, atol=2e-5)
    # the norm: every q head has length sqrt(hd), every k head sqrt(hd) exp(tau)
    np.testing.assert_allclose(jnp.linalg.norm(got[0], axis=-1), 2.0, rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(got[1], axis=-1), jnp.broadcast_to(2.0 * jnp.exp(w["temp"]), (B, S, 2)), rtol=1e-5)


def test_the_value_shift_gives_the_second_kv_head_the_position_before():
    w, a = _layer_params(CFG), _normed()
    _, _, v = _qkv(w, a)
    np.testing.assert_allclose(v[:, :, 0], a @ w["v1"], atol=1e-5)  # head 0: the position itself
    np.testing.assert_allclose(v[:, 1:, 1], (a @ w["v2"])[:, :-1], atol=1e-5)  # head 1: the one before
    np.testing.assert_array_equal(v[:, 0, 1], 0.0)


def test_the_qk_mean_is_shared_across_each_group_of_four():
    """With both convolutions zeroed, q and k ahead of the norm are the
    means alone: ``m_q = (q~ + rep(k~)) / 2`` and ``m_k`` its mean over
    the group; read back through the norm's direction."""
    w, a = _layer_params(CFG), _normed()
    w = {**w, "conv0_w": jnp.zeros_like(w["conv0_w"]), "conv0_b": jnp.zeros_like(w["conv0_b"]),
         "conv1_b": jnp.zeros_like(w["conv1_b"]), "temp": jnp.zeros_like(w["temp"])}
    cfg = dataclasses.replace(CFG, rotary_factor=0.0)
    q, k, _ = _qkv(w, a, cfg)
    q0, k0 = (a @ w["q"]).reshape(B, S, 2, 4, 4), (a @ w["k"]).reshape(B, S, 2, 1, 4)
    m_q = 0.5 * (q0 + k0)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    np.testing.assert_allclose(unit(q), unit(m_q.reshape(B, S, 8, 4)), atol=1e-5)
    np.testing.assert_allclose(unit(k), unit(m_q.mean(axis=3)), atol=1e-5)


@pytest.mark.parametrize("t", [0, 5, S - 2])
def test_cca_qkv_is_causal(t):
    """Position t's q, k and v do not change when later positions do, and
    position t + 1's do when position t does (the convolutions and the
    value shift reach one position back)."""
    w, a = _layer_params(CFG), _normed()
    moved = a.at[:, t + 1:].set(_normed(seed=9)[:, t + 1:])
    for got, want in zip(_qkv(w, moved), _qkv(w, a)):
        np.testing.assert_array_equal(np.asarray(got)[:, : t + 1], np.asarray(want)[:, : t + 1])
    back = a.at[:, t].set(_normed(seed=9)[:, t])
    for got, want in zip(_qkv(w, back), _qkv(w, a)):
        assert not np.allclose(np.asarray(got)[:, t + 1], np.asarray(want)[:, t + 1])
        np.testing.assert_array_equal(np.asarray(got)[:, :t], np.asarray(want)[:, :t])


def test_an_l2_norm_in_bfloat16_fails_the_front_ends_tolerance():
    """The tolerance above (2e-5) is float32's: with the normalised q and
    k rounded to bfloat16 (2^-9 of values of order 1) the same comparison
    reads 2e-3 or more, a hundred times the limit."""
    w, a = _layer_params(CFG), _normed()
    q, k, _ = _qkv(w, a)
    want_q, want_k, _ = _qkv_by_loops(w, a, CFG)
    for got, want in ((q, want_q), (k, want_k)):
        assert np.abs(np.asarray(got) - want).max() <= 2e-5
        assert np.abs(np.asarray(got.astype(jnp.bfloat16).astype(jnp.float32)) - want).max() >= 2e-3


@pytest.mark.parametrize("impl", ["dense", "blockwise", "flash"])
def test_the_dispatch_takes_a_latent_narrower_than_the_stream(impl):
    """``n_heads x head_dim != d_model``: no route assumes them equal, and a
    KV head serves its four query heads (the flash route is the Pallas
    kernels in interpret mode)."""
    w, a = _layer_params(CFG), _normed()
    q, k, v = _qkv(w, a)
    route, attend = causal_attention_route(impl, 16, CFG.n_heads, None, B, S)
    assert route == impl
    kr, vr = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / 2.0
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vr)
    np.testing.assert_allclose(attend(q, k, v), want, atol=2e-5)
    np.testing.assert_allclose(R.attention(w, a, **_ref_args(CFG)), want.reshape(B, S, 32) @ w["o"], atol=2e-5)


# ------------------------------------------------------------------- the router


def _router(w):
    return {k[len("router_"):]: v for k, v in w.items() if k.startswith("router_")}


_route = functools.partial(mlp_top1_route, norm_eps=CFG.norm_eps)


def test_the_route_is_the_mlp_over_the_averaged_carry_and_its_weight_is_not_renormalised():
    w = _layer_params(CFG)
    b, r_prev = _normed().reshape(-1, CFG.d_model), _normed(seed=4, shape=(B * S, CFG.router_dim))
    ids, weights, r = _route(b, r_prev, _router(w))
    assert ids.shape == weights.shape == (B * S, 1) and ids.dtype == jnp.int32 and r.shape == r_prev.shape
    want_r = b @ w["router_down"] + w["router_down_b"] + w["router_decay"] * r_prev
    np.testing.assert_allclose(r, want_r, atol=1e-5)
    u = want_r / jnp.sqrt(jnp.mean(want_r**2, axis=-1, keepdims=True) + CFG.norm_eps) * w["router_norm_scale"]
    gelu = functools.partial(jax.nn.gelu, approximate=False)
    p = jax.nn.softmax(gelu(gelu(u @ w["router_w1"] + w["router_b1"]) @ w["router_w2"] + w["router_b2"]) @ w["router_w3"], axis=-1)
    np.testing.assert_array_equal(ids[:, 0], jnp.argmax(p + w["router_bias"], axis=-1))
    np.testing.assert_allclose(weights[:, 0], jnp.take_along_axis(p, ids, axis=-1)[:, 0], rtol=1e-5)
    assert float(weights.max()) < 1.0 and float(weights.std()) > 0.005  # the probability itself, not 1
    assert len(np.unique(np.asarray(ids))) >= 4  # the positions spread over the experts
    ref_ids, ref_weights, ref_r = R.route(w, b, r_prev, norm_eps=CFG.norm_eps)
    np.testing.assert_array_equal(ids[:, 0], ref_ids)
    np.testing.assert_allclose(weights[:, 0], ref_weights, rtol=1e-5)
    np.testing.assert_allclose(r, ref_r, atol=1e-5)
    # the bias selects and does not weigh: a large one moves every choice to its expert at that expert's probability
    forced = {**_router(w), "bias": jnp.zeros(8).at[5].set(10.0)}
    ids5, weights5, _ = _route(b, r_prev, forced)
    assert (np.asarray(ids5) == 5).all()
    np.testing.assert_allclose(weights5[:, 0], p[:, 5], rtol=1e-5)


def test_the_weight_carries_a_gradient_to_the_router_and_the_selection_bias_takes_none(monkeypatch):
    monkeypatch.setattr(moe, "_ROW_TILE", 16)  # several row tiles an expert
    w = _layer_params(CFG)
    b, r_prev = _normed().reshape(-1, CFG.d_model), _normed(seed=4, shape=(B * S, CFG.router_dim))
    g = jax.random.normal(jax.random.PRNGKey(2), b.shape)
    held_params = {k: v for k, v in w.items() if k.startswith(("router_", "expert_"))}

    def out(p, r_prev):
        y, _, r = mlp_top1_routed(p, b, r_prev, held=CFG.held, norm_eps=CFG.norm_eps)
        return jnp.sum(y * g) + 0.0 * jnp.sum(r)

    grads, d_prev = jax.grad(out, (0, 1))(held_params, r_prev)
    for name in ("down", "down_b", "decay", "norm_scale", "w1", "b1", "w2", "b2", "w3"):
        assert float(jnp.abs(grads[f"router_{name}"]).max()) > 0, name
    assert float(jnp.abs(d_prev).max()) > 0  # and on to the layer before, through the carry
    np.testing.assert_array_equal(grads["router_bias"], 0.0)
    # a renormalised single weight would be 1 whatever the router says: no gradient would reach it
    ids, weights, _ = _route(b, r_prev, _router(w))
    np.testing.assert_array_equal(weights / jnp.sum(weights, axis=-1, keepdims=True), 1.0)


def test_a_router_in_bfloat16_fails_the_routes_tolerance():
    """The route agrees with the reference's to 1e-5 of a probability; with
    the router's input and matrices rounded to bfloat16 the chosen
    probabilities move by 1e-3 and more, and some positions choose
    another expert."""
    w = _layer_params(CFG)
    b, r_prev = _normed(shape=(8, 64, CFG.d_model)).reshape(-1, CFG.d_model), jnp.zeros((512, CFG.router_dim))
    _, want, _ = R.route(w, b, r_prev, norm_eps=CFG.norm_eps)
    _, got, _ = _route(b, r_prev, _router(w))
    assert float(jnp.abs(got[:, 0] - want).max()) <= 1e-5
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    _, rounded, _ = _route(low(b), r_prev, {k: low(v) for k, v in _router(w).items()})
    assert float(jnp.abs(rounded[:, 0] - want).max()) >= 1e-3


def test_the_two_disjoint_shares_add_up_to_the_uncut_layer():
    """Two expert groups hold experts 0-3 and 4-7 of eight (0-7 and 8-15
    of sixteen as published). What both compute alike is counted once:
    attention, the route and its carry, and of the residual scaling the
    kept stream and the branch's bias; what each adds is its own experts'
    term times the branch's scale. The sum is the layer with all eight
    held, and the reference's."""
    cfg = dataclasses.replace(CFG, held=tuple(range(8)))
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    D, F = cfg.d_model, cfg.expert_ff
    w = {**_layer_params(cfg),
         "expert_gate": jax.random.normal(keys[0], (8, D, F)) * D**-0.5,
         "expert_up": jax.random.normal(keys[1], (8, D, F)) * D**-0.5,
         "expert_down": jax.random.normal(keys[2], (8, F, D)) * F**-0.5}
    x, r = 0.3 * _normed(seed=5), _normed(seed=6, shape=(B, S, cfg.router_dim))
    _, attend = causal_attention_route("dense", 16, cfg.n_heads, None, B, S)
    whole, r_whole, ids = M.layer(w, x, r, cfg, attend)
    parts = []
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        share = {**w, **{k: w[k][jnp.asarray(held)] for k in ("expert_gate", "expert_up", "expert_down")}}
        out, r_part, ids_part = M.layer(share, x, r, dataclasses.replace(cfg, held=held), attend)
        np.testing.assert_array_equal(ids_part, ids)  # every share scores and chooses over all eight
        np.testing.assert_array_equal(r_part, r_whole)
        parts.append(out)
    none_held = {**w, **{k: jnp.zeros_like(w[k]) for k in ("expert_gate", "expert_up", "expert_down")}}
    alike, _, _ = M.layer(none_held, x, r, cfg, attend)  # what every chip computes, the experts' term left out
    np.testing.assert_allclose(parts[0] + parts[1] - alike, whole, atol=2e-6)
    assert not np.allclose(parts[0], alike, atol=1e-4) and not np.allclose(parts[1], alike, atol=1e-4)
    want, want_r, want_ids = R.layer(w, x, r, **_ref_args(cfg))
    np.testing.assert_allclose(whole, want, atol=5e-6)
    np.testing.assert_allclose(r_whole, want_r, atol=1e-5)
    np.testing.assert_array_equal(ids[:, 0], want_ids.reshape(-1))


@pytest.mark.parametrize("positions,n_experts,tile", [(8192, 16, 128), (16384, 16, 128), (256, 8, 128), (64, 64, 64)])
def test_the_experts_row_tile_follows_the_positions_alone(positions, n_experts, tile):
    """128 rows whatever the even load, by ``block_diffusion_lm``'s rule."""
    cfg = dataclasses.replace(CFG, n_experts=n_experts, held=(0,))
    assert M.expert_tile(cfg, positions) == tile


# -------------------------------------------------------------------- the stack


@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 1.5e-2, 0.3)])
def test_the_stack_agrees_with_the_reference(dtype, median_tol, max_tol):
    """Logits relative to the reference's largest. float32: reassociation
    only. bfloat16 operands: 2^-9 a rounding over some twenty matmuls at
    the median; the maximum is a position whose expert flipped at a
    near-tie and is held only loosely."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params, tokens = _params(cfg), _batch()["tokens"]
    got = M.forward(params, tokens, cfg)
    want = R.forward(params, tokens, **_ref_args(cfg))
    assert got.shape == (B, S, V) and got.dtype == jnp.float32
    err = np.max(np.abs(np.asarray(got) - np.asarray(want)), axis=-1) / np.max(np.abs(want))
    assert np.median(err) <= median_tol and err.max() <= max_tol, (np.median(err), err.max())


def test_the_scan_carries_both_streams_as_an_unrolled_loop_does():
    """``lax.scan`` over the stacked leaves with ``(x, r)`` as its carry,
    each layer a ``jax.checkpoint``, against a Python loop over
    ``M.layer`` that hands both on by hand; and the carry matters: with
    ``r`` zeroed between layers the result is another."""
    params, tokens = _params(CFG), _batch()["tokens"]
    cp = M.compute_params(params, CFG)
    got, chosen = M._run_layers(cp, tokens, CFG, None)
    _, attend = causal_attention_route("auto", 512, CFG.n_heads, None, B, S)

    def unrolled(keep_carry):
        x, r, ids = cp["embed"][tokens], jnp.zeros((B, S, CFG.router_dim)), []
        for i in range(CFG.n_layers):
            x, r, c = M.layer({k: v[i] for k, v in cp["layers"].items()}, x, r, CFG, attend)
            ids.append(c)
            r = r if keep_carry else jnp.zeros_like(r)
        return M.rmsnorm(x, cp["ln_f_scale"], CFG.norm_eps), jnp.stack(ids)

    want, want_chosen = unrolled(True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_array_equal(chosen, want_chosen)
    dropped, dropped_chosen = unrolled(False)
    assert not np.array_equal(np.asarray(dropped_chosen), np.asarray(chosen))
    # and both carries take a gradient through the checkpointed scan
    g = jax.grad(lambda p: jnp.sum(M._run_layers(M.compute_params(p, CFG), tokens, CFG, None)[0] ** 2))(params)
    assert float(jnp.abs(g["layers"]["router_decay"][1:]).max()) > 0
    np.testing.assert_array_equal(g["layers"]["router_decay"][0], 0.0)  # the first layer's gamma multiplies zeros


@functools.lru_cache(maxsize=None)
def _program_value_and_grad(n_layers):
    """The program's loss and gradient at the tests' weights and batch,
    computed once for the tests below that read them."""
    cfg, batch = dataclasses.replace(CFG, n_layers=n_layers), _batch()
    return jax.value_and_grad(lambda p: M.loss_fn(p, batch, cfg))(_params(cfg))


@pytest.mark.parametrize("n_layers", [1, 3])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(n_layers):
    """The program's loss and ``jax.grad`` of it against the reference's,
    float32: the scan with its two carries, the checkpoints, the blocked
    head, the custom backward of the experts' loops (the dense attention
    route on the CPU). 1e-4 of a leaf's largest gradient is the order of
    float32 sums over 64 positions. Two leaves take no gradient on either
    side: the selection bias, and with one layer the only ``gamma``."""
    cfg = dataclasses.replace(CFG, n_layers=n_layers)
    params, batch = _params(cfg), _batch()
    got, got_grads = _program_value_and_grad(n_layers)
    want, want_grads = jax.value_and_grad(lambda p: R.loss(p, batch["tokens"], batch["targets"], **_ref_args(cfg)))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        name, w = jax.tree_util.keystr(path), np.asarray(flat_want[path])
        if "router_bias" in name or ("router_decay" in name and n_layers == 1):
            assert np.abs(w).max() == 0 and np.abs(np.asarray(g)).max() == 0, name
            continue
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-4 * np.abs(w).max(), err_msg=name)


@pytest.mark.parametrize("head_block", [8, 16, 32, 4096])
def test_the_blocked_head_is_the_whole_heads_loss_and_gradient(head_block):
    """Head and loss over ``head_block`` positions of each sequence at a
    time, each block recomputed in the backward pass, against the whole
    ``(B, S, V)`` logits at once: the loss, the hidden state's gradient and
    the embedding's, summed over the blocks in float32."""
    h, targets = _normed(seed=8), _batch()["targets"]
    embed = M.init_params(jax.random.PRNGKey(0), CFG)["embed"]
    cs = lambda x, spec: x  # noqa: E731

    def whole(h, embed):
        logits = M._head(h, embed, CFG, cs)
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], axis=-1))

    want, want_g = jax.value_and_grad(whole, (0, 1))(h, embed)
    cfg = dataclasses.replace(CFG, head_block=head_block)
    got, got_g = jax.value_and_grad(lambda h, e: M._cross_entropy(h, e, targets, cfg, cs), (0, 1))(h, embed)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_tied_embeddings_gradient_is_the_sum_of_the_lookups_and_the_heads():
    """One leaf, two uses: untied by hand, the gradient with respect to the
    table the lookup reads plus the one with respect to the matrix the
    head reads is the tied leaf's, float32, and neither is zero."""
    params, batch = _params(CFG), _batch()
    cp = M.compute_params(params, CFG)

    def untied(lookup, head):
        h, _ = M._run_layers({**cp, "embed": lookup}, batch["tokens"], CFG, None)
        return M._cross_entropy(h, head, batch["targets"], CFG, lambda x, spec: x)

    d_lookup, d_head = jax.grad(untied, (0, 1))(cp["embed"], cp["embed"])
    tied = _program_value_and_grad(CFG.n_layers)[1]["embed"]
    assert tied.dtype == jnp.float32 and float(jnp.abs(d_lookup).max()) > 0 and float(jnp.abs(d_head).max()) > 0
    np.testing.assert_allclose(tied, d_lookup + d_head, atol=1e-6)
    unseen = np.setdiff1d(np.arange(V), np.asarray(batch["tokens"]))
    np.testing.assert_array_equal(np.asarray(d_lookup)[unseen], 0.0)  # the lookup reaches only the rows it read
    assert np.abs(np.asarray(d_head)[unseen]).max() > 0  # the head reaches every row of the slice


def test_the_published_sizes_count_to_the_cells_state():
    """The cell's cut of ZAYA1-8B: 6 layers, 8 of 16 experts, an eighth of
    the vocabulary; 708.7 M parameters in 35 leaves, 107 with the moments,
    adamw's count and the step; the three expert stacks of 768 MiB are
    over the 512 MiB chunk limit, the temperatures are 48 bytes."""
    cfg = M.CCAMoELMConfig(vocab_size=32784, n_layers=6, held=tuple(range(8)))
    shapes = jax.eval_shape(lambda k: M.init_state(k, cfg, M.make_optimizer()), jax.random.PRNGKey(0))
    params = jax.tree_util.tree_leaves(shapes["params"])
    assert sum(int(np.prod(x.shape)) for x in params) == 708_664_940 and len(params) == 35
    assert len(jax.tree_util.tree_leaves(shapes)) == 107
    count = lambda names: sum(int(np.prod(shapes["params"]["layers"][k].shape[1:])) for k in names)  # noqa: E731
    layers = shapes["params"]["layers"]
    assert count(["q", "k", "v1", "v2", "o", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp"]) == 5_575_682
    assert count([k for k in layers if k.startswith("router_")]) == 660_752
    assert count(["expert_gate", "expert_up", "expert_down"]) == 100_663_296
    assert count([k for k in layers if "scale" in k and "router" not in k or k.endswith("_bias") and "router" not in k]) == 20_480
    assert sum(int(np.prod(x.shape[1:])) for x in layers.values()) == 106_920_210
    big = [x for x in jax.tree_util.tree_leaves(shapes) if x.size * x.dtype.itemsize > 512 << 20]
    assert len(big) == 9 and {x.shape for x in big} == {(6, 8, 2048, 2048)}
    assert min(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(shapes["params"])) == 48
    assert layers["temp"].shape == (6, 2) and layers["o"].shape == (6, 1024, 2048)
    assert cfg.layer_matmul_params == 5_242_880 + 327_680 + 659_456 + 6_291_456
    assert cfg.matmul_params_per_token == 6 * 12_521_472 + 32784 * 2048 == 142_270_464
    assert M.expert_tile(cfg, 8192) == 128 and cfg.rotary_dim == 64


_LOADS_CFG = M.CCAMoELMConfig(vocab_size=4096, d_model=128, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=32, n_experts=16,
                              expert_ff=64, held=tuple(range(8)), router_dim=64, head_block=512, dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted_stats():
    return jax.jit(lambda p, t: M.routing_stats(p, t, _LOADS_CFG))  # compiled once for the seeds below


def _route_loads(init, seed):
    """Held share over the even share and the fullest held expert over the
    mean, per layer, of a toy model of 16 experts, 8 held, router width 64."""
    key = jax.random.PRNGKey(seed)
    params = init(M.init_params(key, _LOADS_CFG), key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, 2048), 0, 4096)
    stats = _jitted_stats()(params, tokens)
    return np.asarray(stats["held_share"]) / 0.5, np.asarray(stats["max_over_mean"])


def _plain_router(params, key):
    """The init without its centring: the router's second and third
    matrices plain normal at ``fan_in^-0.5``."""
    layers = dict(params["layers"])
    for i, name in enumerate(("router_w2", "router_w3")):
        layers[name] = jax.random.normal(jax.random.fold_in(key, 9 + i), layers[name].shape) * layers[name].shape[-2] ** -0.5
    return {**params, "layers": layers}


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_init_keeps_random_routers_near_even_loads(seed):
    """What ``init_params`` says it does, at a toy size where the even load
    is 128 positions an expert and the router 64 wide: the held experts
    get 0.7 to 1.3 of their share in every layer and the fullest under 2.6
    times the mean (measured over these six seeds: 0.75-1.20 and
    1.19-2.51; at the published router width 256, 0.94-1.15 and 1.13-1.89);
    with the router's last two matrices left plain some layer's fullest
    held expert draws over 2.7 times the mean in every seed (3.2-6.6)."""
    share, fullest = _route_loads(lambda p, key: p, seed)
    assert 0.7 <= share.min() and share.max() <= 1.3, share
    assert fullest.max() < 2.6, fullest
    _, plain_fullest = _route_loads(_plain_router, seed)
    assert plain_fullest.max() > 2.7 and plain_fullest.mean() > fullest.mean(), (plain_fullest, fullest)


def test_the_init_starts_at_the_identities_the_docstring_names():
    p = M.init_params(jax.random.PRNGKey(0), CFG)["layers"]
    x = _normed(shape=(B, S, 40))
    np.testing.assert_array_equal(cca.depthwise_causal_conv(x, p["conv0_w"][0], p["conv0_b"][0]), x)
    y = x.reshape(B, S, 10, 4)
    np.testing.assert_allclose(cca.headwise_causal_conv(y, p["conv1_w"][0], p["conv1_b"][0]), y, atol=1e-6)
    assert float(jnp.abs(p["temp"]).max()) == 0 and float(jnp.abs(p["router_bias"]).max()) == 0
    for sub in ("attn", "moe"):
        assert (p[f"{sub}_res_scale"] == 1).all() and (p[f"{sub}_out_scale"] == 1).all()
        assert (p[f"{sub}_res_bias"] == 0).all() and (p[f"{sub}_out_bias"] == 0).all()
    for name in ("router_w2", "router_w3"):  # each column's mean over its inputs taken out
        np.testing.assert_allclose(jnp.mean(p[name], axis=-2), 0.0, atol=1e-7)
    assert abs(float(jnp.std(p["o"])) / (0.3 * 32**-0.5 * 48**-0.5) - 1) < 0.05


def test_a_bad_share_or_head_grouping_is_refused():
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(0, 0))
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(8,))
    with pytest.raises(ValueError, match="KV heads"):
        dataclasses.replace(CFG, n_kv_heads=3)
    with pytest.raises(ValueError, match="KV heads"):
        dataclasses.replace(CFG, n_heads=4, n_kv_heads=1)  # the value shift needs a second half
    with pytest.raises(ValueError, match="experts' weights given"):
        mlp_top1_routed(_layer_params(CFG), _normed(), jnp.zeros((B, S, 16)), held=(0, 1), norm_eps=1e-5)
    with pytest.raises(ValueError, match="query heads"):
        _qkv({**_layer_params(CFG), "conv1_w": jnp.zeros((9, 2, 4, 4))}, _normed())


# ---------------------------------------------------------------- the train step


def _routes():
    params, tokens = _params(CFG), _batch()["tokens"]
    stats, chosen = jax.jit(lambda p, t: (M.routing_stats(p, t, CFG), M.chosen_experts(p, t, CFG)))(params, tokens)
    chosen = np.asarray(chosen)
    assert chosen.shape == (CFG.n_layers, B * S, 1) and M.expert_tile(CFG, B * S) == min(128, B * S)
    want = np.stack([np.asarray(w).reshape(-1, 1) for w in jax.jit(lambda p, t: R.chosen_experts(p, t, **_ref_args(CFG)))(params, tokens)])
    return stats, chosen, want


@pytest.fixture(scope="module")
def model():
    return Model(
        M, CFG, _batch,
        gauges={"cca_moe_lm.layers": 3, "cca_moe_lm.experts_held": 4,
                "cca_moe_lm.matmul_params_per_token": CFG.matmul_params_per_token},
        scopes=("cca_proj", "cca_conv", "cca_qkmean", "cca_norm_rope", "cca_attn", "res_scale", "moe_route",
                "moe_route/router_mlp", "moe_experts", "lm_head"),
        leaves=(107, 3 * 2),  # the temperatures: 2 floats a layer
        # every leaf moves but the selection bias: no gradient, and weight decay of zeros is zero
        still=("['layers']['router_bias']",),
        routes=_routes,
    )


@pytest.mark.parametrize("async_save", [True, False])
def test_the_chunked_state_resumes_the_uninterrupted_run(model, tmp_path, monkeypatch, async_save):
    """Save with the chunk limit shrunk so that the nine expert-stack
    leaves are cut (beside the 8-byte temperatures of a layer and the
    4-byte step), restore into a destination from another seed, leaf for
    leaf equal (107 leaves), and the next step's loss is the
    uninterrupted run's."""
    monkeypatch.setattr(chunked, "DEFAULT_MAX_CHUNK_SIZE_BYTES", 32768)  # over the embedding's 18 KiB
    mgr = checkpoint_round_trip(model, tmp_path / "ckpt", async_save)
    manifest = Snapshot(mgr.path_for(2)).get_manifest()
    cut = sorted(k for k, e in manifest.items() if isinstance(e, ChunkedArrayEntry) and len(e.chunks) > 1)
    assert len(cut) == 9 and all("expert_" in k for k in cut)  # 3 stacks x (param, mu, nu): 54 KiB each
    shapes = jax.eval_shape(model.fresh_state)
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape)) * x.dtype.itemsize
             for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert min(sizes.values()) == 4 and sizes["['params']['layers']['temp']"] == 3 * 8 and max(sizes.values()) == 3 * 4 * 48 * 24 * 4
