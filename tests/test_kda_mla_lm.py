"""The KDA / MLA expert LM (``models/kda_mla_lm.py``: Kimi Delta Attention,
latent attention with a head-wise gate, group-limited sigmoid routing, a
sliced untied vocabulary) against its plain reference
(``benchmarks/chip/reference/kda_mla_lm.py``: float32, ``highest``
precision, the delta rule position by position) and against loops written
here; the chunked delta rule (``ops/kda.py``) against its recurrence; the
group-limited route and the clamped expert (``ops/moe.py``). CPU, small
widths, seeded.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import telemetry
from torchsnapshot_tpu.models import kda_mla_lm as M
from torchsnapshot_tpu.ops import moe
from torchsnapshot_tpu.ops.attention import causal_attention_route
from torchsnapshot_tpu.ops.kda import kda_chunked, kda_recurrent
from model_kit_cases import (  # noqa: F401  (the cases this file runs)
    Model,
    checkpoint_round_trip,
    test_routing_stats_count_what_the_routers_chose,
    test_the_named_scopes_reach_the_lowered_step,
    test_the_step_puts_what_it_compiles_on_the_telemetry_bus,
    test_the_step_reports_a_finite_loss_and_keeps_its_layout,
    test_the_train_steps_gradient_is_the_losss,
)

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "chip", "reference", "kda_mla_lm.py")
_spec = importlib.util.spec_from_file_location("kda_mla_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, B, S = 96, 2, 32
# The toy size keeps what the code depends on: the published stage's layer
# ids (a dense KDA layer, three KDA expert layers, the period's MLA layer,
# one more KDA expert layer), several chunks a sequence and several
# sub-blocks a chunk, q.k wider than v and a rotated
# tail, a latent narrower than the stream, four groups of four experts with
# two kept, fewer experts held than routed, clamps that bite (the cell's
# layers carry none), several head blocks a sequence.
CFG = M.KDAMLALMConfig(
    vocab_size=V, d_model=48, layers=(0, 2, 3, 4, 5, 6), first_k_dense=1, n_heads=4, head_dim=8, chunk=16,
    kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, dense_ff=64, n_experts=16,
    top_k=4, n_group=4, topk_group=2, expert_ff=24, shared_ff=24, held=(0, 1, 2, 3),
    expert_limits=(0.0, 1.0, 1.0, 1.0, 0.75, 1.0), shared_limits=(0.0, 1.5, 1.5, 1.5, 1.25, 1.5),
    head_block=16, dtype=jnp.float32,
)


def _ref_args(cfg):
    return {"layers": cfg.layers, "layer_group_size": cfg.layer_group_size, "first_k_dense": cfg.first_k_dense,
            "n_heads": cfg.n_heads, "lower_bound": cfg.kda_lower_bound, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_rope_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "top_k": cfg.top_k, "routed_scale": cfg.routed_scale, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group, "held": cfg.held, "expert_limits": cfg.expert_limits,
            "shared_limits": cfg.shared_limits, "norm_eps": cfg.norm_eps}


def _params(cfg, seed=0):
    """Seeded weights with every norm scale, bias and per-head scalar off
    its initial value, so that one applied in the wrong place shows."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 128))

    def jitter(path, x):
        name = jax.tree_util.keystr(path).rsplit("'", 2)[-2]
        if name in M._MATRICES or name in ("embed", "head", "router"):
            return x
        return x + 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(jitter, params)


def _batch(mesh=None, seed=7, batch=B, seq=S):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0, V, jnp.int32)
    out = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        out = jax.device_put(out, NamedSharding(mesh, P("data", None)))
    return out


def _normed(seed=3, shape=(B, S, CFG.d_model)):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _layer(cfg, kind, index=0, seed=0):
    return {k: v[index] for k, v in _params(cfg, seed)["layers"][kind].items()}


# ------------------------------------------------------------ the delta rule

chunked = jax.jit(kda_chunked, static_argnames=("chunk",))
recurrent = jax.jit(kda_recurrent)


def _kda_inputs(seed=0, b=2, s=64, h=3, dk=16, dv=8, g=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, s, h, dk))
    k = jax.random.normal(keys[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, s, h, dv))
    if g is None:  # spread over (-5, 0), some channels near either end
        g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (b, s, h, dk)))
    else:
        g = jnp.full((b, s, h, dk), g, jnp.float32)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))


def test_the_recurrence_is_the_equations_in_float64_loops():
    """``S_t = (I - beta k k^T) Diag(e^g) S_(t-1) + beta k v^T``, ``o_t =
    S_t^T q_t``, with explicit matrices in numpy float64: float32 with
    unit-norm keys over 24 positions, 1e-5."""
    q, k, v, g, beta = _kda_inputs(s=24, b=1, h=2)
    o, last = kda_recurrent(q, k, v, g, beta)
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    for h in range(2):
        state = np.zeros((16, 8))
        for t in range(24):
            kt = k[0, t, h][:, None]
            state = (np.eye(16) - beta[0, t, h] * kt @ kt.T) @ np.diag(np.exp(g[0, t, h])) @ state
            state = state + beta[0, t, h] * kt @ v[0, t, h][None, :]
            np.testing.assert_allclose(np.asarray(o)[0, t, h], state.T @ q[0, t, h], atol=1e-5)
        np.testing.assert_allclose(np.asarray(last)[0, h], state, atol=1e-5)


@pytest.mark.parametrize("chunk,s", [(16, 64), (64, 64), (64, 128), (64, 40)])
def test_the_chunked_form_is_the_recurrence(chunk, s):
    """Forward: the WY algebra reorders float32 sums of O(chunk) terms of
    size at most 1 against the state's sequential ones: 2e-6 of an output
    of order 0.3 (read 3e-7). ``s`` 40 < 64 is one chunk of 40 (short
    sequences take the whole as their chunk), re-based every 8."""
    args = _kda_inputs(s=s)
    chunk = chunk if s % chunk == 0 else s
    got = chunked(*args, chunk=chunk)
    want, _ = recurrent(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_forms_gradients_are_the_recurrences(chunk):
    """Every input's gradient of a nonlinear read of the output, to 1e-5 of
    its largest (read 3e-6: the reverse scan's sums are longer)."""
    args = _kda_inputs(seed=1, s=64)
    read = lambda o: jnp.sum(jnp.sin(3.0 * o))  # noqa: E731
    got = jax.jit(jax.grad(lambda *a: read(kda_chunked(*a, chunk=chunk)), argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: read(kda_recurrent(*a)[0]), argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("g", [-5.0, -4.999, -1e-4])
def test_every_decay_at_the_bound_for_a_whole_chunk_stays_finite_and_exact(g):
    """At -5 a position over a chunk of 64, ``exp(-gamma)`` would reach
    ``e^320`` (float32 ends at ``e^88.7``): the sub-blocks' re-basing keeps
    every factor under ``e^75``. Values and gradients finite and equal to
    the recurrence's; and near 0 (no decay) too."""
    args = _kda_inputs(seed=2, s=128, g=g)
    got = chunked(*args, chunk=64)
    want, _ = recurrent(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(kda_chunked(*a, chunk=64) ** 2), argnums=range(5)))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)
    with pytest.raises(ValueError, match="chunks of"):
        kda_chunked(*_kda_inputs(s=48), chunk=32)


def test_without_the_sub_blocks_a_chunk_at_the_bound_overflows(monkeypatch):
    """What the re-basing is for: one sub-block the whole chunk of 64 puts
    ``e^315`` in a factor, and the output is not finite."""
    from torchsnapshot_tpu.ops import kda as K

    monkeypatch.setattr(K, "SUB_BLOCK", 64)
    args = _kda_inputs(seed=2, s=64, g=-5.0)
    assert not np.isfinite(np.asarray(kda_chunked(*args, chunk=64))).all()


@pytest.mark.parametrize("t", [0, 17, 63])
def test_the_delta_rule_is_causal(t):
    args = list(_kda_inputs(seed=3, s=64))
    base = np.asarray(chunked(*args, chunk=16))
    for i in range(4):  # q, k, v, g changed after t
        changed = list(args)
        changed[i] = changed[i].at[:, t + 1:].add(0.5)
        np.testing.assert_array_equal(np.asarray(chunked(*changed, chunk=16))[:, :t + 1], base[:, :t + 1])


def _bf16_state_recurrence(q, k, v, g, beta):
    """The recurrence with its state rounded to bfloat16 after every position."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))[..., None, :]
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    b, _, h, dk = q.shape
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)[1], 0, 1)


def test_a_state_or_a_decay_sum_in_bfloat16_fails_the_tolerance():
    """The tolerance above (2e-6 of outputs of order 0.3) is tight enough
    that KDA's state held in bfloat16 fails it (a state entry loses 2^-9 a
    position: read 5.6e-4 against 6.9e-7 in float32), and so does the
    chunk's cumulative log-decay held in bfloat16 (a sum of up to 64 terms
    of up to 5 rounded at 2^-9 of 320 moves a decay factor by up to e^0.6:
    read 3.2e-2)."""
    args = _kda_inputs(seed=4, s=128)
    want = np.asarray(recurrent(*args)[0])
    assert np.max(np.abs(np.asarray(_bf16_state_recurrence(*args)) - want)) > 100 * 2e-6

    from torchsnapshot_tpu.ops import kda as K
    orig = K.jnp.einsum

    def bf16_cumsum(spec, *ops, **kw):
        out = orig(spec, *ops, **kw)
        return out.astype(jnp.bfloat16).astype(jnp.float32) if spec == "rj,bhnjk->bhnrk" else out

    K.jnp.einsum = bf16_cumsum
    try:
        got = np.asarray(kda_chunked(*args, chunk=64))
    finally:
        K.jnp.einsum = orig
    assert np.max(np.abs(got - want)) > 100 * 2e-6


# ------------------------------------------------------------ latent attention


def test_mla_is_the_published_pieces_written_as_a_loop_over_heads():
    """q and the latent from the stream, one rotated key shared by every
    head, a norm over each head's q.k width ahead of the rotation, softmax
    over a causal score matrix scaled by sqrt(q.k width), a sigmoid gate a
    head, then ``W_o``: numpy float64 loops against the mixer on the dense
    route (float32: 1e-5)."""
    c = CFG
    w = _layer(c, "mla_moe")
    a = _normed()
    _, attend = causal_attention_route("dense", 16, c.n_heads, None, B, S)
    got = np.asarray(M.mla_mixer(w, a, c, attend))
    wn = {k: np.asarray(x, np.float64) for k, x in w.items()}
    an = np.asarray(a, np.float64)
    H, Rk, nope, rope, dv = c.n_heads, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim

    def rms(x, scale):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + c.norm_eps) * scale

    def rot(x):  # (S, d): the last ``rope`` rotated, pairs (i, i + rope / 2)
        half = rope // 2
        ang = np.arange(S)[:, None] * c.rope_theta ** (-np.arange(0, rope, 2) / rope)
        x1, x2 = x[:, -rope:-half], x[:, -half:]
        return np.concatenate([x[:, :-rope], x1 * np.cos(ang) - x2 * np.sin(ang), x2 * np.cos(ang) + x1 * np.sin(ang)], 1)

    want = np.zeros(got.shape)
    for bi in range(B):
        lat = rms(an[bi] @ wn["kv_a"][:, :Rk], wn["kv_a_norm"])
        k_r = an[bi] @ wn["kv_a"][:, Rk:]
        heads = []
        for h in range(H):
            qh = (an[bi] @ wn["q"])[:, h * (nope + rope):(h + 1) * (nope + rope)]
            kvh = (lat @ wn["kv_b"])[:, h * (nope + dv):(h + 1) * (nope + dv)]
            kh = np.concatenate([kvh[:, :nope], k_r], 1)
            qh, kh = rot(rms(qh, wn["q_norm"])), rot(rms(kh, wn["k_norm"]))
            sc = qh @ kh.T / np.sqrt(nope + rope)
            sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
            p = np.exp(sc - sc.max(1, keepdims=True))
            out = (p / p.sum(1, keepdims=True)) @ kvh[:, nope:]
            heads.append(out / (1 + np.exp(-(an[bi] @ wn["gate"])[:, h:h + 1])))
        want[bi] = np.concatenate(heads, 1) @ wn["o"]
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_every_route_takes_v_narrower_than_q_and_k(impl):
    """The pad of v to q's width and the cut after: each route agrees with
    the dense one (float32 blocked softmax: 2e-6)."""
    c, w, a = CFG, _layer(CFG, "mla_moe"), _normed()
    dense = M.mla_mixer(w, a, c, causal_attention_route("dense", 16, c.n_heads, None, B, S)[1])
    route, attend = causal_attention_route(impl, 16, c.n_heads, None, B, S)
    assert route == impl
    np.testing.assert_allclose(np.asarray(M.mla_mixer(w, a, c, attend)), np.asarray(dense), atol=2e-6)


# ------------------------------------------------------------ group-limited routing


def _scores(seed=0, T=64, E=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (T, 24))
    return x, jax.random.normal(keys[1], (24, E)) * 24**-0.5, 0.05 * jax.random.normal(keys[2], (E,))


@pytest.mark.parametrize("n_group,topk_group,top_k", [(4, 2, 4), (8, 4, 8), (4, 1, 2), (4, 4, 3)])
def test_the_grouped_route_is_a_brute_force_over_sets_of_groups(n_group, topk_group, top_k):
    """For each token, every set of ``topk_group`` groups is scored by the
    sum of its groups' two best ``s + bias``; the best set's experts hold
    the ``top_k`` chosen, weighted ``2.5 s / sum of the chosen s``."""
    E = 32 if n_group == 8 else 16
    x, router, bias = _scores(n_group, E=E)
    ids, weights = moe.sigmoid_topk_route(x, router, bias, top_k, 2.5, n_group, topk_group)
    s = np.asarray(jax.nn.sigmoid(np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    sel = s + np.asarray(bias, np.float64)
    size = E // n_group
    for t in range(x.shape[0]):
        group = [np.sort(sel[t, g * size:(g + 1) * size])[-2:].sum() for g in range(n_group)]
        best = max(itertools.combinations(range(n_group), topk_group), key=lambda gs: sum(group[g] for g in gs))
        allowed = [e for g in best for e in range(g * size, (g + 1) * size)]
        chosen = sorted(allowed, key=lambda e: -sel[t, e])[:top_k]
        assert sorted(np.asarray(ids[t]).tolist()) == sorted(chosen), t
        want = 2.5 * s[t, np.asarray(ids[t])] / s[t, chosen].sum()
        np.testing.assert_allclose(np.asarray(weights[t]), want, rtol=1e-5)


def _old_sigmoid_topk_route(x2, router, bias, top_k, scale):
    """``sigmoid_topk_route`` as it was before ``n_group`` (PR 39)."""
    f32 = jnp.float32
    logits = jnp.matmul(x2.astype(f32), router.astype(f32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + jax.lax.stop_gradient(bias.astype(f32)), top_k)
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def test_one_group_is_the_old_route_bit_for_bit():
    x, router, bias = _scores(5, T=128, E=32)
    old = jax.jit(_old_sigmoid_topk_route, static_argnums=(3, 4))(x, router, bias, 6, 2.5)
    for new in (moe.sigmoid_topk_route(x, router, bias, 6, 2.5), moe.sigmoid_topk_route(x, router, bias, 6, 2.5, 1, 1)):
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
        np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(old[1]))
    assert moe.group_limited(x, 1, 1) is x
    with pytest.raises(ValueError, match="groups"):
        moe.group_limited(jnp.zeros((2, 10)), 4, 2)


def _experts(seed, n=4, D=24, F=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (n, D, F)) * D**-0.5, jax.random.normal(keys[1], (n, D, F)) * D**-0.5,
            jax.random.normal(keys[2], (n, F, D)) * F**-0.5)


@pytest.mark.parametrize("limit", [0.0, 4.0, 0.5])
def test_the_clamped_experts_and_their_backward_are_the_plain_forms(limit):
    """The held experts' list with the clamp chosen by its tile (a hand-
    written backward) against ``jax.grad`` of a dense loop of
    ``(silu(min(x G, l)) * clip(x U, -l, l)) D``: limit 4 as the published
    deepest layers (it binds on a few products of rms-normed rows scaled
    by 4), 0.5 where it binds on most; float32 sums in another order, 1e-5
    of each gradient's largest."""
    x = 4.0 * _normed(seed=8, shape=(64, 24))
    ws = _experts(9)
    x_route, router, bias = _scores(10, T=64, E=16)
    params = {"router": router, "router_bias": bias, "expert_gate": ws[0], "expert_up": ws[1], "expert_down": ws[2]}
    held = (0, 1, 2, 3)

    def program(params, x):
        y, _ = moe.sigmoid_group_routed(params, x, top_k=4, held=held, routed_scale=2.5, n_group=4, topk_group=2,
                                        limit=limit)
        return jnp.sum(jnp.sin(y))

    def plain(params, x):
        ids, weights = moe.sigmoid_topk_route(x, params["router"], params["router_bias"], 4, 2.5, 4, 2)
        out = jnp.zeros_like(x)
        for slot, e in enumerate(held):
            h, u = x @ params["expert_gate"][slot], x @ params["expert_up"][slot]
            if limit:
                h, u = jnp.minimum(h, limit), jnp.clip(u, -limit, limit)
            wt = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
            out = out + wt[:, None] * ((jax.nn.silu(h) * u) @ params["expert_down"][slot])
        return jnp.sum(jnp.sin(out))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(params, x)
        want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    rel = lambda a, b: np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(np.asarray(b)))  # noqa: E731
    for k in ("expert_gate", "expert_up", "expert_down", "router"):
        assert rel(got[1][0][k], want[1][0][k]) < 1e-5, k
    assert rel(got[1][1], want[1][1]) < 1e-5
    if limit:  # the clamp binds on some of the products here
        assert np.any(np.asarray(x @ ws[0][0]) > limit) and np.any(np.abs(np.asarray(x @ ws[1][0])) > limit)
    with pytest.raises(ValueError, match="gated"):
        moe._expert_hidden((ws[1], ws[2]), 1.0)


def test_the_shared_expert_is_gated_ffn_without_a_clamp_and_clamped_with_one():
    x, ws = _normed(seed=12, shape=(16, 24)), _experts(13, n=1)
    gate, up, down = (w[0] for w in ws)
    np.testing.assert_array_equal(np.asarray(moe.clamped_gated_ffn(x, gate, up, down, 0.0)),
                                  np.asarray(moe.gated_ffn(x, gate, up, down)))
    want = (jax.nn.silu(jnp.minimum(x @ gate, 0.3)) * jnp.clip(x @ up, -0.3, 0.3)) @ down
    np.testing.assert_allclose(np.asarray(moe.clamped_gated_ffn(x, gate, up, down, 0.3)), np.asarray(want), atol=1e-5)


def test_four_disjoint_shares_add_up_to_the_uncut_layer():
    """Sixteen experts in four groups of four, held four to a share: what
    every share computes alike (the mixer, the route, the shared expert) is
    counted once, what each adds is its own experts' term. The sum is the
    layer with all sixteen held, and the reference's."""
    cfg = dataclasses.replace(CFG, held=tuple(range(16)))
    keys = jax.random.split(jax.random.PRNGKey(14), 3)
    D, F = cfg.d_model, cfg.expert_ff
    w = {**_layer(cfg, "kda_moe"),
         "expert_gate": jax.random.normal(keys[0], (16, D, F)) * D**-0.5,
         "expert_up": jax.random.normal(keys[1], (16, D, F)) * D**-0.5,
         "expert_down": jax.random.normal(keys[2], (16, F, D)) * F**-0.5}
    x = 0.3 * _normed(seed=15)
    attend = causal_attention_route("dense", 16, cfg.n_heads, None, B, S)[1]
    limits = (1.0, 1.5)
    layer = jax.jit(M.layer, static_argnums=(2, 3, 4, 5))
    whole, ids = layer(w, x, "kda_moe", cfg, attend, limits)
    parts = []
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        part = {**w, **{k: w[k][jnp.asarray(held)] for k in ("expert_gate", "expert_up", "expert_down")}}
        out, ids_part = layer(part, x, "kda_moe", dataclasses.replace(cfg, held=held), attend, limits)
        np.testing.assert_array_equal(ids_part, ids)  # every share scores and chooses over all sixteen
        parts.append(out)
    none = {**w, **{k: jnp.zeros_like(w[k]) for k in ("expert_gate", "expert_up", "expert_down")}}
    alike, _ = layer(none, x, "kda_moe", cfg, attend, limits)
    np.testing.assert_allclose(sum(parts) - 3 * alike, whole, atol=5e-6)
    assert all(not np.allclose(p, alike, atol=1e-4) for p in parts)
    args = {**_ref_args(cfg), "limits": limits}
    b = R._rms(x + R.kda(w, R._rms(x, w["ln1_scale"], cfg.norm_eps), **args), w["ln2_scale"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        y, want_ids = R.moe(w, b, **args)
        want = x + R.kda(w, R._rms(x, w["ln1_scale"], cfg.norm_eps), **args) + y
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(np.asarray(want_ids).reshape(ids.shape), -1))


# ------------------------------------------------------------ the stack


def test_the_published_layout_is_five_kda_layers_to_one_mla_layer():
    full = M.KDAMLALMConfig()
    assert [k.split("_")[0] for k in full.kinds].count("mla") == 7
    assert [i for i, k in enumerate(full.kinds) if k.startswith("mla")] == [5, 11, 17, 23, 29, 35, 41]
    assert full.kinds[:2] == ["kda_dense", "kda_dense"] and full.kinds[2] == "kda_moe"
    assert CFG.kinds == ["kda_dense", "kda_moe", "kda_moe", "kda_moe", "mla_moe", "kda_moe"]
    assert CFG.runs == [("kda_dense", 0, 1, (0.0, 0.0)), ("kda_moe", 0, 3, (1.0, 1.5)), ("mla_moe", 0, 1, (0.75, 1.25)),
                        ("kda_moe", 3, 4, (1.0, 1.5))]
    assert CFG.stack_sizes == {"kda_dense": 1, "kda_moe": 4, "mla_moe": 1}


@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 1.5e-2, 0.3)])
def test_the_stack_agrees_with_the_reference(dtype, median_tol, max_tol):
    """Logits relative to the reference's largest. float32: reassociation
    only (read 3e-7 / 6e-7). bfloat16 operands: 2^-9 a product through six
    layers, and a near-tie in a router can go the other way, so the bulk
    is held and the tail is loose (read 3e-3 / 4e-2)."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params, toks = _params(cfg), _batch()["tokens"]
    got = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, toks)
    want = jax.jit(lambda p, t: R.forward(p, t, **_ref_args(cfg)))(params, toks)
    err = np.asarray(jnp.max(jnp.abs(got - want), -1) / jnp.max(jnp.abs(want)))
    assert np.median(err) < median_tol and err.max() < max_tol, (np.median(err), err.max())


@pytest.mark.parametrize("t", [0, 15, S - 2])
def test_the_stack_is_causal(t):
    params, toks = _params(CFG), _batch()["tokens"]
    forward = jax.jit(lambda p, t: M.forward(p, t, CFG))
    base = np.asarray(forward(params, toks))
    changed = toks.at[:, t + 1:].set((toks[:, t + 1:] + 1) % V)
    np.testing.assert_array_equal(np.asarray(forward(params, changed))[:, :t + 1], base[:, :t + 1])


def test_loss_and_every_gradient_leaf_agree_with_the_reference():
    """float32: the loss to 1e-6, every leaf's gradient to 1e-4 of its
    largest (the head's blocks, the hand-written experts' backward and the
    chunked delta rule sum in other orders). The selection bias takes
    none."""
    params, batch = _params(CFG), _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, CFG)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: R.loss(p, batch, **_ref_args(CFG))))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-12)
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
    assert all(not np.any(np.asarray(v["router_bias"])) for k, v in grads["layers"].items() if k.endswith("_moe"))


# The blocks of the head and of a KDA mixer's heads need the head and a KDA
# layer, not the whole stage: the dense KDA layer alone.
ONE_KDA = dataclasses.replace(CFG, layers=(0,), expert_limits=(0.0,), shared_limits=(0.0,))


@pytest.fixture(scope="module")
def whole_head():
    """The loss and gradient with the head and loss in one block and the
    KDA mixer's heads in one block, the same for every case below."""
    batch = _batch()
    return jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, dataclasses.replace(ONE_KDA, head_block=S))))(
        _params(ONE_KDA))


@pytest.mark.parametrize("head_block", [4, 8, 16, 32])
def test_the_blocked_head_and_kda_head_blocks_are_the_whole(head_block, monkeypatch, whole_head):
    """Head and loss in blocks of positions, and the KDA mixer in blocks of
    heads (one or two of four a block): the same loss and gradient (float32
    sums in another order: 2e-6)."""
    params, batch = _params(ONE_KDA), _batch()
    want, gw = whole_head
    cfg = dataclasses.replace(ONE_KDA, head_block=head_block)
    for kda_block in (1, 2):
        monkeypatch.setattr(M, "KDA_HEAD_BLOCK", kda_block)
        got, gg = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, cfg)))(params)
        np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def _objective_value_and_grad(cfg):
    """The objective's value and gradient at the tests' batch, and the tree
    it takes. A new function each call: jit would reuse the trace of one it
    has seen, whatever ``KDA_LAYER_POLICY`` says now."""
    batch = _batch()
    return jax.value_and_grad(lambda p: M._objective(p, batch, cfg, None)), M.compute_params(_params(cfg), cfg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_keeping_the_kda_head_blocks_output_is_the_same_arithmetic(dtype, monkeypatch):
    """A KDA layer's checkpoint keeps ``kda_heads`` in place of running the
    head blocks forward again: the loss and every gradient leaf are the
    ones of the layer checkpoints that keep nothing, bit for bit."""
    cfg = dataclasses.replace(ONE_KDA, dtype=dtype)
    f, params = _objective_value_and_grad(cfg)
    loss, grads = jax.jit(f)(params)
    monkeypatch.setattr(M, "KDA_LAYER_POLICY", jax.checkpoint_policies.nothing_saveable)
    f, params = _objective_value_and_grad(cfg)
    want_loss, want = jax.jit(f)(params)
    assert float(loss) == float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))


def _eqns_named(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _eqns_named(sub, name)
    return n


def test_the_kda_chunk_algebra_runs_forward_twice_a_step():
    """Two solves a chunk algebra's forward, four in its backward: in each
    of ``CFG``'s three KDA layer bodies (the dense layer, the scan's body,
    the last layer) a forward, the head blocks' recomputation and their
    backward are 8 solves; a third forward, in the layer's recomputation,
    would make 10 and the whole 30."""
    f, params = _objective_value_and_grad(CFG)
    solves = _eqns_named(jax.make_jaxpr(f)(params).jaxpr, "triangular_solve")
    assert solves == 24, solves


# The train state's plumbing needs each kind of layer once, not the stage:
# the dense KDA layer, a KDA expert layer and the period's MLA layer.
PLUMBING = dataclasses.replace(CFG, layers=(0, 4, 5), expert_limits=(0.0, 1.0, 0.75), shared_limits=(0.0, 1.5, 1.25))


def _routes():
    params, toks = _params(PLUMBING), _batch()["tokens"]
    stats, chosen = jax.jit(lambda p, t: (M.routing_stats(p, t, PLUMBING), M.chosen_experts(p, t, PLUMBING)))(params, toks)
    chosen = np.stack([np.asarray(i) for i in chosen])
    want = np.stack([np.asarray(i).reshape(-1, CFG.top_k) for i in jax.jit(lambda p, t: R.chosen_experts(p, t, **_ref_args(PLUMBING)))(params, toks)])
    assert len(want) == 2 and stats["held_counts"].shape == (2, 4) and chosen.shape == (2, B * S, CFG.top_k)
    return stats, chosen, want


def test_the_group_share_counts_the_tokens_that_kept_the_held_group(model):
    """A token holding a held expert kept its group; the share kept is at
    least that."""
    stats, chosen, _ = model.routed
    for layer, ids in enumerate(chosen):
        assert float(stats["group_share"][layer]) >= np.mean(np.any(ids < 4, axis=-1)) - 1e-9


_LOADS = dataclasses.replace(CFG, vocab_size=4096, d_model=64, n_experts=512, top_k=8, n_group=8, topk_group=4,
                             held=tuple(range(8)), expert_limits=(), shared_limits=(), dtype=jnp.bfloat16)
_loads_stats = jax.jit(lambda p, t: M.routing_stats(p, t, _LOADS))  # compiled once for the seeds below


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_the_init_keeps_random_routers_near_even_loads(seed):
    """At the published routing (512 experts in 8 groups, 4 kept, top-8;
    the held experts all in group 0) and a small stream: group 0 is kept
    for 0.3-0.7 of the tokens (even: 0.5) and the held experts draw 0.4-2.5
    x their even share in every layer (128 assignments expected a layer,
    over 1024 tokens of a vocabulary of 4096: a vocabulary of 96 would
    give the routers 96 distinct tokens to spread)."""
    cfg = _LOADS
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 50), (4, 256), 0, cfg.vocab_size)
    stats = _loads_stats(params, toks)
    even = len(cfg.held) / cfg.n_experts
    assert np.all((np.asarray(stats["group_share"]) > 0.3) & (np.asarray(stats["group_share"]) < 0.7)), stats
    share = np.asarray(stats["held_share"]) / even
    assert np.all((share > 0.4) & (share < 2.5)), share


def test_a_bad_layout_is_refused():
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(0, 0))
    with pytest.raises(ValueError, match="in order"):
        dataclasses.replace(CFG, layers=(2, 0))
    with pytest.raises(ValueError, match="clamp limits"):
        dataclasses.replace(CFG, expert_limits=(1.0,))
    with pytest.raises(ValueError, match="groups of"):
        dataclasses.replace(CFG, topk_group=1, top_k=8)


def test_the_traced_step_reports_the_bytes_the_kda_checkpoints_keep():
    """Each of the five KDA layers keeps its head blocks' output, ``(B, S,
    H d)`` in bfloat16, across its checkpoint; on the CPU no KDA layer runs
    the Pallas kernels (``tests/test_pallas_kda.py`` forces them)."""
    cfg, tx = dataclasses.replace(CFG, dtype=jnp.bfloat16), M.make_optimizer()
    state = jax.eval_shape(lambda k: M.init_state(k, cfg, tx), jax.random.PRNGKey(0))
    telemetry.set_enabled(True)
    try:
        jax.eval_shape(M.make_train_step(cfg, tx), state, jax.eval_shape(_batch))
        gauges = telemetry.gauges()
    finally:
        telemetry.set_enabled(False)
    assert gauges["kda_mla_lm.saved_heads_bytes"] == B * S * CFG.n_heads * CFG.head_dim * 2 * 5
    assert gauges["kda_mla_lm.kda_kernel_layers"] == 0


@pytest.fixture(scope="module")
def model():
    return Model(
        M, PLUMBING, _batch,
        gauges={"kda_mla_lm.layers": 3, "kda_mla_lm.experts_held": 4,
                "kda_mla_lm.matmul_params_per_token": PLUMBING.matmul_params_per_token},
        scopes=("kda_proj", "kda_conv", "kda_gate", "kda_intra", "kda_inter", "kda_out", "mla_proj", "mla_attn",
                "mla_gate", "moe_route", "moe_route/group_select", "moe_experts", "moe_shared", "lm_head"),
        leaves=(188, 4),  # 62 parameters, both moments, the step and the count
        still=("['layers']['kda_moe']['router_bias']", "['layers']['mla_moe']['router_bias']"),
        update_atol=5e-6,  # the hybrid's bound: two programs that sum in other orders (one leaf read 3.6e-6)
        routes=_routes,
    )


def test_the_train_steps_gradient_is_the_losss_and_the_bias_stays(model):
    """The selection bias, which the step holds fixed against adamw's weight
    decay: it comes back as it went in, where adamw alone would move it."""
    old, opt_state = model.state["params"], model.state["opt_state"]
    new = model.stepped[0]["params"]
    decay, _ = jax.jit(model.tx.update)(jax.tree_util.tree_map(jnp.zeros_like, old), opt_state, old)
    for kind in ("kda_moe", "mla_moe"):
        np.testing.assert_array_equal(np.asarray(new["layers"][kind]["router_bias"]),
                                      np.asarray(old["layers"][kind]["router_bias"]))
        assert float(jnp.max(jnp.abs(decay["layers"][kind]["router_bias"]))) > 0, kind


def test_save_restore_and_the_next_step_is_the_uninterrupted_runs(model, tmp_path):
    """Two steps, an async save through ``CheckpointManager``, a restore
    into a state from another seed (every leaf equal), and the next step's
    loss equal to the uninterrupted run's."""
    checkpoint_round_trip(model, tmp_path / "ckpt", async_save=True)
