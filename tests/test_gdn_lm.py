"""The Gated DeltaNet / gated attention expert LM (``models/gdn_lm.py``:
the scalar-decay delta rule over more value heads than key heads, gated
attention at a wide head, zero-centred norms, softmax top-k routing with a
gated shared expert, a sliced untied vocabulary) against its plain
reference (``benchmarks/chip/reference/gdn_lm.py``: float32, ``highest``
precision, the delta rule position by position) and against loops written
here; the chunked delta rule (``ops/gdn.py``) against its recurrence.
CPU, small widths, seeded.
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

from torchsnapshot_tpu import telemetry
from torchsnapshot_tpu.models import gdn_lm as M
from torchsnapshot_tpu.ops import gdn as G
from torchsnapshot_tpu.ops import moe
from torchsnapshot_tpu.ops.attention import causal_attention_route
from torchsnapshot_tpu.ops.gdn import gdn_chunked, gdn_recurrent
from torchsnapshot_tpu.ops.kda import kda_chunked
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
                    "benchmarks", "chip", "reference", "gdn_lm.py")
_spec = importlib.util.spec_from_file_location("gdn_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, B, S = 96, 2, 32
# The toy size keeps what the code depends on: the published layer ids of a
# period and one more (three GDN layers as a scan, the attention layer, a
# GDN layer alone), two value heads a key head, several chunks a sequence,
# grouped KV heads, a rotated lead narrower than the head, fewer experts
# held than routed, several head blocks a sequence.
CFG = M.GDNLMConfig(
    vocab_size=V, d_model=48, layers=(0, 1, 2, 3, 4), n_key_heads=2, n_value_heads=4, key_head_dim=8,
    value_head_dim=8, chunk=16, n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=4, n_experts=16, top_k=4,
    expert_ff=24, shared_ff=24, held=(0, 1, 2, 3), head_block=16, dtype=jnp.float32,
)


def _ref_args(cfg):
    return {"layers": cfg.layers, "full_attention_interval": cfg.full_attention_interval,
            "n_key_heads": cfg.n_key_heads, "n_value_heads": cfg.n_value_heads, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "rotary_dim": cfg.rotary_dim, "rope_theta": cfg.rope_theta,
            "top_k": cfg.top_k, "held": cfg.held, "norm_eps": cfg.norm_eps}


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

chunked = jax.jit(gdn_chunked, static_argnames=("chunk",))
recurrent = jax.jit(gdn_recurrent)


def _gdn_inputs(seed=0, b=2, s=64, h=3, dk=16, dv=8, g=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, s, h, dk))
    k = jax.random.normal(keys[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, s, h, dv))
    if g is None:  # -exp(A) softplus(.) as initialised: most near 0 to -5, some far below
        g = -jnp.exp(jax.random.uniform(keys[3], (b, s, h), minval=-4.0, maxval=2.5))
    else:
        g = jnp.full((b, s, h), g, jnp.float32)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))


def test_the_recurrence_is_the_equations_in_float64_loops():
    """``S_t = e^g S_(t-1) + beta k (v - e^g S_(t-1)^T k)^T``, ``o_t =
    S_t^T q_t``, with explicit matrices in numpy float64: float32 with
    unit-norm keys over 24 positions, 1e-5."""
    q, k, v, g, beta = _gdn_inputs(s=24, b=1, h=2)
    o, last = gdn_recurrent(q, k, v, g, beta)
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    for h in range(2):
        state = np.zeros((16, 8))
        for t in range(24):
            kt, decayed = k[0, t, h][:, None], np.exp(g[0, t, h]) * state
            state = decayed + beta[0, t, h] * kt @ (v[0, t, h][None, :] - kt.T @ decayed)
            np.testing.assert_allclose(np.asarray(o)[0, t, h], state.T @ q[0, t, h], atol=1e-5)
        np.testing.assert_allclose(np.asarray(last)[0, h], state, atol=1e-5)


@pytest.mark.parametrize("chunk,s", [(16, 64), (64, 64), (64, 128), (64, 40)])
def test_the_chunked_form_is_the_recurrence(chunk, s):
    """Forward: the WY algebra reorders float32 sums of O(chunk) terms of
    size at most 1 against the state's sequential ones: 1e-6 of an output
    of order 0.3 (read 4e-8 to 9e-8). ``s`` 40 < 64 is one chunk of 40."""
    args = _gdn_inputs(s=s)
    chunk = chunk if s % chunk == 0 else s
    got = chunked(*args, chunk=chunk)
    want, _ = recurrent(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_forms_gradients_are_the_recurrences(chunk):
    """Every input's gradient of a nonlinear read of the output, to 1e-5 of
    its largest (the reverse scan's sums are longer)."""
    args = _gdn_inputs(seed=1, s=64)
    read = lambda o: jnp.sum(jnp.sin(3.0 * o))  # noqa: E731
    got = jax.jit(jax.grad(lambda *a: read(gdn_chunked(*a, chunk=chunk)), argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: read(gdn_recurrent(*a)[0]), argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("g", [-60.0, -5.0, -1e-4])
def test_a_decay_far_past_kdas_bound_stays_finite_and_exact(g):
    """At -60 a position (twelve times KDA's bound of -5) a chunk's running
    sum reaches -3840: values and every gradient finite and equal to the
    recurrence's; and at KDA's bound and near 0 (no decay) too. KDA's
    sub-blocked form, given the same decay on every key channel, leaves
    float32 (``e^900`` in a factor)."""
    args = _gdn_inputs(seed=2, s=128, g=g)
    got = chunked(*args, chunk=64)
    want, _ = recurrent(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(gdn_chunked(*a, chunk=64) ** 2), argnums=range(5)))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)
    if g == -60.0:
        q, k, v, gs, beta = args
        per_channel = jnp.broadcast_to(gs[..., None], k.shape)
        assert not np.isfinite(np.asarray(kda_chunked(q, k, v, per_channel, beta, chunk=64))).all()
    with pytest.raises(ValueError, match="chunks of"):
        gdn_chunked(*_gdn_inputs(s=48), chunk=32)


def test_a_small_decay_after_a_long_run_is_its_own_sum():
    """Decays of -40 a position up to a chunk's middle, then -1e-3: the
    running sum sits near -1300, where float32's step is 1.2e-4, and the
    decay between two late positions is ``e^-0.001``. The span's own sum
    keeps it to float32 (the output to 2e-6 of a float64 loop); the
    difference of two running sums would carry the 1e-4 step into every
    late product."""
    q, k, v, _, beta = _gdn_inputs(seed=5, s=64, b=1, h=1)
    g = jnp.where(jnp.arange(64) < 32, -40.0, -1e-3)[None, :, None]
    got = np.asarray(chunked(q, k, v, g, beta, chunk=64), np.float64)
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    state = np.zeros((16, 8))
    for t in range(64):
        kt, decayed = k[0, t, 0][:, None], np.exp(g[0, t, 0]) * state
        state = decayed + beta[0, t, 0] * kt @ (v[0, t, 0][None, :] - kt.T @ decayed)
        np.testing.assert_allclose(got[0, t, 0], state.T @ q[0, t, 0], atol=2e-6)


@pytest.mark.parametrize("t", [0, 17, 63])
def test_the_delta_rule_is_causal(t):
    args = list(_gdn_inputs(seed=3, s=64))
    base = np.asarray(chunked(*args, chunk=16))
    for i in range(5):  # q, k, v, g, beta changed after t
        changed = list(args)
        changed[i] = changed[i].at[:, t + 1:].add(0.5 if i < 3 else -0.25)
        np.testing.assert_array_equal(np.asarray(chunked(*changed, chunk=16))[:, :t + 1], base[:, :t + 1])


def test_the_rule_never_takes_kdas_kernels(monkeypatch):
    """On a TPU with widths of 128 KDA takes its Pallas pair; GDN's route is
    its own, and ``gdn`` runs the chunked form even where KDA's kernels
    would be taken."""
    from torchsnapshot_tpu.ops import kda as K
    from torchsnapshot_tpu.ops import pallas_kda

    def refuse(*a, **kw):
        raise AssertionError("kda_pallas reached")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_kda, "kda_pallas", refuse)
    assert K.kda_route(8192, 128, 128, 64) == "pallas"
    assert G.gdn_route(8192, 128, 128, 64) == "xla" == G.ROUTES[0]
    args = _gdn_inputs(s=64)
    got = jax.jit(G.gdn, static_argnames=("chunk",))(*args, chunk=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(chunked(*args, chunk=16)))


def _bf16_state_recurrence(q, k, v, g, beta):
    """The recurrence with its state rounded to bfloat16 after every position."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))[..., None, :]
        s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    b, _, h, dk = q.shape
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)[1], 0, 1)


def _bf16_sums(monkeypatch):
    """Round the chunk's running sums and span sums of the log-decay to
    bfloat16 in ``ops/gdn.py`` from now on."""
    orig = jnp.einsum

    def einsum(spec, *ops, **kw):
        out = orig(spec, *ops, **kw)
        if spec in ("rj,bhnjx->bhnrx", "rj,bhnji->bhnri"):
            return jax.lax.reduce_precision(out, exponent_bits=8, mantissa_bits=7)
        return out

    monkeypatch.setattr(G.jnp, "einsum", einsum)


def test_a_state_or_a_decay_sum_in_bfloat16_fails_the_tolerance(monkeypatch):
    """The tolerance above (1e-6 of outputs of order 0.3) is tight enough
    that the state held in bfloat16 fails it (read 7.7e-4), and so does the
    chunk's log-decay summed in bfloat16 (read 3.0e-4)."""
    args = _gdn_inputs(seed=4, s=128)
    want = np.asarray(recurrent(*args)[0])
    assert np.max(np.abs(np.asarray(_bf16_state_recurrence(*args)) - want)) > 100 * 1e-6
    _bf16_sums(monkeypatch)
    got = np.asarray(gdn_chunked(*args, chunk=64))
    assert np.max(np.abs(got - want)) > 100 * 1e-6


# ------------------------------------------------------------ the mixers


def test_rms0_with_zero_scales_is_plain_rmsnorm():
    x = 3.0 * _normed(seed=11)
    plain = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(M.rms0(x, jnp.zeros(x.shape[-1]), 1e-6)), np.asarray(plain), rtol=1e-6)
    w = jax.random.normal(jax.random.PRNGKey(12), (x.shape[-1],))
    np.testing.assert_allclose(np.asarray(M.rms0(x, w, 1e-6)), np.asarray(plain * (1 + w)), rtol=1e-5)


def test_gdn_is_the_published_pieces_written_as_a_loop_over_value_heads():
    """Per key head j: ``[q_j | k_j | v | z]`` and ``[b | a]`` from its
    columns, the depthwise convolution over the channels q, k, v, silu, an
    L2 norm a key head, then value head ``2 j + i`` reads key head j's q and
    k; ``beta``, ``g = -e^A softplus(a + dt_bias)``, the delta rule, a norm
    a value head and ``silu(z)``, then ``W_o``: numpy float64 loops against
    the mixer in two head blocks (float32: 1e-5)."""
    c = CFG
    w = _layer(c, "gdn")
    a = _normed()
    got = np.asarray(jax.jit(M.gdn_mixer, static_argnums=2)(w, a, c))
    wn = {k: np.asarray(x, np.float64) for k, x in w.items()}
    an = np.asarray(a, np.float64)
    Hk, Hv, dk, dv, r = c.n_key_heads, c.n_value_heads, c.key_head_dim, c.value_head_dim, c.group
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    want = np.zeros(got.shape)
    for bi in range(B):
        mixed, ba = an[bi] @ wn["qkvz"], an[bi] @ wn["ba"]
        width = 2 * dk + 2 * r * dv
        cols = lambda j, lo, hi: mixed[:, j * width + lo:j * width + hi]  # noqa: E731
        qkv = np.concatenate([cols(j, 0, dk) for j in range(Hk)] + [cols(j, dk, 2 * dk) for j in range(Hk)]
                             + [cols(j, 2 * dk, 2 * dk + r * dv) for j in range(Hk)], axis=1)
        padded = np.concatenate([np.zeros((c.conv_kernel - 1, qkv.shape[1])), qkv])
        qkv = silu(sum(padded[i:i + S] * wn["conv"][i] for i in range(c.conv_kernel)))
        outs = []
        for h in range(Hv):
            j, i = divmod(h, r)
            q = qkv[:, j * dk:(j + 1) * dk]
            k = qkv[:, Hk * dk + j * dk:Hk * dk + (j + 1) * dk]
            v = qkv[:, 2 * Hk * dk + h * dv:2 * Hk * dk + (h + 1) * dv]
            q = q / np.sqrt((q * q).sum(1, keepdims=True) + 1e-6) / np.sqrt(dk)
            k = k / np.sqrt((k * k).sum(1, keepdims=True) + 1e-6)
            beta = 1 / (1 + np.exp(-ba[:, j * 2 * r + i]))
            g = -np.exp(wn["A_log"][h]) * np.log1p(np.exp(ba[:, j * 2 * r + r + i] + wn["dt_bias"][h]))
            z = cols(j, 2 * dk + r * dv + i * dv, 2 * dk + r * dv + (i + 1) * dv)
            state, o = np.zeros((dk, dv)), np.zeros((S, dv))
            for t in range(S):
                decayed = np.exp(g[t]) * state
                state = decayed + beta[t] * np.outer(k[t], v[t] - k[t] @ decayed)
                o[t] = state.T @ q[t]
            o = o / np.sqrt((o * o).mean(1, keepdims=True) + c.norm_eps) * wn["o_norm"]
            outs.append(o * silu(z))
        want[bi] = np.concatenate(outs, 1) @ wn["o"]
    assert M.head_block(c) == 4 and c.n_value_heads == 4  # one block here; the blocked tests below cut it
    np.testing.assert_allclose(got, want, atol=1e-5)


# The published head: 256 wide, its leading 64 rotated, two heads to a KV head.
WIDE = dataclasses.replace(CFG, n_heads=2, n_kv_heads=1, head_dim=256, rotary_dim=64)


def test_gated_attention_is_the_published_pieces_written_as_a_loop_over_heads():
    """``[q_h | gate_h]`` from each head's columns, a zero-centred norm over
    the 256 of q and of k, the rotation on the **leading** 64 in
    rotate-half pairs ``(i, i + 32)``, softmax over a causal score matrix
    scaled by 1/16, each query head on its group's KV head, the output times
    ``sigmoid(gate)`` element by element, then ``W_o``: numpy float64 loops
    against the mixer on the dense route (float32: 1e-5)."""
    c = WIDE
    w = _layer(c, "attn", seed=1)
    a = _normed()
    _, attend = causal_attention_route("dense", 16, c.n_heads, None, B, S)
    got = np.asarray(M.gattn_mixer(w, a, c, attend))
    wn = {k: np.asarray(x, np.float64) for k, x in w.items()}
    an = np.asarray(a, np.float64)
    d, rot = c.head_dim, c.rotary_dim

    def rms0(x, scale):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + c.norm_eps) * (1 + scale)

    def rope(x):  # (S, d): the first ``rot`` rotated, pairs (i, i + rot / 2)
        half = rot // 2
        ang = np.arange(S)[:, None] * c.rope_theta ** (-np.arange(0, rot, 2) / rot)
        x1, x2 = x[:, :half], x[:, half:rot]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang), x2 * np.cos(ang) + x1 * np.sin(ang), x[:, rot:]], 1)

    want = np.zeros(got.shape)
    for bi in range(B):
        heads = []
        for h in range(c.n_heads):
            qg = (an[bi] @ wn["q"])[:, h * 2 * d:(h + 1) * 2 * d]
            kv = h // (c.n_heads // c.n_kv_heads)
            k = rope(rms0((an[bi] @ wn["k"])[:, kv * d:(kv + 1) * d], wn["k_norm"]))
            v = (an[bi] @ wn["v"])[:, kv * d:(kv + 1) * d]
            sc = rope(rms0(qg[:, :d], wn["q_norm"])) @ k.T / 16.0
            sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
            p = np.exp(sc - sc.max(1, keepdims=True))
            heads.append((p / p.sum(1, keepdims=True)) @ v / (1 + np.exp(-qg[:, d:])))
        want[bi] = np.concatenate(heads, 1) @ wn["o"]
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_every_route_takes_the_wide_grouped_head(impl):
    """256-wide heads, two query heads to a KV head: each route agrees with
    the dense one (float32 blocked softmax: 2e-6)."""
    c, w, a = WIDE, _layer(WIDE, "attn", seed=1), _normed()
    dense = M.gattn_mixer(w, a, c, causal_attention_route("dense", 16, c.n_heads, None, B, S)[1])
    route, attend = causal_attention_route(impl, 16, c.n_heads, None, B, S)
    assert route == impl
    np.testing.assert_allclose(np.asarray(M.gattn_mixer(w, a, c, attend)), np.asarray(dense), atol=2e-6)


# ------------------------------------------------------------ the experts


def test_the_shared_expert_is_scaled_a_token_by_its_gate():
    """``sigmoid(x w_s) * gated_ffn(x)``: each token's own scalar, and half
    the expert where the gate's weights are 0."""
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    x = _normed(seed=12, shape=(16, 24))
    gate, up = (jax.random.normal(k, (24, 32)) * 24**-0.5 for k in keys[:2])
    down, w_s = jax.random.normal(keys[2], (32, 24)) * 32**-0.5, jax.random.normal(keys[3], (24, 1))
    plain = moe.gated_ffn(x, gate, up, down)
    got = moe.sigmoid_gated_ffn(x, gate, up, down, w_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jax.nn.sigmoid(x @ w_s) * plain), rtol=1e-5, atol=1e-7)
    scale = np.asarray(got / plain)
    assert np.ptp(scale[:, 0]) > 0.1 and np.allclose(scale, scale[:, :1], rtol=1e-4)  # one scalar a token
    np.testing.assert_allclose(np.asarray(moe.sigmoid_gated_ffn(x, gate, up, down, jnp.zeros((24, 1)))),
                               np.asarray(0.5 * plain), rtol=1e-6)


def test_four_disjoint_shares_add_up_to_the_uncut_layer():
    """Sixteen experts held four to a share: what every share computes
    alike (the mixer, the route, the shared expert and its gate) is counted
    once, what each adds is its own experts' term. The sum is the layer with
    all sixteen held, and the reference's."""
    cfg = dataclasses.replace(CFG, held=tuple(range(16)))
    keys = jax.random.split(jax.random.PRNGKey(14), 3)
    D, F = cfg.d_model, cfg.expert_ff
    w = {**_layer(cfg, "attn"),
         "expert_gate": jax.random.normal(keys[0], (16, D, F)) * D**-0.5,
         "expert_up": jax.random.normal(keys[1], (16, D, F)) * D**-0.5,
         "expert_down": jax.random.normal(keys[2], (16, F, D)) * F**-0.5}
    x = 0.3 * _normed(seed=15)
    attend = causal_attention_route("dense", 16, cfg.n_heads, None, B, S)[1]
    layer = jax.jit(lambda w, x, cfg: M.layer(w, x, "attn", cfg, attend), static_argnums=2)
    whole, ids = layer(w, x, cfg)
    parts = []
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        part = {**w, **{k: w[k][jnp.asarray(held)] for k in ("expert_gate", "expert_up", "expert_down")}}
        out, ids_part = layer(part, x, dataclasses.replace(cfg, held=held))
        np.testing.assert_array_equal(ids_part, ids)  # every share scores and chooses over all sixteen
        parts.append(out)
    none = {**w, **{k: jnp.zeros_like(w[k]) for k in ("expert_gate", "expert_up", "expert_down")}}
    alike, _ = layer(none, x, cfg)
    np.testing.assert_allclose(sum(parts) - 3 * alike, whole, atol=5e-6)
    assert all(not np.allclose(p, alike, atol=1e-4) for p in parts)
    args = _ref_args(cfg)
    with jax.default_matmul_precision("highest"):
        mixed = x + R.attn(w, R._rms(x, 1.0 + w["ln1_scale"], cfg.norm_eps), **args)
        y, want_ids = R.moe(w, R._rms(mixed, 1.0 + w["ln2_scale"], cfg.norm_eps), **args)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(mixed + y), atol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(np.asarray(want_ids).reshape(ids.shape), -1))


# ------------------------------------------------------------ the stack


def test_the_published_layout_is_three_gdn_layers_to_one_attention_layer():
    full = M.GDNLMConfig()
    assert full.kinds.count("attn") == 12 and full.kinds.count("gdn") == 36
    assert [i for i, k in enumerate(full.kinds) if k == "attn"] == list(range(3, 48, 4))
    assert CFG.kinds == ["gdn", "gdn", "gdn", "attn", "gdn"]
    assert CFG.runs == [("gdn", 0, 3), ("attn", 0, 1), ("gdn", 3, 4)] and CFG.stack_sizes == {"gdn": 4, "attn": 1}
    assert (full.group, full.rotary_dim, full.head_dim) == (2, 64, 256)


@pytest.fixture(scope="module")
def reference_logits():
    """The reference's logits for ``CFG``'s weights and the tests' batch."""
    return jax.jit(lambda p, t: R.forward(p, t, **_ref_args(CFG)))(_params(CFG), _batch()["tokens"])


@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-6, 2e-5), (jnp.bfloat16, 1e-2, 0.2)])
def test_the_stack_agrees_with_the_reference(dtype, median_tol, max_tol, reference_logits):
    """Logits relative to the reference's largest. float32: reassociation
    only (read 1.6e-7 / 9e-7). bfloat16 operands: 2^-9 a product through
    five layers, and a near-tie in a router can go the other way, so the
    bulk is held and the tail is loose (read 2.3e-3 / 5e-2)."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params, toks = _params(cfg), _batch()["tokens"]
    got = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, toks)
    want = reference_logits
    err = np.asarray(jnp.max(jnp.abs(got - want), -1) / jnp.max(jnp.abs(want)))
    assert np.median(err) < median_tol and err.max() < max_tol, (np.median(err), err.max())


@pytest.mark.parametrize("where", ["state", "decay sums"])
def test_a_state_or_a_decay_sum_in_bfloat16_fails_the_stacks_float32_tolerance(where, monkeypatch, reference_logits):
    """The float32 stack's median tolerance (2e-6) fails the delta rule's
    state held in bfloat16 (read 6.7e-4) and its log-decay summed in bfloat16
    (read 6.7e-5). With bfloat16 operands the stack already reads 2.3e-3, and
    either departure moves that by under a tenth: the bfloat16 case above
    cannot tell them apart, this one can."""
    if where == "state":
        monkeypatch.setattr(G, "gdn", lambda q, k, v, g, beta, chunk: _bf16_state_recurrence(q, k, v, g, beta))
    else:
        _bf16_sums(monkeypatch)
    got = jax.jit(lambda p, t: M.forward(p, t, CFG))(_params(CFG), _batch()["tokens"])
    want = reference_logits
    err = np.asarray(jnp.max(jnp.abs(got - want), -1) / jnp.max(jnp.abs(want)))
    assert np.median(err) > 10 * 2e-6, np.median(err)


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
    chunked delta rule sum in other orders)."""
    params, batch = _params(CFG), _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, CFG)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: R.loss(p, batch, **_ref_args(CFG))))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-12)
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


# The blocks of the head and of a GDN mixer's heads need the head and a GDN
# layer, not the whole stage: one GDN layer.
ONE_GDN = dataclasses.replace(CFG, layers=(0,))


@pytest.fixture(scope="module")
def whole_head():
    """The loss and gradient with the head and loss in one block and the GDN
    mixer's heads in one block, the same for every case below."""
    batch = _batch()
    return jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, dataclasses.replace(ONE_GDN, head_block=S))))(
        _params(ONE_GDN))


@pytest.mark.parametrize("head_block", [8, 32])
@pytest.mark.parametrize("gdn_block", [2, 4])
def test_the_blocked_head_and_gdn_head_blocks_are_the_whole(head_block, gdn_block, monkeypatch, whole_head):
    """Head and loss in blocks of positions, and the GDN mixer in blocks of
    value heads (one or two key heads a block): the same loss and gradient
    (float32 sums in another order: 2e-6)."""
    params, batch = _params(ONE_GDN), _batch()
    want, gw = whole_head
    monkeypatch.setattr(M, "GDN_HEAD_BLOCK", gdn_block)
    cfg = dataclasses.replace(ONE_GDN, head_block=head_block)
    got, gg = jax.jit(jax.value_and_grad(lambda p: M.loss_fn(p, batch, cfg)))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


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


def test_keeping_the_gdn_head_blocks_output_runs_the_algebra_forward_twice(monkeypatch):
    """A GDN layer's checkpoint keeps ``gdn_heads``: the loss and every
    gradient leaf are those of checkpoints that keep nothing, bit for bit,
    and the layer holds 8 solves (two a forward, four a backward: a forward,
    the head blocks' recomputation and their backward), where a third
    forward, in the layer's recomputation, would make 10."""
    batch = _batch()
    cparams = M.compute_params(_params(ONE_GDN), ONE_GDN)
    f = jax.value_and_grad(lambda p: M._objective(p, batch, ONE_GDN, None))
    assert _eqns_named(jax.make_jaxpr(f)(cparams).jaxpr, "triangular_solve") == 8
    loss, grads = jax.jit(f)(cparams)
    monkeypatch.setattr(M, "GDN_LAYER_POLICY", jax.checkpoint_policies.nothing_saveable)
    f = jax.value_and_grad(lambda p: M._objective(p, batch, ONE_GDN, None))
    assert _eqns_named(jax.make_jaxpr(f)(cparams).jaxpr, "triangular_solve") == 10
    want_loss, want = jax.jit(f)(cparams)
    assert float(loss) == float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))


_LOADS = dataclasses.replace(CFG, vocab_size=4096, d_model=64, layers=(0, 1, 2, 3), key_head_dim=16,
                             value_head_dim=16, chunk=64, head_dim=32, rotary_dim=8, n_experts=512, top_k=10,
                             held=tuple(range(32)), head_block=256, dtype=jnp.bfloat16)
_loads_stats = jax.jit(lambda p, t: M.routing_stats(p, t, _LOADS))  # compiled once for the seeds below


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_init_keeps_random_routers_near_even_loads(seed):
    """At the published routing (softmax top-10 of 512, the held experts
    ids 0-31), the published period and a small stream: the held experts
    draw 0.7-1.4 x their even share in every layer (640 assignments
    expected a layer over 1024 tokens of a vocabulary of 4096; read
    0.89-1.14), and no held expert more than 3.5 x their mean (read
    1.97-2.86 at 20 rows an expert)."""
    params = M.init_params(jax.random.PRNGKey(seed), _LOADS)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 50), (4, 256), 0, _LOADS.vocab_size)
    stats = _loads_stats(params, toks)
    share = np.asarray(stats["held_share"]) / (len(_LOADS.held) / _LOADS.n_experts)
    assert np.all((share > 0.7) & (share < 1.4)), share
    assert np.all(np.asarray(stats["max_over_mean"]) < 3.5), stats["max_over_mean"]


def test_a_bad_layout_is_refused():
    with pytest.raises(ValueError, match="held expert ids"):
        dataclasses.replace(CFG, held=(0, 0))
    with pytest.raises(ValueError, match="in order"):
        dataclasses.replace(CFG, layers=(2, 0))
    with pytest.raises(ValueError, match="value heads over"):
        dataclasses.replace(CFG, n_value_heads=5)


def test_the_traced_step_reports_the_route_the_head_block_and_the_experts_held():
    """As the step is traced: the form the delta rule runs (its index in
    ``ops/gdn.py`` ``ROUTES``: the chunked form, on every backend), the
    value heads a pass of the GDN mixer and the experts held."""
    cfg, tx = dataclasses.replace(CFG, dtype=jnp.bfloat16), M.make_optimizer()
    state = jax.eval_shape(lambda k: M.init_state(k, cfg, tx), jax.random.PRNGKey(0))
    telemetry.set_enabled(True)
    try:
        jax.eval_shape(M.make_train_step(cfg, tx), state, jax.eval_shape(_batch))
        gauges = telemetry.gauges()
    finally:
        telemetry.set_enabled(False)
    assert gauges["gdn_lm.gdn_route"] == G.ROUTES.index("xla") == 0
    assert gauges["gdn_lm.gdn_head_block"] == 4 and gauges["gdn_lm.held_experts"] == 4


# The train state's plumbing needs each kind of layer once, not the stage:
# a GDN layer and the period's attention layer.
PLUMBING = dataclasses.replace(CFG, layers=(2, 3))


def _routes():
    params, toks = _params(PLUMBING), _batch()["tokens"]
    stats, chosen = jax.jit(lambda p, t: (M.routing_stats(p, t, PLUMBING), M.chosen_experts(p, t, PLUMBING)))(params, toks)
    chosen = np.stack([np.asarray(i) for i in chosen])
    want = np.stack([np.asarray(i).reshape(-1, CFG.top_k) for i in jax.jit(lambda p, t: R.chosen_experts(p, t, **_ref_args(PLUMBING)))(params, toks)])
    assert len(want) == 2 and stats["held_counts"].shape == (2, 4) and chosen.shape == (2, B * S, CFG.top_k)
    return stats, chosen, want


@pytest.fixture(scope="module")
def model():
    return Model(
        M, PLUMBING, _batch,
        gauges={"gdn_lm.layers": 2, "gdn_lm.matmul_params_per_token": PLUMBING.matmul_params_per_token},
        scopes=("gdn_proj", "gdn_conv", "gdn_intra", "gdn_inter", "gdn_norm", "gattn_proj", "gattn_attn",
                "gattn_gate", "moe_route", "moe_experts", "moe_shared", "lm_head"),
        leaves=(110, 4),  # 36 parameters, both moments, the step and the count
        update_atol=5e-6,  # the hybrid's bound: two programs that sum in other orders
        routes=_routes,
    )


def test_save_restore_and_the_next_step_is_the_uninterrupted_runs(model, tmp_path):
    """Two steps, an async save through ``CheckpointManager``, a restore
    into a state from another seed (every leaf equal), and the next step's
    loss equal to the uninterrupted run's."""
    checkpoint_round_trip(model, tmp_path / "ckpt", async_save=True)
