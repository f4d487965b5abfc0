"""The looped LM (``models/looped_lm.py``) against its plain reference
(``benchmarks/chip/reference/looped_lm.py``: float32, Python loops over the
passes and the layers, dense attention, no scan, no checkpoint), and through
the train step and ``CheckpointManager`` as the transformer goes.
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

from torchsnapshot_tpu import CheckpointManager, StateDict
from torchsnapshot_tpu.models import looped_lm as M, transformer as T
from torchsnapshot_tpu.ops.attention import causal_attention_route
from torchsnapshot_tpu.parallel import make_mesh
from model_kit_cases import (  # noqa: F401  (the cases this file runs)
    Model,
    test_the_named_scopes_reach_the_lowered_step,
    test_the_step_puts_what_it_compiles_on_the_telemetry_bus,
    test_the_step_reports_a_finite_loss_and_keeps_its_layout,
    test_the_train_state_goes_through_the_checkpoint_manager_bit_exact,
    test_the_train_steps_gradient_is_the_losss,
)

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "chip", "reference", "looped_lm.py")
_spec = importlib.util.spec_from_file_location("looped_lm_reference", _REF)
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

V, D, B, S = 96, 32, 4, 16
CFG = M.LoopedLMConfig(
    vocab_size=V, d_model=D, n_heads=4, head_dim=16, n_layers=3, d_ff=48, ut_steps=4,
    dtype=jnp.float32,
)
MESHES = {"2x2": {"data": 2, "model": 2}, "1x4": {"data": 1, "model": 4}}


def _ref_args(cfg):
    return {"n_heads": cfg.n_heads, "ut_steps": cfg.ut_steps, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps}


def _params(cfg, seed=0):
    """Seeded weights with every scale and the gate's bias off their
    initial 1 and 0, so that a scale applied in the wrong place shows."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))

    def jitter(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "gate_b" in name:
            return x + 0.2 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(jitter, params)


def _batch(mesh=None, seed=7):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, V, jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    return batch


def _mesh(name):
    return make_mesh(MESHES[name], devices=jax.devices()[:4])


# Per position, the largest error over the vocabulary as a share of the
# largest reference logit (the harness's statistic). float32 compute differs
# from the reference only in the order of additions (scan against loop,
# log-space products): 1e-6 read at the worst position, held to 2e-5; a
# bfloat16 program reads 1e-2 and fails it. bfloat16 operands through 4 x 3
# applications at width 32 read 0.5e-2 to 1.1e-2 at the median position,
# growing pass by pass, held to 2e-2; single positions read up to 6e-2 over
# seeds (width 32 averages little), held to 1e-1. float8 operands (2^-4
# against 2^-9) would read 1e-1 at the median, and a wrong mask, rotation,
# scale or order, or a pass left out, is off by the logits' whole range. The
# exit distribution is a probability: the same two bounds, absolute.
@pytest.mark.parametrize("dtype,median_tol,max_tol", [(jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 2e-2, 1e-1)])
def test_every_pass_and_the_exit_distribution_agree_with_the_reference(dtype, median_tol, max_tol):
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params, tokens = _params(cfg), _batch()["tokens"]
    logits, exit_p = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, tokens)
    want = R.forward(params, tokens, **_ref_args(cfg))  # (B, S, T, V + 1)
    assert logits.shape == (cfg.ut_steps, B, S, V) and exit_p.shape == (cfg.ut_steps, B, S)
    scale = float(jnp.max(jnp.abs(want[..., :V])))
    for t in range(cfg.ut_steps):
        err = np.asarray(jnp.max(jnp.abs(logits[t] - want[:, :, t, :V]), axis=-1)) / scale
        assert np.median(err) <= median_tol and err.max() <= max_tol, (t, np.median(err), err.max())
    want_p = jnp.stack(R.exit_distribution([want[:, :, t, V] for t in range(cfg.ut_steps)]))
    err = np.abs(np.asarray(exit_p - want_p))
    assert np.median(err) <= median_tol and err.max() <= max_tol, (np.median(err), err.max())


# Loss and every gradient leaf against jax.grad of the reference's loss, in
# float32: the reference unrolls T x L applications that read the same
# weights, so its gradient is the sum over the passes by construction;
# the program gets it from a scan that closes over them, through
# jax.checkpoint. 4e-6 of a leaf's largest entry read, held to 5e-5.
@pytest.mark.parametrize("ut_steps,n_layers", [(4, 3), (2, 2), (1, 2)])
def test_loss_and_every_gradient_leaf_agree_with_the_reference(ut_steps, n_layers):
    cfg = dataclasses.replace(CFG, ut_steps=ut_steps, n_layers=n_layers)
    params, batch = _params(cfg), _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p, b: M.loss_fn(p, b, cfg)))(params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss(p, b, exit_beta=cfg.exit_beta, **_ref_args(cfg))))(params, batch)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        name = jax.tree_util.keystr(path)
        if ut_steps == 1 and "exit_gate" in name:  # one pass: the gate decides nothing
            assert float(jnp.max(jnp.abs(got[path]))) == 0.0 == float(jnp.max(jnp.abs(w))), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        err = float(jnp.max(jnp.abs(got[path] - w)) / jnp.max(jnp.abs(w)))
        assert err <= 5e-5, (name, err)


def test_one_pass_is_the_first_pass_of_four_and_exits_there():
    params, tokens = _params(CFG), _batch()["tokens"]
    four, _ = M.forward(params, tokens, CFG)
    one, exit_p = M.forward(params, tokens, dataclasses.replace(CFG, ut_steps=1))
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(four[0]), rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(exit_p), np.ones((1, B, S), np.float32))
    # Each later pass starts from the closed state of the one before: not a copy.
    assert float(jnp.max(jnp.abs(four[1] - four[0]))) > 1e-2


@pytest.mark.parametrize("ut_steps", [1, 2, 4, 7])
def test_the_exit_distribution_sums_to_one(ut_steps):
    g = 3.0 * jax.random.normal(jax.random.PRNGKey(ut_steps), (ut_steps, 5, 9))
    p = M.exit_distribution(g)
    assert p.shape == g.shape and float(p.min()) >= 0.0
    np.testing.assert_allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    # Against the definition, pass by pass.
    lam = np.asarray(jax.nn.sigmoid(g), np.float64)
    stayed = np.ones(g.shape[1:])
    for t in range(ut_steps):
        want = stayed if t == ut_steps - 1 else stayed * lam[t]
        np.testing.assert_allclose(np.asarray(p[t]), want, atol=1e-6)
        stayed = stayed * (1 - lam[t])


def test_the_entropy_term_lowers_the_loss_and_a_certain_gate_removes_it():
    """loss = E_p[CE] - beta H(p): H >= 0, so a larger beta never raises
    the loss, and with the gate saturated (always exit at pass 1) the
    entropy is 0 and beta drops out."""
    params, batch = _params(CFG), _batch()
    at = {b: float(M.loss_fn(params, batch, dataclasses.replace(CFG, exit_beta=b))) for b in (0.0, 0.1, 1.0)}
    assert at[0.0] > at[0.1] > at[1.0]
    gate_logits = M.pass_outputs(params, batch["tokens"], CFG)[1]
    p = np.asarray(M.exit_distribution(gate_logits), np.float64)
    entropy = float(np.mean(-(p * np.log(p)).sum(axis=0)))
    np.testing.assert_allclose((at[0.0] - at[1.0]), entropy, rtol=1e-4)
    sure = {**params, "exit_gate_w": jnp.zeros_like(params["exit_gate_w"]),
            "exit_gate_b": jnp.full_like(params["exit_gate_b"], 60.0)}
    np.testing.assert_allclose(
        float(M.loss_fn(sure, batch, dataclasses.replace(CFG, exit_beta=1.0))),
        float(M.loss_fn(sure, batch, dataclasses.replace(CFG, exit_beta=0.0))), rtol=1e-6)


def test_the_published_sizes_count_to_the_published_model():
    cfg = M.LoopedLMConfig()  # the defaults are Ouro-2.6B's config.json
    assert cfg.layer_matmul_params == 51_380_224
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 2.66e9 < n < 2.68e9  # "2.6B": 48 layers and two vocabulary matrices
    assert set(shapes["layers"]) == {"q", "k", "v", "o", "gate", "up", "down",
                                     "ln1_scale", "ln2_scale", "ln3_scale", "ln4_scale"}
    assert cfg.matmul_params_per_token == 4 * (48 * 51_380_224 + 49152 * 2048) + 2048


def test_both_models_take_their_attention_from_one_dispatch(monkeypatch):
    small = T.TransformerConfig(vocab_size=V, d_model=D, n_heads=4, n_layers=1, d_ff=64, max_seq_len=S)
    for mesh, b, s in [(None, 2, 16), (None, 2, 1024), (_mesh("2x2"), 4, 1024)]:
        route = causal_attention_route("auto", 512, 4, mesh, b, s)[0]
        assert M.select_attention(CFG, mesh, b, s) == T.select_attention(small, mesh, b, s) == route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert M.select_attention(CFG, None, 2, 2048) == "flash"
    assert M.select_attention(CFG, _mesh("2x2"), 4, 2048) == "flash_sharded"
    assert M.select_attention(CFG, _mesh("1x4"), 4, 2048) == "flash_sharded"


def test_the_blockwise_route_runs_the_same_block():
    """S past one attention block selects the scanned online-softmax path
    off-TPU; the block around it is the same and agrees with the reference."""
    cfg = dataclasses.replace(CFG, n_layers=1, ut_steps=2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 1024), 0, V, jnp.int32)
    assert M.select_attention(cfg, None, 1, 1024) == "blockwise"
    params = _params(cfg)
    logits, _ = jax.jit(lambda p, t: M.forward(p, t, cfg))(params, tokens)
    want = R.forward(params, tokens, **_ref_args(cfg))
    err = jnp.max(jnp.abs(jnp.moveaxis(logits, 0, 2) - want[..., :V])) / jnp.max(jnp.abs(want))
    assert float(err) <= 2e-5


@pytest.fixture(scope="module")
def model():
    return Model(
        M, CFG, _batch,
        gauges={"looped_lm.ut_steps": 4, "looped_lm.layer_applications": 12,
                "looped_lm.matmul_params_per_token": CFG.matmul_params_per_token},
        scopes=("pass", "block", "exit_gate", "lm_head"),
        leaves=(50, 32),
    )


# As tests/test_transformer_step.py holds the transformer: float32 leaves
# only the order of the sharded additions (held to 2e-6), bfloat16 rounds
# at other points under another layout (held to 2e-2).
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_the_sharded_loss_equals_the_one_device_loss(mesh_name, dtype, tol):
    cfg = dataclasses.replace(CFG, dtype=dtype)
    mesh = _mesh(mesh_name)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    want = jax.jit(lambda p, b: M.loss_fn(p, b, cfg))(params, _batch())
    sharded = M.init_state(jax.random.PRNGKey(0), cfg, M.make_optimizer(), mesh=mesh)["params"]
    assert sharded["embed"].sharding.spec == sharded["head"].sharding.spec == P("model", None)
    got = jax.jit(lambda p, b: M.loss_fn(p, b, cfg, mesh=mesh))(sharded, _batch(mesh))
    np.testing.assert_allclose(float(got), float(want), rtol=tol)


def _train(cfg, tx, mesh, state, steps, first=1):
    step = jax.jit(M.make_train_step(cfg, tx, mesh=mesh), donate_argnums=0)
    loss = None
    for n in range(first, first + steps):
        state, loss = step(state, _batch(mesh, seed=n))
    return state, float(loss)


def test_a_state_saved_under_2x2_resumes_under_1x4(tmp_path):
    """Saved sharded {data 2, model 2}, restored into {data 1, model 4}:
    bit-exact leaf for leaf, the next loss within the reshard band (bf16
    contractions split differently over 'model': the harness's 5e-3)."""
    cfg, tx = dataclasses.replace(CFG, dtype=jnp.bfloat16), M.make_optimizer()
    src, dst_mesh = _mesh("2x2"), _mesh("1x4")
    state, _ = _train(cfg, tx, src, M.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=src), 2)
    saved = jax.tree_util.tree_map(np.asarray, state)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1, keep_last=1, async_save=True)
    assert mgr.save(2, {"train": StateDict(**state)})
    mgr.wait()
    _, want_loss = _train(cfg, tx, src, state, 1, first=3)

    dst = StateDict(**M.init_state(jax.random.PRNGKey(1), cfg, tx, mesh=dst_mesh))
    want_shardings = [x.sharding for x in jax.tree_util.tree_leaves(dict(dst))]
    assert mgr.restore({"train": dst}) == 2
    restored = dict(dst)
    for (path, a), b, sh in zip(jax.tree_util.tree_flatten_with_path(saved)[0],
                                jax.tree_util.tree_leaves(restored), want_shardings):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=jax.tree_util.keystr(path))
        assert b.sharding.is_equivalent_to(sh, b.ndim), jax.tree_util.keystr(path)
    _, got_loss = _train(cfg, tx, dst_mesh, restored, 1, first=3)
    np.testing.assert_allclose(got_loss, want_loss, rtol=5e-3)
