"""The Pallas kernels of the chunked gated delta rule (``ops/pallas_kda.py``)
against the recurrence and the XLA chunked form (``ops/kda.py``), in
interpret mode on the CPU; what precision their products ask for; that
both compile for a described v5e at the ``ling3flash.save`` cell's shape;
and the KDA / MLA model on the kernels' route (``kda_route``)."""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import telemetry
from torchsnapshot_tpu.models import kda_mla_lm as M
from torchsnapshot_tpu.ops import kda as K, pallas_kda as PK

from tests.test_kda_mla_lm import CFG, _batch, _kda_inputs, _objective_value_and_grad


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# (b, s, h, d_k, d_v, chunk, g). At the bound a decay of -5 on every channel
# and position: there ``kda_chunked``'s own gradient of g misses the
# recurrence's by 3e-4 of its largest (the reverse cumulative sum of terms
# that cancel: P's diagonal, which no decay moves), so there the kernels
# are held to the recurrence and to being the nearer of the two.
CASES = {
    "shorter_than_a_chunk": (2, 40, 1, 16, 8, 64, None),
    "several_chunks": (2, 128, 3, 16, 8, 64, None),
    "small_chunks": (1, 64, 1, 16, 8, 16, None),
    "at_the_bound": (2, 128, 1, 16, 8, 64, -5.0),
    "near_no_decay": (2, 128, 1, 16, 8, 64, -1e-4),
    "d_v_wider_than_d_k": (3, 64, 1, 8, 16, 32, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_recurrence_and_the_chunked_form(case):
    """Every input's gradient of a nonlinear read of the output to 1e-5 of
    that gradient's largest, against both; the output to 2e-6 of its
    largest against the recurrence (read 4e-7) and to 2e-6 against
    ``kda_chunked``, the file's tolerance between the two forms, whose own
    error against the recurrence reaches 2.2e-6 of the largest here."""
    b, s, h, dk, dv, chunk, g = CASES[case]
    args = _kda_inputs(seed=5, b=b, s=s, h=h, dk=dk, dv=dv, g=g)
    forms = {"kernels": lambda *a: PK.kda_pallas(*a, chunk=chunk),
             "chunked": lambda *a: K.kda_chunked(*a, chunk=chunk),
             "recurrent": lambda *a: K.kda_recurrent(*a)[0]}
    out = {n: jax.jit(f)(*args) for n, f in forms.items()}
    read = lambda o: jnp.sum(jnp.sin(3.0 * o))  # noqa: E731
    grads = {n: jax.jit(jax.grad(lambda *a, f=f: read(f(*a)), argnums=range(5)))(*args) for n, f in forms.items()}
    assert _rel(out["kernels"], out["recurrent"]) < 2e-6
    np.testing.assert_allclose(np.asarray(out["kernels"]), np.asarray(out["chunked"]), atol=2e-6)
    for ref in ("recurrent", "chunked"):
        for name, got, want, chunked in zip("q k v g beta".split(), grads["kernels"], grads[ref], grads["chunked"]):
            if ref == "chunked" and g == -5.0 and name == "g":
                truth = grads["recurrent"][3]
                assert _rel(chunked, truth) > 1e-5 and _rel(got, truth) < _rel(chunked, truth) / 10
                continue
            assert np.isfinite(np.asarray(got)).all() and _rel(got, want) < 1e-5, (ref, name, _rel(got, want))


def _dots_and_dtypes(jaxpr, found=None):
    """Every ``dot_general``'s params and every value's dtype, in ``jaxpr``
    and every jaxpr inside it."""
    found = found if found is not None else {"dots": [], "dtypes": set(), "kernels": []}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found["dots"].append(eqn.params)
        if eqn.primitive.name == "pallas_call":
            found["kernels"].append(eqn.params["name"])
        found["dtypes"].update(str(v.aval.dtype) for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "dtype"))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dots_and_dtypes(sub, found)
    return found


def _kernel_jaxprs(B=1, S=128, H=2, d=8, chunk=64):
    x, b = jnp.zeros((B, S, H, d)), jnp.zeros((B, S, H))
    states, mats = jnp.zeros((B, S // chunk, H, d, d)), jnp.zeros((B, S // chunk, H, 3, chunk, chunk))
    fwd = jax.make_jaxpr(lambda *a: PK._fwd(*a, chunk=chunk, interpret=False))(x, x, x, x, b)
    bwd = jax.make_jaxpr(lambda *a: PK._bwd(*a, chunk=chunk, interpret=False))(x, x, x, x, b, states, mats, x)
    return _dots_and_dtypes(fwd.jaxpr), _dots_and_dtypes(bwd.jaxpr)


def test_every_product_in_the_kernels_is_float32_at_highest(monkeypatch):
    """Interpret mode on the CPU multiplies in float32 whatever a product
    asks for, and Mosaic's default is lower: every ``dot_general`` inside
    ``kda_fwd`` and ``kda_bwd`` asks for ``HIGHEST`` (Mosaic's
    ``contract_precision<fp32>``) with a float32 result, and no value is
    bfloat16. The guard is alive: at ``DEFAULT`` it sees every product."""
    for found, name in zip(_kernel_jaxprs(), ("kda_fwd", "kda_bwd")):
        assert found["kernels"] == [name]
        assert len(found["dots"]) > 20, len(found["dots"])
        for p in found["dots"]:
            assert p["precision"] == (jax.lax.Precision.HIGHEST,) * 2 and p["preferred_element_type"] == jnp.float32, p
        assert "bfloat16" not in found["dtypes"] and "float32" in found["dtypes"], found["dtypes"]
    monkeypatch.setattr(PK, "HI", jax.lax.Precision.DEFAULT)
    jax.clear_caches()
    fwd, _ = _kernel_jaxprs()
    assert all(p["precision"] != (jax.lax.Precision.HIGHEST,) * 2 for p in fwd["dots"])
    jax.clear_caches()


@pytest.fixture(scope="module")
def v5e():
    """A v5e chip described, not attached (``tests/test_transformer_step.py``
    ``v5e_2x2``): made in a fixture, never at import, since one process at a
    time may hold libtpu and every xdist worker imports this file."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001 - whatever stops the description stops the test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_both_kernels_compile_for_a_v5e_at_the_cells_shape(v5e):
    """``ling3flash.save``'s head block: 8 heads of 128 over 8192 positions,
    chunks of 64. Mosaic's tiling rules and VMEM limits, which interpret
    mode does not apply, accept both kernels."""
    from jax.sharding import SingleDeviceSharding

    B, S, H, d, chunk = 1, 8192, 8, 128, 64
    one = SingleDeviceSharding(v5e)

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)

    x, b = shape(B, S, H, d), shape(B, S, H)
    fwd = jax.jit(lambda *a: PK._fwd(*a, chunk=chunk, interpret=False)).lower(x, x, x, x, b).compile()
    assert "kda_fwd" in fwd.as_text()
    bwd = jax.jit(lambda *a: PK._bwd(*a, chunk=chunk, interpret=False)).lower(
        x, x, x, x, b, shape(B, S // chunk, H, d, d), shape(B, S // chunk, H, 3, chunk, chunk), x).compile()
    assert "kda_bwd" in bwd.as_text()


def test_the_route_takes_the_kernels_on_a_tpu_at_tiled_widths_only(monkeypatch):
    assert K.kda_route(8192, 128, 128, 64) == "xla"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.kda_route(8192, 128, 128, 64) == "pallas"
    assert K.kda_route(8192, 64, 128, 64) == "xla" and K.kda_route(8192, 128, 96, 64) == "xla"
    assert K.kda_route(8160, 128, 128, 64) == "xla" and K.kda_route(40, 128, 128, 64) == "xla"


def test_the_model_on_the_kernels_route_is_the_xla_routes_and_says_so(monkeypatch):
    """``CFG`` cut to its first two layers (a dense KDA layer and a KDA
    expert layer) with the route forced to the kernels (toy widths,
    interpret mode): the loss and every gradient leaf are the XLA route's
    (float32 sums in another order: 1e-5 of a leaf's largest), the kernels
    run forward twice a KDA layer and backward once, and the traced step's
    gauge counts the two KDA layers."""
    cfg = dataclasses.replace(CFG, layers=CFG.layers[:2], expert_limits=CFG.expert_limits[:2],
                              shared_limits=CFG.shared_limits[:2])
    f, params = _objective_value_and_grad(cfg)
    want_loss, want = jax.jit(f)(params)
    monkeypatch.setattr(K, "kda_route", lambda *a: "pallas")
    f, params = _objective_value_and_grad(cfg)
    traced = jax.jit(f).trace(params)
    kernels = [n for n in _dots_and_dtypes(traced.jaxpr.jaxpr)["kernels"] if n.startswith("kda")]
    assert sorted(kernels) == ["kda_bwd"] * 2 + ["kda_fwd"] * 4, kernels
    loss, grads = traced.lower().compile()(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-12)
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(w) / scale, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    tx = M.make_optimizer()
    state = jax.eval_shape(lambda k: M.init_state(k, cfg, tx), jax.random.PRNGKey(0))
    telemetry.set_enabled(True)
    try:
        jax.eval_shape(M.make_train_step(cfg, tx), state, jax.eval_shape(_batch))
        gauges = telemetry.gauges()
    finally:
        telemetry.set_enabled(False)
    assert gauges["kda_mla_lm.kda_kernel_layers"] == 2
