"""The train-state plumbing every model of the zoo goes through
(``torchsnapshot_tpu/models/kit.py``), tested once for all of them.

A model's test file defines a module-scoped ``model`` fixture that returns
a ``Model`` and imports the cases it runs by name; pytest collects them
there, so they run on the worker that holds that file, and the fixture
builds each state and compiles each step once for all of them. What only
one model does (a frozen leaf, a noise key, a gauge set as the step is
traced) is tested in that model's own file, on the same fixture.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from torchsnapshot_tpu import CheckpointManager, StateDict, telemetry
from torchsnapshot_tpu.ops.moe import held_row_tile
from torchsnapshot_tpu.parallel import make_mesh

MESH_2X2 = {"data": 2, "model": 2}


@dataclasses.dataclass
class Model:
    """One model of the zoo at a toy size: ``module`` is the model's file,
    ``cfg`` its float32 toy config, ``batch(mesh=None, seed=7)`` a batch of
    ``B`` sequences of ``S``. The rest is what the cases expect of it."""

    module: Any
    cfg: Any
    batch: Callable[..., Dict[str, jax.Array]]
    gauges: Dict[str, Any]  # what make_train_step puts on the bus
    scopes: Tuple[str, ...]  # named scopes the lowered step must carry
    leaves: Tuple[int, int]  # leaves of the train state, and the size of its smallest array that is not a scalar
    still: Tuple[str, ...] = ()  # parameter leaves one step leaves where they are
    descends: bool = True  # three steps on one batch lower the loss (no noise drawn a step)
    update_atol: float = 2e-6  # a float32 step's parameters against loss_fn's gradient's update (lr 1e-3)
    loss_kwargs: Callable[[Dict[str, Any]], Dict[str, Any]] = lambda state: {}  # loss_fn's keywords for a state
    routes: Optional[Callable[[], Tuple[Dict[str, Any], np.ndarray, np.ndarray]]] = None
    route_agreement: float = 1.0  # share of positions whose chosen set is the reference's (float32: bar an exact tie)
    ratio_rtol: float = 1e-6  # routing_stats' max_over_mean against the count's
    _compiled: Dict[Any, Any] = dataclasses.field(default_factory=dict, repr=False)

    @functools.cached_property
    def tx(self):
        return self.module.make_optimizer()

    @functools.cached_property
    def state(self):
        return self.module.init_state(jax.random.PRNGKey(0), self.cfg, self.tx)

    def loss(self, params, batch, state, mesh=None):
        return self.module.loss_fn(params, batch, self.cfg, mesh=mesh, **self.loss_kwargs(state))

    @functools.cached_property
    def lowered(self):
        """The float32 step, lowered for ``state`` and the default batch."""
        return jax.jit(self.module.make_train_step(self.cfg, self.tx)).lower(self.state, self.batch())

    @functools.cached_property
    def compiled(self):
        return self.lowered.compile()

    @functools.cached_property
    def stepped(self):
        """(state, loss) of one float32 step from ``state``."""
        return self.compiled(self.state, self.batch())

    @functools.cached_property
    def routed(self):
        return self.routes()

    @functools.cached_property
    def low_precision(self):
        return dataclasses.replace(self.cfg, dtype=jnp.bfloat16)

    def fresh_state(self, seed=0, mesh=None):
        return self.module.init_state(jax.random.PRNGKey(seed), self.low_precision, self.tx, mesh=mesh)

    def compiled_step(self, mesh_axes=None):
        """(mesh, the bfloat16 step), donated, compiled ahead of time once a
        layout: a drifted layout is an error, not a recompile."""
        key = tuple(mesh_axes.items()) if mesh_axes else None
        if key not in self._compiled:
            mesh = make_mesh(mesh_axes, devices=jax.devices()[:4]) if mesh_axes else None
            step = jax.jit(self.module.make_train_step(self.low_precision, self.tx, mesh=mesh), donate_argnums=0)
            shapes = jax.eval_shape(lambda: self.fresh_state(mesh=mesh))
            self._compiled[key] = mesh, step.lower(shapes, self.batch(mesh)).compile()
        return self._compiled[key]

    def train(self, state, steps, first=1):
        """``steps`` bfloat16 steps from ``state`` on the batches of seeds ``first``, ..."""
        _, step = self.compiled_step()
        loss = None
        for n in range(first, first + steps):
            state, loss = step(state, self.batch(seed=n))
        return state, float(loss)


def _named(tree):
    return [(jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_the_step_puts_what_it_compiles_on_the_telemetry_bus(model):
    telemetry.set_enabled(True)
    try:
        model.module.make_train_step(model.cfg, model.tx)
        gauges = telemetry.gauges()
    finally:
        telemetry.set_enabled(False)
    assert {k: gauges.get(k) for k in model.gauges} == model.gauges


def test_the_named_scopes_reach_the_lowered_step(model):
    text = model.lowered.as_text(debug_info=True)
    for scope in model.scopes:
        assert scope in text, scope


def test_the_train_steps_gradient_is_the_losss(model):
    """The step differentiates the tree the layers read (matrices cast once
    a step); in float32 the cast is the identity, so its loss is
    ``loss_fn``'s and every parameter leaf takes the update that
    ``loss_fn``'s gradient gives, but the leaves it holds fixed."""
    state, batch = model.state, model.batch()
    new, loss = model.stepped
    assert int(new["step"]) == 1
    want_loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, batch, state)))(state["params"])
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    updates, _ = jax.jit(model.tx.update)(grads, state["opt_state"], state["params"])
    want = jax.tree_util.tree_map(lambda p, u: p + u, state["params"], updates)
    still = []
    for (name, old), w, got in zip(_named(state["params"]), jax.tree_util.tree_leaves(want),
                                   jax.tree_util.tree_leaves(new["params"])):
        if np.array_equal(np.asarray(got), np.asarray(old)):
            still.append(name)
            if not np.array_equal(np.asarray(w), np.asarray(old)):
                continue  # held fixed against the optimizer: the model's own file says which
        np.testing.assert_allclose(np.asarray(got), np.asarray(w), rtol=0, atol=model.update_atol, err_msg=name)
    assert sorted(still) == sorted(model.still)


@pytest.mark.parametrize("mesh_axes", [None, MESH_2X2])
def test_the_step_reports_a_finite_loss_and_keeps_its_layout(model, mesh_axes):
    mesh, step = model.compiled_step(mesh_axes)
    state = model.fresh_state(mesh=mesh)
    before = [(x.shape, x.dtype, x.sharding) for x in jax.tree_util.tree_leaves(state)]
    batch = model.batch(mesh)
    losses = []
    for _ in range(3):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and int(state["step"]) == 3
    assert losses[-1] < losses[0] or not model.descends, losses
    for (name, x), (shape, dtype, sharding) in zip(_named(state), before):
        assert (x.shape, x.dtype) == (shape, dtype), name
        if mesh is not None:
            assert x.sharding.is_equivalent_to(sharding, x.ndim), name


def test_the_sharded_loss_equals_the_one_device_loss(model):
    """The same parameters laid out on {data 2, model 2}: the embedding
    (and an untied head) over the vocabulary, the loss the one device's."""
    m, cfg = model.module, model.cfg
    mesh = make_mesh(MESH_2X2, devices=jax.devices()[:4])
    state = model.state
    want = jax.jit(lambda p, b: model.loss(p, b, state))(state["params"], model.batch())
    sharded = m.init_state(jax.random.PRNGKey(0), cfg, model.tx, mesh=mesh)["params"]
    for name in ("embed", "head"):
        if name in sharded:
            assert sharded[name].sharding.spec == P("model", None), name
    got = jax.jit(lambda p, b: model.loss(p, b, state, mesh=mesh))(sharded, model.batch(mesh))
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    B, S = model.batch()["tokens"].shape
    assert m.select_attention(cfg, mesh, B, S) == m.select_attention(cfg, None, B, S) == "dense"


def test_routing_stats_count_what_the_routers_chose(model):
    """``routing_stats`` against the ids the program's routers chose,
    ``(L, T, k)``, and those against the reference's: the held experts'
    counts, their share of the assignments, the fullest over the mean, and
    the expert lists' row tiles (an expert's own rows rounded up to tiles,
    one tile for an expert of no rows)."""
    stats, chosen, want = model.routed
    held = model.cfg.held
    _, T, k = chosen.shape
    counts = np.stack([[np.sum(layer == e) for e in held] for layer in chosen])
    if "held_counts" in stats:
        np.testing.assert_array_equal(stats["held_counts"], counts)
    np.testing.assert_allclose(stats["held_share"], counts.sum(1) / (T * k), rtol=1e-6)
    np.testing.assert_allclose(stats["max_over_mean"], counts.max(1) / counts.mean(1), rtol=model.ratio_rtol)
    share = np.asarray(stats["held_share"])
    assert ((0 < share) & (share < 1)).all(), share  # some assignments fall on experts held elsewhere
    tile = held_row_tile(T)
    assert tile == min(128, T)
    trips = np.sum(np.maximum(-(-counts // tile), 1), axis=1)
    np.testing.assert_array_equal(stats["trips"], trips)
    np.testing.assert_allclose(stats["tile_fill"], counts.sum(1) / (trips * tile), rtol=1e-6)
    assert (trips >= len(held)).all()
    same = (np.sort(chosen, -1) == np.sort(want.reshape(chosen.shape), -1)).all(-1)
    assert same.mean() >= model.route_agreement, same.mean()


def checkpoint_round_trip(model, path, async_save):
    """Two bfloat16 steps, a save through ``CheckpointManager``, a restore
    into a state from another seed, leaf for leaf equal, and the next step's
    loss the uninterrupted run's. Returns the manager."""
    state, _ = model.train(model.fresh_state(), 2)
    saved = _named(jax.tree_util.tree_map(np.asarray, state))
    mgr = CheckpointManager(str(path), save_interval_steps=1, keep_last=1, async_save=async_save)
    assert mgr.save(2, {"train": StateDict(**state)})
    mgr.wait()
    _, want_loss = model.train(state, 1, first=3)

    dst = StateDict(**model.fresh_state(seed=1))
    assert mgr.restore({"train": dst}) == 2
    restored = dict(dst)
    counted = (len(saved), min(a.size for _, a in saved if a.ndim))
    assert counted == model.leaves, counted
    for (name, a), b in zip(saved, jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=name)
    _, got_loss = model.train(restored, 1, first=3)
    assert got_loss == want_loss
    return mgr


@pytest.mark.parametrize("async_save", [True, False])
def test_the_train_state_goes_through_the_checkpoint_manager_bit_exact(model, tmp_path, async_save):
    checkpoint_round_trip(model, tmp_path / "ckpt", async_save)
