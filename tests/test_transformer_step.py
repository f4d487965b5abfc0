"""What the first real trainer run needed from the model and the restore
path: the attention selection is readable, the train step keeps the
layout it was given, and a restored state lowers to the same program as
the one the first run compiled (or every resume misses the compile cache).
And what the four-chip step needed: the tied embedding sharded over the
vocabulary, so that no logits-sized tensor crosses the 'model' axis; and
every matrix's gradient summed over 'data' once a step, after the backward
scan, through a replica dimension that exists only inside the step.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import ssm_lm, transformer as T
from torchsnapshot_tpu.ops.moe import _held_experts, held_row_tile
from torchsnapshot_tpu.parallel import make_mesh
from torchsnapshot_tpu.parallel.mesh import collective_bytes, collectives, spanned_axes, with_replica_dim

CFG = T.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq_len=16
)


def _batch(B=4, S=16):
    toks = jnp.zeros((B, S), jnp.int32)
    return {"tokens": toks, "targets": toks}


def test_select_attention_says_what_forward_runs(monkeypatch):
    # Off-TPU, "auto" gives way by sequence length — and says so.
    assert T.select_attention(CFG, None, 2, 16) == "dense"
    assert T.select_attention(CFG, None, 2, 1024) == "blockwise"
    assert T.select_attention(dataclasses.replace(CFG, attn_impl="flash"), None, 2, 1024) == "flash"
    # A cp request with no mesh runs dense; with a mesh it needs a 'seq' axis.
    ring = dataclasses.replace(CFG, attn_impl="ring")
    assert T.select_attention(ring, None, 2, 16) == "dense"
    with pytest.raises(ValueError, match="'seq' axis"):
        T.select_attention(ring, make_mesh({"data": 2, "model": 4}), 2, 16)
    seq_mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    assert T.select_attention(ring, seq_mesh, 2, 16) == "ring"
    with pytest.raises(ValueError, match="unknown attn_impl"):
        T.select_attention(dataclasses.replace(CFG, attn_impl="nope"), None, 2, 16)

    # On a TPU backend the same requests select the kernels.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh({"data": 2, "model": 4})
    assert T.select_attention(CFG, None, 2, 1024) == "flash"
    assert T.select_attention(CFG, mesh, 2, 1024) == "flash_sharded"
    assert T.select_attention(ring, seq_mesh, 2, 1024) == "ring_flash"
    zig = dataclasses.replace(CFG, attn_impl="zigzag")
    assert T.select_attention(zig, seq_mesh, 2, 1024) == "zigzag_flash"
    # Batch 3 does not divide 'data': the kernel cannot be shard_mapped.
    assert T.select_attention(CFG, mesh, 3, 1024) == "blockwise"


def test_train_step_returns_the_layout_it_was_given():
    """Left to GSPMD, replicated leaves came back sharded over 'model', so
    step 2 recompiled for the drifted input and the saved layout was no
    longer the declared one."""
    mesh = make_mesh({"data": 2, "model": 4})
    tx = T.make_optimizer()
    state = T.init_state(jax.random.PRNGKey(0), CFG, tx, mesh=mesh)
    want = jax.tree_util.tree_map(lambda x: x.sharding, state)
    batch = jax.device_put(
        _batch(), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data", None))
    )
    # Compiled once, ahead of time: a drifted input is then an error, not
    # a silent second compile.
    step = (
        jax.jit(T.make_train_step(CFG, tx, mesh=mesh), donate_argnums=0)
        .lower(state, batch)
        .compile()
    )
    for _ in range(2):
        state, loss = step(state, batch)
    got = jax.tree_util.tree_map(lambda x: x.sharding, state)
    for (path, w), g in zip(
        jax.tree_util.tree_flatten_with_path(want)[0], jax.tree_util.tree_leaves(got)
    ):
        assert g.is_equivalent_to(w, len(w.spec)), jax.tree_util.keystr(path)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("commit", [False, True])
def test_restore_keeps_the_destinations_committedness(tmp_path, commit):
    """The destination is the spec. An uncommitted state (plain jnp
    creation) restored as committed arrays lowers the caller's step with
    explicit argument shardings: a different module, a compile-cache miss
    on every resume."""
    tx = T.make_optimizer()

    def fresh(seed):
        state = T.init_state(jax.random.PRNGKey(seed), CFG, tx)
        return jax.device_put(state, jax.devices()[0]) if commit else state

    state = fresh(0)
    Snapshot.take(str(tmp_path / "snap"), {"train": StateDict(**state)})
    dst = StateDict(**fresh(1))
    Snapshot(str(tmp_path / "snap")).restore({"train": dst})
    restored = dict(dst)

    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        assert b.committed == commit == a.committed
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    step = jax.jit(T.make_train_step(CFG, tx), donate_argnums=0)
    batch = _batch()
    assert step.lower(state, batch).as_text() == step.lower(restored, batch).as_text()


def test_streamed_restore_keeps_an_uncommitted_destination_uncommitted(tmp_path, monkeypatch):
    """Same contract on the per-sub-chunk device sink (large entries)."""
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(64 << 10))
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "always")
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 256), jnp.float32)  # 512 KB
    assert not w.committed
    Snapshot.take(str(tmp_path / "snap"), {"m": StateDict(w=w)})
    dst = StateDict(w=jnp.zeros_like(w))
    Snapshot(str(tmp_path / "snap")).restore({"m": dst})
    assert not dst["w"].committed
    np.testing.assert_array_equal(np.asarray(dst["w"]), np.asarray(w))


# ------------------------------------------- the tied embedding's layout

# Sizes no two of which coincide: a vocabulary-sized dimension (V, V/2,
# V/4) in a collective's shape is then the vocabulary and nothing else.
V, D, B, S = 1000, 32, 4, 16
DENSE = T.TransformerConfig(vocab_size=V, d_model=D, n_heads=4, n_layers=2, d_ff=64, max_seq_len=S)
FAMILIES = {
    "dense": (T, DENSE),
    "moe": (T, dataclasses.replace(DENSE, n_experts=4)),
    "ssm": (ssm_lm, ssm_lm.SSMConfig(vocab_size=V, d_model=D, d_state=4, n_layers=2, d_ff=64)),
}
MESHES = {"2x2": {"data": 2, "model": 2}, "1x4": {"data": 1, "model": 4}}
OLD_EMBED = P(None, "model")  # the layout before: the hidden width sharded


def _mesh(name, devices=None):
    return make_mesh(MESHES[name], devices=(devices or jax.devices())[:4])


def _random_batch(mesh=None):
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S + 1), 0, V, jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh is not None:
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    return batch


def _compiled_step_text(M, cfg, mesh, batch=(B, S)):
    """The donated train step compiled from shapes alone, so the mesh may be
    of devices that are described and not attached."""
    tx = T.make_optimizer()

    def on_mesh(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, spec))

    shapes = jax.eval_shape(lambda k: M.init_state(k, cfg, tx), jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(on_mesh, shapes, M.state_specs(cfg, shapes))
    tok = on_mesh(jax.ShapeDtypeStruct(batch, jnp.int32), P("data", None))
    step = jax.jit(M.make_train_step(cfg, tx, mesh=mesh), donate_argnums=0)
    return step.lower(state, {"tokens": tok, "targets": tok}).compile().as_text()


def _vocab_sized(text, mesh, cfg):
    """Collectives with a vocabulary-sized dimension, but for the one that
    has to be there: the gradient of the embedding's own shard
    (V/model, D), summed over 'data' where the mesh has data parallelism."""
    shard = cfg.vocab_size // mesh.shape["model"]
    grad = {(shard, cfg.d_model)} if mesh.shape["data"] > 1 else set()
    return [
        (c["kind"], shape)
        for c in collectives(text)
        for _, shape in c["shapes"]
        if {cfg.vocab_size, shard} & set(shape) and shape not in grad
    ]


@pytest.mark.parametrize(
    "family,mesh_name",
    [("dense", "2x2"), ("dense", "1x4"), ("moe", "2x2"), ("moe", "1x4"), ("ssm", "2x2")],
)
def test_no_vocabulary_sized_collective_in_the_sharded_step(family, mesh_name):
    M, cfg = FAMILIES[family]
    mesh = _mesh(mesh_name)
    text = _compiled_step_text(M, cfg, mesh)
    assert collective_bytes(text), "a sharded step without collectives: nothing was read"
    assert _vocab_sized(text, mesh, cfg) == []


@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_the_old_layout_moved_the_logits_and_the_counter_sees_it(monkeypatch, mesh_name):
    """The detector is alive: with the hidden width sharded (patched in
    here; the program has no such switch) the step all-reduces or gathers
    (B, S, V) tensors, and moves more bytes in all."""
    mesh = _mesh(mesh_name)
    new = _compiled_step_text(T, DENSE, mesh)
    specs = T.param_specs
    monkeypatch.setattr(T, "param_specs", lambda cfg: {**specs(cfg), "embed": OLD_EMBED})
    old = _compiled_step_text(T, DENSE, mesh)
    # (B, S, V), or (R, B/R, S, V) where the head runs replica by replica
    logits = [shape for _, shape in _vocab_sized(old, mesh, DENSE) if len(shape) >= 3]
    assert logits and all(B * S * V // 4 <= np.prod(s) <= B * S * V for s in logits)
    assert sum(collective_bytes(new).values()) < sum(collective_bytes(old).values())


@pytest.fixture(scope="module")
def v5e_2x2():
    """The TPU's own compiler, for a chip that is described and not attached
    (``on-chip-measurement`` section 2.3): no time comes out of it, only the
    collectives its partitioner puts in, which need not be the CPU's. Made
    in a fixture, never at import: only one process may hold libtpu, and
    every xdist worker imports this file."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - whatever stops the description stops the test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_no_logits_sized_collective_at_full_width_on_the_tpu_partitioner(v5e_2x2, monkeypatch, mesh_name):
    """The widths and the batch the four-chip cell runs (OLMo-1B), 2 layers."""
    cfg = T.TransformerConfig(
        vocab_size=50304, d_model=2048, n_heads=16, n_layers=2, d_ff=8192, max_seq_len=2048
    )
    # The program picks the flash kernel by asking for the backend; the
    # step compiled here is then the step the chip runs.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(mesh_name, v5e_2x2)
    text = _compiled_step_text(T, cfg, mesh, batch=(4, 2048))
    assert _vocab_sized(text, mesh, cfg) == []
    # The layers' reductions are still there: something was read.
    assert collective_bytes(text).get("all-reduce", 0) > 4 * 2048 * cfg.d_model


def test_collectives_reads_loops_and_async_pairs():
    """On the TPU's text a scan's trip count is only in the loop's
    condition, and a permute is a -start/-done pair; the CPU's text carries
    known_trip_count. A conditional's branches both count."""
    text = """
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
%cond (p: (s32[], bf16[2,8])) -> pred[] {
  %constant.1 = s32[]{:T(128)} constant(8)
  %p = (s32[]{:T(128)}, bf16[2,8]{1,0}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %constant.1), direction=LT
}
%body (p.1: (s32[], bf16[2,8])) -> (s32[], bf16[2,8]) {
  %p.1 = (s32[]{:T(128)}, bf16[2,8]{1,0}) parameter(0)
  %x = bf16[2,8]{1,0:T(8,128)(2,1)} get-tuple-element(%p.1), index=1
  %all-reduce.1 = (bf16[2,8]{1,0:T(8,128)(2,1)}, f32[4]{0}) all-reduce(%x, %y), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add
  %collective-permute-start = (bf16[2,8]{1,0}, bf16[2,8]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=2, source_target_pairs={{0,1},{2,3}}
  %collective-permute-done = bf16[2,8]{1,0} collective-permute-done(%collective-permute-start)
  ROOT %t = (s32[]{:T(128)}, bf16[2,8]{1,0}) tuple(%i.1, %collective-permute-done)
}
ENTRY %main (a.1: bf16[2,8]) -> bf16[4,8] {
  %a.1 = bf16[2,8]{1,0} parameter(0)
  %while.1 = (s32[]{:T(128)}, bf16[2,8]{1,0}) while(%init), condition=%cond, body=%body
  %while.2 = (s32[], bf16[2,8]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  %conditional.1 = (s32[], bf16[2,8]{1,0}) conditional(%k, %init, %init), branch_computations={%body, %body}
  ROOT %all-gather.1 = bf16[4,8]{1,0} all-gather(%a.1), channel_id=3, replica_groups=[2,2]<=[4], dimensions={0}
}
"""
    assert {c["name"]: c["groups"] for c in collectives(text)} == {
        "all-reduce.1": [(0, 2), (1, 3)],
        "collective-permute-start": [(0, 1), (2, 3)],
        "all-gather.1": [(0, 1), (2, 3)],
    }
    got = {(c["name"], c["times"]): (c["kind"], c["shapes"], c["bytes"]) for c in collectives(text)}
    reduce = ("all-reduce", [("bf16", (2, 8)), ("f32", (4,))], 48)
    permute = ("collective-permute", [("bf16", (2, 8))], 32)
    assert got == {
        ("all-reduce.1", 8): reduce,
        ("collective-permute-start", 8): permute,
        ("all-reduce.1", 3): reduce,
        ("collective-permute-start", 3): permute,
        ("all-reduce.1", 1): reduce,  # both branches of the conditional: same key
        ("collective-permute-start", 1): permute,
        ("all-gather.1", 1): ("all-gather", [("bf16", (4, 8))], 64),
    }
    assert collective_bytes(text) == {"all-reduce": 48 * 13, "collective-permute": 32 * 13, "all-gather": 64}


def _one_device_and_sharded_grads(cfg, mesh):
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p, b: T.loss_fn(p, b, cfg)))(
        params, _random_batch()
    )
    sharded = T.init_state(jax.random.PRNGKey(0), cfg, T.make_optimizer(), mesh=mesh)["params"]
    assert sharded["embed"].sharding.spec == P("model", None)
    got_loss, got = jax.jit(jax.value_and_grad(lambda p, b: T.loss_fn(p, b, cfg, mesh=mesh)))(
        sharded, _random_batch(mesh)
    )
    return want_loss, want, got_loss, got


# Tolerances, as shares of the largest entry of the reference gradient.
# float32 compute leaves only the order of the additions: 2.0e-7 to 3.5e-7
# read here, held to 2e-6. bf16 compute: every sharded reduction adds in
# another order and rounds to bf16 on the way; 0.8e-2 to 0.9e-2 read, held
# to 2e-2. An MoE in bf16 also routes on rounded logits, and a near-tie that
# goes the other way changes that token's whole row (1 or 2 rows of 1000
# here, by 0.09 to 0.12): rows are held to 2e-2, all but 1 % of them.
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2), (jnp.float32, 2e-6)])
@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_sharded_loss_and_embed_gradient_match_one_device(mesh_name, family, dtype, tol):
    cfg = dataclasses.replace(FAMILIES[family][1], dtype=dtype)
    want_loss, want, got_loss, got = _one_device_and_sharded_grads(cfg, _mesh(mesh_name))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=tol)
    want_g = np.asarray(want["embed"])
    rows = np.abs(np.asarray(got["embed"]) - want_g).max(axis=1) / np.abs(want_g).max()
    rerouted = 0.01 if (family == "moe" and dtype == jnp.bfloat16) else 0.0
    assert (rows > tol).mean() <= rerouted, (rows.max(), int((rows > tol).sum()))


@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_a_vocabulary_the_model_axis_does_not_divide_is_refused_at_placement(mesh_name):
    cfg = dataclasses.replace(DENSE, vocab_size=V + 1)
    with pytest.raises(ValueError, match=r"cannot place \['embed'\] \(1001, 32\).*not divisible"):
        T.init_state(jax.random.PRNGKey(0), cfg, T.make_optimizer(), mesh=_mesh(mesh_name))


def test_a_snapshot_of_the_old_embed_layout_restores_under_the_new_one(tmp_path):
    """A layout change must not strand old snapshots: saved on 2x2 with the
    hidden width sharded, restored on 1x4 with the vocabulary sharded."""
    cfg, tx = DENSE, T.make_optimizer()
    src_mesh, dst_mesh = _mesh("2x2"), _mesh("1x4")
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx)
    old_specs = jax.tree_util.tree_map_with_path(
        lambda path, spec: OLD_EMBED if "embed" in jax.tree_util.keystr(path) else spec,
        T.state_specs(cfg, state),
    )
    state = jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(src_mesh, spec)), state, old_specs
    )
    old = [state["params"]["embed"], *(m["embed"] for m in state["opt_state"][0][1:])]
    assert all(x.sharding.spec == OLD_EMBED for x in old)
    Snapshot.take(str(tmp_path / "snap"), {"train": StateDict(**state)})

    dst = StateDict(**T.init_state(jax.random.PRNGKey(1), cfg, tx, mesh=dst_mesh))
    want = jax.tree_util.tree_map(lambda x: x.sharding, dict(dst))
    Snapshot(str(tmp_path / "snap")).restore({"train": dst})
    assert dst["params"]["embed"].sharding.spec == P("model", None)
    for (path, a), b, w in zip(
        jax.tree_util.tree_flatten_with_path(state)[0],
        jax.tree_util.tree_leaves(dict(dst)),
        jax.tree_util.tree_leaves(want),
    ):
        assert b.sharding.is_equivalent_to(w, b.ndim), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))


# ------------------------------- the data-parallel gradient, once a step

FULL = T.TransformerConfig(  # the four-chip cell's step: OLMo-1B's widths, 8 layers, batch 4
    vocab_size=50304, d_model=2048, n_heads=16, n_layers=8, d_ff=8192, max_seq_len=2048
)
MB = 1e6


def _data_reductions(text, mesh):
    return [
        c for c in collectives(text)
        if c["kind"] == "all-reduce" and "data" in spanned_axes(c["groups"], mesh)
    ]


def _assert_reduced_once(text, mesh, cfg, in_loop_limit):
    """No all-reduce over 'data' of more than ``in_loop_limit`` bytes runs
    more than once a step, and exactly one carries the embedding's shard."""
    over_data = _data_reductions(text, mesh)
    assert over_data, "a data-parallel step without a gradient reduction: nothing was read"
    in_loop = [(c["name"], c["times"], c["shapes"]) for c in over_data if c["times"] > 1 and c["bytes"] > in_loop_limit]
    assert in_loop == []
    embed = (cfg.vocab_size // mesh.shape["model"], cfg.d_model)
    assert sum(embed in [shape for _, shape in c["shapes"]] for c in over_data) == 1


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_matrix_gradients_cross_data_once_a_step_on_the_cpu_partitioner(monkeypatch, family):
    """The stacked matrices and the embedding take the replica dimension;
    what stays in the scans over 'data' is the norm scales (two D-vectors a
    layer) and, in an MoE, the router's and the dispatch's own traffic:
    ``moe_ffn`` takes no replica dimension, so its leaves are reduced where
    they are produced, as before."""
    cfg, mesh = FAMILIES[family][1], _mesh("2x2")
    taken = []
    monkeypatch.setattr(
        T, "with_replica_dim", lambda w, *a, **k: taken.append(w.shape) or with_replica_dim(w, *a, **k)
    )
    text = _compiled_step_text(T, cfg, mesh)
    L, F = cfg.n_layers, cfg.d_ff
    matrices = [(L, D, 3 * D), (L, D, D)] + ([] if cfg.n_experts else [(L, D, F), (L, F, D)])
    assert sorted(taken) == sorted([*matrices, (V, D)])
    attn_shard = 4 * D * D // 2  # one layer's smallest matrix shard, float32
    if family == "dense":
        _assert_reduced_once(text, mesh, cfg, in_loop_limit=attn_shard - 1)
    else:  # the dispatch moves (E, capacity, D) activations over 'data' in the scan
        in_loop = [s for c in _data_reductions(text, mesh) if c["times"] > 1 for _, s in c["shapes"]]
        assert not {(1, D, 3 * D // 2), (1, D // 2, D), (1, D, 3 * D), (1, D, D)} & set(in_loop)
        assert any(set(s) == {cfg.n_experts, D} for s in in_loop), "the router's gradient left the scan"


def test_no_replica_dimension_without_data_parallelism(monkeypatch):
    """``data`` 1, no mesh, or a batch 'data' does not divide: the plain path."""
    monkeypatch.setattr(T, "with_replica_dim", lambda *a, **k: pytest.fail("replica dimension taken"))
    params = jax.eval_shape(lambda k: T.init_params(k, DENSE), jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    for mesh, tokens in [(_mesh("1x4"), toks), (None, toks), (_mesh("2x2"), jax.ShapeDtypeStruct((3, S), jnp.int32))]:
        jaxpr = str(jax.make_jaxpr(lambda p, t: T.forward(p, t, DENSE, mesh=mesh))(params, tokens))
        assert f"[1,{V},{D}]" not in jaxpr and f"[2,{V},{D}]" not in jaxpr
        # and the head still casts the embedding before it transposes it: the
        # other order cost olmo1b.save 2.9 ms a step (chip call B, PR 29)
        assert f"f32[{D},{V}]" not in jaxpr and f"bf16[{D},{V}]" in jaxpr


def test_the_replica_dimension_is_in_the_jaxpr_under_data_parallelism():
    """The detector above is alive: under {data 2, model 2} the embedding
    is there as (R, V, D)."""
    params = jax.eval_shape(lambda k: T.init_params(k, DENSE), jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    mesh = _mesh("2x2")
    assert f"[2,{V},{D}]" in str(jax.make_jaxpr(lambda p, t: T.forward(p, t, DENSE, mesh=mesh))(params, toks))


def test_data_gradient_is_reduced_once_at_full_width_on_the_tpu_partitioner(v5e_2x2, monkeypatch):
    """The four-chip cell's train layout, compiled for the chip: no
    reduction over 'data' of more than 1 MB in either scan, one reduction of
    the embedding's gradient, and the bytes that go with that: 1076.2 MB of
    all-reduce per device and step (1179.3 with the embedding reduced twice;
    1581.9 if the float32 parameter is broadcast before the cast)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh("2x2", v5e_2x2)
    text = _compiled_step_text(T, FULL, mesh, batch=(4, 2048))
    _assert_reduced_once(text, mesh, FULL, in_loop_limit=1 * MB)
    assert 900 * MB < collective_bytes(text)["all-reduce"] <= 1080 * MB
    after_scan = sum(c["bytes"] for c in _data_reductions(text, mesh) if c["times"] == 1)
    assert abs(after_scan - 2 * (FULL.param_count // 2)) < 1 * MB  # every parameter's shard once, in bf16


def test_the_resume_layout_compiles_to_what_it_did_on_the_tpu_partitioner(v5e_2x2, monkeypatch):
    """{data 1, model 4}: R is 1, and the collectives are the parent's
    (4161.0 MB per device and step, PERF.md section 6, PR 27)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(T, "with_replica_dim", lambda *a, **k: pytest.fail("replica dimension taken"))
    mesh = _mesh("1x4", v5e_2x2)
    text = _compiled_step_text(T, FULL, mesh, batch=(4, 2048))
    assert round(sum(collective_bytes(text).values()) / MB, 1) == 4161.0
    assert _data_reductions(text, mesh) == []


def _in_loop_bodies(text):
    """The instruction lines of every computation a ``while`` body reaches."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and not line.startswith("}"):
            cur.append(line)
    callees = lambda lines, roles: {c for line in lines for c in re.findall(rf"\b(?:{roles})=%([^\s,)}}]+)", line)}  # noqa: E731
    todo, seen = callees([l for ls in comps.values() for l in ls], "body"), set()
    while todo:
        comp = todo.pop()
        seen.add(comp)
        todo |= callees(comps[comp], "body|calls|to_apply|true_computation|false_computation") - seen
    return [line for comp in sorted(seen) for line in comps[comp]]


# (T, D, F, held experts, matrices, the most experts a token can be on): the held experts of sdar30b.save and zaya1_8b.save
HELD_SIZES = {"sdar30b.save": (8192, 2048, 768, 16, 3, 8), "zaya1_8b.save": (8192, 2048, 2048, 8, 3, 1)}


@pytest.mark.parametrize("cell", sorted(HELD_SIZES))
def test_no_loop_of_the_held_experts_moves_the_whole_accumulator_on_the_tpu_compiler(v5e_2x2, monkeypatch, cell):
    """``_held_experts`` forward and backward at a routed cell's sizes,
    compiled for the chip. The grouped form: no array of ``T x top_k`` rows
    (the list is walked a chunk of 1024 rows at a time, the backward pass'
    buffers are a slab of ``T`` and a tile an expert), every weight gradient
    is a transposed grouped product's result and nothing in a loop zero-fills
    a stack of the experts' shape, slices an expert out of one or updates
    one in place (the parent's loops did each an expert), and, as since PR
    35, no loop body copies, slices or scatters into the float32
    accumulator: its rows are added where it lies."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels compiled, not interpreted
    T_, D_, F_, E_, n_matrices, most = HELD_SIZES[cell]
    tile = held_row_tile(T_)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    ws = (arg((E_, D_, F_), jnp.bfloat16),) * (n_matrices - 1) + (arg((E_, F_, D_), jnp.bfloat16),)

    def both(x2, w_held, order, counts, ws, g):
        y, vjp = jax.vjp(lambda x, w, ws: _held_experts(x, w, order, counts, ws, tile), x2, w_held, ws)
        return y, vjp(g)

    compiled = jax.jit(both).lower(
        arg((T_, D_), jnp.bfloat16), arg((E_, T_), jnp.float32), arg((E_, T_), jnp.int32), arg((E_,), jnp.int32), ws,
        arg((T_, D_), jnp.float32),
    ).compile()
    text = compiled.as_text()
    in_loops = _in_loop_bodies(text)
    accumulator = re.compile(rf"f32\[{T_},(?:1,)?{D_}\]")
    moved = [
        line.strip()[:160] for line in in_loops
        if accumulator.search(line) and re.search(r" (copy-start|slice-start|scatter)\(", line)
    ]
    assert moved == []
    # the loops were read: they carry the accumulator, row by row, and the stacks of gradients
    assert any(re.search(rf"f32\[{T_},1,{D_}\]\S* custom-call\(", line) for line in in_loops)
    stack = rf"(?:bf16|f32)\[{E_},(?:{D_},{F_}|{F_},{D_})\]"
    made = [line for line in in_loops if re.match(rf"\s*(?:ROOT )?%\S+ = {stack}\S* (?!get-tuple-element|parameter|bitcast)", line)]
    assert made and all(" custom-call(" in line and "grouped_matmul_t" in line for line in made), [m.strip()[:120] for m in made]
    assert not [line for line in in_loops if re.search(rf"f32\[(?:{E_},)?(?:{D_},{F_}|{F_},{D_})\]", line)]  # no float32 copy of one either
    # nothing as long as the worst case: T x (the most experts a token is on) rows, a row tile an expert more
    longest = max(int(m) for line in text.splitlines() for m in re.findall(r"= (?:bf16|f32|s32)\[(\d+),(?:\d+)\]", line))
    assert longest <= -(-(T_ + E_ * tile) // 1024) * 1024 < max(T_ * most, 2 * T_)
    assert text.count('custom_call_target="tpu_custom_call"') > 10


@pytest.mark.parametrize("cell,parents_gb", [("sdar30b.save", 13.58), ("zaya1_8b.save", 14.98)])
def test_a_routed_cells_step_fits_the_chip_on_the_tpu_compiler(v5e_2x2, monkeypatch, request, cell, parents_gb):
    """The train step of the two routed cells that fill the chip, at their
    published batch and sequence, compiled for a described v5e: what
    ``memory_analysis()`` plans is under the chip's 15.75 GB (the list's
    chunks and the backward pass' slab buffers are 0.2 to 0.3 GB over the
    parent's loops), and the step holds the grouped products."""
    import sys

    chip = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip")
    monkeypatch.syspath_prepend(chip)
    harness = lambda: [m for m in sys.modules if m == "lib" or m.startswith("lib.")]  # noqa: E731
    for name in harness():
        monkeypatch.delitem(sys.modules, name)
    from lib import model as chip_model, spec

    request.addfinalizer(lambda: [sys.modules.pop(name) for name in harness()])  # the harness' modules leave with the test

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = spec.resolve_cell(spec.load_benchmark(), cell).config
    model = chip_model.Model(cfg, 0, v5e_2x2[:1], None)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    state = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), model.shapes)
    tokens = jax.ShapeDtypeStruct((model.batch_size, model.seq), jnp.int32, sharding=one)
    compiled = model._step.lower(state, {"tokens": tokens, "targets": tokens}).compile()
    m = compiled.memory_analysis()
    planned = m.argument_size_in_bytes + m.temp_size_in_bytes + max(0, m.output_size_in_bytes - m.alias_size_in_bytes)
    assert parents_gb * 1e9 - 0.1e9 < planned < 15.75e9 - 0.3e9, planned
    text = compiled.as_text()
    assert "grouped_matmul_t" in text and "add_rows" in text


CP = {"data": 2, "seq": 2, "model": 2}


# Tolerances as above. An MoE in float32 reads 1.7e-6 on the router's
# gradient, with the replica dimension as without it: held to 4e-6. In bf16
# it reroutes tokens (above), which moves every leaf: not compared here.
@pytest.mark.parametrize(
    "family,dtype,tol",
    [("dense", jnp.bfloat16, 2e-2), ("dense", jnp.float32, 2e-6), ("moe", jnp.float32, 4e-6)],
)
@pytest.mark.parametrize("axes,impl", [(MESHES["2x2"], "auto"), (CP, "ring")], ids=["2x2", "cp-ring"])
def test_every_gradient_leaf_of_the_sharded_step_matches_one_device(axes, impl, family, dtype, tol):
    """R = 2 with two rows of the batch a replica."""
    cfg = dataclasses.replace(FAMILIES[family][1], dtype=dtype, attn_impl=impl)
    mesh = make_mesh(axes, devices=jax.devices()[: int(np.prod(list(axes.values())))])
    assert mesh.shape["data"] == 2 and B // 2 > 1
    want_loss, want, got_loss, got = _one_device_and_sharded_grads(cfg, mesh)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=tol)
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0], jax.tree_util.tree_leaves(got)):
        w = np.asarray(w)
        err = np.abs(np.asarray(g) - w).max() / np.abs(w).max()
        assert err <= tol, (jax.tree_util.keystr(path), err)


# ------------------------------------- the saved state did not change

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "transformer_2x2_snapshot")


def _fixture_run():
    """What tests/data/gen_transformer_2x2_snapshot.py ran, on today's code."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen", os.path.join(os.path.dirname(FIXTURE), "gen_transformer_2x2_snapshot.py")
    )
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen.build()


def _layout(manifest):
    """A manifest without what the values decide: checksums."""

    def strip(x):
        if isinstance(x, dict):
            return {k: strip(v) for k, v in x.items() if k != "checksum"}
        return [strip(v) for v in x] if isinstance(x, list) else x

    return strip(json.loads(json.dumps(manifest, default=dataclasses.asdict)))


def test_state_specs_are_the_layout_they_were():
    dense = T.state_specs(DENSE, jax.eval_shape(lambda k: T.init_state(k, DENSE, T.make_optimizer()), jax.random.PRNGKey(0)))
    want = {
        "embed": P("model", None),
        "layers": {
            "attn_qkv": P(None, None, "model"), "attn_out": P(None, "model", None),
            "ff_in": P(None, None, "model"), "ff_out": P(None, "model", None),
            "ln1_scale": P(None, None), "ln2_scale": P(None, None),
        },
        "ln_f_scale": P(None),
    }
    assert dense["params"] == want and dense["step"] == P()
    adam = dense["opt_state"][0]
    assert adam.mu == want and adam.nu == want and adam.count == P()


def test_a_snapshot_taken_before_the_replica_dimension_restores_and_steps(tmp_path):
    """The fixture was saved by the parent commit after one sharded step.
    Today's save of the same run has the same manifest (paths, shapes,
    pieces; 41 payload files), and the old snapshot restores into today's
    state and steps to the loss the parent stepped to."""
    cfg, tx, mesh, batch, step = _fixture_run()
    # A copy: a restore writes its history beside the snapshot it reads.
    fixture = shutil.copytree(FIXTURE, str(tmp_path / "old" / "snap"))
    with open(os.path.join(fixture, "expected.json")) as f:
        expected = json.load(f)
    state, before = step(T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh), batch)
    np.testing.assert_allclose(float(before), expected["loss_before"], rtol=1e-6)
    Snapshot.take(str(tmp_path / "snap"), {"train": StateDict(**state)})
    old, new = Snapshot(fixture).get_manifest(), Snapshot(str(tmp_path / "snap")).get_manifest()
    assert _layout(new) == _layout(old)

    dst = StateDict(**T.init_state(jax.random.PRNGKey(1), cfg, tx, mesh=mesh))
    Snapshot(fixture).restore({"train": dst})
    _, after = step(dict(dst), batch)
    np.testing.assert_allclose(float(after), expected["loss_after"], rtol=2e-3)
