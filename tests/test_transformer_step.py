"""What the first real trainer run needed from the model and the restore
path: the attention selection is readable, the train step keeps the
layout it was given, and a restored state lowers to the same program as
the one the first run compiled (or every resume misses the compile cache).
And what the four-chip step needed: the tied embedding sharded over the
vocabulary, so that no logits-sized tensor crosses the 'model' axis.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import ssm_lm, transformer as T
from torchsnapshot_tpu.parallel import make_mesh
from torchsnapshot_tpu.parallel.mesh import collective_bytes, collectives

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
    logits = [shape for _, shape in _vocab_sized(old, mesh, DENSE) if len(shape) == 3]
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
  %all-reduce.1 = (bf16[2,8]{1,0:T(8,128)(2,1)}, f32[4]{0}) all-reduce(%x, %y), channel_id=1, to_apply=%add
  %collective-permute-start = (bf16[2,8]{1,0}, bf16[2,8]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=2
  %collective-permute-done = bf16[2,8]{1,0} collective-permute-done(%collective-permute-start)
  ROOT %t = (s32[]{:T(128)}, bf16[2,8]{1,0}) tuple(%i.1, %collective-permute-done)
}
ENTRY %main (a.1: bf16[2,8]) -> bf16[4,8] {
  %a.1 = bf16[2,8]{1,0} parameter(0)
  %while.1 = (s32[]{:T(128)}, bf16[2,8]{1,0}) while(%init), condition=%cond, body=%body
  %while.2 = (s32[], bf16[2,8]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  %conditional.1 = (s32[], bf16[2,8]{1,0}) conditional(%k, %init, %init), branch_computations={%body, %body}
  ROOT %all-gather.1 = bf16[4,8]{1,0} all-gather(%a.1), channel_id=3, dimensions={0}
}
"""
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
    mesh = _mesh(mesh_name)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p, b: T.loss_fn(p, b, cfg)))(
        params, _random_batch()
    )
    sharded = T.init_state(jax.random.PRNGKey(0), cfg, T.make_optimizer(), mesh=mesh)["params"]
    assert sharded["embed"].sharding.spec == P("model", None)
    got_loss, got = jax.jit(jax.value_and_grad(lambda p, b: T.loss_fn(p, b, cfg, mesh=mesh)))(
        sharded, _random_batch(mesh)
    )
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
