"""What the first real trainer run needed from the model and the restore
path: the attention selection is readable, the train step keeps the
layout it was given, and a restored state lowers to the same program as
the one the first run compiled (or every resume misses the compile cache).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import transformer as T
from torchsnapshot_tpu.parallel import make_mesh

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
