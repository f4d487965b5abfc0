"""A leaf over the chunk limit is cut into its chunks when they are staged,
not when the save is planned (``io_preparers/chunked.py``,
``ArrayBufferStager._cut``): no device copy of a chunk exists from
``prepare_write`` on, the cut is made inside the DtoH window and dropped
with the staged payload, and what is written (manifest layout, chunk
locations, bytes, checksums) is what it always was, so old snapshots
restore and new ones restore under an older reader.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import CheckpointManager, Snapshot, StateDict, telemetry
from torchsnapshot_tpu.io_preparers import array as A, chunked
from torchsnapshot_tpu.io_preparers.chunked import ChunkedArrayIOPreparer
from torchsnapshot_tpu.manifest import ChunkedArrayEntry


@pytest.fixture
def small_chunks(monkeypatch):
    """1 KiB chunks: four rows of 64 floats."""
    monkeypatch.setattr(chunked, "DEFAULT_MAX_CHUNK_SIZE_BYTES", 1024)


@pytest.fixture
def bus():
    telemetry.reset()
    telemetry.set_enabled(True)
    yield telemetry
    telemetry.set_enabled(False)
    telemetry.reset()


def _spans(name):
    return [e for e in telemetry.events() if e["ph"] == "span" and e["name"] == name]


def _live():
    """Distinct live device buffers (several arrays may share one)."""
    gc.collect()
    return len({a.unsafe_buffer_pointer() for a in jax.live_arrays()})


def _leaf(rows=10, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, 64), jnp.float32)


def test_prepare_write_makes_no_device_copy_of_a_chunk(small_chunks):
    """The parent sliced every chunk out of the device array here, and the
    slices lived until the save was done: a second state beside the first."""
    w = _leaf()
    shards = ChunkedArrayIOPreparer.chunk_shards(w.shape, "float32")
    assert len(shards) == 3  # 4 + 4 + 2 rows
    before = _live()
    entry, reqs = ChunkedArrayIOPreparer.prepare_write("m/w", w, shards)
    assert _live() == before
    assert [r.path for r in reqs] == ["m/w_0_0", "m/w_4_0", "m/w_8_0"]
    assert [c.array.shape for c in entry.chunks] == [[4, 64], [4, 64], [2, 64]]
    assert all(r.buffer_stager.arr is w and r.buffer_stager.index is not None for r in reqs)
    assert [r.buffer_stager.get_staging_cost_bytes() for r in reqs] == [1024, 1024, 512]
    # staged one by one, each payload is its rows, and no cut outlives its stage
    for req, (offsets, sizes) in zip(reqs, shards):
        buf = asyncio.run(req.buffer_stager.stage_buffer(None))
        lo, n = offsets[0], sizes[0]
        assert bytes(buf) == np.asarray(w)[lo:lo + n].tobytes()
        del buf
        assert _live() == before


def test_a_leaf_under_the_limit_and_a_whole_chunk_are_not_cut(small_chunks, bus):
    w = _leaf(rows=4)  # exactly one chunk
    shards = ChunkedArrayIOPreparer.chunk_shards(w.shape, "float32")
    entry, reqs = ChunkedArrayIOPreparer.prepare_write("m/w", w, shards)
    assert len(reqs) == 1 and reqs[0].buffer_stager.index is None
    assert bytes(asyncio.run(reqs[0].buffer_stager.stage_buffer(None))) == np.asarray(w).tobytes()
    assert not _spans("stage_chunk_cut") and "chunk_payloads" not in telemetry.counters()


@pytest.mark.parametrize("async_take", [False, True])
def test_a_save_adds_no_live_device_buffer_and_restores_bit_exact(tmp_path, small_chunks, bus, async_take):
    w, b = _leaf(rows=10), _leaf(rows=2, seed=1)
    state = {"m": StateDict(w=w, b=b)}
    before = _live()
    if async_take:
        Snapshot.async_take(str(tmp_path / "snap"), state).wait()
    else:
        Snapshot.take(str(tmp_path / "snap"), state)
    assert _live() == before
    entry = Snapshot(str(tmp_path / "snap")).get_manifest()["0/m/w"]
    assert isinstance(entry, ChunkedArrayEntry) and len(entry.chunks) == 3
    # the cut's own evidence: a span and a count a chunk payload, none for the small leaf
    assert len(_spans("stage_chunk_cut")) == 3 and telemetry.counters()["chunk_payloads"] == 3
    assert sorted(e["args"]["bytes"] for e in _spans("stage_chunk_cut")) == [512, 1024, 1024]
    dst = {"m": StateDict(w=jnp.zeros_like(w), b=jnp.zeros_like(b))}
    Snapshot(str(tmp_path / "snap")).restore(dst)
    np.testing.assert_array_equal(np.asarray(dst["m"]["w"]), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(dst["m"]["b"]), np.asarray(b))


def test_the_manifest_and_the_bytes_are_those_of_a_save_that_sliced_at_prepare_time(tmp_path, small_chunks):
    """A numpy leaf's chunks are views, cut the way the parent cut every
    leaf's; a device leaf of the same values writes the same entry (layout,
    locations, checksums) and the same files."""
    w = _leaf(rows=10)
    Snapshot.take(str(tmp_path / "device"), {"m": StateDict(w=w)})
    Snapshot.take(str(tmp_path / "host"), {"m": StateDict(w=np.asarray(w))})
    got = Snapshot(str(tmp_path / "device")).get_manifest()["0/m/w"]
    want = Snapshot(str(tmp_path / "host")).get_manifest()["0/m/w"]
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    assert [c.array.location for c in got.chunks] == ["0/m/w_0_0", "0/m/w_4_0", "0/m/w_8_0"]
    assert [(c.offsets, c.sizes) for c in got.chunks] == [([0, 0], [4, 64]), ([4, 0], [4, 64]), ([8, 0], [2, 64])]
    assert all(c.array.checksum for c in got.chunks)
    for c in got.chunks:
        lo, n = c.offsets[0], c.sizes[0]
        for root in ("device", "host"):
            with open(os.path.join(tmp_path, root, c.array.location), "rb") as f:
                assert f.read() == np.asarray(w)[lo:lo + n].tobytes()


def test_the_cut_is_made_inside_the_dtoh_window(small_chunks, bus, monkeypatch):
    """A chunk of a device-backed leaf is cut after its admission, so the
    cuts alive at once are bounded by the window and not by the leaf."""
    order = []

    class Leaf:  # a device-backed array as the stager sees one
        def __init__(self, host, name="leaf"):
            self.host, self.name = host, name
            self.shape, self.dtype = host.shape, host.dtype
            from types import SimpleNamespace

            self.sharding = SimpleNamespace(device_set=[SimpleNamespace(platform="tpu")])

        def __getitem__(self, index):
            order.append(("cut", index[0].start))
            return Leaf(self.host[index], "chunk")

        def copy_to_host_async(self):
            order.append(("kick", self.name))

        def delete(self):
            order.append(("delete", self.name))

        def __array__(self, dtype=None, copy=None):
            return self.host

    real = A._is_jax_array
    monkeypatch.setattr(A, "_is_jax_array", lambda a: isinstance(a, Leaf) or real(a))
    host = np.arange(10 * 64, dtype=np.float32).reshape(10, 64)
    leaf = Leaf(host)
    _, reqs = ChunkedArrayIOPreparer.prepare_write("w", leaf, ChunkedArrayIOPreparer.chunk_shards(host.shape, "float32"))
    assert order == []  # nothing is cut at prepare time

    class Window(A.DtoHWindow):
        async def admit(self, nbytes):
            order.append(("admit", nbytes))
            await super().admit(nbytes)

    async def stage_all():
        token = A.dtoh_window.set(Window(1024))
        try:
            return [bytes(await r.buffer_stager.stage_buffer(None)) for r in reqs]
        finally:
            A.dtoh_window.reset(token)

    staged = asyncio.run(stage_all())
    assert b"".join(staged) == host.tobytes()
    # a cut's device copy is deleted when its bytes leave the window, ahead of the next admission
    assert order == [("admit", 1024), ("cut", 0), ("kick", "chunk"), ("delete", "chunk"),
                     ("admit", 1024), ("cut", 4), ("kick", "chunk"), ("delete", "chunk"),
                     ("admit", 512), ("cut", 8), ("kick", "chunk"), ("delete", "chunk")]
    assert ("delete", "leaf") not in order  # never the caller's array
    assert len(_spans("stage_chunk_cut")) == 3 == len(_spans("stage_dtoh_gate"))


def test_stats_counts_the_chunk_payloads_of_a_take(tmp_path, small_chunks, bus, capsys):
    """The counter reaches the persisted summary, the fleet aggregate and
    ``stats -v``'s listing."""
    import json

    from torchsnapshot_tpu.cli import main

    Snapshot.take(str(tmp_path / "snap"), {"m": StateDict(w=_leaf(rows=10), v=_leaf(rows=9, seed=2))})
    doc = json.loads((tmp_path / "snap" / ".snapshot_telemetry").read_text())
    assert doc["ranks"][0]["counters"]["chunk_payloads"] == 6
    assert doc["fleet"]["aggregate"]["chunk_payloads"] == 6
    assert main(["stats", "-v", str(tmp_path / "snap")]) == 0
    assert "chunk_payloads" in capsys.readouterr().out


def test_the_models_chunked_state_resumes_the_uninterrupted_run(tmp_path, monkeypatch):
    """The new family's state with its stacked leaves over a (shrunk) chunk
    limit, through ``CheckpointManager``: saved chunked, restored bit-exact
    into a state from another seed, and the next step's loss is the
    uninterrupted run's."""
    from torchsnapshot_tpu.models import block_diffusion_lm as M

    monkeypatch.setattr(chunked, "DEFAULT_MAX_CHUNK_SIZE_BYTES", 16384)
    cfg = M.BlockDiffusionLMConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8,
                                   n_experts=16, top_k=4, expert_ff=24, held=(0, 1, 2, 3))
    tx = M.make_optimizer()
    step = jax.jit(M.make_train_step(cfg, tx))

    def batch(n):
        toks = jax.random.randint(jax.random.PRNGKey(n), (2, 32), 0, 96, jnp.int32)
        return {"tokens": toks, "targets": toks}

    state = M.init_state(jax.random.PRNGKey(0), cfg, tx)
    for n in (1, 2):
        state, _ = step(state, batch(n))
    mgr = CheckpointManager(str(tmp_path / "ckpt"), save_interval_steps=1, keep_last=1, async_save=True)
    before = _live()
    assert mgr.save(2, {"train": StateDict(**state)})
    mgr.wait()
    assert _live() == before
    _, want = step(state, batch(3))
    manifest = Snapshot(mgr.path_for(2)).get_manifest()
    chunked_leaves = [k for k, e in manifest.items() if isinstance(e, ChunkedArrayEntry) and len(e.chunks) > 1]
    assert len(chunked_leaves) == 9 and all("expert_" in k for k in chunked_leaves)  # 3 stacks x (param, mu, nu)
    dst = StateDict(**M.init_state(jax.random.PRNGKey(1), cfg, tx))
    assert mgr.restore({"train": dst}) == 2
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(state)[0], jax.tree_util.tree_leaves(dict(dst))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    _, got = step(dict(dst), batch(3))
    assert float(got) == float(want)
