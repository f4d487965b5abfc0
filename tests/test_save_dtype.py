"""save_dtype: store checkpoints downcast, restore widens back.

``Snapshot.take(..., save_dtype={"glob": "dtype"})`` downcasts matching
float array leaves before staging — on device for jax arrays (astype
preserves sharding; DtoH then moves half the bytes for fp32 states) — and
the manifest records the stored dtype, so cast-on-restore widens back into
the destination's params transparently. Int and object leaves under a glob
are left alone (same_kind casts only).

No reference analogue (torchsnapshot stores tensors byte-exact only); the
orbax counterpart is SaveArgs dtype casting.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import CheckpointManager, Snapshot, StateDict
from torchsnapshot_tpu.manifest import ArrayEntry, ShardedArrayEntry


def _entries(path):
    from torchsnapshot_tpu.manifest import get_manifest_for_rank

    return get_manifest_for_rank(Snapshot(path=path).metadata, 0)


def _payload_bytes(path):
    total = 0
    for dp, _, fs in os.walk(path):
        for f in fs:
            if not f.startswith("."):
                total += os.path.getsize(os.path.join(dp, f))
    return total


def test_downcast_halves_storage_and_restores_back(tmp_path):
    src_w = np.arange(4096, dtype=np.float32)
    state = {"m": StateDict(w=jnp.asarray(src_w), step=np.int64(7))}
    full = str(tmp_path / "full")
    half = str(tmp_path / "half")
    Snapshot.take(full, state)
    Snapshot.take(half, state, save_dtype={"m/**": "bfloat16"})

    # Stored dtype is recorded; the int leaf is untouched.
    ents = _entries(half)
    assert ents["m/w"].dtype == "bfloat16"
    # Payload bytes roughly halve (metadata excluded above).
    assert _payload_bytes(half) < 0.6 * _payload_bytes(full)

    # Restore widens back into fp32 params.
    dst = {"m": StateDict(w=jnp.zeros(4096, jnp.float32), step=np.int64(0))}
    Snapshot(path=half).restore(dst)
    assert dst["m"]["w"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w"]), src_w.astype("bfloat16").astype(np.float32)
    )
    assert int(dst["m"]["step"]) == 7


def test_int_array_leaves_under_float_glob_stay_int(tmp_path):
    """The optax trap: ``count`` is an int32 ARRAY (not a scalar). numpy's
    same_kind alone would permit int->float — corrupting counts > 256 and
    making the snapshot unrestorable into the original int destination
    (restore forbids float->int) — so the class rule must keep it int."""
    state = {
        "opt": StateDict(
            mu=jnp.ones(64, jnp.float32),
            count=jnp.asarray(np.full(4, 301, np.int32)),
            flag=np.array([True, False]),
        )
    }
    path = str(tmp_path / "s")
    Snapshot.take(path, state, save_dtype={"opt/**": "bfloat16"})
    ents = _entries(path)
    assert ents["opt/mu"].dtype == "bfloat16"
    assert ents["opt/count"].dtype == "int32"
    assert ents["opt/flag"].dtype == "bool"

    dst = {
        "opt": StateDict(
            mu=jnp.zeros(64, jnp.float32),
            count=jnp.zeros(4, jnp.int32),
            flag=np.array([False, False]),
        )
    }
    Snapshot(path=path).restore(dst)
    np.testing.assert_array_equal(np.asarray(dst["opt"]["count"]), [301] * 4)


def test_int_to_int_narrowing_by_explicit_glob(tmp_path):
    # numpy leaves both ways: jax silently downgrades int64 under the
    # suite's JAX_ENABLE_X64=0, which would mask the cast being tested.
    state = {"m": StateDict(ids=np.arange(128, dtype=np.int64))}
    path = str(tmp_path / "s")
    Snapshot.take(path, state, save_dtype={"m/ids": "int32"})
    assert _entries(path)["m/ids"].dtype == "int32"
    dst = np.zeros(128, np.int64)
    Snapshot(path=path).restore({"m": StateDict(ids=dst)})
    np.testing.assert_array_equal(dst, np.arange(128))


def test_invalid_dtype_name_fails_fast(tmp_path):
    state = {"m": StateDict(w=jnp.ones(4, jnp.float32))}
    with pytest.raises(ValueError, match="save_dtype.*bf16"):
        Snapshot.take(str(tmp_path / "s"), state, save_dtype={"m/**": "bf16"})
    assert not os.path.exists(str(tmp_path / "s"))
    with pytest.raises(ValueError, match="save_dtype"):
        Snapshot.async_take(
            str(tmp_path / "s2"), state, save_dtype={"m/**": "half"}
        )


def test_non_matching_globs_untouched(tmp_path):
    state = {
        "m": StateDict(w=jnp.ones(64, jnp.float32)),
        "opt": StateDict(mu=jnp.ones(64, jnp.float32)),
    }
    path = str(tmp_path / "s")
    Snapshot.take(path, state, save_dtype={"opt/**": "bfloat16"})
    ents = _entries(path)
    assert ents["m/w"].dtype == "float32"
    assert ents["opt/mu"].dtype == "bfloat16"


def test_first_matching_glob_wins(tmp_path):
    state = {"m": StateDict(a=jnp.ones(8, jnp.float32), b=jnp.ones(8, jnp.float32))}
    path = str(tmp_path / "s")
    Snapshot.take(
        path, state, save_dtype={"m/a": "float32", "m/**": "bfloat16"}
    )
    ents = _entries(path)
    assert ents["m/a"].dtype == "float32"  # explicit no-op match shields m/a
    assert ents["m/b"].dtype == "bfloat16"


def test_sharded_downcast_preserves_sharding(tmp_path):
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("x", "y"))
    data = np.arange(32 * 16, dtype="float32").reshape(32, 16)
    src = jax.device_put(jnp.asarray(data), NamedSharding(mesh, P("x", "y")))
    path = str(tmp_path / "s")
    Snapshot.take(path, {"m": StateDict(w=src)}, save_dtype={"m/**": "bfloat16"})

    ent = _entries(path)["m/w"]
    assert isinstance(ent, ShardedArrayEntry)
    assert ent.dtype == "bfloat16"

    dst = jax.device_put(
        jnp.zeros((32, 16), jnp.float32), NamedSharding(mesh, P("x", "y"))
    )
    out = {"m": StateDict(w=dst)}
    Snapshot(path=path).restore(out)
    restored = out["m"]["w"]
    assert restored.dtype == jnp.float32
    assert restored.sharding == dst.sharding
    np.testing.assert_array_equal(
        np.asarray(restored), data.astype("bfloat16").astype(np.float32)
    )


def test_async_take_save_dtype(tmp_path):
    state = {"m": StateDict(w=jnp.arange(1024, dtype=jnp.float32))}
    path = str(tmp_path / "s")
    pending = Snapshot.async_take(path, state, save_dtype={"m/**": "bfloat16"})
    pending.wait()
    assert _entries(path)["m/w"].dtype == "bfloat16"


def test_manager_save_dtype_end_to_end(tmp_path):
    mgr = CheckpointManager(str(tmp_path), save_dtype={"m/**": "bfloat16"})
    state = {"m": StateDict(w=jnp.arange(256, dtype=jnp.float32))}
    mgr.warmup(state)  # warms at the CONVERTED slab sizes
    assert mgr.save(0, state)
    ents = _entries(mgr.path_for(0))
    assert ents["m/w"].dtype == "bfloat16"
    dst = {"m": StateDict(w=jnp.zeros(256, jnp.float32))}
    Snapshot(path=mgr.path_for(0)).restore(dst)
    assert dst["m"]["w"].dtype == jnp.float32


def test_warmup_sizes_follow_save_dtype():
    """The pool must be warmed at the converted slab size, or the first
    real save misses the exact-size free list entirely."""
    from torchsnapshot_tpu.io_preparers import array as array_mod

    from torchsnapshot_tpu._native import native_available

    if not native_available():
        pytest.skip("staging pool inactive on this host")

    state = {"m": StateDict(w=np.ones(100_000, np.float32))}
    warmed = array_mod.warmup_staging(state, save_dtype={"m/**": "bfloat16"})
    # 100k fp32 elements stored as bf16 = 200 kB slab, not 400 kB.
    # (prewarm returns bytes newly faulted; 0 if this exact size is
    # already pooled from an earlier test — check the pool either way.)
    with array_mod._staging_pool._lock:
        assert 200_000 in array_mod._staging_pool._free
    assert warmed in (0, 200_000)


def test_save_dtype_upcast_also_works(tmp_path):
    """The mapping is a cast, not only a downcast: same_kind either way."""
    state = {"m": StateDict(w=jnp.arange(64, dtype=jnp.bfloat16))}
    path = str(tmp_path / "s")
    Snapshot.take(path, state, save_dtype={"m/**": "float32"})
    assert _entries(path)["m/w"].dtype == "float32"


def test_fp8_quarter_size_storage(tmp_path):
    """fp8 is in the float class: 4x smaller storage for tolerant state
    (e.g. EMA shadows); restore widens back through the same machinery."""
    src = np.linspace(-2, 2, 1024, dtype=np.float32)
    path = str(tmp_path / "s")
    Snapshot.take(
        path,
        {"m": StateDict(w=jnp.asarray(src))},
        save_dtype={"m/**": "float8_e4m3fn"},
    )
    assert _entries(path)["m/w"].dtype == "float8_e4m3fn"
    dst = {"m": StateDict(w=jnp.zeros(1024, jnp.float32))}
    Snapshot(path=path).restore(dst)
    import ml_dtypes

    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w"]),
        src.astype(ml_dtypes.float8_e4m3fn).astype(np.float32),
    )


def test_composes_with_incremental_and_compression(tmp_path):
    """Digests are computed on the CONVERTED bytes, so an unchanged leaf
    dedups across a save_dtype chain, and compression applies on top."""
    mgr = CheckpointManager(
        str(tmp_path),
        incremental=True,
        compression="zstd",
        save_dtype={"m/**": "bfloat16"},
    )
    w = jnp.arange(4096, dtype=jnp.float32)
    frozen = jnp.ones(4096, jnp.float32)
    assert mgr.save(0, {"m": StateDict(w=w, frozen=frozen)})
    assert mgr.save(1, {"m": StateDict(w=w * 2, frozen=frozen)})

    ents = _entries(mgr.path_for(1))
    assert ents["m/w"].dtype == "bfloat16"
    # The unchanged leaf's payload points back at step 0's bytes.
    frozen_ent = ents["m/frozen"]
    inner = (
        frozen_ent.chunks[0].array
        if hasattr(frozen_ent, "chunks")
        else frozen_ent
    )
    assert inner.origin is not None and "step_0000000000" in inner.origin

    dst = {
        "m": StateDict(
            w=jnp.zeros(4096, jnp.float32), frozen=jnp.zeros(4096, jnp.float32)
        )
    }
    Snapshot(path=mgr.path_for(1)).restore(dst)
    assert dst["m"]["w"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w"]),
        (np.arange(4096, dtype="float32") * 2).astype("bfloat16").astype("float32"),
    )
    np.testing.assert_array_equal(np.asarray(dst["m"]["frozen"]), np.ones(4096, "float32"))
