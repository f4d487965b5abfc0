"""Serving hot-reload: follow a training run's checkpoints, moving only
the bytes that changed.

A serving/eval process keeps model state resident on device and
periodically picks up the trainer's newest snapshot. With incremental
snapshots + device digests the reload cost scales with what CHANGED,
not with model size, on both ends:

- the trainer saves step N+1 incrementally against step N — unchanged
  payloads skip the DtoH transfer and the storage write entirely
  (fingerprinted on device, device_digest.py);
- the server restores step N+1 with ``device_digests=True`` — its
  resident arrays are fingerprinted on device against the snapshot's
  manifest, and only changed payloads are read and transferred HtoD.

Here the "trainer" freezes the backbone and trains a small adapter (the
LoRA pattern): each reload moves only the adapter's bytes while the
backbone — most of the model — never crosses the wire in either
direction after step 0.

Run: JAX_PLATFORMS=cpu python examples/serving_reload.py
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The final act reshards across a 4-device mesh; give the CPU backend
# virtual devices BEFORE jax initializes (a plain JAX_PLATFORMS=cpu run
# has one device and would silently skip the demo's point).
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()

import jax

from torchsnapshot_tpu.compile_cache import enable_compilation_cache

enable_compilation_cache()

import jax.numpy as jnp
import numpy as np

from torchsnapshot_tpu import CheckpointManager, Snapshot, StateDict

BACKBONE = (512, 512)
ADAPTER = (512, 8)


def main() -> None:
    tmp = tempfile.mkdtemp(prefix="serving_reload_")
    root = os.path.join(tmp, "ckpt")

    # ---- trainer side -------------------------------------------------
    key = jax.random.PRNGKey(0)
    backbone = jax.random.normal(key, BACKBONE, jnp.bfloat16)  # frozen
    adapter = jnp.zeros(ADAPTER, jnp.float32)

    trainer = CheckpointManager(root, incremental=True, device_digests=True)

    def train_and_save(step: int, adapter):
        adapter = adapter + 0.01 * (step + 1)  # "training"
        trainer.save(
            step,
            {"model": StateDict(backbone=backbone, adapter=adapter)},
            force=True,
        )
        return adapter

    adapter = train_and_save(0, adapter)

    # ---- server side --------------------------------------------------
    # Resident state: restored once in full, then hot-reloaded.
    served = {
        "model": StateDict(
            backbone=jnp.zeros(BACKBONE, jnp.bfloat16),
            adapter=jnp.zeros(ADAPTER, jnp.float32),
        )
    }
    step = trainer.latest_step()
    Snapshot(trainer.path_for(step)).restore(served)
    print(f"server: cold restore of step {step} (full read)")

    # Count payload consumes to show exactly what later reloads move.
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer

    reads = []
    orig = ArrayBufferConsumer._consume_sync

    def counting(self, buf):
        reads.append(self.entry.location)
        return orig(self, buf)

    ArrayBufferConsumer._consume_sync = counting
    try:
        for step in (1, 2, 3):
            adapter = train_and_save(step, adapter)
            reads.clear()
            Snapshot(trainer.path_for(step)).restore(served, device_digests=True)
            assert all("adapter" in loc for loc in reads), reads
            print(
                f"server: hot-reloaded step {step} — {len(reads)} payload(s) "
                f"moved ({', '.join(sorted(reads))}); backbone untouched"
            )
    finally:
        ArrayBufferConsumer._consume_sync = orig

    np.testing.assert_array_equal(
        np.asarray(served["model"]["adapter"]), np.asarray(adapter)
    )
    np.testing.assert_array_equal(
        np.asarray(served["model"]["backbone"]), np.asarray(backbone)
    )
    print("served state bit-exact with the trainer's latest. done.")

    # ---- serving mesh != training mesh --------------------------------
    # The skip survives a LAYOUT change: the server shards the model for
    # inference differently than the trainer saved it. Saved pieces are
    # fingerprinted against (re)assembled slices of the destination —
    # global slices on a fully-addressable host, stitched local shards in
    # multi-process pods (io_preparers/sharded.py:_dst_already_matches) —
    # so only the changed adapter moves even though every box differs.
    if len(jax.devices()) >= 4:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = np.array(jax.devices()[:4])
        train_mesh = Mesh(devs.reshape(2, 2), ("data", "model"))
        serve_mesh = Mesh(devs.reshape(4), ("model",))

        backbone_t = jax.device_put(
            backbone, NamedSharding(train_mesh, P("data", "model"))
        )
        adapter_t = jax.device_put(
            adapter, NamedSharding(train_mesh, P("model", None))
        )
        trainer.save(
            4,
            {"model": StateDict(backbone=backbone_t, adapter=adapter_t)},
            force=True,
        )

        served_sharded = {
            "model": StateDict(
                backbone=jax.device_put(
                    np.asarray(served["model"]["backbone"]),
                    NamedSharding(serve_mesh, P("model", None)),
                ),
                adapter=jax.device_put(
                    np.asarray(served["model"]["adapter"]) * 0,  # stale
                    NamedSharding(serve_mesh, P(None, "model")),
                ),
            )
        }
        from torchsnapshot_tpu.io_preparers.sharded import _ShardScatterConsumer

        sharded_reads = []
        orig_s = _ShardScatterConsumer._consume_sync

        def counting_s(self, buf):
            sharded_reads.append(self.shard.array.location)
            return orig_s(self, buf)

        _ShardScatterConsumer._consume_sync = counting_s
        try:
            Snapshot(trainer.path_for(4)).restore(
                served_sharded, device_digests=True
            )
        finally:
            _ShardScatterConsumer._consume_sync = orig_s
        assert all("adapter" in loc for loc in sharded_reads), sharded_reads
        np.testing.assert_array_equal(
            np.asarray(served_sharded["model"]["backbone"]), np.asarray(backbone)
        )
        np.testing.assert_array_equal(
            np.asarray(served_sharded["model"]["adapter"]), np.asarray(adapter)
        )
        print(
            "server (different mesh): reloaded step 4 — "
            f"{len(sharded_reads)} shard read(s), all adapter; backbone "
            "verified across the layout change without a byte moved"
        )


if __name__ == "__main__":
    main()
