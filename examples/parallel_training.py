"""End-to-end parallel training + checkpointing demo.

Runs on whatever JAX finds, on any device count divisible by four: the
four-chip TPU host, or 8 virtual CPU devices with ``JAX_PLATFORMS=cpu``
(no TPU pod needed). Ran to the end on the four-chip v5e host on 2026-09-26.

1. Train a MoE transformer with dp x cp x tp x ep sharding — ring attention
   over the 'seq' axis, tensor-parallel weights over 'model', top-2 MoE
   experts sharded over 'model'.
2. Mid-training, take a non-blocking snapshot (``async_take``) and keep
   training through the storage I/O.
3. "Elastic resume": rebuild the model on a DIFFERENT mesh layout and
   restore the same snapshot into it — overlap resharding handles the
   layout change.
4. Production checkpoint config: async + incremental + mirrored saves
   composed (an unchanged re-save writes zero payloads).
5. Bonus: run a GPipe pipeline-parallel train step on a ('data','pipe')
   mesh (see parallel/pipeline.py).

Usage: JAX_PLATFORMS=cpu python examples/parallel_training.py
"""

from __future__ import annotations

import os
import sys
import tempfile


def main() -> None:
    # Virtual devices for a CPU run; the flag does nothing on a TPU.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    from torchsnapshot_tpu.models import transformer as T
    from torchsnapshot_tpu.parallel import make_mesh

    # ---- 1. dp x cp x tp x ep training -----------------------------------
    n = len(jax.devices())
    if n % 4:
        sys.exit(f"needs a device count divisible by 4, found {n}")
    mesh = make_mesh({"data": n // 4, "seq": 2, "model": 2})
    cfg = T.TransformerConfig(
        vocab_size=256, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, attn_impl="ring", n_experts=2,
    )
    tx = T.make_optimizer()
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    step = jax.jit(T.make_train_step(cfg, tx, mesh=mesh))

    rng = np.random.default_rng(0)
    def batch():
        toks = rng.integers(0, 256, (4, 64), dtype=np.int32)
        b = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(np.roll(toks, -1, 1))}
        return jax.device_put(b, NamedSharding(mesh, P("data", "seq")))

    for i in range(3):
        state, loss = step(state, batch())
        print(f"step {int(state['step'])}: loss {float(loss):.4f}")

    # ---- 2. async snapshot mid-training ----------------------------------
    tmp = tempfile.mkdtemp(prefix="tsnap_demo_")
    pending = Snapshot.async_take(f"{tmp}/ckpt", {"train": StateDict(state=state)})
    for i in range(2):  # training continues during storage I/O
        state, loss = step(state, batch())
        print(f"step {int(state['step'])} (snapshot in flight): loss {float(loss):.4f}")
    snapshot = pending.wait()
    print(f"snapshot committed at {snapshot.path}")

    # ---- 3. elastic resume on a different mesh ---------------------------
    mesh2 = make_mesh({"data": n // 2, "seq": 1, "model": 2})
    cfg2 = T.TransformerConfig(
        vocab_size=256, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, attn_impl="dense", n_experts=2,
    )
    state2 = T.init_state(jax.random.PRNGKey(1), cfg2, tx, mesh=mesh2)
    dst = {"train": StateDict(state=state2)}
    snapshot.restore(dst)
    resumed = dst["train"]["state"]
    # the resumed step counter picks up where the snapshot was taken
    print(f"resumed on mesh {dict(mesh2.shape)} at step {int(resumed['step'])}")
    step2 = jax.jit(T.make_train_step(cfg2, tx, mesh=mesh2))
    b = jax.device_put(
        {
            "tokens": jnp.zeros((4, 64), jnp.int32),
            "targets": jnp.zeros((4, 64), jnp.int32),
        },
        NamedSharding(mesh2, P("data", None)),
    )
    resumed, loss = step2(resumed, b)
    print(f"post-resume step {int(resumed['step'])}: loss {float(loss):.4f}")

    # ---- 4. production checkpoint config ---------------------------------
    # Periodic saves compose: async (no training stall past staging) +
    # incremental (unchanged payloads referenced, not rewritten) + a
    # durable mirror tier (fast local primary, background replica).
    prod_opts = {"mirror_url": f"{tmp}/durable_0"}
    Snapshot.take(
        f"{tmp}/prod_0", {"train": StateDict(state=resumed)},
        storage_options=prod_opts, record_digests=True,
    )
    # A re-save against the base writes only what changed — nothing has
    # trained since prod_0, so ZERO payloads hit storage here (a full
    # optimizer step touches every tensor; examples/lora_incremental.py
    # shows the frozen-backbone case where the win persists through
    # training).
    pending = Snapshot.async_take(
        f"{tmp}/prod_1", {"train": StateDict(state=resumed)},
        storage_options={"mirror_url": f"{tmp}/durable_1"},
        incremental_base=f"{tmp}/prod_0",
    )
    resumed, loss = step2(resumed, b)  # keeps training during I/O
    pending.wait()
    def payload_count(root):
        return sum(
            1 for _, _, files in os.walk(root)
            for f in files if f != ".snapshot_metadata"
        )

    print(
        f"incremental+mirrored snapshot committed: "
        f"{payload_count(f'{tmp}/prod_1')} of {payload_count(f'{tmp}/prod_0')} "
        "payloads rewritten (unchanged ones reference prod_0)"
    )
    # Disaster recovery: deduplicated payloads record each base's MIRROR
    # in the metadata, so the durable tier alone restores the whole chain
    # even after every fast/primary tier is gone.
    import shutil

    shutil.rmtree(f"{tmp}/prod_0")
    shutil.rmtree(f"{tmp}/prod_1")
    dst2 = {"train": StateDict(state=T.init_state(jax.random.PRNGKey(3), cfg2, tx, mesh=mesh2))}
    Snapshot(f"{tmp}/durable_1").restore(dst2)
    print(
        "primaries wiped; durable tier restores the chain at step "
        f"{int(dst2['train']['state']['step'])} "
        "(deduped payloads read from durable_0 via origin_mirrors)"
    )
    # To retire a chain into one self-contained artifact:
    from torchsnapshot_tpu.dedup import consolidate

    consolidate(f"{tmp}/durable_1", f"{tmp}/durable_standalone")
    print("consolidated standalone replica written (no bases required)")

    # ---- 5. pipeline parallelism -----------------------------------------
    from torchsnapshot_tpu.parallel import pipeline_param_sharding, pipelined_apply

    pmesh = make_mesh({"data": n // 4, "pipe": 4})
    L, D = 8, 16

    def layer_fn(layer, h):
        return jnp.tanh(h @ layer["w"])

    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (L, D, D)) * (D**-0.5)}
    params = jax.device_put(params, pipeline_param_sharding(params, pmesh))
    x = jax.device_put(jnp.ones((8, D)), NamedSharding(pmesh, P("data")))
    out = jax.jit(
        lambda p, x: pipelined_apply(p, x, pmesh, layer_fn=layer_fn, n_micro=4)
    )(params, x)
    print(f"pipeline output: shape {out.shape}, finite {bool(jnp.isfinite(out).all())}")


if __name__ == "__main__":
    main()
