"""Frozen-backbone (LoRA-style) fine-tuning with incremental snapshots.

The dominant fine-tuning pattern: a large frozen backbone plus a small
trainable adapter. Incremental snapshots make checkpointing cost scale
with the TRAINABLE fraction — the backbone's bytes are written once, and
every later snapshot references them instead of rewriting them
(torchsnapshot_tpu/dedup.py). The chain is then consolidated into a
self-contained snapshot so the old checkpoints can be deleted, and a
restart restores from it bit-exactly.

Run: JAX_PLATFORMS=cpu python examples/lora_incremental.py
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from torchsnapshot_tpu.compile_cache import enable_compilation_cache

enable_compilation_cache()
import jax.numpy as jnp
import numpy as np
import optax

from torchsnapshot_tpu import Snapshot, StateDict


D_IN, D_HID, RANK = 64, 256, 4


def init_state(key):
    kb1, kb2, ka = jax.random.split(key, 3)
    backbone = {
        "w1": jax.random.normal(kb1, (D_IN, D_HID)) * 0.05,
        "w2": jax.random.normal(kb2, (D_HID, 1)) * 0.05,
    }
    adapter = {  # low-rank update to w1, LoRA-style
        "a": jax.random.normal(ka, (D_IN, RANK)) * 0.05,
        "b": jnp.zeros((RANK, D_HID)),
    }
    return backbone, adapter


@jax.jit
def loss_fn(backbone, adapter, x, y):
    w1 = backbone["w1"] + adapter["a"] @ adapter["b"]
    pred = jnp.tanh(x @ w1) @ backbone["w2"]
    return jnp.mean((pred - y) ** 2)


from functools import partial


@partial(jax.jit, static_argnames="tx_update")
def train_step(backbone, adapter, opt_state, x, y, tx_update):
    grads = jax.grad(loss_fn, argnums=1)(backbone, adapter, x, y)
    updates, opt_state = tx_update(grads, opt_state, adapter)
    return optax.apply_updates(adapter, updates), opt_state


def snap_bytes(path):
    return sum(
        os.path.getsize(os.path.join(r, f))
        for r, _, fs in os.walk(path)
        for f in fs
    )


def main() -> None:
    work = tempfile.mkdtemp(prefix="lora_snap_")
    key = jax.random.PRNGKey(0)
    backbone, adapter = init_state(key)
    tx = optax.adam(1e-2)
    opt_state = tx.init(adapter)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, D_IN))
    y = jnp.sum(x[:, :4], axis=1, keepdims=True)

    def app_state(step):
        return {
            "backbone": StateDict(**backbone),  # frozen: identical each save
            "adapter": StateDict(**adapter),
            "opt": StateDict(state=opt_state),
            "progress": StateDict(step=step),
        }

    ckpts = []
    for step in range(30):
        adapter, opt_state = train_step(backbone, adapter, opt_state, x, y, tx.update)
        if (step + 1) % 10 == 0:
            path = os.path.join(work, f"step_{step + 1}")
            base = ckpts[-1] if ckpts else None
            # device_digests: the frozen backbone is detected unchanged ON
            # DEVICE, so on TPU it never even crosses to the host — the
            # dominant save cost for this workload (see device_digest.py).
            Snapshot.take(
                path,
                app_state(step + 1),
                incremental_base=base,
                record_digests=True,
                device_digests=True,
            )
            ckpts.append(path)
            kind = f"incremental on {os.path.basename(base)}" if base else "full"
            print(
                f"step {step + 1}: saved {os.path.basename(path)} "
                f"({kind}, {snap_bytes(path) / 1e3:.0f} KB on disk)"
            )

    # Retire the chain: one self-contained snapshot, old checkpoints deletable.
    from torchsnapshot_tpu.dedup import consolidate

    final = os.path.join(work, "final")
    consolidate(ckpts[-1], final)
    print(f"consolidated -> final ({snap_bytes(final) / 1e3:.0f} KB, no bases needed)")

    # Simulated restart: fresh state, restore, verify.
    backbone2, adapter2 = init_state(jax.random.PRNGKey(9))
    opt_state2 = tx.init(adapter2)
    progress = StateDict(step=0)
    dst = {
        "backbone": StateDict(**backbone2),
        "adapter": StateDict(**adapter2),
        "opt": StateDict(state=opt_state2),
        "progress": progress,
    }
    Snapshot(final).restore(dst)
    np.testing.assert_array_equal(
        np.asarray(dst["adapter"]["a"]), np.asarray(adapter["a"])
    )
    np.testing.assert_array_equal(
        np.asarray(dst["backbone"]["w1"]), np.asarray(backbone["w1"])
    )
    print(f"restored at step {progress['step']}; parameters bit-exact. done.")


if __name__ == "__main__":
    main()
