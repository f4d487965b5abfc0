"""The production checkpointing recipe, end to end.

Everything a real training loop wants from the framework, composed the
way a job would actually run it:

1. `CheckpointManager` owns cadence, naming, retention, and resume.
2. `warmup()` pre-faults staging buffers so even the FIRST async save
   blocks only for steady-state staging time.
3. Async saves block the loop only for staging; storage I/O overlaps
   the next steps.
4. A mirror root gives two-tier durability (fast primary + replica per
   step) without slowing the loop.
5. The process "crashes"; a fresh manager discovers the latest
   committed step and resumes — and re-running the restored step does
   NOT overwrite its committed snapshot.
6. A preemption (SIGTERM, as cloud spot/maintenance eviction sends)
   triggers a collectively consistent off-cadence emergency save; the
   loop exits cleanly, and a third run resumes from the exact
   preempted step.

Run: JAX_PLATFORMS=cpu python examples/production_loop.py
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

from torchsnapshot_tpu import (
    CheckpointManager,
    PreemptionWatcher,
    RNGState,
    StateDict,
    simulate_preemption_now,
)

D = 256


def init_state(key):
    params = {
        "w1": jax.random.normal(key, (D, D)) * 0.05,
        "w2": jnp.zeros((D, 1)),
    }
    tx = optax.adamw(1e-3)
    return params, tx, tx.init(params)


@jax.jit
def loss_fn(params, x, y):
    return jnp.mean((jnp.tanh(x @ params["w1"]) @ params["w2"] - y) ** 2)


def train(
    root: str,
    mirror: str,
    n_steps: int,
    crash_at: int | None,
    preempt_at: int | None = None,
) -> float:
    key = jax.random.PRNGKey(0)
    params, tx, opt_state = init_state(key)

    watcher = PreemptionWatcher()   # SIGTERM -> flag; handler chained
    mgr = CheckpointManager(
        root,
        save_interval_steps=5,      # checkpoint every 5 steps
        keep_last=2,                # retention: newest 2 survive
        async_save=True,            # block only for staging
        storage_options={"mirror_url": mirror},
        preemption=watcher,         # emergency save on eviction
    )
    app_state = {
        "model": StateDict(params=params),
        "optim": StateDict(state=opt_state),
        "progress": StateDict(step=0),
        "rng": RNGState(),
    }

    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        start = mgr.restore(app_state) + 1
        params = app_state["model"]["params"]
        opt_state = app_state["optim"]["state"]
        print(f"resumed from step {latest}; continuing at {start}")
    else:
        # Pre-fault staging buffers off the critical path: the first
        # async save now blocks like a warm one.
        warmed = mgr.warmup(app_state)
        print(f"warmup pre-faulted {warmed / 1e6:.0f} MB of staging buffers")

    grad_fn = jax.jit(jax.grad(loss_fn))
    rng = np.random.default_rng(7)
    loss = float("nan")
    for step in range(start, n_steps):
        x = jnp.asarray(rng.standard_normal((64, D), np.float32))
        y = jnp.asarray(rng.standard_normal((64, 1), np.float32))
        grads = grad_fn(params, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)

        app_state["model"] = StateDict(params=params)
        app_state["optim"] = StateDict(state=opt_state)
        app_state["progress"] = StateDict(step=step)
        if preempt_at is not None and step == preempt_at:
            # What the cloud does to a spot slice, self-inflicted:
            simulate_preemption_now()
        mgr.save(step, app_state)   # no-op unless due; drains previous async
        if watcher.consumed:
            # Emergency snapshot committed ON EVERY RANK (consumed is the
            # collective signal; `preempted` is rank-local). Exit inside
            # the grace window.
            print(f"preempted: emergency snapshot committed at step {step}")
            watcher.close()
            return float("nan")

        if crash_at is not None and step == crash_at:
            mgr.wait()
            watcher.close()  # a real crash wouldn't, but an in-process
            # "crash" must not leak its SIGTERM handler into later runs
            print(f"simulating a crash after step {step}")
            return float("nan")

        loss = float(loss_fn(params, x, y))
    mgr.wait()
    watcher.close()
    return loss


def main() -> None:
    tmp = tempfile.mkdtemp(prefix="production_loop_")
    root = os.path.join(tmp, "ckpt")
    mirror = f"fs://{tmp}/mirror"

    train(root, mirror, n_steps=20, crash_at=11)   # run 1: dies at step 11
    train(root, mirror, n_steps=20, crash_at=None, preempt_at=17)  # run 2: evicted
    loss = train(root, mirror, n_steps=20, crash_at=None)  # run 3: resumes

    steps = sorted(os.listdir(root))
    print(f"committed snapshots after retention: {steps}")
    # Step 17 is the off-cadence emergency snapshot from the eviction.
    assert steps == ["step_0000000015", "step_0000000017"], steps
    # Retention governs the PRIMARY tier; the durable mirror keeps every
    # step as archival history (prune it with `torchsnapshot-tpu prune`
    # when that history should be bounded too).
    mirrors = sorted(os.listdir(os.path.join(tmp, "mirror")))
    print(f"mirror replicas (archival, unpruned): {mirrors}")
    print(f"final loss {loss:.5f} — resume + retention + mirror + preemption all verified")


if __name__ == "__main__":
    main()
