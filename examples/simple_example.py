"""Minimal train/checkpoint/resume loop (reference: examples/simple_example.py).

Trains a tiny MLP with optax, snapshots every few steps (progress counter
in a StateDict), then simulates a restart: rebuilds fresh state, restores,
and continues from the saved step with a bit-exact parameter match.

Run: python examples/simple_example.py [--work-dir /tmp/snapshots]
"""

from __future__ import annotations

import argparse
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

from torchsnapshot_tpu import RNGState, Snapshot, StateDict


def init_params(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (8, 16)) * 0.1,
        "w2": jax.random.normal(k2, (16, 1)) * 0.1,
    }


@jax.jit
def loss_fn(params, x, y):
    h = jnp.tanh(x @ params["w1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - y) ** 2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--work-dir", default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--snapshot-every", type=int, default=5)
    args = ap.parse_args()
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="simple_example_")

    tx = optax.adam(1e-2)
    params = init_params(jax.random.PRNGKey(0))
    opt_state = tx.init(params)
    progress = StateDict(step=0)
    grad_fn = jax.jit(jax.grad(loss_fn))

    x = jnp.asarray(np.random.default_rng(0).standard_normal((32, 8)))
    y = jnp.sum(x, axis=1, keepdims=True)

    app_state = {
        "model": StateDict(params=params),
        "optim": StateDict(state=opt_state),
        "progress": progress,
        "rng": RNGState(),
    }

    last_snapshot = None
    while progress["step"] < args.steps:
        grads = grad_fn(app_state["model"]["params"], x, y)
        updates, new_opt = tx.update(
            grads, app_state["optim"]["state"], app_state["model"]["params"]
        )
        app_state["model"]["params"] = optax.apply_updates(
            app_state["model"]["params"], updates
        )
        app_state["optim"]["state"] = new_opt
        progress["step"] += 1

        if progress["step"] % args.snapshot_every == 0:
            path = f"{work_dir}/step_{progress['step']}"
            # async_take returns once staging is done; training can resume
            # immediately while storage I/O completes in the background.
            pending = Snapshot.async_take(path, app_state)
            last_snapshot = (path, pending)
            print(f"step {progress['step']}: snapshot -> {path}")

    if last_snapshot is None or last_snapshot[0] != f"{work_dir}/step_{args.steps}":
        # Final step didn't land on the cadence — snapshot it synchronously
        # so the restart below always resumes from step == args.steps.
        # Drain the superseded async snapshot first: dropping its handle
        # would orphan in-flight I/O and swallow its errors.
        if last_snapshot is not None and last_snapshot[1] is not None:
            last_snapshot[1].wait()
        path = f"{work_dir}/step_{args.steps}"
        Snapshot.take(path, app_state)
        last_snapshot = (path, None)
        print(f"step {progress['step']}: final snapshot -> {path}")

    path, pending = last_snapshot
    if pending is not None:
        pending.wait()

    # ----- simulated restart: fresh state, restore, verify
    params_before = app_state["model"]["params"]
    restored = {
        "model": StateDict(params=init_params(jax.random.PRNGKey(42))),
        "optim": StateDict(state=tx.init(init_params(jax.random.PRNGKey(42)))),
        "progress": StateDict(step=0),
        "rng": RNGState(),
    }
    Snapshot(path).restore(restored)
    assert restored["progress"]["step"] == args.steps
    for a, b in zip(
        jax.tree.leaves(restored["model"]["params"]), jax.tree.leaves(params_before)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"resumed from step {restored['progress']['step']}: params bit-exact")


if __name__ == "__main__":
    main()
