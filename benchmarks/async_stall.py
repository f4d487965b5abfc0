"""Async-take training stall benchmark — the north-star metric.

Runs a jitted transformer train step in a loop, fires
``Snapshot.async_take`` mid-run, and reports:

- ``blocked_s``: how long the ``async_take`` call itself blocked training
  (the staging / consistency-point interval);
- ``stall_pct``: step-time inflation while snapshot storage I/O overlaps
  training, relative to the undisturbed baseline step time;
- ``total_overhead_s``: blocked_s plus the summed per-step inflation —
  the total training time the snapshot cost.

Reference analogue: benchmarks/torchrec/main.py:136-151 measures the
blocked interval of its async path separately from total save time.
Target: stall_pct < 5.

Usage: python benchmarks/async_stall.py [model_mb] (default 256)
Emits one JSON line via bench_utils.report.
"""

from __future__ import annotations

import os
import statistics
import sys
import time


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench_utils import report

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.models import transformer as T

    model_mb = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    # d_model sized so params+opt state ~ model_mb (params are fp32; adamw
    # doubles them with mu/nu).
    d_model = max(128, int((model_mb * 1e6 / (3 * 4 * 12 * 4)) ** 0.5) // 64 * 64)
    cfg = T.TransformerConfig(
        vocab_size=4096,
        d_model=d_model,
        n_heads=8,
        n_layers=4,
        d_ff=4 * d_model,
        max_seq_len=128,
    )
    tx = T.make_optimizer()
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx)
    step = jax.jit(T.make_train_step(cfg, tx))
    batch = {
        "tokens": jnp.zeros((8, 128), jnp.int32),
        "targets": jnp.zeros((8, 128), jnp.int32),
    }

    nbytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state) if hasattr(x, "nbytes")
    )

    def run_step(state):
        state, loss = step(state, batch)
        jax.block_until_ready(loss)
        return state

    # Warm-up (compile) + baseline.
    state = run_step(state)
    baseline_times = []
    for _ in range(10):
        t0 = time.perf_counter()
        state = run_step(state)
        baseline_times.append(time.perf_counter() - t0)
    baseline = statistics.median(baseline_times)

    import shutil
    import tempfile

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(prefix="tsnap_stall_", dir=base)
    try:
        app_state = {"train": StateDict(dict(state))}

        t0 = time.perf_counter()
        pending = Snapshot.async_take(f"{tmp}/snap", app_state)
        blocked_s = time.perf_counter() - t0

        # Train through the overlapping storage I/O.
        overlap_times = []
        while not pending.done():
            t0 = time.perf_counter()
            state = run_step(state)
            overlap_times.append(time.perf_counter() - t0)
        overlapped_steps = len(overlap_times)
        # A few steps after completion (should match baseline again).
        for _ in range(3):
            state = run_step(state)
        pending.wait()

        overlap_mean = (
            statistics.mean(overlap_times) if overlap_times else baseline
        )
        stall_pct = max(0.0, (overlap_mean - baseline) / baseline * 100.0)
        total_overhead_s = blocked_s + max(
            0.0, sum(overlap_times) - baseline * overlapped_steps
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    report(
        "async_stall",
        {
            "model_bytes": nbytes,
            "baseline_step_s": round(baseline, 4),
            "blocked_s": round(blocked_s, 3),
            "overlapped_steps": overlapped_steps,
            "overlap_step_s": round(overlap_mean, 4),
            "stall_pct": round(stall_pct, 1),
            "total_overhead_s": round(total_overhead_s, 3),
        },
    )


if __name__ == "__main__":
    main()
