"""Row-wise sharded embedding checkpoint benchmark
(reference: benchmarks/torchrec/main.py:54-231 — DLRM row-wise sharded
embedding tables; sync vs async save with the caller-blocked interval and
peak RSS measured).

Usage:
  python benchmarks/embedding_save.py [--gb 1.0] [--tables 8] [--cpu-devices 8]
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gb", type=float, default=0.5, help="total table size, decimal GB")
    ap.add_argument("--tables", type=int, default=8)
    ap.add_argument("--cpu-devices", type=int, default=0)
    args = ap.parse_args()

    from bench_utils import force_cpu_devices, report, timed_rss

    if args.cpu_devices:
        force_cpu_devices(args.cpu_devices)
    import jax

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.models import embedding as E
    from torchsnapshot_tpu.parallel import make_mesh

    mesh = make_mesh()
    dim = 64
    rows = int(args.gb * 1e9 / args.tables / dim / 4)
    # rows must tile over all devices for the row-wise layout
    n_dev = len(jax.devices())
    rows -= rows % max(n_dev, 1)
    cfg = E.EmbeddingConfig(n_tables=args.tables, rows_per_table=rows, dim=dim)
    import optax

    tx = optax.adagrad(1e-2)  # DLRM-style sparse-friendly optimizer
    state = E.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    jax.block_until_ready(state)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(dir=base, prefix="bench_embedding_")
    try:
        app_state = {"train": StateDict(**state)}

        res: dict = {"param_count": cfg.param_count, "rows_per_table": rows}
        with timed_rss(res):
            Snapshot.take(f"{tmp}/sync", app_state)
        report("embedding_save/sync", res, nbytes)

        # Cold = first async_take of the process, with the staging pool
        # pre-faulted by warmup_staging (the production recipe: warm up
        # once after building state, off the training-loop critical path).
        from torchsnapshot_tpu import warmup_staging

        res = {}
        t0 = time.perf_counter()
        res["warmup_mb"] = round(warmup_staging(app_state) / 1e6, 1)
        res["warmup_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        pending = Snapshot.async_take(f"{tmp}/async", app_state)
        res["caller_blocked_s"] = round(time.perf_counter() - t0, 3)
        pending.wait()
        res["total_s"] = round(time.perf_counter() - t0, 3)
        # Steady state: a training loop checkpoints repeatedly; from the
        # second async_take the staging-buffer pool recycles, so warm
        # numbers are the production caller-blocked cost.
        shutil.rmtree(f"{tmp}/async", ignore_errors=True)
        time.sleep(1.0)
        t0 = time.perf_counter()
        pending = Snapshot.async_take(f"{tmp}/async", app_state)
        res["warm_caller_blocked_s"] = round(time.perf_counter() - t0, 3)
        pending.wait()
        res["warm_total_s"] = round(time.perf_counter() - t0, 3)
        report("embedding_save/async", res, nbytes)

        fresh = E.init_state(jax.random.PRNGKey(1), cfg, tx, mesh=mesh)
        dst = {"train": StateDict(**fresh)}
        res = {}
        with timed_rss(res):
            Snapshot(f"{tmp}/sync").restore(dst)
        report("embedding_save/restore", res, nbytes)

        a = np.asarray(jax.device_get(state["params"]["tables"]["table_0"]))
        b = np.asarray(jax.device_get(dst["train"]["params"]["tables"]["table_0"]))
        assert a.tobytes() == b.tobytes(), "restore not bit-exact"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
