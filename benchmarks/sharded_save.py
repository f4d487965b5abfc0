"""Sharded training-state save/restore benchmark
(reference analogues: benchmarks/fsdp/main.py:36-103 — sharded Transformer
state — and benchmarks/torchrec/main.py:136-151 — sync vs async save with
the caller-blocked interval measured separately).

Builds the flagship transformer with GSPMD-sharded params/optimizer state
on a device mesh, then measures:
  - sync Snapshot.take
  - Snapshot.async_take: caller-blocked time (staging) vs total time to
    commit — the async-stall metric from BASELINE.json
  - restore into a freshly-initialized sharded state

Usage:
  python benchmarks/sharded_save.py [--layers 4] [--d-model 512] [--cpu-devices 8]
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help=">0: run on N virtual CPU devices")
    args = ap.parse_args()

    from bench_utils import force_cpu_devices, report, timed_rss

    if args.cpu_devices:
        force_cpu_devices(args.cpu_devices)
    import jax

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.models import transformer as T
    from torchsnapshot_tpu.parallel import make_mesh

    mesh = make_mesh()
    cfg = T.TransformerConfig(
        vocab_size=8192,
        d_model=args.d_model,
        n_heads=8,
        n_layers=args.layers,
        d_ff=4 * args.d_model,
        max_seq_len=256,
    )
    tx = T.make_optimizer()
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    jax.block_until_ready(state)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(dir=base, prefix="bench_sharded_")
    try:
        app_state = {"train": StateDict(**state)}

        res: dict = {"param_count": cfg.param_count}
        with timed_rss(res):
            Snapshot.take(f"{tmp}/sync", app_state)
        report("sharded_save/sync", res, nbytes)

        res = {}
        t0 = time.perf_counter()
        pending = Snapshot.async_take(f"{tmp}/async", app_state)
        res["caller_blocked_s"] = round(time.perf_counter() - t0, 3)
        pending.wait()
        res["total_s"] = round(time.perf_counter() - t0, 3)
        res["io_overlap_frac"] = round(
            1 - res["caller_blocked_s"] / max(res["total_s"], 1e-9), 3
        )
        # Steady state (staging-buffer pool warm), the production cost of
        # a periodic checkpoint in a training loop.
        shutil.rmtree(f"{tmp}/async", ignore_errors=True)
        time.sleep(1.0)
        t0 = time.perf_counter()
        pending = Snapshot.async_take(f"{tmp}/async", app_state)
        res["warm_caller_blocked_s"] = round(time.perf_counter() - t0, 3)
        pending.wait()
        res["warm_total_s"] = round(time.perf_counter() - t0, 3)
        report("sharded_save/async", res, nbytes)

        fresh = T.init_state(jax.random.PRNGKey(1), cfg, tx, mesh=mesh)
        dst = {"train": StateDict(**fresh)}
        res = {}
        with timed_rss(res):
            Snapshot(f"{tmp}/sync").restore(dst)
        report("sharded_save/restore", res, nbytes)

        a = np.asarray(jax.device_get(state["params"]["embed"]))
        b = np.asarray(jax.device_get(dst["train"]["params"]["embed"]))
        assert a.tobytes() == b.tobytes(), "restore not bit-exact"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
