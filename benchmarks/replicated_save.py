"""Replicated-parameter save benchmark (reference: benchmarks/ddp/main.py:38-70).

Workload: N params of ~100 MB each, fully replicated on device (the DDP
analogue on TPU: a fully-replicated NamedSharding). Compares:
  - snapshot: Snapshot.take through the budgeted async scheduler
  - naive:    jax.device_get + np.save per param (the torch.save analogue)

Usage:
  python benchmarks/replicated_save.py [--gb 1.0] [--params 10] [--cpu]
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gb", type=float, default=0.5, help="total model size, decimal GB")
    ap.add_argument("--params", type=int, default=5)
    ap.add_argument("--cpu", action="store_true", help="force CPU backend")
    args = ap.parse_args()

    from bench_utils import force_cpu_devices, payload_bytes, report, timed_rss

    if args.cpu:
        force_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    from torchsnapshot_tpu import Snapshot, StateDict

    per_param = int(args.gb * 1e9) // args.params
    side = int((per_param // 4) ** 0.5)
    key = jax.random.PRNGKey(0)
    params = {}
    for i in range(args.params):
        key, sub = jax.random.split(key)
        params[f"param_{i}"] = jax.random.normal(sub, (side, side), jnp.float32)
    jax.block_until_ready(params)
    nbytes = sum(v.nbytes for v in params.values())

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(dir=base, prefix="bench_replicated_")
    try:
        # naive baseline: serial DtoH + np.save per param
        res: dict = {}
        with timed_rss(res):
            for name, v in params.items():
                np.save(f"{tmp}/naive_{name}.npy", np.asarray(jax.device_get(v)))
        report("replicated_save/naive_npsave", res, nbytes)

        res = {}
        with timed_rss(res):
            Snapshot.take(f"{tmp}/snap", {"model": StateDict(**params)})
        report("replicated_save/snapshot", res, nbytes)

        # restore
        dst = StateDict(**{k: jnp.zeros_like(v) for k, v in params.items()})
        res = {}
        with timed_rss(res):
            Snapshot(f"{tmp}/snap").restore({"model": dst})
        report("replicated_save/snapshot_restore", res, nbytes)
        a = np.asarray(jax.device_get(params["param_0"]))
        b = np.asarray(jax.device_get(dst["param_0"]))
        assert a.tobytes() == b.tobytes(), "restore not bit-exact"

        # reduced-precision storage: fp32 state stored bf16 (half the
        # staged/written bytes), restored back into fp32 params
        res = {}
        with timed_rss(res):
            Snapshot.take(
                f"{tmp}/snap_bf16",
                {"model": StateDict(**params)},
                save_dtype={"model/**": "bfloat16"},
            )
        res["written_mb"] = round(payload_bytes(f"{tmp}/snap_bf16") / 1e6, 1)
        report("replicated_save/snapshot_bf16", res, nbytes)

        dst16 = StateDict(**{k: jnp.zeros_like(v) for k, v in params.items()})
        res = {}
        with timed_rss(res):
            Snapshot(f"{tmp}/snap_bf16").restore({"model": dst16})
        report("replicated_save/snapshot_bf16_restore", res, nbytes)
        want = np.asarray(jax.device_get(params["param_0"])).astype(
            "bfloat16"
        ).astype("float32")
        got = np.asarray(jax.device_get(dst16["param_0"]))
        assert got.dtype == np.float32 and got.tobytes() == want.tobytes(), (
            "bf16 round-trip mismatch"
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
