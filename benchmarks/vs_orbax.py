"""Head-to-head: torchsnapshot_tpu vs orbax (the incumbent JAX checkpointer).

Saves and restores the same pytree of bf16 arrays with both libraries on
the same storage and reports wall time + GB/s each way. Sizes default to
1 GiB; pass GiB as argv[1].

Usage: JAX_PLATFORMS=cpu python benchmarks/vs_orbax.py [gib]
Emits one JSON line per (library, direction) via bench_utils.report.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench_utils import report

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    gib = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
    total = int(gib * (1 << 30))
    n_arrays = 16
    side = int((total / n_arrays / 2) ** 0.5)
    key = jax.random.PRNGKey(0)
    state = {}
    for i in range(n_arrays):
        key, sub = jax.random.split(key)
        state[f"param_{i}"] = jax.random.normal(sub, (side, side), jnp.bfloat16)
    jax.block_until_ready(state)
    nbytes = sum(a.nbytes for a in state.values())
    print(f"[vs_orbax] state {nbytes / 1e9:.2f} GB", file=sys.stderr, flush=True)

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = tempfile.mkdtemp(prefix="tsnap_vs_orbax_", dir=base)
    try:
        results = {}

        # --- torchsnapshot_tpu ------------------------------------------
        from torchsnapshot_tpu import Snapshot, StateDict

        t0 = time.perf_counter()
        Snapshot.take(f"{tmp}/tsnap", {"m": StateDict(**state)})
        results["tsnap_save"] = time.perf_counter() - t0

        dst = StateDict(**{k: jnp.zeros_like(v) for k, v in state.items()})
        t0 = time.perf_counter()
        Snapshot(f"{tmp}/tsnap").restore({"m": dst})
        results["tsnap_restore"] = time.perf_counter() - t0

        # --- torchsnapshot_tpu incremental (no orbax counterpart) -------
        # The frozen-backbone pattern: second save where only 1/16 of the
        # state changed. Orbax rewrites everything every save; this is the
        # capability gap the dedup layer exists for.
        Snapshot.take(
            f"{tmp}/tsnap_base", {"m": StateDict(**state)}, record_digests=True
        )
        state_inc = dict(state)
        state_inc["param_0"] = state["param_0"] + jnp.bfloat16(1.0)
        jax.block_until_ready(state_inc["param_0"])
        t0 = time.perf_counter()
        Snapshot.take(
            f"{tmp}/tsnap_inc",
            {"m": StateDict(**state_inc)},
            incremental_base=f"{tmp}/tsnap_base",
        )
        results["tsnapincr_save"] = time.perf_counter() - t0

        # --- orbax ------------------------------------------------------
        import orbax.checkpoint as ocp

        with ocp.PyTreeCheckpointer() as ckptr:
            t0 = time.perf_counter()
            ckptr.save(f"{tmp}/orbax", dict(state))
            results["orbax_save"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            restored = ckptr.restore(f"{tmp}/orbax")
            results["orbax_restore"] = time.perf_counter() - t0

        # sanity: every reported save restores bit-exactly
        import numpy as np

        for k, src in state.items():
            ref = np.asarray(src, np.float32)
            np.testing.assert_array_equal(np.asarray(dst[k], np.float32), ref)
            np.testing.assert_array_equal(np.asarray(restored[k], np.float32), ref)

        inc_dst = StateDict(**{k: jnp.zeros_like(v) for k, v in state_inc.items()})
        Snapshot(f"{tmp}/tsnap_inc").restore({"m": inc_dst})
        for k, src in state_inc.items():
            np.testing.assert_array_equal(
                np.asarray(inc_dst[k], np.float32), np.asarray(src, np.float32)
            )

        for name, dt in results.items():
            lib, direction = name.split("_")
            other_lib = "orbax" if lib.startswith("tsnap") else "tsnap"
            other = results.get(f"{other_lib}_{direction}")
            report(
                f"vs_orbax_{name}",
                {
                    "platform": jax.default_backend(),
                    "bytes": nbytes,
                    "wall_s": round(dt, 3),
                    "speedup_vs_other": round(other / dt, 2) if other else None,
                },
                data_bytes=nbytes,
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
