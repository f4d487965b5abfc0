"""Device-resident change detection on REAL TPU hardware.

An unchanged incremental resave through the host dedup path costs a full
DtoH transfer + SHA-256 before discovering nothing changed; with
``device_digests=True`` the array is fingerprinted ON DEVICE
(device_digest.py) and only 16 bytes cross to the host. This measures
both paths over the same state, warm (fingerprint jits compiled — the
steady state of a training loop saving every N steps):

- ``device_dedup/unchanged_resave``: wall time of an incremental
  ``Snapshot.take`` whose payloads are all unchanged, host vs device
  detection, best of ``trials``. The speedup scales with state size:
  the host path is DtoH-bandwidth-bound, the device path is one pass at
  HBM bandwidth plus a fixed dispatch + 16-byte fetch per array.
- ``device_dedup/chain_reload_restore``: the serving-reload story — a
  process holding step N's state restores step N+1 (incremental on N,
  one small payload changed). Plain restore re-reads + re-transfers
  everything; ``restore(..., device_digests=True)`` fingerprints the
  destination and reads only the changed payload. Timed through
  ``block_until_ready`` on the destination (device_put is async; an
  un-drained plain restore looks artificially instant).

Usage: python benchmarks/device_dedup.py [state_mb] [trials]
Emits one JSON line per leg; exits 2 (no JSON) off-TPU.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench_utils import report

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    if jax.default_backend() != "tpu":
        print(
            f"not a TPU backend ({jax.default_backend()}); this measures "
            "real DtoH avoidance only",
            file=sys.stderr,
        )
        return 2

    from torchsnapshot_tpu import Snapshot, StateDict

    state_mb = float(sys.argv[1]) if len(sys.argv) > 1 else 8.0
    trials = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    n = int(state_mb * 1e6 / 2 / 2)  # two bf16 arrays

    def fresh(seed):
        # Fresh buffers each trial: jax caches fetched host copies on the
        # Array, which would let the host path skip its DtoH.
        k = jax.random.PRNGKey(seed)
        s = StateDict(
            w=jax.random.normal(k, (n,), jnp.bfloat16),
            b=jax.random.normal(jax.random.fold_in(k, 1), (n,), jnp.bfloat16),
        )
        jax.block_until_ready(list(s.values()))
        return s

    tmp = tempfile.mkdtemp(prefix="device_dedup_")
    try:
        st = fresh(0)
        nbytes = sum(v.nbytes for v in st.values())
        # Base take with device digests compiles the fingerprint jits.
        Snapshot.take(os.path.join(tmp, "base"), {"m": st}, device_digests=True)
        legs = {}
        # host leg pins device_digests=False: with the env opt-in set,
        # kwarg None would resolve to the env and turn the control leg
        # into a second device leg (speedup ~1.0, meaningless).
        for name, kw in (
            ("host", {"device_digests": False}),
            ("device", {"device_digests": True}),
        ):
            times = []
            for trial in range(trials + 1):
                s2 = fresh(0)
                t0 = time.perf_counter()
                Snapshot.take(
                    os.path.join(tmp, f"incr_{name}_{trial}"),
                    {"m": s2},
                    incremental_base=os.path.join(tmp, "base"),
                    **kw,
                )
                times.append(time.perf_counter() - t0)
            legs[name] = times[1:]  # drop the per-leg warm-up trial
        t_host, t_dev = min(legs["host"]), min(legs["device"])
        report(
            "device_dedup/unchanged_resave",
            {
                "state_mb": round(nbytes / 1e6, 1),
                "host_dedup_s": round(t_host, 3),
                "device_dedup_s": round(t_dev, 3),
                "speedup": round(t_host / max(t_dev, 1e-9), 1),
                "platform": "tpu",
            },
        )

        # ---- restore side: reload step N+1 while holding step N -------
        # The skip trades one host<->device roundtrip per array
        # (fingerprint dispatch + 16-byte fetch) against the payload's
        # read + HtoD; the leg uses a 3x state to sit past breakeven
        # (where breakeven lies on this host: not measured).
        def fresh_big(seed):
            k = jax.random.PRNGKey(seed)
            s = StateDict(
                w=jax.random.normal(k, (3 * n,), jnp.bfloat16),
                b=jax.random.normal(jax.random.fold_in(k, 1), (3 * n,), jnp.bfloat16),
            )
            jax.block_until_ready(list(s.values()))
            return s

        st = fresh_big(0)
        restore_nbytes = sum(v.nbytes for v in st.values())
        adapter = jax.random.normal(jax.random.PRNGKey(7), (64, 64), jnp.float32)
        s0, s1 = os.path.join(tmp, "r0"), os.path.join(tmp, "r1")
        Snapshot.take(
            s0, {"m": StateDict(**st, a=adapter)}, device_digests=True
        )
        Snapshot.take(
            s1,
            {"m": StateDict(**{k: v + 0 for k, v in st.items()}, a=adapter * 2)},
            incremental_base=s0,
            device_digests=True,
        )
        restore_legs = {}
        # plain leg pins device_digests=False for the same reason as the
        # take-side host leg: the env opt-in must not contaminate the
        # control.
        for name, kw in (
            ("plain", {"device_digests": False}),
            ("digest", {"device_digests": True}),
        ):
            times = []
            for trial in range(trials + 1):
                dst = {
                    "m": StateDict(
                        **{k: v + 0 for k, v in st.items()}, a=adapter + 0
                    )
                }
                jax.block_until_ready(list(dst["m"].values()))
                t0 = time.perf_counter()
                Snapshot(s1).restore(dst, **kw)
                jax.block_until_ready(list(dst["m"].values()))
                times.append(time.perf_counter() - t0)
            restore_legs[name] = min(times[1:])
        report(
            "device_dedup/chain_reload_restore",
            {
                "state_mb": round(restore_nbytes / 1e6, 1),
                "plain_restore_s": round(restore_legs["plain"], 3),
                "digest_restore_s": round(restore_legs["digest"], 3),
                "speedup": round(
                    restore_legs["plain"] / max(restore_legs["digest"], 1e-9), 1
                ),
                "platform": "tpu",
            },
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
