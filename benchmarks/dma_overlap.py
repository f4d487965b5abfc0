"""DtoH DMA-staging overlap on REAL TPU hardware.

The TPU-native staging design's core claim is that ``copy_to_host_async``
lets DtoH transfers overlap — with each other and with on-chip compute —
where a serial ``device_get`` loop strictly alternates. This measures
both claims, sized from the link rate it measures first:

0. ``dma_overlap/ceiling``: the MEASURED link/host ceilings every other
   number is normalized against — raw ``device_get`` bandwidth on one
   large buffer (= what one DtoH stream delivers on this host) and
   single-thread host memcpy bandwidth (= what the host pipeline can
   possibly deliver). The other legs report achieved-%-of-ceiling.
1. ``dma_overlap/stage``: N device arrays fetched serially
   (``np.asarray`` one by one) vs all DMAs kicked first via
   ``copy_to_host_async`` then drained. overlap_ratio = serial/async
   wall; > 1 means the copies genuinely ran concurrently.
2. ``dma_overlap/async_take``: a jitted on-chip train step timed bare,
   then with ``Snapshot.async_take`` of a small device state in flight
   — step_inflation shows how much staging+I/O steals from compute.
3. ``dma_overlap/sync_take``: a warm-machinery ``Snapshot.take`` over
   FRESH device arrays (uncached DtoH) with a bit-exact restore —
   the end-to-end on-chip checkpoint number, sized from the measured
   ceiling to a ~40 s transfer budget (a faster link automatically
   gets a bigger, more credible absolute datapoint).

Usage: python benchmarks/dma_overlap.py [n_arrays] [mb_per_array]
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
    import numpy as np

    from bench_utils import report

    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    if jax.default_backend() != "tpu":
        print(
            f"not a TPU backend ({jax.default_backend()}); this measures "
            "real DMA overlap only",
            file=sys.stderr,
        )
        return 2

    n_arrays = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    mb = float(sys.argv[2]) if len(sys.argv) > 2 else 2.0
    n_elem = int(mb * 1e6 / 2)  # bf16

    # --- leg 0: measured ceilings ------------------------------------
    # DtoH ceiling: one large uncached device_get. Two probes — a small
    # one sizes the big one so a slow link doesn't eat the budget.
    small = jax.random.normal(jax.random.PRNGKey(7), (1 << 21,), jnp.bfloat16)
    jax.block_until_ready(small)
    t0 = time.perf_counter()
    np.asarray(small)
    small_mbps = (small.nbytes / 1e6) / max(time.perf_counter() - t0, 1e-9)
    # Size the real probe to ~10 s of transfer at the observed rate,
    # clamped to [4 MB, 512 MB].
    probe_mb = max(4.0, min(512.0, small_mbps * 10.0))
    big = jax.random.normal(
        jax.random.PRNGKey(8), (int(probe_mb * 1e6 / 2),), jnp.bfloat16
    )
    jax.block_until_ready(big)
    t0 = time.perf_counter()
    np.asarray(big)
    dtoh_ceiling_mbps = (big.nbytes / 1e6) / max(time.perf_counter() - t0, 1e-9)
    del big

    # Host ceiling: single-thread memcpy on a 256 MB buffer (the save
    # pipeline's floor cost is one pass over the bytes on the host).
    src = np.ones(256 * 1024 * 1024, np.uint8)
    dst_buf = np.empty_like(src)
    np.copyto(dst_buf, src)  # fault pages
    t0 = time.perf_counter()
    np.copyto(dst_buf, src)
    host_memcpy_gbps = (src.nbytes / 1e9) / max(time.perf_counter() - t0, 1e-9)
    del src, dst_buf

    report(
        "dma_overlap/ceiling",
        {
            "dtoh_probe_mb": round(probe_mb, 1),
            "dtoh_ceiling_mbps": round(dtoh_ceiling_mbps, 2),
            "host_memcpy_gbps": round(host_memcpy_gbps, 2),
            "platform": "tpu",
        },
    )

    # jax caches the fetched host copy on the Array (_npy_value), and
    # copy_to_host_async early-returns once it is set — each leg must
    # fetch FRESH device arrays or it times cache hits, not transfers.
    def build(seed):
        key = jax.random.PRNGKey(seed)
        arrs = []
        for _ in range(n_arrays):
            key, sub = jax.random.split(key)
            arrs.append(jax.random.normal(sub, (n_elem,), jnp.bfloat16))
        jax.block_until_ready(arrs)
        return arrs

    serial_arrs = build(0)
    async_arrs = build(0)  # same seed: same values, distinct buffers

    # Warm the transfer path on a throwaway array.
    warm = jax.random.normal(jax.random.PRNGKey(99), (n_elem,), jnp.bfloat16)
    np.asarray(warm)

    # --- serial device_get -------------------------------------------
    t0 = time.perf_counter()
    hosts = [np.asarray(a) for a in serial_arrs]
    t_serial = time.perf_counter() - t0

    # --- kick all DMAs, then drain -----------------------------------
    t0 = time.perf_counter()
    for a in async_arrs:
        a.copy_to_host_async()
    hosts2 = [np.asarray(a) for a in async_arrs]
    t_async = time.perf_counter() - t0

    for h1, h2 in zip(hosts, hosts2):
        np.testing.assert_array_equal(h1, h2)

    total_mb = n_arrays * mb
    report(
        "dma_overlap/stage",
        {
            "n_arrays": n_arrays,
            "mb_per_array": mb,
            "serial_s": round(t_serial, 3),
            "async_s": round(t_async, 3),
            "overlap_ratio": round(t_serial / max(t_async, 1e-9), 2),
            "serial_mbps": round(total_mb / max(t_serial, 1e-9), 2),
            "async_mbps": round(total_mb / max(t_async, 1e-9), 2),
            # Overlapped staging vs what the link can possibly deliver.
            "async_pct_of_ceiling": round(
                100.0
                * (total_mb / max(t_async, 1e-9))
                / max(dtoh_ceiling_mbps, 1e-9),
                1,
            ),
            "platform": "tpu",
        },
    )

    # --- async_take overlapping an on-chip step ----------------------
    from torchsnapshot_tpu import Snapshot, StateDict

    d = 1024
    w = jax.random.normal(jax.random.PRNGKey(1), (d, d), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, d), jnp.bfloat16)

    n_inner = 512

    @jax.jit
    def step(w, x):
        def body(carry, _):
            h = jnp.tanh(carry @ w)
            return h, None

        out, _ = jax.lax.scan(body, x, None, length=n_inner)
        return jnp.float32(out.sum())

    float(step(w, x))  # compile
    t0 = time.perf_counter()
    float(step(w, x))
    t_step = time.perf_counter() - t0

    state = {"m": StateDict(w=w)}
    tmp = tempfile.mkdtemp(prefix="dma_overlap_")
    try:
        t0 = time.perf_counter()
        pending = Snapshot.async_take(os.path.join(tmp, "snap"), state)
        blocked = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(step(w, x))  # compute while staging I/O drains
        t_overlap = time.perf_counter() - t0
        pending.wait()
        total = time.perf_counter() - t0 + blocked
        report(
            "dma_overlap/async_take",
            {
                "state_mb": round(w.nbytes / 1e6, 1),
                "bare_step_s": round(t_step, 3),
                "overlapped_step_s": round(t_overlap, 3),
                "step_inflation": round(t_overlap / max(t_step, 1e-9), 2),
                "caller_blocked_s": round(blocked, 3),
                "commit_total_s": round(total, 3),
                "platform": "tpu",
            },
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # --- timed sync take over fresh (uncached) device state ----------
    # Warm the snapshot machinery on one state, then time a take over
    # FRESH device arrays so the DtoH is real, not an _npy_value hit.
    # SIZE FROM THE MEASURED CEILING: the leg pays TWO full transfers of
    # the state (the timed take's DtoH + the bit-exact verification
    # fetch), so each gets half the budget (clamped to [8 MB, 2 GB]):
    # a faster link gets a larger, more credible absolute datapoint, a
    # slow one stays inside the side-leg deadline.
    take_budget_s = float(os.environ.get("BENCH_SYNC_TAKE_BUDGET_S", "40"))
    state_mb_target = max(
        8.0, min(2048.0, dtoh_ceiling_mbps * take_budget_s / 2.0)
    )
    cols = max(1, int(state_mb_target * 1e6 / 4 / (2 * d)))  # two bf16 arrays

    def build_state(seed, cols_n=None):
        cols_n = cols if cols_n is None else cols_n
        k = jax.random.PRNGKey(seed)
        s = StateDict(
            w=jax.random.normal(k, (2 * d, cols_n), jnp.bfloat16),
            b=jax.random.normal(
                jax.random.fold_in(k, 1), (2 * d, cols_n), jnp.bfloat16
            ),
        )
        jax.block_until_ready(list(s.values()))
        return s

    tmp = tempfile.mkdtemp(prefix="tpu_take_")
    try:
        # Warm the machinery (jits, pools, event loop) on a SMALL state:
        # warmth is about code paths, not bytes — a full-size warm take
        # would double the leg's transfer bill for nothing.
        Snapshot.take(os.path.join(tmp, "warm"), {"m": build_state(3, 1024)})
        st = build_state(4)
        nbytes = sum(v.nbytes for v in st.values())
        t0 = time.perf_counter()
        snap = Snapshot.take(os.path.join(tmp, "timed"), {"m": st})
        t_take = time.perf_counter() - t0
        dst = {
            "m": StateDict(
                w=np.zeros((2 * d, cols), np.float32),
                b=np.zeros((2 * d, cols), np.float32),
            )
        }
        t0 = time.perf_counter()
        snap.restore(dst)
        t_restore = time.perf_counter() - t0
        ok = np.array_equal(
            np.asarray(st["w"], np.float32), dst["m"]["w"]
        ) and np.array_equal(np.asarray(st["b"], np.float32), dst["m"]["b"])
        take_mbps = nbytes / 1e6 / max(t_take, 1e-9)
        report(
            "dma_overlap/sync_take",
            {
                "state_mb": round(nbytes / 1e6, 1),
                "take_s": round(t_take, 2),
                "take_mbps": round(take_mbps, 2),
                # Fraction of what one measured DtoH stream delivers
                # (end-to-end take = DtoH + serialize + checksum + write).
                "take_pct_of_ceiling": round(
                    100.0 * take_mbps / max(dtoh_ceiling_mbps, 1e-9), 1
                ),
                "ceiling_mbps": round(dtoh_ceiling_mbps, 2),
                "restore_s": round(t_restore, 2),
                "bit_exact": ok,
                "platform": "tpu",
            },
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
