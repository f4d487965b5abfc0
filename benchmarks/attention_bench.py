"""Attention kernel benchmark: Pallas flash vs XLA blockwise, fwd+bwd.

Compute-only (scalar outputs), so it is meaningful on a real TPU chip even
when host<->device bandwidth is poor. Reports per-step wall time for a
train-shaped loss (forward + backward through attention) and the flash/
blockwise speedup. The reference has no attention code at all (SURVEY.md
§5.7) — this benchmarks the beyond-parity kernel path.

Usage: python benchmarks/attention_bench.py [B S H D] (default 4 2048 8 128)
Emits one JSON line per kernel via bench_utils.report.
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
    from torchsnapshot_tpu.ops import blockwise_attention, flash_attention

    args = [int(a) for a in sys.argv[1:5]]
    B, S, H, D = args + [4, 2048, 8, 128][len(args):]
    platform = jax.default_backend()
    print(f"[attention_bench] platform={platform} B={B} S={S} H={H} D={D}",
          file=sys.stderr, flush=True)

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in ks)

    def bench(name, attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))

        grad = jax.grad(loss, argnums=(0, 1, 2))

        step = jax.jit(grad)
        jax.block_until_ready(step(q, k, v))  # compile + warm
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(step(q, k, v))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    t_block = bench(
        "blockwise",
        lambda q, k, v: blockwise_attention(q, k, v, block_size=512, causal=True),
    )
    t_flash = bench(
        "flash",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
    )

    # Ring-flash on a 1-device ring: measures the ring harness overhead
    # (shard_map + custom VJP + lse merge) over the bare kernel — on a
    # multi-chip mesh the same code path adds only the ppermute hops.
    import numpy as np
    from jax.sharding import Mesh

    from torchsnapshot_tpu.ops import ring_flash_attention_sharded

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("seq",))
    t_ring = bench(
        "ring_flash(ring=1)",
        lambda q, k, v: ring_flash_attention_sharded(q, k, v, mesh1, causal=True),
    )

    # Causal attention FLOPs (fwd 2 matmuls + bwd 5) ≈ 3.5 * 4 * B*H*S^2*D / 2.
    flops = 3.5 * 2 * B * H * S * S * D
    for name, t in (
        ("blockwise", t_block),
        ("flash", t_flash),
        ("ring_flash", t_ring),
    ):
        report(
            f"attention_fwdbwd_{name}",
            {
                "platform": platform,
                "device_kind": jax.devices()[0].device_kind,
                "shape": [B, S, H, D],
                # Host clock around block_until_ready: dispatch included,
                # a device trace (ROADMAP A5) is the kernel's own time.
                "step_s": round(t, 5),
                "tflops": round(flops / t / 1e12, 2),
                "speedup_vs_blockwise": round(t_block / t, 2),
            },
        )


if __name__ == "__main__":
    main()
