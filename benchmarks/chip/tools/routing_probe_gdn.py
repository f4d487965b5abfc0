"""What a GDN cell's softmax routers send to the held experts over the steps
of a run, at the cell's seeds.

    python3 benchmarks/chip/tools/routing_probe_gdn.py --workload qwen3next80b.save --seeds 1 2 [--steps 40] [--dry-run 1]

Trains the cell's state from the seed on the batches ``run.py`` gives it
(``lib/model.py``: the same weights, batches and compiled step), and before
each of ``--steps`` steps reads the program's own counter
(``models/gdn_lm.py`` ``routing_stats``) on that step's batch. Prints one
JSON line a seed: per layer, over the steps, the least and the most of
``held_share`` over its even share (``len(held) / n_experts``) and of
``max_over_mean`` (the most positions a held expert gets over their mean),
the row tiles the held experts' list multiplies (``trips``), and step 1's
and the last step's values. A router that tilts as it trains, or from one
seed to the next, moves the step's held-expert work, which the cell's
``step_ms`` reads. The sibling of ``routing_probe_kda.py`` for this family;
one process for all seeds. Exits non-zero without a TPU unless
``--dry-run 1``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--dry-run", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np

    from lib import model as M, spec
    from torchsnapshot_tpu.models import gdn_lm as G

    if not args.dry_run and jax.default_backend() != "tpu":
        print("routing_probe_gdn: no TPU (use --dry-run 1 for a rehearsal)", file=sys.stderr)
        return 3
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload, bool(args.dry_run))
    cfg = cell.config
    family = spec.load_module("families", cfg["program"]["family"])
    kcfg = family.kconfig(cfg)
    stats_of = jax.jit(lambda params, tokens: G.routing_stats(params, tokens, kcfg))
    even = len(kcfg.held) / kcfg.n_experts

    for seed in args.seeds:
        model = M.Model(cfg, seed, jax.devices()[:1], None)
        state = model.init()
        model.compile_step(state, count_kernels=False)
        share, peak, trips = [], [], []
        for n in range(1, args.steps + 1):
            batch = model.batch(n)
            stats = jax.device_get(stats_of(state["params"], batch["tokens"]))
            share.append(np.asarray(stats["held_share"]) / even)
            peak.append(np.asarray(stats["max_over_mean"]))
            trips.append(np.asarray(stats["trips"]))
            state, loss = model.step(state, batch)
        loss = float(loss)
        M.free(state)
        share, peak, trips = (np.stack(x) for x in (share, peak, trips))  # (steps, L)
        rounded = lambda xs, n: [round(float(x), n) for x in xs]  # noqa: E731
        out = {"workload": args.workload, "seed": seed, "device": jax.devices()[0].device_kind,
               "dry_run": bool(args.dry_run), "steps": args.steps, "even_share": even, "last_loss": loss,
               "share_over_even_min": rounded(share.min(0), 4), "share_over_even_max": rounded(share.max(0), 4),
               "max_over_mean_min": rounded(peak.min(0), 3), "max_over_mean_max": rounded(peak.max(0), 3),
               "trips_min": [int(x) for x in trips.min(0)], "trips_max": [int(x) for x in trips.max(0)],
               "share_over_even_first": rounded(share[0], 4), "share_over_even_last": rounded(share[-1], 4),
               "max_over_mean_first": rounded(peak[0], 3), "max_over_mean_last": rounded(peak[-1], 3)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
