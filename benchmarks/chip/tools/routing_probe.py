"""What a routed cell's routers do with step 1's batch, and where the
bfloat16 program and the float32 reference choose other experts.

    python3 benchmarks/chip/tools/routing_probe.py --workload nemotron2t30b.save --seed 7 [--dry-run 1]

Prints one JSON object: per expert layer the program's own counter
(``models/hybrid_lm.py`` ``routing_stats``: the share of the top-k
assignments that fall on experts held here, and the most tokens a held
expert gets over their mean) and ``sets_differ``, the share of positions
whose chosen set differs between the program (``chosen_experts``: the train
step's forward pass, bfloat16) and ``reference/hybrid_lm.py`` ``chosen_experts`` (float32,
highest precision). The harness gives a family no place to hand counters to
the run's record, so this is a tool beside it, for the builder's chip run;
the weights and the batch are those ``run.py`` makes from the same seed.
Exits non-zero without a TPU unless ``--dry-run 1`` (toy widths, CPU).
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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp

    from lib import model as M, spec
    from torchsnapshot_tpu.models import hybrid_lm

    if not args.dry_run and jax.default_backend() != "tpu":
        print("routing_probe: no TPU (use --dry-run 1 for a rehearsal)", file=sys.stderr)
        return 3
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload, bool(args.dry_run))
    cfg = cell.config
    model = M.Model(cfg, args.seed, jax.devices()[:1], None)
    hcfg = model.family.hconfig(cfg)
    ref = spec.load_module("reference", cfg["program"]["reference"])
    ref_args = model.family.reference_args(cfg)
    params = model.init()["params"]
    tokens = model.batch(1)["tokens"]

    @jax.jit
    def probe(params, tokens):
        want = ref.chosen_experts(params, tokens, **ref_args)
        got = hybrid_lm.chosen_experts(params, tokens, hcfg)
        differ = [
            jnp.mean(jnp.any(jnp.sort(a.reshape(b.shape), axis=-1) != jnp.sort(b, axis=-1), axis=-1))
            for a, b in zip(got.values(), want)
        ]
        return hybrid_lm.routing_stats(params, tokens, hcfg), differ

    stats, differ = jax.device_get(probe(params, tokens))
    out = {"workload": args.workload, "seed": args.seed, "tokens": int(tokens.size),
           "device": jax.devices()[0].device_kind, "dry_run": bool(args.dry_run),
           "even_share": len(hcfg.held) / hcfg.n_experts, "layers": {}}
    for (name, s), d in zip(stats.items(), differ):
        out["layers"][name] = {k: float(v) for k, v in s.items()} | {"sets_differ": float(d)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
