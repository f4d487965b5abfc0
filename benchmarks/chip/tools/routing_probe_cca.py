"""What a CCA / MLP-routed cell's routers do with step 1's batch, over
several seeds, and where the bfloat16 program and the float32 reference
choose another expert.

    python3 benchmarks/chip/tools/routing_probe_cca.py --workload zaya1_8b.save --seeds 1 2 3 [--dry-run 1]

Prints one JSON line a seed: per layer the program's own counter
(``models/cca_moe_lm.py`` ``routing_stats``: ``held_share``, the share of
positions whose expert is held here; ``max_over_mean``, the most positions
a held expert gets over their mean; ``trips`` and ``tile_fill``, what the
held experts' loops make of it at the layer's tile), and ``ids_differ``,
the share of positions that choose another expert in the program
(``chosen_experts``: the train step's forward pass, bfloat16 operands
around a float32 router) than in ``reference/cca_moe_lm.py``
``chosen_experts`` (float32, highest precision). The sibling of
``routing_probe.py`` and ``routing_probe_bd.py`` for this family (the
harness gives a family no place to hand counters to the run's record); the
weights and the batch are those ``run.py``'s first step sees for the same
seed. One process for all seeds: the programs compile once. Exits
non-zero without a TPU unless ``--dry-run 1``.
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
    ap.add_argument("--reference", type=int, choices=(0, 1), default=1)
    ap.add_argument("--dry-run", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp

    from lib import model as M, spec
    from torchsnapshot_tpu.models import cca_moe_lm as C

    if not args.dry_run and jax.default_backend() != "tpu":
        print("routing_probe_cca: no TPU (use --dry-run 1 for a rehearsal)", file=sys.stderr)
        return 3
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload, bool(args.dry_run))
    cfg = cell.config
    ref = spec.load_module("reference", cfg["program"]["reference"])
    family = spec.load_module("families", cfg["program"]["family"])
    ccfg, ref_args = family.cconfig(cfg), family.reference_args(cfg)

    @jax.jit
    def probe(params, tokens):
        stats = C.routing_stats(params, tokens, ccfg)
        if not args.reference:
            return stats, jnp.zeros((ccfg.n_layers,))
        want = ref.chosen_experts(params, tokens, **ref_args)
        got = C.chosen_experts(params, tokens, ccfg)
        return stats, jnp.stack([jnp.mean(a.reshape(b.shape) != b) for a, b in zip(got, want)])

    for seed in args.seeds:
        model = M.Model(cfg, seed, jax.devices()[:1], None)
        state = model.init()
        stats, differ = jax.device_get(probe(state["params"], model.batch(1)["tokens"]))
        M.free(state)
        rounded = lambda xs, n: [round(float(x), n) for x in xs]  # noqa: E731
        out = {"workload": args.workload, "seed": seed, "device": jax.devices()[0].device_kind,
               "dry_run": bool(args.dry_run), "even_share": len(ccfg.held) / ccfg.n_experts,
               "tile": C.expert_tile(ccfg, model.tokens_per_step()),
               "held_share": rounded(stats["held_share"], 4), "max_over_mean": rounded(stats["max_over_mean"], 3),
               "trips": [int(x) for x in stats["trips"]], "tile_fill": rounded(stats["tile_fill"], 3),
               "ids_differ": rounded(differ, 4)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
