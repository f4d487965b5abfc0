"""What a block-diffusion cell's routers do with step 1's batch and noise,
over several seeds, and where the bfloat16 program and the float32
reference choose other experts.

    python3 benchmarks/chip/tools/routing_probe_bd.py --workload sdar30b.save --seeds 1 2 3 [--dry-run 1]

Prints one JSON line a seed: per layer the program's own counter
(``models/block_diffusion_lm.py`` ``routing_stats``: the share of the top-k
assignments that fall on experts held here, the most positions a held
expert gets over their mean), ``tiles``, the trips of ``tile`` rows the
held experts' loops make in the layer (``--tile``, 512 and 1024 by
default), and ``sets_differ``, the share of positions whose chosen set
differs between the program (``chosen_experts``: the train step's forward
pass, bfloat16) and ``reference/block_diffusion_lm.py`` ``chosen_experts``
(float32, highest precision). The sibling of ``routing_probe.py`` for this
family (the harness gives a family no place to hand counters to the run's
record); the weights, the batch and the noise are those ``run.py``'s first
step sees for the same seed. One process for all seeds: the programs
compile once. Exits non-zero without a TPU unless ``--dry-run 1``.
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
    ap.add_argument("--tile", type=int, nargs="+", default=[512, 1024])
    ap.add_argument("--reference", type=int, choices=(0, 1), default=1)
    ap.add_argument("--dry-run", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import model as M, spec
    from torchsnapshot_tpu.models import block_diffusion_lm as B

    if not args.dry_run and jax.default_backend() != "tpu":
        print("routing_probe_bd: no TPU (use --dry-run 1 for a rehearsal)", file=sys.stderr)
        return 3
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload, bool(args.dry_run))
    cfg = cell.config
    ref = spec.load_module("reference", cfg["program"]["reference"])
    family = spec.load_module("families", cfg["program"]["family"])
    bcfg = family.bconfig(cfg)
    ref_args = {k: v for k, v in family.reference_args(cfg).items() if k != "masked"}

    @jax.jit
    def probe(state, tokens):
        masked, _ = B.draw_noise(B.noise_key(state), *tokens.shape, bcfg)
        stats = B.routing_stats(state["params"], tokens, masked, bcfg)
        if not args.reference:
            return stats, jnp.zeros((bcfg.n_layers,))
        want = ref.chosen_experts(state["params"], tokens, masked=masked, **ref_args)
        got = B.chosen_experts(state["params"], tokens, masked, bcfg)
        differ = [jnp.mean(jnp.any(jnp.sort(a.reshape(b.shape), axis=-1) != jnp.sort(b, axis=-1), axis=-1))
                  for a, b in zip(got, want)]
        return stats, jnp.stack(differ)

    for seed in args.seeds:
        model = M.Model(cfg, seed, jax.devices()[:1], None)
        state = model.init()
        stats, differ = jax.device_get(probe(state, model.batch(1)["tokens"]))
        M.free(state)
        counts = np.asarray(stats["held_counts"])
        out = {"workload": args.workload, "seed": seed, "device": jax.devices()[0].device_kind,
               "dry_run": bool(args.dry_run), "even_share": len(bcfg.held) / bcfg.n_experts,
               "held_share": [round(float(x), 4) for x in stats["held_share"]],
               "max_over_mean": [round(float(x), 3) for x in stats["max_over_mean"]],
               "tiles": {str(t): [int(np.sum(-(-counts[i] // t))) for i in range(counts.shape[0])] for t in args.tile},
               "sets_differ": [round(float(x), 4) for x in differ]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
