"""What a KDA / MLA cell's group-limited routers do with step 1's batch,
over several seeds, and where the bfloat16 program and the float32
reference choose another held set.

    python3 benchmarks/chip/tools/routing_probe_kda.py --workload ling3flash.save --seeds 1 2 3 [--dry-run 1]

Prints one JSON line a seed: per expert layer the program's own counter
(``models/kda_mla_lm.py`` ``routing_stats``: ``held_share``, the share of
the ``top_k`` assignments that fall on experts held here; ``group_share``,
the share of positions whose kept groups include the held experts' group;
``max_over_mean``, the most positions a held expert gets over their mean;
``trips`` and ``tile_fill``, what the held experts' list makes of it), and
``held_set_differs``, the share of positions whose chosen experts **held
here** are another set in the program (``chosen_experts``: the train
step's forward pass, bfloat16 operands around a float32 router) than in
``reference/kda_mla_lm.py`` ``chosen_experts`` (float32, highest
precision); ``ids_differ``, the share whose whole chosen set differs. The
sibling of ``routing_probe.py``, ``routing_probe_bd.py`` and
``routing_probe_cca.py`` for this family; the weights and the batch are
those ``run.py``'s first step sees for the same seed. One process for all
seeds. Exits non-zero without a TPU unless ``--dry-run 1``.
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
    from torchsnapshot_tpu.models import kda_mla_lm as K

    if not args.dry_run and jax.default_backend() != "tpu":
        print("routing_probe_kda: no TPU (use --dry-run 1 for a rehearsal)", file=sys.stderr)
        return 3
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload, bool(args.dry_run))
    cfg = cell.config
    ref = spec.load_module("reference", cfg["program"]["reference"])
    family = spec.load_module("families", cfg["program"]["family"])
    kcfg, ref_args = family.kconfig(cfg), family.reference_args(cfg)
    held = jnp.asarray(kcfg.held)
    n_moe = sum(k.endswith("_moe") for k in kcfg.kinds)

    @jax.jit
    def probe(params, tokens):
        stats = K.routing_stats(params, tokens, kcfg)
        if not args.reference:
            return stats, jnp.zeros((n_moe,)), jnp.zeros((n_moe,))
        want = ref.chosen_experts(params, tokens, **ref_args)
        got = K.chosen_experts(params, tokens, kcfg)
        member = lambda ids: jnp.any(ids[..., None] == held, axis=-2)  # noqa: E731  (T, n): which held experts
        sets = [jnp.mean(jnp.any(member(a) != member(b.reshape(a.shape)), axis=-1)) for a, b in zip(got, want)]
        whole = [jnp.mean(jnp.any(jnp.sort(a, -1) != jnp.sort(b.reshape(a.shape), -1), axis=-1)) for a, b in zip(got, want)]
        return stats, jnp.stack(sets), jnp.stack(whole)

    for seed in args.seeds:
        model = M.Model(cfg, seed, jax.devices()[:1], None)
        state = model.init()
        stats, sets, whole = jax.device_get(probe(state["params"], model.batch(1)["tokens"]))
        M.free(state)
        rounded = lambda xs, n: [round(float(x), n) for x in xs]  # noqa: E731
        out = {"workload": args.workload, "seed": seed, "device": jax.devices()[0].device_kind,
               "dry_run": bool(args.dry_run), "even_share": len(kcfg.held) / kcfg.n_experts,
               "even_group_share": kcfg.topk_group / kcfg.n_group,
               "held_share": rounded(stats["held_share"], 5), "group_share": rounded(stats["group_share"], 4),
               "max_over_mean": rounded(stats["max_over_mean"], 3),
               "trips": [int(x) for x in stats["trips"]], "tile_fill": rounded(stats["tile_fill"], 3),
               "held_set_differs": rounded(sets, 5), "ids_differ": rounded(whole, 5)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
