"""Compile each cell's programs at full width for a described v5e, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/topology_compile.py [workload ...]
        [--set num_hidden_layers=8] [--set program.batch=4]

A rehearsal (``on-chip-measurement`` section 2.3), never a measurement: the
TPU's compiler runs here against ``v5e:2x2`` as a description, and says what
the chip's compiler would say about memory and kernels before chip time is
spent. Prints, per layout of each cell, the bytes the compiler planned for
the train step, the init program and the checksum program on one device.

The program asks ``jax.default_backend()`` to choose the flash kernel and
interpret mode; that call is patched to answer "tpu" here, in this tool
only, so the step that is compiled is the step the chip runs.
"""

from __future__ import annotations

import argparse
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

HBM_BYTES = 15.75e9  # usable on a v5e chip (PERF.md)


def _planned(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in ("argument", "output", "alias", "temp")}
    out["total"] = out["argument"] + out["temp"] + max(0, out["output"] - out["alias"])
    return out


def _fmt(p: dict) -> str:
    return (f"{p['total'] / 1e9:6.2f} GB of {HBM_BYTES / 1e9:.2f} "
            f"(args {p['argument'] / 1e9:.2f}, temps {p['temp'] / 1e9:.2f}, "
            f"unaliased out {max(0, p['output'] - p['alias']) / 1e9:.2f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a configuration key for this rehearsal (dotted for program.*)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from lib import model as M, spec

    jax.config.update("jax_enable_compilation_cache", False)  # entries made here cannot be read back
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = spec.load_benchmark()
    names = args.workloads or [w["name"] for w in bench["workloads"]]
    rc = 0
    for name in names:
        cell = spec.resolve_cell(bench, name)
        cfg = dict(cell.config)
        cfg["program"] = dict(cfg["program"])
        for item in args.set:
            key, value = item.split("=", 1)
            value = int(value) if value.lstrip("-").isdigit() else value
            if key.startswith("program."):
                cfg["program"][key[len("program."):]] = value
            else:
                cfg[key] = value
        layouts = ["mesh_train", "mesh_resume"] if cell.chips > 1 else [None]
        for layout in layouts:
            axes = cfg.get(layout) if layout else None
            with mock.patch.object(jax, "default_backend", return_value="tpu"):
                model = M.Model(cfg, 0, topo.devices[: cell.chips], axes)
                if model.mesh is not None:
                    specs = model.family.state_specs(cfg, model.shapes)
                    shard = lambda s: NamedSharding(model.mesh, s)  # noqa: E731
                    batch_sharding = shard(model.family.BATCH_SPEC)
                else:
                    specs = jax.tree_util.tree_map(lambda _: None, model.shapes)
                    one = SingleDeviceSharding(topo.devices[0])
                    shard = lambda _s: one  # noqa: E731
                    batch_sharding = one
                state = jax.tree_util.tree_map(
                    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard(s if s is not None else P())),
                    model.shapes, specs, is_leaf=lambda x: x is None,
                )
                tok = jax.ShapeDtypeStruct((model.batch_size, model.seq), jnp.int32, sharding=batch_sharding)
                batch = {"tokens": tok, "targets": tok}
                key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=shard(P()))
                head = (f"{name} [{layout or 'one chip'} {axes or ''}] L={cfg['num_hidden_layers']} "
                        f"B={model.batch_size} S={model.seq}: {model.n_params / 1e6:.1f} M params "
                        f"({model.n_active / 1e6:.1f} M active), state {model.state_bytes / 1e9:.2f} GB in "
                        f"{len(model.leaf_bytes)} leaves, largest {max(model.leaf_bytes.values()) / 1e6:.0f} MB, "
                        f"attention {model.attention()}")
                print(head, flush=True)
                for what, fn, fargs in (
                    ("step", model._step, (state, batch)),
                    ("init", model._init, (key,)),
                    ("checksum", model._checksum, (state,)),
                ):
                    try:
                        lowered = fn.lower(*fargs)
                        kernels = lowered.as_text().count("tpu_custom_call")
                        planned = _planned(lowered.compile())
                        fits = "fits" if planned["total"] <= HBM_BYTES else "DOES NOT FIT"
                        print(f"  {what:9s} {_fmt(planned)}  {fits}  ({kernels} Mosaic calls)", flush=True)
                    except Exception as e:  # noqa: BLE001 - the compiler's refusal is the answer
                        rc = 1
                        print(f"  {what:9s} REFUSED: {str(e).splitlines()[0][:300]}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
