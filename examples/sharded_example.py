"""Sharded training-state checkpointing on a device mesh.

Shows the GSPMD path: the flagship transformer's params/optimizer state
sharded over a ('data','model') mesh, saved once (shard-deduped), then
restored onto a DIFFERENT mesh layout — the resharding that makes
checkpoints world-size- and layout-independent.

Runs on any device count; use virtual CPU devices to try multi-chip:
  python examples/sharded_example.py --cpu-devices 8
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--cpu-devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--cpu-devices") + 1])
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax

from torchsnapshot_tpu.compile_cache import enable_compilation_cache

enable_compilation_cache()

import numpy as np

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import transformer as T
from torchsnapshot_tpu.parallel import make_mesh


def main() -> None:
    n = len(jax.devices())
    work_dir = tempfile.mkdtemp(prefix="sharded_example_")

    cfg = T.TransformerConfig(
        vocab_size=1024, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_seq_len=64
    )
    tx = T.make_optimizer()

    mesh_a = make_mesh(devices=jax.devices())
    state = T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh_a)
    print(f"mesh A: {dict(mesh_a.shape)}")

    path = f"{work_dir}/snap"
    Snapshot.take(path, {"train": StateDict(**state)})
    print(f"saved sharded state -> {path}")

    # Restore onto a different layout: swap the axis sizes if possible.
    if n >= 2:
        mesh_b = make_mesh({"data": 1, "model": n}, devices=jax.devices())
    else:
        mesh_b = mesh_a
    fresh = T.init_state(jax.random.PRNGKey(7), cfg, tx, mesh=mesh_b)
    dst = {"train": StateDict(**fresh)}
    Snapshot(path).restore(dst)
    print(f"restored onto mesh B: {dict(mesh_b.shape)}")

    a = np.asarray(jax.device_get(state["params"]["embed"]))
    b = np.asarray(jax.device_get(dst["train"]["params"]["embed"]))
    assert a.tobytes() == b.tobytes()
    emb = dst["train"]["params"]["embed"]
    print(f"bit-exact across resharding; restored sharding: {emb.sharding}")


if __name__ == "__main__":
    main()
