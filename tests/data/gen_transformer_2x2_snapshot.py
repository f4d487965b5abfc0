"""Regenerate tests/data/transformer_2x2_snapshot: a tiny transformer train
state after one sharded step under {data 2, model 2}, saved by the checkout
this script is run against.

    python tests/data/gen_transformer_2x2_snapshot.py [/path/to/checkout]

The committed fixture was written by the commit before the replica
dimension entered ``models/transformer.py`` (PR 29's parent), so
tests/test_transformer_step.py can hold that a snapshot taken before that
change still restores and steps, and that today's save of the same state
has the same manifest. ``expected.json`` beside it holds the loss of the
step taken before the save and of the one after. Keep the state tiny: the
fixture is committed.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "transformer_2x2_snapshot")
CFG = dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_seq_len=8)
BATCH, SEQ = 4, 8


def build():
    """(cfg, tx, mesh, batch, jitted step) of the run; the test reuses it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.models import transformer as T
    from torchsnapshot_tpu.parallel import make_mesh

    cfg, tx = T.TransformerConfig(**CFG), T.make_optimizer()
    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    toks = jax.random.randint(jax.random.PRNGKey(7), (BATCH, SEQ + 1), 0, cfg.vocab_size, jnp.int32)
    batch = jax.device_put(
        {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, NamedSharding(mesh, P("data", None))
    )
    return cfg, tx, mesh, batch, jax.jit(T.make_train_step(cfg, tx, mesh=mesh))


def main() -> None:
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, sys.argv[1] if len(sys.argv) > 1 else os.path.dirname(os.path.dirname(HERE)))
    import jax

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.models import transformer as T

    cfg, tx, mesh, batch, step = build()
    state, before = step(T.init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh), batch)
    shutil.rmtree(OUT, ignore_errors=True)
    Snapshot.take(OUT, {"train": StateDict(**state)})
    _, after = step(state, batch)
    with open(os.path.join(OUT, "expected.json"), "w") as f:
        json.dump({"loss_before": float(before), "loss_after": float(after)}, f)


if __name__ == "__main__":
    main()
