"""The train state and the step a cell saves under, built the normal way.

One jitted program makes the whole state from the seed, on the device(s),
in the layout it is trained in; one makes the batch of step *n*; one is the
donated train step, compiled ahead of time so its planned memory can be
read; one checksums every leaf on the device. The family adapter
(``families/<family>.py``) supplies the program's own init, specs and step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

from . import spec

_K1, _K2 = 2654435761, 0x9E3779B9  # odd multiplier, golden-ratio offset


# ------------------------------------------------------------ checksums


def _as_uint32(x):
    size = jnp.dtype(x.dtype).itemsize
    if size == 4:
        return lax.bitcast_convert_type(x, jnp.uint32)
    if size in (1, 2):
        return lax.bitcast_convert_type(x, {1: jnp.uint8, 2: jnp.uint16}[size]).astype(jnp.uint32)
    raise TypeError(f"no checksum for {x.dtype}")


def _leaf_checksum(x):
    """[position-weighted wrap-sum, plain wrap-sum] of the leaf's bits as
    uint32 (sums, because a sum is what every backend can reduce across
    devices).

    Element-wise on the leaf's own shape and then reduced, so a sharded
    leaf is summed where it lives (no reshape, no gather); integer
    arithmetic, so the result does not depend on the layout."""
    u = _as_uint32(x)
    if u.ndim == 0:
        u = u.reshape(1)
    idx, stride = jnp.zeros(u.shape, jnp.uint32), 1
    for axis in reversed(range(u.ndim)):
        idx = idx + lax.broadcasted_iota(jnp.uint32, u.shape, axis) * np.uint32(stride % 2**32)
        stride *= u.shape[axis]
    w = (idx * np.uint32(_K1) + np.uint32(_K2)) | np.uint32(1)
    return jnp.stack([jnp.sum(u * w, dtype=jnp.uint32), jnp.sum(u, dtype=jnp.uint32)])


def host_checksum(a: np.ndarray, block: int = 1 << 22) -> tuple:
    """``_leaf_checksum`` on the host, in blocks that stay in cache."""
    flat = np.ascontiguousarray(a).reshape(-1)
    size = flat.dtype.itemsize
    if size == 4:
        u = flat.view(np.uint32)
    elif size in (1, 2):
        u = flat.view({1: np.uint8, 2: np.uint16}[size])
    else:
        raise TypeError(f"no checksum for {a.dtype}")
    s, x = np.uint32(0), np.uint32(0)
    with np.errstate(over="ignore"):
        # (lo + j) * K1 + K2 = (j * K1 + K2) + lo * K1: one table for every block
        table = np.arange(min(block, u.size), dtype=np.uint32) * np.uint32(_K1) + np.uint32(_K2)
        for lo in range(0, u.size, block):
            b = u[lo:lo + block].astype(np.uint32, copy=False)
            w = (table[: b.size] + np.uint32(lo * _K1 % 2**32)) | np.uint32(1)
            s = s + np.sum(b * w, dtype=np.uint32)
            x = x + np.sum(b, dtype=np.uint32)
    return int(s), int(x)


def flat_paths(tree) -> Dict[str, Any]:
    return {jax.tree_util.keystr(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def fetch_checksums(sums) -> Dict[str, tuple]:
    """Device checksums (possibly still being computed) -> host ints."""
    return {k: (int(v[0]), int(v[1])) for k, v in flat_paths(jax.device_get(sums)).items()}


# ---------------------------------------------------------------- model


class Model:
    """Everything of one configuration under one layout."""

    def __init__(self, cfg: Dict[str, Any], seed: int, devices, mesh_axes: Optional[Dict[str, int]]):
        self.cfg = cfg
        self.seed = seed
        self.family = spec.load_module("families", cfg["program"]["family"])
        self.devices = list(devices)
        self.mesh = None
        if mesh_axes:
            shape = tuple(mesh_axes.values())
            self.mesh = Mesh(np.asarray(self.devices).reshape(shape), tuple(mesh_axes))
        run = cfg["program"]
        self.batch_size, self.seq = run["batch"], run["seq"]

        key = jax.random.PRNGKey(0)
        self.shapes = jax.eval_shape(lambda k: self.family.init_state(k, cfg), key)
        leaves = flat_paths(self.shapes)
        self.leaf_bytes = {k: int(np.prod(v.shape, dtype=np.int64)) * v.dtype.itemsize for k, v in leaves.items()}
        self.state_bytes = sum(self.leaf_bytes.values())
        self.param_shapes = {k: v.shape for k, v in flat_paths(self.shapes["params"]).items()}
        self.n_params = sum(int(np.prod(s, dtype=np.int64)) for s in self.param_shapes.values())
        self.n_active = self.family.active_params(cfg, self.param_shapes)

        shardings = None
        if self.mesh is not None:
            specs = self.family.state_specs(cfg, self.shapes)
            shardings = jax.tree_util.tree_map(
                lambda _, s: NamedSharding(self.mesh, s), self.shapes, specs
            )
        self._init = jax.jit(lambda k: self.family.init_state(k, cfg), out_shardings=shardings)
        batch_sharding = None
        if self.mesh is not None:
            batch_sharding = NamedSharding(self.mesh, self.family.BATCH_SPEC)
        self._batch = jax.jit(self._make_batch, out_shardings=batch_sharding)
        self._step = jax.jit(self.family.train_step(cfg, self.mesh), donate_argnums=0)
        self._checksum = jax.jit(lambda s: jax.tree_util.tree_map(_leaf_checksum, s))
        self.step = None  # the compiled step, after compile_step()
        self.step_memory: Dict[str, int] = {}
        self.mosaic_calls = 0

    # The weights of a run come from its --seed; a destination that a
    # restore must overwrite comes from another.
    def init(self, seed_offset: int = 0):
        return self._init(jax.random.PRNGKey(self.seed + seed_offset))

    def _make_batch(self, n):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), n)
        tokens = jax.random.randint(
            key, (self.batch_size, self.seq + 1), 0, self.cfg["vocab_size"], jnp.int32
        )
        return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    def batch(self, n: int):
        """The batch of step ``n``: a function of ``n`` and the seed alone."""
        return self._batch(np.int32(n))

    def compile_step(self, state, count_kernels: bool) -> Dict[str, float]:
        t0 = time.monotonic()
        lowered = self._step.lower(state, self.batch(1))
        lower_s = time.monotonic() - t0
        if count_kernels:
            # Interpret mode lowers a pallas_call to plain HLO loops, so 0
            # here means no Mosaic kernel is in the step. The text of a
            # whole step is large: traced runs only.
            self.mosaic_calls = lowered.as_text().count("tpu_custom_call")
        t1 = time.monotonic()
        self.step = lowered.compile()
        mem = self.step.memory_analysis()
        for k in ("argument", "output", "alias", "temp"):
            self.step_memory[k] = int(getattr(mem, f"{k}_size_in_bytes", 0) or 0)
        return {"lower_s": lower_s, "compile_s": time.monotonic() - t1}

    @property
    def step_planned_bytes(self) -> int:
        """Per device, as the compiler planned the donated step: arguments
        and temporaries, plus what of the output does not alias them."""
        m = self.step_memory
        return m["argument"] + m["temp"] + max(0, m["output"] - m["alias"])

    def checksum(self, state):
        """Dispatches the checksum program; returns device arrays."""
        return self._checksum(state)

    def attention(self) -> str:
        return self.family.attention(self.cfg, self.mesh)

    def tokens_per_step(self) -> int:
        return self.batch_size * self.seq


def bytes_on_fullest_device(tree) -> int:
    """Bytes of ``tree`` held by the device that holds most of it."""
    per: Dict[Any, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device] = per.get(shard.device, 0) + shard.data.nbytes
    return max(per.values())


def free(tree) -> None:
    """Give a state's device memory back now, not at the next collection."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
