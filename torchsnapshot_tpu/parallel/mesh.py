"""Device-mesh construction and pytree sharding helpers.

The reference framework (torchsnapshot) consumes state from externally
parallelized models (DDP replication, ShardedTensor TP layouts, FSDP —
SURVEY.md §2 "Parallelism / distribution strategies"). On TPU the analogue
is GSPMD: a `jax.sharding.Mesh` plus `NamedSharding` annotations, with XLA
inserting the collectives. This module provides the small amount of shared
machinery the models/benchmarks need to produce such state.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Tuple[str, ...] = ("data", "model"),
) -> Mesh:
    """Build a Mesh over `devices` (default: all).

    If `axis_sizes` is given it maps axis name -> size (one axis may be -1
    to absorb the remainder) and determines the axis names. Otherwise the
    last of `axis_names` (the tp-like axis) gets the largest power-of-two
    divisor <= sqrt(n), the first absorbs the rest, and middle axes get 1 —
    a sensible dp x tp default on any device count.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axis_sizes is None:
        model = 1
        while model * 2 <= int(math.isqrt(n)) and n % (model * 2) == 0:
            model *= 2
        axis_sizes = {name: 1 for name in axis_names}
        axis_sizes[axis_names[-1]] = model
        axis_sizes[axis_names[0]] = (n // model) * axis_sizes[axis_names[0]]
    else:
        if axis_names != ("data", "model") and tuple(axis_sizes) != axis_names:
            raise ValueError(
                f"axis_names {axis_names} conflicts with axis_sizes keys "
                f"{tuple(axis_sizes)}; pass one or the other."
            )
        axis_names = tuple(axis_sizes.keys())
        sizes = list(axis_sizes.values())
        if -1 in sizes:
            known = math.prod(s for s in sizes if s != -1)
            sizes[sizes.index(-1)] = n // known
        axis_sizes = dict(zip(axis_names, sizes))
    shape = tuple(axis_sizes[a] for a in axis_names)
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {axis_sizes} != {n} devices")
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """device_put every leaf of `tree` with the matching PartitionSpec leaf.

    `specs` is a pytree with the same treedef whose leaves are
    PartitionSpec (or None for fully replicated). A dimension that its
    mesh axes do not divide is refused here, by the leaf's name (e.g. a
    vocabulary of 50 with `embed` sharded `P("model", None)` over 4).
    """

    def _put(path, x, spec):
        spec = spec if spec is not None else P()
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else tuple(axes)
            n = math.prod(mesh.shape[a] for a in axes)
            if x.shape[dim] % n:
                raise ValueError(
                    f"cannot place {jax.tree_util.keystr(path)} {tuple(x.shape)} as "
                    f"{spec}: dimension {dim} ({x.shape[dim]}) is not divisible by "
                    f"the {n} devices of mesh axes {axes}"
                )
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(
        _put, tree, specs, is_leaf=lambda x: x is None
    )


def optax_state_specs(p_specs: Any, opt_state: Any) -> Tuple[Any, ...]:
    """PartitionSpecs for an optax optimizer state given the param specs.

    Adam-family moments (mu/nu) inherit their parameter's spec; everything
    else (counts, empty states, schedule scalars) is replicated. Scalars
    must be placed ON the mesh, not left uncommitted: a restored scalar
    comes back committed, and a single-device scalar next to
    mesh-committed params is an invalid jit input mix.
    """
    import optax

    def map_entry(entry):
        if isinstance(entry, optax.ScaleByAdamState):
            return optax.ScaleByAdamState(count=P(), mu=p_specs, nu=p_specs)
        return jax.tree_util.tree_map(lambda _: P(), entry)

    return tuple(map_entry(e) for e in opt_state)


# ------------------------------------------------------- replica dimension


def data_replicas(mesh: Optional[Mesh], batch: int) -> int:
    """How many data-parallel replicas a step on ``mesh`` gives a batch of
    ``batch`` rows: the size of the 'data' axis, or 1 without a mesh,
    without the axis, or where it does not divide the batch."""
    n = 1 if mesh is None else mesh.shape.get("data", 1)
    return n if batch % n == 0 else 1


def with_replica_dim(w: jax.Array, spec: P, mesh: Mesh, *, dim: int = 0) -> jax.Array:
    """``w`` (laid out as ``spec``) with a replica dimension inserted at
    ``dim``: one copy per member of the 'data' axis, sharded over it, so
    every device's piece is the shard of ``w`` it already holds and no
    bytes move. A matmul batched over that dimension against the replica's
    rows of the batch is local, and so is its transpose: the weight's
    gradient comes out per replica, and autodiff's transpose of the
    broadcast, a sum over the dimension, is the one reduction over 'data',
    wherever the caller's program puts this call (outside a `lax.scan`:
    once for the stacked leaf, after the backward scan)."""
    n = mesh.shape["data"]
    w = jnp.broadcast_to(jnp.expand_dims(w, dim), (*w.shape[:dim], n, *w.shape[dim:]))
    spec = tuple(spec) + (None,) * (w.ndim - 1 - len(spec))
    return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, P(*spec[:dim], "data", *spec[dim:])))


# ------------------------------------------------- collectives of a program

_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]m[0-9][a-z]*)?)\[([0-9,]*)\]")
_CALLEE = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation)=%([^\s,)}]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_GROUPS = re.compile(
    r"\b(?:replica_groups|source_target_pairs)="
    r"(?:\{([0-9,{} ]*)\}|\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?)"
)


def _array_bytes(dtype: str, dims: Tuple[int, ...]) -> int:
    bits = re.search(r"[0-9]+", dtype)  # bf16, f8e4m3fn, c64; pred has none
    return max(1, int(bits.group()) // 8 if bits else 1) * math.prod(dims)


def _ints(text: str) -> List[int]:
    return [int(x) for x in re.findall(r"[0-9]+", text)]


def _groups(line: str) -> List[Tuple[int, ...]]:
    """The device groups of a collective instruction: ``{{0,2},{1,3}}``
    written out, or the iota form ``[groups,size]<=[dims]T(perm)``: the
    ids ``0..n`` reshaped to ``dims``, transposed, cut into rows."""
    m = _GROUPS.search(line)
    if not m:
        return []
    listed, shape, dims, perm = m.groups()
    if shape is None:
        return [tuple(_ints(g)) for g in re.findall(r"\{([0-9, ]*)\}", listed)]
    ids = np.arange(math.prod(_ints(dims))).reshape(_ints(dims))
    if perm:
        ids = ids.transpose(_ints(perm))
    return [tuple(int(i) for i in row) for row in ids.reshape(_ints(shape))]


def spanned_axes(groups: Sequence[Sequence[int]], mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes along which the members of any of ``groups`` (a
    `collectives` record's ``groups``) differ: the axes the collective
    moves data across. An id is a position in ``mesh.devices.flat``, the
    order `jax.jit` assigns partitions in. No groups: every axis larger
    than 1 (``replica_groups={}`` is all devices)."""
    if not groups:
        return tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    spans = np.zeros(len(mesh.axis_names), bool)
    for group in groups:
        coords = np.stack(np.unravel_index(list(group), mesh.devices.shape), axis=1)
        spans |= (coords != coords[0]).any(axis=0)
    return tuple(a for a, s in zip(mesh.axis_names, spans) if s)


def collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """The collectives of a compiled program, from ``compiled.as_text()``.

    One record per collective instruction: ``kind`` (all-reduce, all-gather,
    reduce-scatter, all-to-all, collective-permute, collective-broadcast),
    ``name``, ``shapes`` (``(dtype, dims)`` of each array it produces on one
    device; a combined all-reduce produces several), ``bytes`` (their sum),
    ``groups`` (the devices it runs among, as tuples of partition ids; the
    (source, target) pairs of a permute; ``[]`` where the instruction names
    none, which is all of them: `spanned_axes` turns them into mesh axes)
    and ``times``, how often one run of the program executes it: the
    product of the trip counts of the loops around it. A trip count is the
    loop's ``known_trip_count``, else the constant its condition compares
    the counter with (`lax.scan` lowers to ``i < n``), else 1. Every branch
    of a conditional counts as taken.

    Static: a count of what the partitioner put in, not a time. The async
    form counts at its ``-start`` (whose result is ``(operand, result,
    context)``: the result half is taken) and not at its ``-done``.
    """
    comps: Dict[str, List[str]] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(.*\{\s*$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)

    def trip_count(line: str, cond: Optional[str]) -> int:
        known = re.search(r'"known_trip_count":\{"n":"([0-9]+)"', line)
        if known:
            return int(known.group(1))
        body = comps.get(cond or "", [])
        bounds = [m for m in (re.search(r"\bs32\[\][^ ]* constant\(([0-9]+)\)", b) for b in body) if m]
        if len(bounds) == 1 and any("ROOT" in b and "direction=LT" in b for b in body):
            return int(bounds[0].group(1))
        return 1

    found: List[Dict[str, Any]] = []

    def walk(comp: str, times: int) -> None:
        for line in comps.get(comp, ()):
            m = _INSTR.match(line)
            if not m:
                continue
            name, result, op = m.groups()
            callees = dict(_CALLEE.findall(line))
            for role, callee in callees.items():
                loop = trip_count(line, callees.get("condition")) if role == "body" else 1
                walk(callee, times * loop)
            for branches in _BRANCHES.findall(line):
                for callee in re.findall(r"%([^\s,]+)", branches):
                    walk(callee, times)
            kind = op.removesuffix("-start")
            if kind not in _COLLECTIVE_KINDS:
                continue
            shapes = [
                (d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in _ARRAY.findall(result)
            ]
            if op.endswith("-start") and kind != "all-reduce":
                arrays = [s for s in shapes if s[1]]
                shapes = arrays[len(arrays) // 2:]
            found.append(
                {
                    "kind": kind,
                    "name": name,
                    "shapes": shapes,
                    "bytes": sum(_array_bytes(*s) for s in shapes),
                    "groups": _groups(line),
                    "times": times,
                }
            )

    if entry is not None:
        walk(entry, 1)
    return found


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Bytes each kind of collective produces on one device in one run of
    the compiled program (``bytes`` x ``times`` of `collectives`), by kind."""
    out: Dict[str, int] = {}
    for c in collectives(hlo_text):
        out[c["kind"]] = out.get(c["kind"], 0) + c["bytes"] * c["times"]
    return out
