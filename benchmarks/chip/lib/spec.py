"""How the harness finds things: everything by name, nothing by table.

``BENCHMARK.json`` names cells, configurations and metrics. Each name
resolves to a file under ``benchmarks/chip/`` and nothing in this module
lists them, so a later PR adds a cell, a mix, a kind, a reader or a metric
as new files plus new entries, without editing a file that is there:

    workloads[].config   -> configs[].file              (sizes, as run)
    workloads[].traffic  -> traffic/<traffic>.json      (parameters)
    traffic ``kind``     -> kinds/<kind>.py             (``run(session)``)
    config ``family``    -> families/<family>.py        (program adapter)
    metric ``name``      -> metrics/<name>.json         (reader + arguments)
    metric ``reader``    -> readers/<reader>.py         (``read(record, **args)``)

No jax here: the harness test imports this module as it is.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/chip
REPO = os.path.dirname(os.path.dirname(HERE))
GROUPS = {0: "end_to_end", 1: "per_layer"}  # by --trace


class SpecError(Exception):
    """A name in BENCHMARK.json that resolves to nothing."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{os.path.relpath(path, REPO)} does not exist") from None


def load_benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(REPO, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # the configuration file, per-chips cut applied
    traffic: Dict[str, Any]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _entry(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json (have: {known})")


def apply_cut(config: Dict[str, Any], chips: int, dry_run: bool) -> Dict[str, Any]:
    """The configuration as this cell runs it: top-level keys are the
    one-chip cut; ``per_chips[<n>]`` overrides them for an n-chip cell;
    ``dry_run`` (CPU rehearsal only) overrides every size with a toy one."""
    out = {k: v for k, v in config.items() if k not in ("per_chips", "dry_run")}
    overrides = [config.get("per_chips", {}).get(str(chips), {})]
    if dry_run:
        overrides.append(config["dry_run"])
    for override in overrides:
        for k, v in override.items():  # a nested group overrides key by key
            out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def resolve_cell(bench: Dict[str, Any], name: str, dry_run: bool = False) -> Cell:
    w = _entry(bench["workloads"], name, "workload")
    c = _entry(bench["configs"], w["config"], "config")
    config = apply_cut(_load_json(os.path.join(REPO, c["file"])), w["chips"], dry_run)
    traffic = _load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    return Cell(w["name"], w["chips"], config, traffic)


def cell_metrics(bench: Dict[str, Any], cell: str, group: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` this cell reports: those whose optional
    ``workloads`` list names it (no list: every cell), and, per layer, only
    where the end-to-end metric they move is reported too."""

    def here(entry: Dict[str, Any]) -> bool:
        return "workloads" not in entry or cell in entry["workloads"]

    out = [m for m in bench[group] if here(m)]
    if group == "per_layer":
        moved = {m["name"] for m in bench["end_to_end"] if here(m)}
        out = [m for m in out if m["moves"] in moved]
    return out


def load_metric(name: str) -> Dict[str, Any]:
    return _load_json(os.path.join(HERE, "metrics", name + ".json"))


def load_module(subdir: str, name: str):
    """``benchmarks/chip/<subdir>/<name>.py`` as a module."""
    path = os.path.join(HERE, subdir, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"{os.path.relpath(path, REPO)} does not exist")
    spec = importlib.util.spec_from_file_location(f"chipbench_{subdir}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
