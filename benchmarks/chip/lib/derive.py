"""What several readers need from a record: which steps a save disturbed."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def split_steps(record: Dict[str, Any]) -> Tuple[List[float], List[float]]:
    """Durations of the window's steps: (undisturbed, under a drain). A step
    is under a drain when it overlaps [return, commit] of a save, the
    warm-up save whose drain opens the window included."""
    calm, drained = [], []
    saves = ([record["warmup_save"]] if record.get("warmup_save") else []) + record["saves"]
    drains = [(r["t_ret"], r.get("t_commit", float("inf"))) for r in saves]
    for st in record["steps"]:
        lo, hi = st["t0"], st["t0"] + st["dur"]
        (drained if any(a < hi and lo < b for a, b in drains) else calm).append(st["dur"])
    return calm, drained


def ok_records(record: Dict[str, Any], of: str, *keys: str) -> List[Dict[str, Any]]:
    return [r for r in record[of] if r.get("ok") and all(k in r for k in keys)]
