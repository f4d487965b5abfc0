"""From the program's telemetry spans to seconds per resource.

The program emits spans (``telemetry/core.py``: name, ``ts`` and ``dur`` in
seconds on ``time.monotonic``). This is the benchmark's own reduction of
them, so that a PR which changes the program cannot change the yardstick:
a copy of the arithmetic of ``telemetry/critpath.py`` (``SPAN_CATEGORIES``,
``FUSED_SPANS``, the interval union), clipped to the operation's wall as the
host clock saw it. A category's seconds are the union of its spans, so
sixteen concurrent sub-chunk copies count once; ``sched_idle`` is the part
of the wall that no span covers, which is also where un-spanned work hides.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

SPAN_CATEGORIES: Dict[str, str] = {
    "stage_hash": "hash",
    "sub_chunk_stage": "stage_copy",
    "sub_chunk_dtoh": "stage_copy",
    "storage_write": "storage_write",
    "storage_read": "storage_read",
    "consume": "decode",
    "consume_chunk": "decode",
    "sub_chunk_htod": "decode",
    "coop_read": "peer_transfer",
    "peer_send": "peer_transfer",
    "peer_recv": "peer_transfer",
    "reshard_plan": "peer_transfer",
    "peer_reshard": "peer_transfer",
    "native_write": "native_io",
    "native_read": "native_io",
    "collective_wait": "collective_wait",
}
# name -> (category of the residue, categories whose spans cover it): the
# part of a fused span in which none of its inner resources ran is charged
# to the resource the span was waiting on.
FUSED_SPANS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "stream_write": ("storage_write", ("stage_copy", "hash", "native_io")),
    "stream_read": ("storage_read", ("decode", "peer_transfer", "native_io")),
    "stage": ("stage_copy", ("hash", "stage_copy")),
}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def union_seconds(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def subtract(intervals: Iterable[Interval], cover: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    cover = merge(cover)
    for a, b in merge(intervals):
        cur = a
        for ca, cb in cover:
            if cb <= cur:
                continue
            if ca >= b:
                break
            if ca > cur:
                out.append((cur, min(ca, b)))
            cur = max(cur, cb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def category_intervals(spans: Iterable[Tuple[str, float, float]]) -> Dict[str, List[Interval]]:
    """``spans`` are (name, ts, dur)."""
    per_cat: Dict[str, List[Interval]] = {}
    fused: Dict[str, List[Interval]] = {}
    for name, ts, dur in spans:
        if dur is None or dur < 0:
            continue
        if name in SPAN_CATEGORIES:
            per_cat.setdefault(SPAN_CATEGORIES[name], []).append((ts, ts + dur))
        elif name in FUSED_SPANS:
            fused.setdefault(name, []).append((ts, ts + dur))
    for name in sorted(fused):  # "stage" folds in before "stream_*" reads it
        residual, covers = FUSED_SPANS[name]
        cover = [iv for c in covers for iv in per_cat.get(c, [])]
        per_cat.setdefault(residual, []).extend(subtract(fused[name], cover))
    return per_cat


def attribute(spans: Iterable[Tuple[str, float, float]], lo: float, hi: float) -> Dict[str, float]:
    """Seconds per category inside [lo, hi], ``sched_idle`` for the rest,
    ``wall`` and ``covered`` beside them."""
    per_cat = {c: clip(iv, lo, hi) for c, iv in category_intervals(spans).items()}
    out = {c: union_seconds(iv) for c, iv in per_cat.items() if iv}
    covered = union_seconds(iv for ivs in per_cat.values() for iv in ivs)
    out["wall"] = hi - lo
    out["covered"] = covered
    out["sched_idle"] = max(0.0, (hi - lo) - covered)
    return out
