"""Median, over the window's saves or restores that passed, of end - start.

args: ``of`` ("saves" | "restores"), ``start``, ``end`` (keys of a record
of that list, host-clock seconds), ``scale`` (1000 for milliseconds)."""

from lib import derive, stats


def read(record, of, start, end, scale=1.0):
    xs = [(r[end] - r[start]) * scale for r in derive.ok_records(record, of, start, end)]
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
