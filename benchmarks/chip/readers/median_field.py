"""Median, over the window's saves or restores that passed, of one field.

args: ``of`` ("saves" | "restores"), ``field``."""

from lib import derive, stats


def read(record, of, field):
    xs = [r[field] for r in derive.ok_records(record, of, field)]
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
