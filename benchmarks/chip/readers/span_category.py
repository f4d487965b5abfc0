"""Median over the window's takes or restores of one resource's seconds,
from the program's telemetry spans (``lib/spans.py``).

args: ``op`` ("take" | "restore"), ``category``."""

from lib import spans, stats


def read(record, op, category):
    xs = [
        spans.attribute(o["spans"], o["lo"], o["hi"]).get(category, 0.0)
        for o in record["ops"] if o["op"] == op and o["spans"]
    ]
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
