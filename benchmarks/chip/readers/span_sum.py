"""Median over the window's takes or restores of a sum of span durations,
each span clipped to its operation's wall (``lib/spans.py``).

args: ``op`` ("take" | "restore"), ``names`` (span names), ``minus``
(span names, optional): the clipped durations of the ``names`` spans added
up, less those of the ``minus`` spans. Span-seconds, not wall: spans open
at once count once each. Where every ``minus`` span lies inside one
``names`` span and no two of them overlap there, the difference is what
the outer spans hold under no inner name. An operation without a
``names`` span does not count; with none that has one, nothing is read."""

from lib import spans, stats


def _seconds(op, names):
    return sum(b - a for a, b in spans.clip(
        ((ts, ts + dur) for name, ts, dur in op["spans"] if name in names and dur is not None),
        op["lo"], op["hi"],
    ))


def read(record, op, names, minus=()):
    xs = []
    for o in record["ops"]:
        if o["op"] != op:
            continue
        total = _seconds(o, names)
        if total > 0:
            xs.append(float(total - _seconds(o, minus)))
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
