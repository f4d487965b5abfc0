"""Median over the window's takes or restores of a statistic of the named
telemetry spans, each clipped to its operation's wall (``lib/spans.py``).

args: ``op`` ("take" | "restore"), ``names`` (span names), ``stat``:
"union" is the seconds in which at least one named span was open;
"overlap" is the sum of the clipped durations over that union: how many
were open at once, on average, while any was. An operation without a
named span does not count; with none that has one, nothing is read."""

from lib import spans, stats


def read(record, op, names, stat):
    if stat not in ("union", "overlap"):
        raise ValueError(f"span_stat: stat is 'union' or 'overlap', not {stat!r}")
    xs = []
    for o in record["ops"]:
        if o["op"] != op:
            continue
        open_ = spans.clip(
            ((ts, ts + dur) for name, ts, dur in o["spans"] if name in names and dur is not None),
            o["lo"], o["hi"],
        )
        union = spans.union_seconds(open_)
        if union > 0:
            xs.append(float(union if stat == "union" else sum(b - a for a, b in open_) / union))
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
