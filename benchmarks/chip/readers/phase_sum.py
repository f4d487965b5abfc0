"""Median over the window's takes of the seconds in the named phases
(the program's ``phase:<name>`` marks, each carrying its duration).

args: ``op``, ``phases`` (names without the ``phase:`` prefix); with
``as_rate`` the state's bytes over those seconds, in GB/s."""

from lib import stats


def read(record, op, phases, as_rate=False):
    wanted = {"phase:" + p for p in phases}
    xs = []
    for o in record["ops"]:
        durs = [d for name, _, d in o["phases"] if name in wanted and d is not None]
        if o["op"] == op and durs and sum(durs) > 0:
            xs.append(record["facts"]["state_bytes"] / sum(durs) / 1e9 if as_rate else sum(durs))
    return {"value": stats.median(xs), "n": len(xs)} if xs else None
