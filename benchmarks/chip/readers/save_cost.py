"""Loop time lost per save: (wall of the save windows - steps completed x
the undisturbed step) / saves completed. Counts the blocked part of
``mgr.save``, slower steps under a drain and any wait for the drain before,
so cost moved from one of them to another is not a gain."""

from lib import derive, stats


def read(record):
    calm, _ = derive.split_steps(record)
    saves = derive.ok_records(record, "saves", "t_commit")
    wall = sum(w["t1"] - w["t0"] for w in record.get("windows", []) if w["kind"] == "save")
    if not calm or not saves or wall <= 0:
        return None
    value = (wall - len(record["steps"]) * stats.median(calm)) / len(saves)
    return {"value": value, "n": len(saves)}
