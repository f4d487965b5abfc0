"""Highest allocator peak seen after a restore, over the bytes of the state
one device holds. The allocator's peak does not count a program's planned
temporaries, so this is the restore's own footprint."""

from lib import derive


def read(record):
    xs = [
        r["peak_bytes"] / r["bytes_per_device"]
        for r in derive.ok_records(record, "restores", "peak_bytes", "bytes_per_device")
        if r["peak_bytes"] and r["bytes_per_device"]
    ]
    return {"value": max(xs), "n": len(xs)} if xs else None
