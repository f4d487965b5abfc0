"""The window's train steps, split by whether a save was draining.

args: ``which``: "undisturbed_ms" (median step with no save blocked or
draining, in ms) | "inflation" (median step under a drain over that)."""

from lib import derive, stats


def read(record, which):
    calm, drained = derive.split_steps(record)
    if not calm:
        return None
    if which == "undisturbed_ms":
        return {"value": stats.median(calm) * 1e3, "n": len(calm)}
    if which == "inflation" and drained:
        return {"value": stats.median(drained) / stats.median(calm), "n": len(drained)}
    return None
