"""Model FLOP/s utilization of the undisturbed step, in %: 6 x active
parameters x tokens per step / step seconds, over chips x the peak of
``peaks.json`` for this device kind. An unknown kind is an error."""

import json
import os

from lib import derive, spec, stats


def read(record):
    calm, _ = derive.split_steps(record)
    facts = record["facts"]
    if not calm or facts["dry_run"]:  # a CPU has no row in the table of peaks, by design
        return None
    with open(os.path.join(spec.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][facts["device_kind"]]["bf16_flops"]
    flops = 6 * facts["n_active_params"] * facts["tokens_per_step"] / stats.median(calm)
    return {"value": 100.0 * flops / (facts["chips"] * peak), "n": len(calm)}
