"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chip(s): it sets up (compile cache, state from
the seed, warm-up of this cell's programs), measures for ``--seconds``,
checks what the program wrote, and prints as its last line of standard
output one JSON object with the keys ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` with ``--trace 1``). With
``--trace 0`` the metrics are the cell's end-to-end metrics of
``BENCHMARK.json``, with ``--trace 1`` its per-layer metrics.

Exits non-zero, and prints no result line, when JAX finds no TPU or fewer
chips than the cell asks for. ``--dry-run 1`` is a rehearsal of the control
flow on CPU devices at toy widths: its line names the cpu platform and
every metric carries the prefix ``dryrun.``, so nothing from it can be read
as a device number. The driver never passes it.

How cells, mixes, kinds, readers and metrics are found: ``lib/spec.py`` and
``README.md`` beside this file.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # before any heavy import: set-up counts them

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
# The program is used from the checkout, not from an installation.
sys.path[:0] = [HERE, REPO]

EXIT_BAD_SPEC = 4
DTOH_PROBE_BYTES = 1 << 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", type=int, choices=(0, 1), default=0,
                    help="rehearse on CPU devices at toy widths; never a measurement")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, also write the compact trace and a listing of its planes there")
    args = ap.parse_args(argv)

    from lib import spec

    try:
        bench = spec.load_benchmark()
        cell = spec.resolve_cell(bench, args.workload, bool(args.dry_run))
        group = spec.GROUPS[args.trace]
        metrics = [(m, spec.load_metric(m["name"])) for m in spec.cell_metrics(bench, cell.name, group)]
        readers = {d["reader"]: spec.load_module("readers", d["reader"]) for _, d in metrics}
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_BAD_SPEC

    # Environment, before jax or the program is imported.
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell.chips}"
    if args.trace:
        os.environ["TORCHSNAPSHOT_TPU_TELEMETRY"] = "1"  # the program's own switch
    # The program's helper takes the directory from this variable; the
    # driver may set it, else the cache sits at a fixed path in the checkout.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(HERE, ".cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nothing outside the checkout

    from lib.session import Session, log

    s = Session(cell, args.seed, args.seconds, bool(args.trace), bool(args.dry_run),
                T_START, keep_trace=args.keep_trace)
    try:
        kind = spec.load_module("kinds", cell.kind)  # after the environment is set: kinds import jax
        if args.trace:
            s.record["dtoh"] = s.dtoh_probe(DTOH_PROBE_BYTES >> (6 if args.dry_run else 0))
        kind.run(s)
        s.keep_ops()
        result, samples = _result(s, metrics, readers)
    finally:
        s.close()
    print(json.dumps({"samples": samples, "setup": s.record["setup"],
                      "checks": s.record["checks"], "readback_s": s.record.get("readback_s")}))
    print(json.dumps(result), flush=True)
    log(f"done in {time.monotonic() - T_START:.1f} s: correct={result['correct']}")
    return 0


def _result(s, metrics, readers):
    rec = s.record
    prefix = "dryrun." if s.dry_run else ""
    out, samples = {}, {}
    for entry, definition in metrics:
        got = readers[definition["reader"]].read(rec, **definition.get("args", {}))
        if got is None or got.get("value") is None:
            continue  # nothing to read: the metric is left out of the line
        out[prefix + entry["name"]] = {"value": got["value"], "unit": entry["unit"]}
        if "n" in got:
            samples[entry["name"]] = got["n"]

    ops = rec["saves"] + rec["restores"]
    failed = sum(1 for r in ops if not r.get("ok") or ("t_commit" not in r and "t_call" in r))
    losses = [st["loss"] for st in rec["steps"]] + [r["loss"] for r in rec["restores"] if "loss" in r]
    checks_ok = all(c["ok"] for c in rec["checks"])
    finite = all(map(math.isfinite, losses))
    correct = bool(ops) and failed == 0 and checks_ok and finite and rec.get("window_compiles", 0) == 0

    peaks = s.peak_bytes()
    planned = max((v.get("step_planned_bytes", 0) for v in rec["setup"].values() if isinstance(v, dict)), default=0)
    device = dict(s.device_info)
    # The allocator's peak does not count a program's planned temporaries
    # (PERF.md): the fullest chip's peak is the larger of the two.
    device["memory_peak_bytes"] = max(max(peaks), planned)
    device["memory_peak_allocator_bytes"] = max(peaks)
    device["memory_step_planned_bytes"] = planned
    result = {"correct": correct, "attempted": len(ops), "failed": failed, "metrics": out, "device": device}
    if s.trace:
        tr = rec.get("trace")
        if tr:
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    if s.dry_run:
        result["dry_run"] = True
    samples["each_save"] = [
        {"blocked_s": round(r["t_ret"] - r["t_call"], 3), "commit_s": round(r.get("t_commit", r["t_ret"]) - r["t_call"], 3),
         **{k: round(v, 3) for k, v in r.get("host", {}).items()}}
        for r in ([rec["warmup_save"]] if rec.get("warmup_save") else []) + rec["saves"]
    ]
    samples["each_restore"] = [
        {"restore_s": round(r["t_restored"] - r["t0"], 3), "resume_s": round(r["t_first_step"] - r["t0"], 3),
         "compiles": r.get("compiles")} for r in rec["restores"]
    ]
    samples.update(steps=len(rec["steps"]), saves=len(rec["saves"]), restores=len(rec["restores"]),
                   window_compiles=rec.get("window_compiles"), compile_cache=s.compiles(),
                   windows=[{**w, "wall_s": w["t1"] - w["t0"]} for w in rec.get("windows", [])])
    return result, samples


if __name__ == "__main__":
    sys.exit(main())
