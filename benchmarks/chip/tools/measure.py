"""Run a cell several times, as the driver does, and report the spread.

    python3 benchmarks/chip/tools/measure.py --workload olmo1b.save --sets 2 --runs 6 \
        [--seconds <run_seconds>] [--seed0 100] [--trace 0] [--out chiprun_out/measure]

Each run is a new process with another ``--seed``, one after another (this
parent never imports jax, so the child holds the chip alone). Per set and
metric: median, quartiles and spread (distance between the quartiles over
the median); then the wider of the sets' spreads and how far the second
set's median is from the first's. Every run's last line is appended to
``<out>/<workload>.jsonl``, its earlier output to ``<out>/<workload>.log``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from lib import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "measure"))
    ap.add_argument("extra", nargs="*", help="further arguments for run.py, after --")
    args = ap.parse_args(argv)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    lines_path = os.path.join(args.out, f"{args.workload}.jsonl")
    log_path = os.path.join(args.out, f"{args.workload}.log")

    sets: list = []
    seed = args.seed0
    for k in range(args.sets):
        sets.append([])
        for _ in range(args.runs):
            cmd = [*bench["command"], "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(args.trace), *args.extra]
            t0 = time.monotonic()
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
            wall = time.monotonic() - t0
            out = proc.stdout.strip().splitlines()
            with open(log_path, "a") as f:
                f.write(f"### set {k} seed {seed} rc {proc.returncode} wall {wall:.1f}\n{proc.stderr}\n")
                f.write("\n".join(out[:-1]) + "\n")
            seed += 1
            if proc.returncode != 0 or not out:
                print(f"set {k} seed {seed - 1}: exit {proc.returncode}, no result\n{proc.stderr[-2000:]}", flush=True)
                continue
            line = json.loads(out[-1])
            line.update(set=k, seed=seed - 1, wall_s=wall)
            with open(lines_path, "a") as f:
                f.write(json.dumps(line) + "\n")
            sets[k].append(line)
            shown = {m: round(v["value"], 4) for m, v in line["metrics"].items()}
            print(f"set {k} seed {seed - 1}: correct={line['correct']} wall {wall:.0f} s {shown}", flush=True)

    names = sorted({m for runs in sets for line in runs for m in line["metrics"]})
    print(f"\n{args.workload}: {args.sets} set(s) of {args.runs} run(s), {seconds:g} s each")
    for name in names:
        per_set = [[line["metrics"][name]["value"] for line in runs if name in line["metrics"]] for runs in sets]
        per_set = [xs for xs in per_set if xs]
        if not per_set:
            continue
        # setup_s is judged without the run that compiled
        shown = [xs[1:] if name.endswith("setup_s") and k == 0 and len(xs) > 1 else xs for k, xs in enumerate(per_set)]
        spreads = [stats.spread(xs) or 0.0 for xs in shown]
        medians = [stats.median(xs) for xs in shown]
        drift = (medians[-1] - medians[0]) / medians[0] if len(medians) > 1 and medians[0] else 0.0
        cells = "  ".join(
            f"set{k}: med {stats.median(xs):.4f} [{stats.quantile(xs, .25):.4f}, {stats.quantile(xs, .75):.4f}] n={len(xs)}"
            for k, xs in enumerate(shown)
        )
        print(f"  {name:22s} widest spread {100 * max(spreads):5.2f} %  set drift {100 * drift:+6.2f} %  {cells}")
    ok = all(line["correct"] for runs in sets for line in runs) and all(len(r) == args.runs for r in sets)
    print("all runs correct" if ok else "SOME RUNS FAILED OR WERE NOT CORRECT")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
