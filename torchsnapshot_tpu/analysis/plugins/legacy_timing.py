"""Ported legacy lint: no ad-hoc timing outside telemetry (rule
``timing``).

This is ``scripts/check_timing_lint.py`` moved onto the tsalint
framework bit-for-bit: same allowlists, same banned attributes, same
walk (including ``benchmarks/``), same per-violation text. The script
remains as a thin wrapper importing everything from here, so existing
CI invocations and tests/test_timing_lint.py keep working unchanged.

The telemetry subsystem (torchsnapshot_tpu/telemetry/) is the ONE
measurement mechanism for the pipeline — spans, counters, rates, and the
blessed ``telemetry.monotonic`` clock. Wall-clock DEADLINE logic (store
RPC timeouts, the test launcher's subprocess deadline) is not
measurement and stays on raw ``time.monotonic`` via the explicit
allowlist; registered benchmark files measure wall clock deliberately.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

from ..core import Finding, PACKAGE_DIR, REPO_DIR, Project

RULES = ("timing",)

REPO = REPO_DIR
PACKAGE = PACKAGE_DIR
BENCH_DIR = os.path.join(REPO, "benchmarks")

# Paths (relative to the package) allowed to call time.monotonic/
# perf_counter directly. Deadline/timeout bookkeeping only — add a file
# here ONLY for wall-deadline logic, never for measurement (measurement
# belongs on the telemetry bus).
ALLOWLIST = {
    "dist_store.py",  # store RPC / barrier deadline arithmetic
    "test_utils.py",  # multi-process launcher subprocess deadline
}

# Benchmark files (relative to benchmarks/) that measure wall clock
# deliberately — the registration is the point: a benchmark timing the
# pipeline from outside NEEDS raw perf_counter, and listing it here
# records that the choice was deliberate rather than drift.
BENCHMARK_ALLOWLIST = {
    "async_stall.py",
    "attention_bench.py",
    "bench_utils.py",
    "chaos_soak.py",  # soak wall + the disabled-injector overhead gate
    "coop_restore.py",  # fan-out vs direct restore walls time wall clock
    "device_dedup.py",
    "dist_verify.py",
    "dma_overlap.py",
    "embedding_save.py",
    "fleet_restore.py",  # direct vs seeded fleet restore walls time wall clock
    "georep_rpo.py",  # WAN ship walls + the foreground-overhead gate
    "manifest_scale.py",
    "journal_rpo.py",  # epoch-append vs full-save walls time wall clock
    "lazy_restore.py",  # TTFI vs eager restore walls time wall clock
    "reshard_throughput.py",  # planned vs direct restore walls time wall clock
    "restore_overlap.py",  # read/consume overlap legs time wall clock
    "sharded_save.py",
    "store_scale.py",
    "stream_overlap.py",
    "tenant_admission.py",  # solo vs contended restore walls time wall clock
    "vs_orbax.py",
}

_BANNED_ATTRS = {"monotonic", "perf_counter", "monotonic_ns", "perf_counter_ns"}


def _violations_in(path: str) -> list:
    with open(path, "r") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:  # pragma: no cover - package must parse
        return [(e.lineno or 0, f"syntax error: {e}")]
    out = []
    # Names bound by `from time import monotonic/perf_counter [as alias]`.
    from_time_aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in _BANNED_ATTRS:
                    from_time_aliases.add(alias.asname or alias.name)
                    out.append(
                        (node.lineno, f"from time import {alias.name}")
                    )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in _BANNED_ATTRS
            and isinstance(fn.value, ast.Name)
            and fn.value.id in ("time", "_time")
        ):
            out.append((node.lineno, f"{fn.value.id}.{fn.attr}()"))
        elif isinstance(fn, ast.Name) and fn.id in from_time_aliases:
            out.append((node.lineno, f"{fn.id}()"))
    return out


# Files INSIDE telemetry/ that are clock CONSUMERS, not the clock's
# owner: they must go through core.monotonic like the rest of the
# package, so the lint covers them despite living in the exempt dir.
# (core.py/export.py own the clock; history.py records calendar time.)
# critpath.py consumes recorded span timestamps and promexp.py serves
# scrapes — neither may ever grow a private clock.
TELEMETRY_COVERED = {
    "flightrec.py",
    "health.py",
    "critpath.py",
    "promexp.py",
    "forensics.py",
}


def collect_failures() -> List[Tuple[str, int, str]]:
    """The legacy walk: (package-relative path, line, what) triples."""
    failures: List[Tuple[str, int, str]] = []
    for dirpath, dirnames, filenames in os.walk(PACKAGE):
        rel_dir = os.path.relpath(dirpath, PACKAGE)
        if rel_dir.split(os.sep)[0] == "telemetry":
            # The telemetry package owns the raw clock — EXCEPT its
            # consumer modules (the flight recorder, the health plane),
            # which are linted like everything else.
            for name in sorted(filenames):
                if name not in TELEMETRY_COVERED:
                    continue
                rel = os.path.normpath(os.path.join(rel_dir, name))
                for lineno, what in _violations_in(os.path.join(dirpath, name)):
                    failures.append((rel, lineno, what))
            continue
        for name in filenames:
            if not name.endswith(".py"):
                continue
            rel = os.path.normpath(os.path.join(rel_dir, name))
            if rel in ALLOWLIST:
                continue
            for lineno, what in _violations_in(os.path.join(dirpath, name)):
                failures.append((rel, lineno, what))
    if os.path.isdir(BENCH_DIR):
        for name in sorted(os.listdir(BENCH_DIR)):
            if not name.endswith(".py") or name in BENCHMARK_ALLOWLIST:
                continue
            for lineno, what in _violations_in(os.path.join(BENCH_DIR, name)):
                failures.append((os.path.join("..", "benchmarks", name), lineno, what))
    return failures


def run_pass(project: Project) -> List[Finding]:
    out = []
    for rel, lineno, what in sorted(collect_failures()):
        file = os.path.normpath(os.path.join("torchsnapshot_tpu", rel))
        out.append(
            Finding(
                rule="timing",
                file=file.replace(os.sep, "/"),
                line=lineno,
                message=(
                    f"{what} — ad-hoc timing outside telemetry/ (use "
                    "telemetry.span()/record_rate()/telemetry.monotonic, or "
                    "register a DEADLINE-logic file in the allowlist)"
                ),
            )
        )
    return out


def main() -> int:
    failures = collect_failures()
    if failures:
        print(
            "ad-hoc timing outside torchsnapshot_tpu/telemetry/ "
            "(use telemetry.span()/record_rate()/telemetry.monotonic, or "
            "add a DEADLINE-logic file to the allowlist in "
            "scripts/check_timing_lint.py):",
            file=sys.stderr,
        )
        for rel, lineno, what in sorted(failures):
            print(f"  torchsnapshot_tpu/{rel}:{lineno}: {what}", file=sys.stderr)
        return 1
    print("timing lint: clean")
    return 0
