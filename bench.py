"""Headline benchmark: Snapshot save throughput for device state.

Mirrors the reference's DDP benchmark (benchmarks/ddp/main.py: save a model
of N x 100MB params, report wall time). Reference baseline on comparable
1-worker hardware: 18 GB in ~45 s => 0.40 GB/s (benchmarks/ddp/README.md:15,
reproduced in BASELINE.md). We report save throughput in GB/s on one chip;
vs_baseline is the ratio against that 0.40 GB/s figure.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N,
   "p50_gbps": N, "restore_gbps": N,
   "device": {"platform": ..., "kind": ..., "count": N}, ...}
value is best-of-N save throughput; p50_gbps the median of the same
trials; restore_gbps the best timed restore of the same state. All
diagnostics go to stderr. (The cells, medians and device peaks a real
benchmark needs are ROADMAP A1's; this file only stopped hiding the
device.)

The device is never chosen here. The backend is initialised once, in this
process, and named in the record. Off-TPU the run exits non-zero before
measuring anything, unless the caller set ``JAX_PLATFORMS=cpu`` itself —
then every number is labelled cpu. A leg that was started and failed is
listed under ``failed_legs`` and the run exits non-zero after printing
what it has.

One process for each chip: the two hardware side legs
(benchmarks/dma_overlap.py, device_dedup.py) need the chip, so they run
as children BEFORE this process touches JAX. The subsystem legs that run
while this process holds the chip are CPU drills, and their children get
``JAX_PLATFORMS=cpu`` set outright.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE_SAVE_GBPS = 18.0 / 45.0  # benchmarks/ddp/README.md:15 (1 worker)
_LEG_TIMEOUT_S = 420.0


def _log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


class LegFailed(RuntimeError):
    """A leg that was started did not produce its result."""


class _SubprocResult:
    def __init__(self, returncode, stdout, stderr, killed, pgid=None):
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr
        self.killed = killed
        self.pgid = pgid  # the child-led process group (== child pid)


def _run_in_own_group(cmd, timeout, env=None):
    """subprocess.run, but the child leads its OWN process group and a
    timeout kills the WHOLE group — then verifies no orphan survived.

    ``subprocess.run(timeout=...)`` kills only the direct child, not
    whatever it forked; an orphan would compete for the host's cores
    during the timed saves. A group member that survives SIGKILL
    (unkillable D-state) is loudly reported so the caller can annotate
    the run as contaminated.
    """
    proc = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        start_new_session=True,  # child = leader of a fresh process group
    )
    killed = False
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        killed = True
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        stdout, stderr = proc.communicate()
    if killed:
        _verify_group_dead(proc.pid)
    return _SubprocResult(
        proc.returncode, stdout or "", stderr or "", killed, pgid=proc.pid
    )


def _verify_group_dead(pgid, wait_s: float = 5.0) -> bool:
    """Poll until no process remains in ``pgid``; log loudly if one
    survives (it will contaminate subsequent timing windows)."""
    deadline = time.monotonic() + wait_s
    while time.monotonic() < deadline:
        try:
            os.killpg(pgid, 0)
        except ProcessLookupError:
            return True  # whole group reaped
        except PermissionError:
            break  # exists but not ours — report below
        time.sleep(0.2)
    _log(
        f"WARNING: process group {pgid} still has live members after "
        f"SIGKILL + {wait_s}s; the host may be contaminated for timing"
    )
    return False


# Floor for the memcpy self-calibration: all bench state fits in RAM and
# the pipeline is memory-bandwidth-bound, so a host that can't stream
# copies at this rate is either contended or misconfigured — the timed
# window would measure the contention, not the snapshot pipeline.
_MEMCPY_FLOOR_GBPS = float(os.environ.get("BENCH_MEMCPY_FLOOR_GBPS", "1.0"))


def _host_calibration():
    """Measure the host BEFORE opening the timed window: 1-minute load
    average and achieved memcpy bandwidth (3x 256 MB, best-of), so a
    loaded host is visible in the artifact instead of silently costing
    the headline. Returns a dict embedded in the JSON under
    "host_calibration" with a ``contaminated`` verdict."""
    import numpy as np

    cpu_count = os.cpu_count() or 1
    try:
        load1 = os.getloadavg()[0]
    except OSError:  # pragma: no cover
        load1 = 0.0
    src = np.empty(256 << 20, np.uint8)
    src[::4096] = 1  # fault the pages outside the timed copies
    dst = np.empty_like(src)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = max(best, src.nbytes / max(time.perf_counter() - t0, 1e-9))
    del src, dst
    memcpy_gbps = best / 1e9
    contaminated = load1 > 1.5 * cpu_count or memcpy_gbps < _MEMCPY_FLOOR_GBPS
    cal = {
        "load1": round(load1, 2),
        "cpu_count": cpu_count,
        "memcpy_gbps": round(memcpy_gbps, 2),
        "contaminated": contaminated,
    }
    if contaminated:
        cal["reason"] = (
            f"load1={load1:.2f} vs {cpu_count} cpu(s)"
            if load1 > 1.5 * cpu_count
            else f"memcpy {memcpy_gbps:.2f} GB/s < {_MEMCPY_FLOOR_GBPS} GB/s floor"
        )
    _log(f"host calibration: {cal}")
    return cal


def _json_records(stdout: str) -> "dict[str, dict]":
    """Parse a subprocess's stdout into {benchmark_name: record} from its
    one-JSON-object-per-line output, skipping banners/noise."""
    legs = {}
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            legs[rec.get("benchmark", "?")] = rec
    return legs


def _run_script(script: str, *args: str, timeout_s: float, on_chip: bool = False):
    """Run ``benchmarks/<script>`` in its own process group and return its
    JSON records; raise LegFailed when it is killed at the deadline or
    exits non-zero.

    ``on_chip=False`` (the subsystem drills) sets the child's platform to
    cpu OUTRIGHT: this process holds the chip by then, and a child that
    inherited the machine's own ``JAX_PLATFORMS`` would wait for it until
    its deadline. ``on_chip=True`` children inherit the environment
    untouched and must run before this process initialises JAX.
    """
    env = dict(os.environ)
    if not on_chip:
        env["JAX_PLATFORMS"] = "cpu"
    r = _run_in_own_group(
        [sys.executable, os.path.join(HERE, "benchmarks", script), *args],
        timeout=timeout_s,
        env=env,
    )
    if r.killed or r.returncode != 0:
        raise LegFailed(
            f"{script} rc={r.returncode} killed={r.killed} "
            f"stderr={r.stderr.strip()[-300:]!r}"
        )
    return _json_records(r.stdout)


def _need(records: dict, name: str) -> dict:
    if name not in records:
        raise LegFailed(f"no {name!r} record in the output ({sorted(records)})")
    return records[name]


def _legs_of(records: dict, prefix: str) -> list:
    return [
        rec
        for name, rec in records.items()
        if name.startswith(prefix + "/") and name != prefix + "/summary"
    ]


def _write_artifact(name: str, payload: dict, env_note: dict) -> str:
    """Persist a CPU drill's record beside this file, labelled as what it
    is: a count on the cpu backend, never a device metric."""
    out = os.path.join(HERE, name)
    with open(out, "w") as f:
        json.dump(
            {**payload, "platform": "cpu", "env": {"JAX_PLATFORMS": "cpu", **env_note}},
            f,
            indent=1,
        )
        f.write("\n")
    return out


def _tpu_hw_leg(timeout_s: float = _LEG_TIMEOUT_S) -> dict:
    """Run benchmarks/dma_overlap.py and device_dedup.py against the chip,
    each in its own process, BEFORE this process initialises JAX.

    Returns a compact summary (DMA overlap ratio, train-step inflation
    under an in-flight async_take, on-chip sync-take throughput +
    bit-exactness, unchanged-resave speedup) for embedding in the main
    JSON line. Both share the announced budget: the second gets what the
    first left over (min 60 s).
    """
    _log(f"running TPU hardware side-leg ({timeout_s:.0f}s budget) ...")
    t_begin = time.monotonic()
    legs = _run_script("dma_overlap.py", timeout_s=timeout_s, on_chip=True)
    stage = _need(legs, "dma_overlap/stage")
    take = _need(legs, "dma_overlap/async_take")
    sync = _need(legs, "dma_overlap/sync_take")
    ceiling = _need(legs, "dma_overlap/ceiling")
    out = {
        "dma_overlap_ratio": stage["overlap_ratio"],
        "async_step_inflation": take["step_inflation"],
        "sync_take_mbps": sync["take_mbps"],
        "sync_take_state_mb": sync["state_mb"],
        "sync_take_bit_exact": sync["bit_exact"],
        # >100% is possible — the pipeline overlaps many DtoH streams
        # while the ceiling probe is one serial device_get.
        "ceiling_gbps": round(ceiling["dtoh_ceiling_mbps"] / 1e3, 4),
        "host_memcpy_gbps": ceiling["host_memcpy_gbps"],
        "achieved_pct": sync["take_pct_of_ceiling"],
        "async_stage_pct_of_ceiling": stage["async_pct_of_ceiling"],
    }
    remaining = max(60.0, timeout_s - (time.monotonic() - t_begin))
    dedup = _run_script("device_dedup.py", timeout_s=remaining, on_chip=True)
    out["device_dedup_speedup"] = _need(dedup, "device_dedup/unchanged_resave")[
        "speedup"
    ]
    _log(f"TPU hardware side-leg ok: {out}")
    return out


@dataclasses.dataclass(frozen=True)
class _SummaryLeg:
    """A subsystem drill of the one common shape: a script whose records
    are named ``<prefix>/<leg>``, with ``<prefix>/summary`` the result
    embedded in the main record and the rest persisted to ``artifact``."""

    key: str
    script: str
    prefix: str
    artifact: str
    unit: str
    env_note: dict

    def run(self) -> dict:
        records = _run_script(self.script, timeout_s=_LEG_TIMEOUT_S)
        summary = _need(records, f"{self.prefix}/summary")
        _write_artifact(
            self.artifact,
            {
                "metric": self.key,
                "unit": self.unit,
                "summary": summary,
                "legs": _legs_of(records, self.prefix),
            },
            self.env_note,
        )
        compact = dict(summary)
        compact.pop("benchmark", None)
        return compact


# Each script asserts its own acceptance bound and exits non-zero when it
# does not hold (see the script's docstring for the drill and the bound).
_SUMMARY_LEGS = (
    _SummaryLeg(
        "journal", "journal_rpo.py", "journal_rpo", "BENCH_r12.json",
        "seconds of recoverable-state interval at 1% sustained checkpoint "
        "overhead / MiB/s append",
        {"TORCHSNAPSHOT_TPU_JOURNAL": "1", "TORCHSNAPSHOT_TPU_NATIVE_IO": "never"},
    ),
    _SummaryLeg(
        "fleet_distribution", "fleet_restore.py", "fleet_restore", "BENCH_r13.json",
        "storage-read amplification (x payload) / GB/s aggregate / bytes per "
        "replica per rolling update",
        {"TORCHSNAPSHOT_TPU_SEED_RESTORE": "always", "TORCHSNAPSHOT_TPU_JOURNAL": "1"},
    ),
    _SummaryLeg(
        "lazy_restore", "lazy_restore.py", "lazy_restore", "BENCH_r15.json",
        "time-to-first-inference speedup (x eager wall) / payload-read "
        "amplification (x eager bytes)",
        {"TORCHSNAPSHOT_TPU_LAZY_RESTORE": "always"},
    ),
    _SummaryLeg(
        "georep", "georep_rpo.py", "georep_rpo", "BENCH_r17.json",
        "seconds of remote-tier recovery point vs journal cadence on a "
        "20 MB/s WAN",
        {"TORCHSNAPSHOT_TPU_JOURNAL": "1"},
    ),
)


def _coop_restore_leg():
    """Cooperative restore fan-out (benchmarks/coop_restore.py): 1/2/4-
    process throttled-storage restores of replicated-heavy state —
    aggregate restore GB/s and storage-read amplification."""
    records = _run_script("coop_restore.py", "64", timeout_s=_LEG_TIMEOUT_S)
    summary = _need(records, "coop_restore/summary")
    _write_artifact(
        "BENCH_r09.json",
        {
            "metric": "cooperative_restore_fanout",
            "unit": "GB/s aggregate",
            "payload_mb": summary.get("payload_mb"),
            "throttle_mbps": summary.get("throttle_mbps"),
            "worlds": summary.get("worlds"),
            "legs": _legs_of(records, "coop_restore"),
        },
        {},
    )
    return summary["worlds"]


def _reshard_leg():
    """Planned reshard: reshard_throughput.py (world-2 tp2 -> world-4
    column cross-cut on throttled storage, RESHARD=never vs =always) and
    manifest_scale.py's plan-time bound over a ~50k-shard manifest."""
    deadline = time.monotonic() + _LEG_TIMEOUT_S
    records = _run_script("reshard_throughput.py", timeout_s=_LEG_TIMEOUT_S)
    summary = _need(records, "reshard_throughput/summary")
    ms = _need(
        _run_script(
            "manifest_scale.py", timeout_s=max(30.0, deadline - time.monotonic())
        ),
        "manifest_scale",
    )
    plan = {
        "shard_leaves": ms.get("shard_leaves"),
        "planned_units": ms.get("reshard_planned_units"),
        "plan_s": ms.get("reshard_plan_s"),
    }
    _write_artifact(
        "BENCH_r11.json",
        {
            "metric": "planned_reshard",
            "unit": "storage-read amplification (x payload) / GB/s",
            "summary": summary,
            "legs": _legs_of(records, "reshard_throughput"),
            "plan_scale": plan,
        },
        {},
    )
    compact = dict(summary, plan_scale=plan)
    compact.pop("benchmark", None)
    return compact


def _tenancy_leg():
    """Multi-tenant: the million-entry columnar manifest plane
    (manifest_scale.py --columnar) and the admission drill
    (tenant_admission.py: a priority-1 bulk save contending with a
    priority-4 restore on one throttled bucket)."""
    manifest_rec = _need(
        _run_script("manifest_scale.py", "--columnar", timeout_s=_LEG_TIMEOUT_S),
        "manifest_scale_columnar",
    )
    records = _run_script("tenant_admission.py", timeout_s=_LEG_TIMEOUT_S)
    admission = _need(records, "tenant_admission/summary")
    summary = {
        "manifest_entries": manifest_rec.get("entries"),
        "manifest_shard_leaves": manifest_rec.get("shard_leaves"),
        "manifest_total_s": manifest_rec.get("total_s"),
        "manifest_compaction_x": manifest_rec.get("compaction_x"),
        "admission_degradation_x": admission.get("degradation_x"),
        "no_admission_degradation_x": admission.get("no_admission_degradation_x"),
    }
    _write_artifact(
        "BENCH_r14.json",
        {
            "metric": "tenancy",
            "unit": "seconds for 1M-leaf manifest round-trip / restore "
            "p50 degradation (x solo) under a contending save",
            "summary": summary,
            "legs": [manifest_rec] + _legs_of(records, "tenant_admission"),
        },
        {},
    )
    return summary


# Subsystem drills run after the main leg; independent of one another.
_SUBSYSTEM_LEGS = (
    ("coop_restore", _coop_restore_leg),
    ("reshard", _reshard_leg),
    ("tenancy", _tenancy_leg),
    *((leg.key, leg.run) for leg in _SUMMARY_LEGS),
)


def _attempt(name: str, leg, failed: list):
    """Run one leg; a failure is recorded and the bench goes on, so the
    run can print what it has before exiting non-zero."""
    _log(f"running leg {name} ...")
    try:
        out = leg()
    except LegFailed as e:
        _log(f"leg {name} FAILED: {e}")
        failed.append(name)
        return None
    _log(f"leg {name} ok")
    return out


def _native_io_leg(tmp: str, app_state, state, nbytes: int):
    """Side-by-side native-engine vs Python-path legs (ISSUE 9),
    persisted to BENCH_r10.json and embedded in the main record.

    Both save legs pin a 32 MB sub-chunk so the streamed write path (the
    surface the engine replaces) engages for every entry under BOTH
    modes — the comparison measures the engine, not the streaming
    election; both restore legs force streamed reads for the same
    reason. Trials are back-to-back best-of-N. Returns the record dict; a
    host with no native engine is reported as skipped, not failed (the
    leg never starts: there is nothing to compare)."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict, native_io

    if native_io.engine_kind() is None:
        _log("native I/O leg skipped: engine probe failed")
        return {"skipped": "no native I/O engine on this host"}

    pinned = {
        "TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES": str(32 << 20),
        "TORCHSNAPSHOT_TPU_STREAM_READS": "always",
    }
    saved_env = {
        k: os.environ.get(k)
        for k in list(pinned) + ["TORCHSNAPSHOT_TPU_NATIVE_IO"]
    }
    legs: "dict[str, dict]" = {}
    try:
        os.environ.update(pinned)
        for mode in ("never", "always"):
            os.environ["TORCHSNAPSHOT_TPU_NATIVE_IO"] = mode
            root = f"{tmp}/native_{mode}"
            saves, restores = [], []
            Snapshot.take(f"{root}/warm", app_state)  # discarded warmup
            shutil.rmtree(f"{root}/warm", ignore_errors=True)
            for trial in range(4):
                t0 = time.perf_counter()
                Snapshot.take(f"{root}/s", app_state)
                saves.append(time.perf_counter() - t0)
                dst = {
                    "model": StateDict(
                        {k: jnp.zeros_like(v) for k, v in state.items()}
                    )
                }
                t0 = time.perf_counter()
                Snapshot(f"{root}/s").restore(dst)
                restores.append(time.perf_counter() - t0)
                if trial < 3:
                    shutil.rmtree(f"{root}/s", ignore_errors=True)
            shutil.rmtree(root, ignore_errors=True)
            legs[mode] = {
                "save_trials_s": [round(t, 3) for t in saves],
                "restore_trials_s": [round(t, 3) for t in restores],
                "save_gbps": round(nbytes / 1e9 / min(saves), 3),
                "save_p50_gbps": round(
                    nbytes / 1e9 / statistics.median(saves), 3
                ),
                "restore_gbps": round(nbytes / 1e9 / min(restores), 3),
                "restore_p50_gbps": round(
                    nbytes / 1e9 / statistics.median(restores), 3
                ),
            }
            _log(
                f"native leg [{mode}]: save best "
                f"{legs[mode]['save_gbps']:.2f} GB/s p50 "
                f"{legs[mode]['save_p50_gbps']:.2f} | restore best "
                f"{legs[mode]['restore_gbps']:.2f} p50 "
                f"{legs[mode]['restore_p50_gbps']:.2f}"
            )
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    from torchsnapshot_tpu import _native

    record = {
        "engine": native_io.engine_kind(),
        "queue_depth": native_io.queue_depth(),
        "slab_caps_seen": _native.slab_caps_seen(),
        "sub_chunk_bytes_pinned": 32 << 20,
        "python": legs["never"],
        "native": legs["always"],
        "native_vs_python_save": round(
            legs["always"]["save_p50_gbps"]
            / max(legs["never"]["save_p50_gbps"], 1e-9),
            3,
        ),
        "native_vs_python_restore": round(
            legs["always"]["restore_p50_gbps"]
            / max(legs["never"]["restore_p50_gbps"], 1e-9),
            3,
        ),
    }
    out = os.path.join(HERE, "BENCH_r10.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"native I/O side-by-side written to {out}")
    return record



def build_state(total_bytes: int, n_arrays: int = 18):
    """n_arrays bf16 arrays totalling ~total_bytes, on device."""
    import jax
    import jax.numpy as jnp

    per = total_bytes // n_arrays
    n_elem = per // 2  # bf16
    side = int(n_elem**0.5)
    key = jax.random.PRNGKey(0)
    arrs = {}
    for i in range(n_arrays):
        key, sub = jax.random.split(key)
        arrs[f"param_{i}"] = jax.random.normal(sub, (side, side), jnp.bfloat16)
    jax.block_until_ready(arrs)
    return arrs


def _init_backend() -> dict:
    """The one backend initialisation, in this process: whatever JAX
    selects (the caller's ``JAX_PLATFORMS`` included), named as JAX
    reports it."""
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _log(f"backend up in {time.perf_counter() - t0:.1f}s: {device}")
    return device


def main() -> int:
    failed: "list[str]" = []
    # Only the caller may put the run on the cpu; then nothing is a
    # device number and the chip legs have nothing to measure.
    caller_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    # Chip legs first: each child needs the chip, and once this process
    # touches JAX it holds it.
    tpu_hw = None if caller_cpu else _attempt("tpu_hw", _tpu_hw_leg, failed)

    device = _init_backend()
    if device["platform"] != "tpu" and not caller_cpu:
        _log(
            f"no TPU (backend is {device['platform']!r}); refusing to "
            "benchmark another device under a TPU metric's name. Set "
            "JAX_PLATFORMS=cpu yourself for a cpu-labelled run."
        )
        return 2

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    total = int(float(sys.argv[1]) * (1 << 30)) if len(sys.argv) > 1 else 2 << 30
    state = build_state(total)
    nbytes = sum(a.nbytes for a in state.values())
    app_state = {"model": StateDict(state)}
    _log(f"state built: {nbytes / 1e9:.2f} GB across {len(state)} arrays")

    # Self-calibrate BEFORE the timed window: a contaminated host (noisy
    # neighbor, throttled memory) gets one cool-down + re-check, and the
    # verdict is recorded in the artifact either way — a loaded host can
    # degrade the number but cannot masquerade as a code regression.
    calibration = _host_calibration()
    if calibration["contaminated"]:
        _log("host contaminated; cooling down 30s and re-checking")
        time.sleep(30)
        calibration = _host_calibration()

    # Write to tmpfs when available AND large enough (a snapshot is written
    # twice concurrently at peak: previous + current trial): the reference
    # baseline ran against FSx Lustre (a fast parallel FS); a slow container
    # disk would measure the disk, not the snapshot pipeline.
    base = None
    if os.path.isdir("/dev/shm"):
        if shutil.disk_usage("/dev/shm").free > int(nbytes * 2.5):
            base = "/dev/shm"
        else:
            _log("/dev/shm too small for the snapshot; using default tmpdir")
    tmp = tempfile.mkdtemp(prefix="tsnap_bench_", dir=base)
    try:
        # Warm-up at FULL size, untimed: on lazily-backed VMs the first
        # touch of never-used pages costs several x a normal fault — one
        # full pass warms the guest page pool so the timed trials measure
        # the pipeline, not the hypervisor (round 2 saw a 5.7x
        # run-to-run spread from this; with the warm-up p50 sits within
        # a few percent of best).
        Snapshot.take(f"{tmp}/warm", app_state)
        shutil.rmtree(f"{tmp}/warm", ignore_errors=True)
        _log("full-size warm-up snapshot done; starting timed saves")

        # 6 trials, not 4: on a 1-core VM the hypervisor occasionally
        # steals the core for seconds mid-trial; with 4 trials one such
        # outlier drags p50 below the pipeline's real rate, with 6 the
        # median holds (the raw trials stay in the JSON for audit).
        n_trials = int(os.environ.get("BENCH_TRIALS", "6"))
        # Per-trial purity guard: a ~64 MB memcpy immediately after each
        # trial measures whether the host was contended DURING the
        # window (the pre-window calibration can't see contention that
        # arrives later, when neighbor load makes pipeline trials measure
        # the neighbor).
        # A trial whose probe runs at <50% of the calibrated memcpy rate
        # is discarded and retried (bounded); every discarded wall time
        # still lands in the JSON for audit.
        import numpy as _np

        probe_src = _np.empty(64 << 20, _np.uint8)
        probe_src[::4096] = 1
        probe_dst = _np.empty_like(probe_src)
        # Pre-fault the destination too: on this lazily-backed VM a
        # first-touch copy runs at a fraction of the calibrated rate and
        # would falsely flag trial 0 as contended.
        probe_dst[::4096] = 1

        def _memcpy_probe_gbps() -> float:
            t0 = time.perf_counter()
            _np.copyto(probe_dst, probe_src)
            return probe_src.nbytes / max(time.perf_counter() - t0, 1e-9) / 1e9

        import psutil as _psutil

        proc = _psutil.Process()

        save_times = []
        discarded_trials = []
        max_retries = int(os.environ.get("BENCH_TRIAL_RETRIES", "6"))
        retries = 0
        trial = 0
        while trial < n_trials:
            cpu0 = proc.cpu_times()
            t0 = time.perf_counter()
            Snapshot.take(f"{tmp}/snap", app_state)
            trial_dt = time.perf_counter() - t0
            cpu1 = proc.cpu_times()
            # The save is CPU-bound on this path (memcpy + CRC + tmpfs
            # writes): a clean trial's process CPU time ~= wall. When
            # the hypervisor/a neighbor steals the core mid-window, wall
            # inflates while our CPU time doesn't — the ratio is a
            # DURING-trial contention detector the post-trial probe
            # can't be (the thief may leave before the probe runs).
            cpu_ratio = (
                (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system)
            ) / max(trial_dt, 1e-9)
            probe = _memcpy_probe_gbps()
            # The cpu/wall criterion only holds on tmpfs, where the save
            # is CPU-bound; on the disk-directory fallback trials block
            # in I/O wait and a low ratio is the storage medium, not a
            # noisy neighbor — flagging those would discard every clean
            # trial and mislabel the artifact's audit trail.
            contended = probe < 0.5 * calibration["memcpy_gbps"] or (
                base is not None and cpu_ratio < 0.6
            )
            _log(
                f"timed save {trial}: {trial_dt:.2f}s "
                f"({nbytes / 1e9 / trial_dt:.2f} GB/s), cpu/wall "
                f"{cpu_ratio:.2f}, post-trial memcpy {probe:.1f} GB/s"
                f"{' CONTENDED' if contended else ''}"
            )
            # Trials run BACK-TO-BACK deliberately: on this lazily-backed
            # VM, freed tmpfs pages that sit idle get reclaimed by the
            # host and the next trial refaults them at hypervisor speed
            # (measured 0.1 GB/s on all-fresh pages vs 2.5 GB/s reusing
            # just-freed ones). Sleeping between trials — the previous
            # rounds' approach — invited exactly that reclaim; the tight
            # loop reuses the pages the rmtree just freed.
            if contended and retries < max_retries:
                discarded_trials.append(round(trial_dt, 3))
                retries += 1
                shutil.rmtree(f"{tmp}/snap", ignore_errors=True)
                continue
            save_times.append(trial_dt)
            trial += 1
            if trial < n_trials:
                shutil.rmtree(f"{tmp}/snap", ignore_errors=True)
        del probe_src, probe_dst
        dt = min(save_times)
        p50 = statistics.median(save_times)

        # Telemetry leg: one-two takes with the telemetry bus enabled so
        # (a) the per-take summary JSON lands alongside the BENCH_*
        # artifacts — bench trajectory and traces now come from the SAME
        # instrumentation as production saves — and (b) the enabled-vs-
        # disabled overhead is measured and bounded (<3% best-vs-best;
        # the subsystem's contract is near-zero cost). Runs before the
        # restores so they read the final (telemetry-written) snapshot —
        # bit-identical payloads either way.
        from torchsnapshot_tpu import telemetry as _telemetry

        max_overhead = float(os.environ.get("BENCH_TELEMETRY_MAX_PCT", "3.0"))
        # Relative budget with a small absolute floor: persisting the
        # summary + trace costs a fixed few ms, which dominates any
        # percentage on debug-size invocations (~40 ms saves) while
        # vanishing at real sizes (measured +0.65% at 1 GiB).
        overhead_budget_s = max(max_overhead / 100.0 * dt, 0.05)
        tele_times = []
        _telemetry.set_enabled(True)
        try:
            # Up to 6 trials, stopping early once one lands within the
            # overhead budget: this host's lazily-backed VM throws
            # bimodal trials (documented above for the main leg — the
            # disabled trials show the same 2x spread), so a fixed
            # best-of-2 vs the main leg's best-of-6 would measure
            # sampling luck, not the subsystem.
            for tele_trial in range(6):
                shutil.rmtree(f"{tmp}/snap", ignore_errors=True)
                t0 = time.perf_counter()
                Snapshot.take(f"{tmp}/snap", app_state)
                tele_times.append(time.perf_counter() - t0)
                _log(
                    f"telemetry-enabled save {tele_trial}: "
                    f"{tele_times[-1]:.2f}s "
                    f"({nbytes / 1e9 / tele_times[-1]:.2f} GB/s)"
                )
                if tele_trial >= 1 and (min(tele_times) - dt) < overhead_budget_s:
                    break
        finally:
            _telemetry.set_enabled(False)
        tele_summary = _telemetry.last_summary()
        tele_fleet = _telemetry.last_fleet()
        telemetry_overhead_pct = round((min(tele_times) - dt) / dt * 100, 2)
        tele_out = os.path.join(HERE, "BENCH_TELEMETRY.json")
        with open(tele_out, "w") as f:
            json.dump(
                {
                    "telemetry_trials_s": [round(t, 3) for t in tele_times],
                    "baseline_best_s": round(dt, 3),
                    "overhead_pct": telemetry_overhead_pct,
                    "summary": tele_summary,
                    "fleet": tele_fleet,
                },
                f,
                indent=1,
            )
        _log(
            f"telemetry leg: overhead {telemetry_overhead_pct:+.2f}% "
            f"(best-vs-best); summary written to {tele_out}"
        )
        if calibration["contaminated"]:
            _log("host contaminated: telemetry overhead bound not checked")
        elif (min(tele_times) - dt) >= overhead_budget_s:
            _log(
                f"telemetry-enabled save overhead {telemetry_overhead_pct:.2f}% "
                f">= {max_overhead}% budget (disabled best {dt:.3f}s vs "
                f"enabled best {min(tele_times):.3f}s)"
            )
            failed.append("telemetry_overhead")

        # Forensics leg: the main leg's saves ran with the hang watchdog
        # armed (the shipping default — telemetry/forensics.py). A few
        # watchdog-disabled saves bound its always-on cost the other way
        # around: overhead = main-leg best MINUS disabled best. Same
        # early-stop recipe as the telemetry leg (bimodal host).
        from torchsnapshot_tpu.telemetry import forensics as _forensics

        forensics_budget_s = max(0.01 * dt, 0.05)
        noforensics_times = []
        _forensics.set_enabled(False)
        try:
            for nf_trial in range(6):
                shutil.rmtree(f"{tmp}/snap", ignore_errors=True)
                t0 = time.perf_counter()
                Snapshot.take(f"{tmp}/snap", app_state)
                noforensics_times.append(time.perf_counter() - t0)
                _log(
                    f"forensics-disabled save {nf_trial}: "
                    f"{noforensics_times[-1]:.2f}s "
                    f"({nbytes / 1e9 / noforensics_times[-1]:.2f} GB/s)"
                )
                if nf_trial >= 1 and (dt - min(noforensics_times)) < forensics_budget_s:
                    break
        finally:
            _forensics.set_enabled(True)
        forensics_overhead_pct = round(
            (dt - min(noforensics_times)) / min(noforensics_times) * 100, 2
        )
        _log(
            f"forensics leg: overhead {forensics_overhead_pct:+.2f}% "
            "(enabled main-leg best vs disabled best)"
        )
        if calibration["contaminated"]:
            _log("host contaminated: forensics overhead bound not checked")
        elif (dt - min(noforensics_times)) >= forensics_budget_s:
            _log(
                f"always-on hang-watchdog overhead {forensics_overhead_pct:.2f}% "
                f">= 1% budget (disabled best {min(noforensics_times):.3f}s vs "
                f"enabled best {dt:.3f}s, floor 50 ms)"
            )
            failed.append("forensics_overhead")

        # Timed restores into a device-resident destination (mmap read
        # path + zero-copy device_put).
        dst = {"model": StateDict({k: jnp.zeros_like(v) for k, v in state.items()})}
        restore_times = []
        for trial in range(2):
            t0 = time.perf_counter()
            Snapshot(f"{tmp}/snap").restore(dst)
            restore_times.append(time.perf_counter() - t0)
            _log(
                f"timed restore {trial}: {restore_times[-1]:.2f}s "
                f"({nbytes / 1e9 / restore_times[-1]:.2f} GB/s)"
            )
        import numpy as np

        a = np.asarray(jax.device_get(state["param_0"]))
        b = np.asarray(jax.device_get(dst["model"]["param_0"]))
        if a.tobytes() != b.tobytes():
            _log("restore NOT bit-exact")
            failed.append("restore_bit_exact")
        else:
            _log("restore round-trip verified bit-exact")

        # Native-engine side-by-side (BENCH_r10.json): never vs always
        # at a pinned sub-chunk so both modes stream every entry.
        native_leg = _native_io_leg(tmp, app_state, state, nbytes)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    gbps = (nbytes / 1e9) / dt  # decimal GB/s, same unit as the 18 GB/45 s baseline
    record = {
        "metric": "snapshot_save_throughput_1chip",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / REFERENCE_SAVE_GBPS, 2),
        "p50_gbps": round((nbytes / 1e9) / p50, 3),
        # Raw trial walls: makes best-vs-p50 divergence auditable when a
        # 1-core VM throws an outlier trial (page-cache effects).
        "save_trials_s": [round(t, 3) for t in save_times],
        "restore_gbps": round((nbytes / 1e9) / min(restore_times), 3),
        "device": device,
        "platform": device["platform"],
        "host_calibration": calibration,
        # Enabled-vs-disabled cost of the telemetry subsystem (full
        # per-take summary + trace in BENCH_TELEMETRY.json).
        "telemetry_overhead_pct": telemetry_overhead_pct,
        # Always-on hang-watchdog cost (telemetry/forensics.py): main-leg
        # best (watchdog armed, the default) vs watchdog-disabled best.
        "forensics_overhead_pct": forensics_overhead_pct,
    }
    if discarded_trials:
        # Trials where the post-trial memcpy probe showed the host was
        # contended mid-window (neighbor/hypervisor, not the pipeline).
        record["discarded_contended_trials_s"] = discarded_trials
    if tpu_hw is not None:
        record["tpu_hw"] = tpu_hw
    record["native_io"] = native_leg
    for key, leg in _SUBSYSTEM_LEGS:
        out = _attempt(key, leg, failed)
        if out is not None:
            record[key] = out
    if failed:
        record["failed_legs"] = failed
    print(json.dumps(record), flush=True)
    if failed:
        _log(f"FAILED legs: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
