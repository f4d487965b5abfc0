"""Chaos soak: randomized (seeded) fault schedules against real takes,
plus the disabled-injector overhead leg.

Two legs:

``--soak`` (default; ``--iterations N``, default 40)
    Generates N seeded fault plans over the write-path sites — random
    site, trigger hit, action, and corruption offsets drawn from ONE
    seeded RNG, so a failing iteration replays from its printed plan
    string — runs a real SIGKILL-capable take under each in a
    subprocess, and asserts the crash-consistency invariant every time:
    the run either commits a bit-exact restorable snapshot or leaves
    the previous snapshot restorable and fsck-clean (and a committed
    snapshot that does NOT restore bit-exact must be fsck-dirty).
    This is the open-ended complement to the deterministic tier-1
    matrix (tests/test_chaos_matrix.py): same invariant, unbounded
    schedule space.

``--overhead``
    The acceptance gate for the injector's disabled hot path: times a
    ~2 GiB save with the injector disabled (one module-global flag
    check per site hit — the shipping configuration) against the same
    save with the shim bypassed entirely (site/mutate monkeypatched to
    raw no-ops), and ASSERTS the best-vs-best delta is under 1% (with a
    50 ms absolute floor — bench.py's recipe for this bimodal host).
    Also gates the coordination store's disabled-path overhead: with
    replication off, the failover machinery's per-op bookkeeping
    (idempotency stamps, dedup table) must stay under 1% of the KV
    round-trip time (5 ms floor over 3000 mixed ops).
    And gates the flight recorder's ALWAYS-ON cost (ISSUE 7): the same
    2 GiB save with the recorder enabled (the shipping default — ring
    appends on every phase/fence/progress event) vs hard-disabled
    (``record`` monkeypatched to a raw no-op), best-vs-best < 1% with
    the same 50 ms floor. The recorder records tens of events per save,
    never per-sub-chunk samples, so the gate has enormous margin — it
    exists to keep that invariant pinned.
    And gates the hang watchdog's ALWAYS-ON cost (ISSUE 13): the same
    2 GiB save with the stall-forensics watchdog armed (the shipping
    default — a daemon thread sampling every thread's stack twice a
    second plus duration-ring bookkeeping at every storage guard) vs
    ``forensics.set_enabled(False)``, best-vs-best < 1% with the 50 ms
    floor. Sampling is O(threads) every half second, off the hot path
    entirely.
    And gates the latency-histogram instrument (ISSUE 8): the same
    2 GiB save with the telemetry bus ENABLED and the histograms fully
    wired (per-sub-chunk and per-entry observations recording) vs the
    same enabled bus with ``histogram_observe`` bypassed to a raw
    no-op, best-vs-best < 1% with the 50 ms floor — the marginal cost
    of the distribution metric on top of the already-gated bus must be
    bucket math plus one uncontended lock, nothing more. (The DISABLED
    path needs no new gate: with the bus off every observation site is
    one flag check, the exact shape the injector gate above pins.)
    And gates the native I/O election (ISSUE 9): the 2 GiB save with
    the io_uring engine elected vs ``TORCHSNAPSHOT_TPU_NATIVE_IO=never``
    — electing the native engine may win but can never cost more than
    the 1% budget with the 50 ms floor.
    And gates the delta journal's DISABLED path (ISSUE 14): the same
    2 GiB save through CheckpointManager with journaling off (the
    shipping default — ``_journal_seed`` runs one env check per
    committed save and returns) vs that hook bypassed entirely,
    best-vs-best < 1% with the 50 ms floor. The enabled path's cost is
    measured, not gated, by the bench.py journal leg (BENCH_r12.json).
    And gates the fleet seeding tier's DISABLED path (ISSUE 16): a
    2 GiB RESTORE with ``TORCHSNAPSHOT_TPU_SEED_RESTORE`` unset (the
    shipping default — ``maybe_wrap_restore`` is one env check) vs that
    hook bypassed to a raw passthrough, best-vs-best < 1% with the
    50 ms floor. The enabled path's win is measured by bench.py's
    fleet-distribution leg (BENCH_r13.json).
    And gates the geo-replication tier's DISABLED path (ISSUE 20): a
    2 GiB CheckpointManager save with ``TORCHSNAPSHOT_TPU_GEOREP``
    unset (the shipping default — one ``remote_url`` env check at
    construction, one attribute check per commit) vs that env check
    bypassed to a raw ``None``, best-vs-best < 1% with the 50 ms floor.
    The ARMED shipper's foreground cost is gated separately by
    bench.py's georep leg (BENCH_r17.json).

Usage::

    python benchmarks/chaos_soak.py --soak --iterations 40 --seed 7
    python benchmarks/chaos_soak.py --overhead
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.bench_utils import report  # noqa: E402

# Write-path sites a soak take can hit (read sites are covered by the
# deterministic matrix; the soak's focus is commit-protocol integrity).
_SOAK_SITES = [
    "fs.write", "fs.pwrite", "scheduler.stage", "commit.metadata",
]
_SOAK_ACTIONS = [
    "transient", "permanent", "kill", "corrupt", "truncate:0.5",
    "delay:0.01",
]

_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from torchsnapshot_tpu import Snapshot, StateDict, faultinject

root, plan = sys.argv[1], sys.argv[2]

def state(seed):
    rng = np.random.default_rng(seed)
    return {"model": StateDict(
        **{f"p{i}": rng.standard_normal(400_000).astype(np.float32)
           for i in range(4)}
    )}

if plan:
    faultinject.configure(plan)
Snapshot.take(os.path.join(root, "cur"), state(1))
"""


def _expected_state(seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return {
        f"p{i}": rng.standard_normal(400_000).astype(np.float32)
        for i in range(4)
    }


def _random_plan(rng: random.Random) -> str:
    site = rng.choice(_SOAK_SITES)
    action = rng.choice(_SOAK_ACTIONS)
    hit = rng.randint(1, 8)
    trigger = f"{hit}+" if rng.random() < 0.3 else str(hit)
    return f"{site}@{trigger}={action};seed={rng.randint(0, 2**31)}"


def _run_soak_iteration(root: str, plan: str) -> str:
    """One seeded schedule; returns the outcome label. Raises on any
    invariant violation."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.cli import run_fsck

    cur = os.path.join(root, "cur")
    shutil.rmtree(cur, ignore_errors=True)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, root, plan],
        capture_output=True,
        text=True,
        timeout=300,
    )
    killed = r.returncode == -signal.SIGKILL
    # Aborts must trace back to the plan, not to an unrelated crash.
    # Downstream consequences count: a corrupted/truncated fence write
    # surfaces as StaleCommitError (the commit refusing to trust a fence
    # it can no longer read) — that IS the protocol working.
    fault_signature = any(
        s in r.stderr
        for s in ("Injected", "fault injection", "StaleCommitError")
    )
    if not killed and r.returncode != 0 and not fault_signature:
        raise AssertionError(
            f"plan {plan!r}: child failed outside the injector "
            f"(rc={r.returncode}):\n{r.stderr[-2000:]}"
        )

    committed = os.path.exists(os.path.join(cur, ".snapshot_metadata"))
    expected = _expected_state(1)
    if committed:
        dst = {
            "model": StateDict(
                **{k: np.zeros_like(v) for k, v in expected.items()}
            )
        }
        exact = False
        try:
            Snapshot(cur).restore(dst)
            exact = all(
                np.array_equal(dst["model"][k], expected[k]) for k in expected
            )
        except Exception:  # noqa: BLE001
            exact = False
        if exact:
            return "committed"
        code, _ = run_fsck(cur, echo=lambda *a, **k: None)
        if code == 0:
            raise AssertionError(
                f"plan {plan!r}: committed, not bit-exact restorable, fsck "
                "clean — SILENT CORRUPTION"
            )
        return "committed-detectable"
    # Nothing committed: prev must be restorable + fsck-clean.
    prev = os.path.join(root, "prev")
    prev_expected = _expected_state(0)
    dst = {
        "model": StateDict(
            **{k: np.zeros_like(v) for k, v in prev_expected.items()}
        )
    }
    Snapshot(prev).restore(dst)
    assert all(
        np.array_equal(dst["model"][k], prev_expected[k])
        for k in prev_expected
    ), f"plan {plan!r}: previous snapshot damaged"
    code, _ = run_fsck(prev, echo=lambda *a, **k: None)
    assert code == 0, f"plan {plan!r}: previous snapshot not fsck-clean"
    if os.path.isdir(cur):
        code, _ = run_fsck(cur, echo=lambda *a, **k: None)
        assert code in (1, 2), f"plan {plan!r}: rubble fsck'd clean"
    return "killed" if killed else "aborted"


def soak(iterations: int, seed: int) -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    rng = random.Random(seed)
    root = tempfile.mkdtemp(prefix="chaos_soak_")
    try:
        Snapshot.take(
            os.path.join(root, "prev"),
            {
                "model": StateDict(
                    **{k: v for k, v in _expected_state(0).items()}
                )
            },
        )
        outcomes: dict = {}
        t0 = time.perf_counter()
        for it in range(iterations):
            plan = _random_plan(rng)
            outcome = _run_soak_iteration(root, plan)
            outcomes[outcome] = outcomes.get(outcome, 0) + 1
            print(
                json.dumps({"iter": it, "plan": plan, "outcome": outcome}),
                flush=True,
            )
        report(
            "chaos_soak",
            {
                "iterations": iterations,
                "seed": seed,
                "outcomes": outcomes,
                "wall_s": round(time.perf_counter() - t0, 3),
            },
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)


def overhead(trials: int = 5) -> None:
    """Disabled-injector overhead on a ~2 GiB save: flag-check shim vs
    bypassed shim. Asserts best-vs-best delta < 1% with a 50 ms floor
    (ISSUE 5 acceptance)."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, faultinject

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    try:
        import psutil
    except ImportError:  # pragma: no cover - baked into the image
        psutil = None
    proc = psutil.Process() if psutil is not None else None

    def timed_save() -> tuple:
        """One save's (wall, cpu/wall ratio). The save is CPU-bound on
        tmpfs (memcpy + CRC), so a clean trial's process CPU time ~=
        wall; when the host steals the core or reclaims pages mid-window
        wall inflates while CPU time doesn't — same DURING-trial
        contention detector bench.py uses."""
        root = tempfile.mkdtemp(prefix="chaos_overhead_")
        try:
            cpu0 = proc.cpu_times() if proc is not None else None
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            wall = time.perf_counter() - t0
            if cpu0 is None:
                return wall, 1.0
            cpu1 = proc.cpu_times()
            busy = (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system)
            return wall, busy / max(wall, 1e-9)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def bypassed(fn):
        saved = (faultinject.site, faultinject.mutate)
        faultinject.site = lambda name: None
        faultinject.mutate = lambda name, buf: buf
        try:
            return fn()
        finally:
            faultinject.site, faultinject.mutate = saved

    # One discarded warmup save: the FIRST take of a process pays the
    # staging-pool first-touch faults and page-cache population — ~30x a
    # warm save — which would otherwise land entirely on one leg.
    faultinject.disable()
    timed_save()
    # Paired trials with ALTERNATING leg order: the second save of a
    # back-to-back pair periodically eats a multi-second page-reclaim
    # stall from the first save's 2 GiB rmtree (measured 0.8 s vs 5.8 s
    # on this lazily-backed VM). A fixed order pins that stall to one
    # leg and measures the host, not the shim; alternating cancels the
    # positional bias, and contended pairs (either leg's cpu/wall below
    # the bench.py 0.6 threshold) are discarded and retried, bounded.
    bypass_walls, shim_walls = [], []
    contended = []
    # Best-vs-best with an absolute floor and early stop — bench.py's
    # telemetry-leg recipe for exactly this host: bimodal trials (reclaim
    # stalls, hypervisor steals) only ever INFLATE a wall time, so each
    # leg's min is the honest estimate of its intrinsic cost, and one
    # shim trial landing within budget of the bypass best already proves
    # the flag check is cheap. The 50 ms floor keeps the gate meaningful
    # when a contended host drags both legs around: 236 shim calls per
    # 2 GiB save cost microseconds, not percents.
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            byp, byp_ratio = bypassed(timed_save)
            faultinject.disable()
            shim, shim_ratio = timed_save()
        else:
            faultinject.disable()
            shim, shim_ratio = timed_save()
            byp, byp_ratio = bypassed(timed_save)
        # cpu/wall ratio is the DURING-trial contention detector (the
        # save is CPU-bound on tmpfs); flagged trials still count into
        # the mins — noise can only make the gate pessimistic — but are
        # recorded for audit.
        if proc is not None and min(byp_ratio, shim_ratio) < 0.6:
            contended.append(
                {"bypass_s": round(byp, 3), "shim_s": round(shim, 3)}
            )
        bypass_walls.append(byp)
        shim_walls.append(shim)
        budget_s = max(0.01 * min(bypass_walls), 0.05)
        if pair + 1 >= trials and (
            min(shim_walls) - min(bypass_walls)
        ) < budget_s:
            break
    bypass_best = min(bypass_walls)
    shim_best = min(shim_walls)
    budget_s = max(0.01 * bypass_best, 0.05)
    delta = (shim_best - bypass_best) / bypass_best
    report(
        "chaos_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(bypass_walls),
            "bypass_trials_s": [round(t, 3) for t in bypass_walls],
            "shim_trials_s": [round(t, 3) for t in shim_walls],
            "bypass_best_s": round(bypass_best, 3),
            "shim_best_s": round(shim_best, 3),
            "overhead_pct": round(delta * 100, 3),
            "contended_pairs": contended,
        },
        data_bytes=nbytes,
    )
    assert (shim_best - bypass_best) < budget_s, (
        f"disabled-injector overhead {delta * 100:.2f}% over the 1% budget "
        f"(bypass best {bypass_best:.3f}s vs shim best {shim_best:.3f}s, "
        f"floor 50 ms)"
    )


def flightrec_overhead(trials: int = 5) -> None:
    """Always-on flight-recorder overhead on a ~2 GiB save: the shipping
    default (recorder enabled, ring appends at every phase/fence/
    progress event) vs hard-disabled (``record`` monkeypatched to a raw
    no-op — no flag check, no append). Asserts best-vs-best delta < 1%
    with a 50 ms floor (ISSUE 7 acceptance; same paired/alternating
    recipe as the injector gate above — bimodal-host noise only ever
    inflates a wall time, so each leg's min is its honest cost)."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.telemetry import flightrec

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    def timed_save() -> float:
        root = tempfile.mkdtemp(prefix="flightrec_overhead_")
        try:
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def disabled(fn):
        saved = flightrec.record
        flightrec.record = lambda event, **args: None
        try:
            return fn()
        finally:
            flightrec.record = saved

    flightrec.set_enabled(True)  # the shipping default, made explicit
    timed_save()  # discarded warmup (staging-pool first-touch faults)
    on_walls, off_walls = [], []
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            off = disabled(timed_save)
            on = timed_save()
        else:
            on = timed_save()
            off = disabled(timed_save)
        on_walls.append(on)
        off_walls.append(off)
        budget_s = max(0.01 * min(off_walls), 0.05)
        if pair + 1 >= trials and (min(on_walls) - min(off_walls)) < budget_s:
            break
    off_best, on_best = min(off_walls), min(on_walls)
    budget_s = max(0.01 * off_best, 0.05)
    delta = (on_best - off_best) / off_best
    report(
        "flightrec_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(on_walls),
            "disabled_trials_s": [round(t, 3) for t in off_walls],
            "enabled_trials_s": [round(t, 3) for t in on_walls],
            "disabled_best_s": round(off_best, 3),
            "enabled_best_s": round(on_best, 3),
            "overhead_pct": round(delta * 100, 3),
            "ring_events_total": flightrec.recorded_total(),
        },
        data_bytes=nbytes,
    )
    assert (on_best - off_best) < budget_s, (
        f"always-on flight-recorder overhead {delta * 100:.2f}% over the 1% "
        f"budget (disabled best {off_best:.3f}s vs enabled best "
        f"{on_best:.3f}s, floor 50 ms)"
    )


def forensics_overhead(trials: int = 5) -> None:
    """Always-on hang-watchdog overhead on a ~2 GiB save: the shipping
    default (watchdog armed per op, stack sampler ticking on its own
    daemon thread, storage guards feeding the per-kind duration rings)
    vs hard-disabled (``forensics.set_enabled(False)`` — ``arm``
    returns ``None``, no thread, guards fall through). Asserts
    best-vs-best delta < 1% with a 50 ms floor (ISSUE 13 acceptance;
    same paired/alternating bimodal-host recipe as the legs above)."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.telemetry import forensics

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    def timed_save() -> float:
        root = tempfile.mkdtemp(prefix="forensics_overhead_")
        try:
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def disabled(fn):
        forensics.set_enabled(False)
        try:
            return fn()
        finally:
            forensics.set_enabled(True)

    forensics.set_enabled(True)  # the shipping default, made explicit
    timed_save()  # discarded warmup (staging-pool first-touch faults)
    on_walls, off_walls = [], []
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            off = disabled(timed_save)
            on = timed_save()
        else:
            on = timed_save()
            off = disabled(timed_save)
        on_walls.append(on)
        off_walls.append(off)
        budget_s = max(0.01 * min(off_walls), 0.05)
        if pair + 1 >= trials and (min(on_walls) - min(off_walls)) < budget_s:
            break
    off_best, on_best = min(off_walls), min(on_walls)
    budget_s = max(0.01 * off_best, 0.05)
    delta = (on_best - off_best) / off_best
    report(
        "forensics_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(on_walls),
            "sample_cadence_s": forensics.sample_cadence_s(),
            "disabled_trials_s": [round(t, 3) for t in off_walls],
            "enabled_trials_s": [round(t, 3) for t in on_walls],
            "disabled_best_s": round(off_best, 3),
            "enabled_best_s": round(on_best, 3),
            "overhead_pct": round(delta * 100, 3),
        },
        data_bytes=nbytes,
    )
    assert (on_best - off_best) < budget_s, (
        f"always-on hang-watchdog overhead {delta * 100:.2f}% over the 1% "
        f"budget (disabled best {off_best:.3f}s vs enabled best "
        f"{on_best:.3f}s, floor 50 ms)"
    )


def histogram_overhead(trials: int = 5) -> None:
    """Histogram-instrument overhead on a ~2 GiB save with the telemetry
    bus ENABLED (the configuration where the instruments actually fire):
    fully wired (shipping ``histogram_observe`` — bucket math + one
    uncontended lock per observation, per sub-chunk and per entry) vs
    the same enabled bus with the instrument bypassed to a raw no-op.
    Asserts best-vs-best delta < 1% with a 50 ms floor (ISSUE 8
    acceptance; same paired/alternating bimodal-host recipe as the legs
    above — noise only ever inflates a wall time, so each leg's min is
    its honest cost)."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, telemetry

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    observed = [0]

    def timed_save() -> float:
        root = tempfile.mkdtemp(prefix="hist_overhead_")
        try:
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)
            observed[0] = max(
                observed[0],
                sum(
                    h["count"]
                    for by_key in telemetry.histograms().values()
                    for h in by_key.values()
                ),
            )
            telemetry.reset()  # drop the op's events between trials

    def bypassed(fn):
        # Call sites resolve ``telemetry.histogram_observe`` at call
        # time, so patching the package attribute bypasses every wired
        # instrument (scheduler, retry tier, pg_wrapper) at once.
        saved = telemetry.histogram_observe
        telemetry.histogram_observe = lambda name, seconds, key=None: None
        try:
            return fn()
        finally:
            telemetry.histogram_observe = saved

    telemetry.set_enabled(True)
    try:
        timed_save()  # discarded warmup (staging-pool first-touch faults)
        on_walls, off_walls = [], []
        max_pairs = 2 * trials
        for pair in range(max_pairs):
            if pair % 2 == 0:
                off = bypassed(timed_save)
                on = timed_save()
            else:
                on = timed_save()
                off = bypassed(timed_save)
            on_walls.append(on)
            off_walls.append(off)
            budget_s = max(0.01 * min(off_walls), 0.05)
            if pair + 1 >= trials and (
                min(on_walls) - min(off_walls)
            ) < budget_s:
                break
        n_observations = observed[0]
    finally:
        telemetry.set_enabled(False)
        telemetry.reset()
    off_best, on_best = min(off_walls), min(on_walls)
    budget_s = max(0.01 * off_best, 0.05)
    delta = (on_best - off_best) / off_best
    report(
        "histogram_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(on_walls),
            "bypassed_trials_s": [round(t, 3) for t in off_walls],
            "wired_trials_s": [round(t, 3) for t in on_walls],
            "bypassed_best_s": round(off_best, 3),
            "wired_best_s": round(on_best, 3),
            "overhead_pct": round(delta * 100, 3),
            "observations_last_save": n_observations,
        },
        data_bytes=nbytes,
    )
    assert (on_best - off_best) < budget_s, (
        f"histogram-instrument overhead {delta * 100:.2f}% over the 1% "
        f"budget (bypassed best {off_best:.3f}s vs wired best "
        f"{on_best:.3f}s, floor 50 ms)"
    )


def native_io_overhead(trials: int = 5) -> None:
    """Elected-native vs never-forced on the ~2 GiB save (ISSUE 9
    acceptance): with the io_uring engine elected (the shipping auto
    election on a host where the probe succeeds), the save must never be
    SLOWER than the forced Python path beyond the 1% budget with the
    50 ms floor — the engine may win, but electing it can never cost.
    Same paired/alternating bimodal-host recipe as the legs above.
    Skips (reported, not failed) when the engine probe fails — there is
    no native leg to measure on such a host."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, native_io

    if native_io.engine_kind() is None:
        report("native_io_overhead", {"skipped": "no native engine"})
        return

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    def timed_save(mode: str) -> float:
        os.environ["TORCHSNAPSHOT_TPU_NATIVE_IO"] = mode
        root = tempfile.mkdtemp(prefix="native_overhead_")
        try:
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)

    saved_mode = os.environ.get("TORCHSNAPSHOT_TPU_NATIVE_IO")
    try:
        timed_save("never")  # discarded warmup (pool + page-cache faults)
        native_walls, python_walls = [], []
        max_pairs = 2 * trials
        for pair in range(max_pairs):
            if pair % 2 == 0:
                py = timed_save("never")
                nat = timed_save("always")
            else:
                nat = timed_save("always")
                py = timed_save("never")
            native_walls.append(nat)
            python_walls.append(py)
            budget_s = max(0.01 * min(python_walls), 0.05)
            if pair + 1 >= trials and (
                min(native_walls) - min(python_walls)
            ) < budget_s:
                break
    finally:
        if saved_mode is None:
            os.environ.pop("TORCHSNAPSHOT_TPU_NATIVE_IO", None)
        else:
            os.environ["TORCHSNAPSHOT_TPU_NATIVE_IO"] = saved_mode
    python_best, native_best = min(python_walls), min(native_walls)
    budget_s = max(0.01 * python_best, 0.05)
    delta = (native_best - python_best) / python_best
    report(
        "native_io_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(native_walls),
            "python_trials_s": [round(t, 3) for t in python_walls],
            "native_trials_s": [round(t, 3) for t in native_walls],
            "python_best_s": round(python_best, 3),
            "native_best_s": round(native_best, 3),
            "native_vs_python_pct": round(delta * 100, 3),
        },
        data_bytes=nbytes,
    )
    assert (native_best - python_best) < budget_s, (
        f"elected-native save {delta * 100:.2f}% slower than the Python "
        f"path (python best {python_best:.3f}s vs native best "
        f"{native_best:.3f}s, 1% budget with 50 ms floor)"
    )


def store_overhead(trials: int = 5, ops: int = 3000) -> None:
    """Disabled-path overhead of the store replication tier (ISSUE 6
    acceptance): with replication OFF (no replicas joined — the shipping
    single-host configuration), the client's (client_id, seq) stamp is
    ALREADY skipped by design (it only arms once a failover target is
    known), so the residual per-op cost is the server's log/dedup
    bookkeeping and role/registry checks. Times ``ops`` mixed KV round
    trips as shipped vs with that server bookkeeping bypassed
    (``_MUTATING_OPS`` emptied — read per call), and asserts
    best-vs-best delta < 1% with a 5 ms absolute floor (same
    bimodal-host recipe as the injector gate above: loopback RTT noise
    only ever inflates). The stamped path's cost is intentionally NOT
    gated here — it only runs in replicated deployments, where one
    extra µs per metadata op is noise against real network RTTs."""
    from torchsnapshot_tpu import dist_store

    store = dist_store.TCPStore("127.0.0.1", is_server=True, timeout=30.0)

    def timed() -> float:
        t0 = time.perf_counter()
        for i in range(ops // 4):
            k = f"k{i & 255}"
            store.set(k, b"v")
            store.add("ctr", 1)
            store.check(k)
            store.get(k)
        return time.perf_counter() - t0

    def bypassed(fn):
        saved = dist_store._MUTATING_OPS
        dist_store._MUTATING_OPS = frozenset()
        try:
            return fn()
        finally:
            dist_store._MUTATING_OPS = saved

    try:
        timed()  # warmup: connection buffers, dict growth, allocator
        shipped_walls, bypass_walls = [], []
        for pair in range(trials):
            if pair % 2 == 0:
                byp = bypassed(timed)
                shp = timed()
            else:
                shp = timed()
                byp = bypassed(timed)
            bypass_walls.append(byp)
            shipped_walls.append(shp)
        bypass_best = min(bypass_walls)
        shipped_best = min(shipped_walls)
        budget_s = max(0.01 * bypass_best, 0.005)
        delta = (shipped_best - bypass_best) / bypass_best
        report(
            "store_overhead",
            {
                "ops": ops,
                "pairs": len(bypass_walls),
                "bypass_trials_s": [round(t, 4) for t in bypass_walls],
                "shipped_trials_s": [round(t, 4) for t in shipped_walls],
                "bypass_best_s": round(bypass_best, 4),
                "shipped_best_s": round(shipped_best, 4),
                "overhead_pct": round(delta * 100, 3),
                "per_op_us": round(shipped_best / ops * 1e6, 2),
            },
        )
        assert (shipped_best - bypass_best) < budget_s, (
            f"disabled-path store overhead {delta * 100:.2f}% over the 1% "
            f"budget (bypass best {bypass_best:.4f}s vs shipped best "
            f"{shipped_best:.4f}s, floor 5 ms)"
        )
    finally:
        store.close()


def journal_overhead(trials: int = 5) -> None:
    """Disabled-path overhead of the delta journal (ISSUE 14): a ~2 GiB
    CheckpointManager save with journaling off (the shipping default —
    ``_journal_seed`` runs one ``enabled_by_env`` check after the commit
    and returns) vs that hook bypassed to a raw no-op. Best-vs-best < 1%
    with the 50 ms floor, same bimodal-host recipe as the injector gate.
    The ENABLED path (fingerprinting, appends) is a measured trade-off,
    not a gate — see bench.py's journal leg / BENCH_r12.json."""
    import numpy as np

    from torchsnapshot_tpu import CheckpointManager, StateDict
    from torchsnapshot_tpu import manager as manager_mod

    os.environ.pop("TORCHSNAPSHOT_TPU_JOURNAL", None)

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    try:
        import psutil
    except ImportError:  # pragma: no cover - baked into the image
        psutil = None
    proc = psutil.Process() if psutil is not None else None

    def timed_save() -> tuple:
        root = tempfile.mkdtemp(prefix="journal_overhead_")
        try:
            mgr = CheckpointManager(root, save_interval_steps=1)
            cpu0 = proc.cpu_times() if proc is not None else None
            t0 = time.perf_counter()
            mgr.save(0, state)
            wall = time.perf_counter() - t0
            if cpu0 is None:
                return wall, 1.0
            cpu1 = proc.cpu_times()
            busy = (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system)
            return wall, busy / max(wall, 1e-9)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def bypassed(fn):
        saved = manager_mod.CheckpointManager._journal_seed
        manager_mod.CheckpointManager._journal_seed = (
            lambda self, step, app_state: None
        )
        try:
            return fn()
        finally:
            manager_mod.CheckpointManager._journal_seed = saved

    timed_save()  # warmup: staging-pool first touch, page cache
    bypass_walls, shim_walls = [], []
    contended = []
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            byp, byp_ratio = bypassed(timed_save)
            shim, shim_ratio = timed_save()
        else:
            shim, shim_ratio = timed_save()
            byp, byp_ratio = bypassed(timed_save)
        if proc is not None and min(byp_ratio, shim_ratio) < 0.6:
            contended.append(
                {"bypass_s": round(byp, 3), "shim_s": round(shim, 3)}
            )
        bypass_walls.append(byp)
        shim_walls.append(shim)
        budget_s = max(0.01 * min(bypass_walls), 0.05)
        if pair + 1 >= trials and (
            min(shim_walls) - min(bypass_walls)
        ) < budget_s:
            break
    bypass_best = min(bypass_walls)
    shim_best = min(shim_walls)
    budget_s = max(0.01 * bypass_best, 0.05)
    delta = (shim_best - bypass_best) / bypass_best
    report(
        "journal_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(bypass_walls),
            "bypass_trials_s": [round(t, 3) for t in bypass_walls],
            "shim_trials_s": [round(t, 3) for t in shim_walls],
            "bypass_best_s": round(bypass_best, 3),
            "shim_best_s": round(shim_best, 3),
            "overhead_pct": round(delta * 100, 3),
            "contended_pairs": contended,
        },
        data_bytes=nbytes,
    )
    assert (shim_best - bypass_best) < budget_s, (
        f"disabled-journal overhead {delta * 100:.2f}% over the 1% budget "
        f"(bypass best {bypass_best:.3f}s vs shipping best "
        f"{shim_best:.3f}s, floor 50 ms)"
    )


def distrib_overhead(trials: int = 5) -> None:
    """Disabled-path overhead of the fleet seeding tier (ISSUE 16): a
    ~2 GiB restore with seeding off (the shipping default —
    ``maybe_wrap_restore`` runs one env check and returns the storage
    untouched) vs that hook bypassed to a raw passthrough lambda.
    Best-vs-best < 1% with the 50 ms floor, same bimodal-host recipe as
    the legs above. The ENABLED path (registry lookups, peer fetches) is
    a measured trade-off on throttled storage, not a gate — see
    bench.py's fleet-distribution leg / BENCH_r13.json."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, distrib

    os.environ.pop("TORCHSNAPSHOT_TPU_SEED_RESTORE", None)

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }
    root = tempfile.mkdtemp(prefix="distrib_overhead_")
    snap = os.path.join(root, "s")
    dst = {
        "model": StateDict(
            **{k: np.zeros_like(v) for k, v in state["model"].items()}
        )
    }

    def timed_restore() -> float:
        t0 = time.perf_counter()
        Snapshot(snap).restore(dst)
        return time.perf_counter() - t0

    def bypassed(fn):
        # snapshot.py resolves the hook as a distrib attribute at call
        # time, so patching the module function bypasses the env check
        # entirely — the honest zero-cost floor.
        saved = distrib.maybe_wrap_restore
        distrib.maybe_wrap_restore = (
            lambda storage, path, pg_wrapper=None: (storage, None)
        )
        try:
            return fn()
        finally:
            distrib.maybe_wrap_restore = saved

    try:
        Snapshot.take(snap, state)
        timed_restore()  # discarded warmup (page cache, pool first touch)
        bypass_walls, shim_walls = [], []
        max_pairs = 2 * trials
        for pair in range(max_pairs):
            if pair % 2 == 0:
                byp = bypassed(timed_restore)
                shim = timed_restore()
            else:
                shim = timed_restore()
                byp = bypassed(timed_restore)
            bypass_walls.append(byp)
            shim_walls.append(shim)
            budget_s = max(0.01 * min(bypass_walls), 0.05)
            if pair + 1 >= trials and (
                min(shim_walls) - min(bypass_walls)
            ) < budget_s:
                break
    finally:
        shutil.rmtree(root, ignore_errors=True)
    bypass_best = min(bypass_walls)
    shim_best = min(shim_walls)
    budget_s = max(0.01 * bypass_best, 0.05)
    delta = (shim_best - bypass_best) / bypass_best
    report(
        "distrib_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(bypass_walls),
            "bypass_trials_s": [round(t, 3) for t in bypass_walls],
            "shim_trials_s": [round(t, 3) for t in shim_walls],
            "bypass_best_s": round(bypass_best, 3),
            "shim_best_s": round(shim_best, 3),
            "overhead_pct": round(delta * 100, 3),
        },
        data_bytes=nbytes,
    )
    assert (shim_best - bypass_best) < budget_s, (
        f"disabled-seeding restore overhead {delta * 100:.2f}% over the 1% "
        f"budget (bypass best {bypass_best:.3f}s vs shipping best "
        f"{shim_best:.3f}s, floor 50 ms)"
    )


def tenancy_overhead(trials: int = 5) -> None:
    """Disabled-path overhead of the multi-tenant plane (ISSUE 17): a
    ~2 GiB save with no tenant configured (the shipping default —
    ``tenancy_admission.maybe_arm`` runs one contextvar read + one env
    check and returns None; the scheduler's admission getattr misses)
    vs the arm/disarm hooks bypassed to raw no-op lambdas. Best-vs-best
    < 1% with the 50 ms floor, same bimodal-host recipe as the legs
    above. The ENABLED path (namespacing, quota, pacing) is a measured
    trade-off — see bench.py's tenancy leg / BENCH_r14.json."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, snapshot
    from torchsnapshot_tpu.tenancy import TENANT_ENV_VAR

    os.environ.pop(TENANT_ENV_VAR, None)

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    def timed_save() -> float:
        root = tempfile.mkdtemp(prefix="tenancy_overhead_")
        try:
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(root, "s"), state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def bypassed(fn):
        # snapshot.py resolves the hooks as module attributes at call
        # time, so patching them bypasses even the env check — the
        # honest zero-cost floor.
        saved_arm = snapshot.tenancy_admission.maybe_arm
        saved_disarm = snapshot.tenancy_admission.disarm
        snapshot.tenancy_admission.maybe_arm = (
            lambda op, storage=None, pg_wrapper=None, tenant=None: None
        )
        snapshot.tenancy_admission.disarm = lambda storage, session: None
        try:
            return fn()
        finally:
            snapshot.tenancy_admission.maybe_arm = saved_arm
            snapshot.tenancy_admission.disarm = saved_disarm

    timed_save()  # discarded warmup (staging-pool first-touch faults)
    bypass_walls, shim_walls = [], []
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            byp = bypassed(timed_save)
            shim = timed_save()
        else:
            shim = timed_save()
            byp = bypassed(timed_save)
        bypass_walls.append(byp)
        shim_walls.append(shim)
        budget_s = max(0.01 * min(bypass_walls), 0.05)
        if pair + 1 >= trials and (
            min(shim_walls) - min(bypass_walls)
        ) < budget_s:
            break
    bypass_best = min(bypass_walls)
    shim_best = min(shim_walls)
    budget_s = max(0.01 * bypass_best, 0.05)
    delta = (shim_best - bypass_best) / bypass_best
    report(
        "tenancy_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(bypass_walls),
            "bypass_trials_s": [round(t, 3) for t in bypass_walls],
            "shim_trials_s": [round(t, 3) for t in shim_walls],
            "bypass_best_s": round(bypass_best, 3),
            "shim_best_s": round(shim_best, 3),
            "overhead_pct": round(delta * 100, 3),
        },
        data_bytes=nbytes,
    )
    assert (shim_best - bypass_best) < budget_s, (
        f"disabled-tenancy save overhead {delta * 100:.2f}% over the 1% "
        f"budget (bypass best {bypass_best:.3f}s vs shipping best "
        f"{shim_best:.3f}s, floor 50 ms)"
    )


def georep_overhead(trials: int = 5) -> None:
    """Disabled-path overhead of the geo-replication tier (ISSUE 20): a
    ~2 GiB CheckpointManager save with no remote configured (the
    shipping default — one ``remote_url`` env check at construction,
    one attribute check after the commit) vs that env check bypassed to
    a raw ``None``. Best-vs-best < 1% with the 50 ms floor, same
    bimodal-host recipe as the injector gate. The ENABLED path's cost
    (WAN shipping) is measured, not gated — see bench.py's georep leg /
    BENCH_r17.json and its foreground gate for the armed shipper."""
    import numpy as np

    from torchsnapshot_tpu import CheckpointManager, StateDict
    from torchsnapshot_tpu import georep as georep_mod

    os.environ.pop("TORCHSNAPSHOT_TPU_GEOREP", None)

    nbytes = 2 << 30
    n_arrays = 8
    per = nbytes // n_arrays // 4
    state = {
        "model": StateDict(
            **{
                f"p{i}": np.random.default_rng(i)
                .standard_normal(per)
                .astype(np.float32)
                for i in range(n_arrays)
            }
        )
    }

    def timed_save() -> float:
        root = tempfile.mkdtemp(prefix="georep_overhead_")
        try:
            mgr = CheckpointManager(root, save_interval_steps=1)
            t0 = time.perf_counter()
            mgr.save(0, state)
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def bypassed(fn):
        saved = georep_mod.remote_url
        georep_mod.remote_url = lambda: None
        try:
            return fn()
        finally:
            georep_mod.remote_url = saved

    timed_save()  # warmup: staging-pool first touch, page cache
    bypass_walls, shim_walls = [], []
    max_pairs = 2 * trials
    for pair in range(max_pairs):
        if pair % 2 == 0:
            byp = bypassed(timed_save)
            shim = timed_save()
        else:
            shim = timed_save()
            byp = bypassed(timed_save)
        bypass_walls.append(byp)
        shim_walls.append(shim)
        budget_s = max(0.01 * min(bypass_walls), 0.05)
        if pair + 1 >= trials and (
            min(shim_walls) - min(bypass_walls)
        ) < budget_s:
            break
    bypass_best = min(bypass_walls)
    shim_best = min(shim_walls)
    budget_s = max(0.01 * bypass_best, 0.05)
    delta = (shim_best - bypass_best) / bypass_best
    report(
        "georep_overhead",
        {
            "gib": round(nbytes / (1 << 30), 2),
            "pairs": len(bypass_walls),
            "bypass_trials_s": [round(t, 3) for t in bypass_walls],
            "shim_trials_s": [round(t, 3) for t in shim_walls],
            "bypass_best_s": round(bypass_best, 3),
            "shim_best_s": round(shim_best, 3),
            "overhead_pct": round(delta * 100, 3),
        },
        data_bytes=nbytes,
    )
    assert (shim_best - bypass_best) < budget_s, (
        f"disabled-georep overhead {delta * 100:.2f}% over the 1% budget "
        f"(bypass best {bypass_best:.3f}s vs shipping best "
        f"{shim_best:.3f}s, floor 50 ms)"
    )


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--soak", action="store_true")
    parser.add_argument("--overhead", action="store_true")
    parser.add_argument("--iterations", type=int, default=40)
    parser.add_argument("--seed", type=int, default=0xC4A05)
    parser.add_argument("--trials", type=int, default=5)
    args = parser.parse_args()
    if not (args.soak or args.overhead):
        args.soak = True
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.soak:
        soak(args.iterations, args.seed)
    if args.overhead:
        overhead(args.trials)
        flightrec_overhead(args.trials)
        forensics_overhead(args.trials)
        histogram_overhead(args.trials)
        native_io_overhead(args.trials)
        store_overhead(args.trials)
        journal_overhead(args.trials)
        distrib_overhead(args.trials)
        tenancy_overhead(args.trials)
        georep_overhead(args.trials)


if __name__ == "__main__":
    main()
