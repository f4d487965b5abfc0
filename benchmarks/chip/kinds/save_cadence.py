"""Closed loop: ``save_every_steps`` train steps, ``mgr.save``, repeat.

Parameters (``traffic/<mix>.json``): ``save_every_steps``, ``async_save``,
``keep_last``, ``warmup_saves``, ``warm_steps``, ``trace_steps_before`` /
``trace_steps_after`` (the traced stretch around one save).

The window opens when the last warm-up save returns, with its drain in
flight, and closes when the last save of a whole cycle returns: every
cycle then holds one drain's tail and one blocked save, as in a long job.
After the first, no cycle is started that, by the length of the one before,
would not end inside the window. Commits are stamped by the watcher thread; the loop
never waits for one except inside ``mgr.save`` itself.

After the window, outside every metric, the last committed snapshot is
read back to the host and compared leaf for leaf with checksums taken on
the device when it was saved: acknowledged means readable.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from torchsnapshot_tpu import CheckpointManager, StateDict

from lib import loop, model as M
from lib.session import Session, log


def _save(s: Session, mgr, trainer: loop.Trainer, sums: dict, keep: bool = True) -> dict:
    rec = {"step": trainer.n, "ok": False, "bytes": trainer.model.state_bytes}
    # Dispatched, not awaited: the device sums the state while the save's
    # host side starts; fetched after the window.
    sums[trainer.n] = trainer.model.checksum(trainer.state)
    cpu0 = os.times()
    rec["t_call"] = s.now()
    try:
        with s.note("save"):
            rec["ok"] = bool(mgr.save(trainer.n, trainer.app_state()))
    except Exception as e:  # noqa: BLE001 - a failed save is a result, not a crash
        rec["error"] = repr(e)
        log(f"save at step {trainer.n} raised {e!r}")
    rec["t_ret"] = s.now()
    # CPU seconds of all the process's threads while the loop was blocked,
    # printed beside the timings: a save's kernel time varies threefold on
    # the chip's host, and with it the save (PERF.md).
    cpu1 = os.times()
    rec["host"] = {"user_s": cpu1.user - cpu0.user, "sys_s": cpu1.system - cpu0.system}
    if rec["ok"]:
        s.watcher.expect(s.marker(mgr, trainer.n), rec)
    if keep:
        s.record["saves"].append(rec)
    return rec


def _scrape_committed(s: Session) -> None:
    for rec in s.record["saves"]:
        if "t_commit" in rec and not rec.get("scraped"):
            rec["scraped"] = True
            s.scrape_op("take", rec["t_call"], rec["t_commit"])


def save_phase(s: Session, trainer: loop.Trainer, mgr, p: dict, budget_s: float) -> dict:
    """Warm-up saves, then whole cycles for ``budget_s``. Returns the
    device checksums by saved step; the record holds the rest."""
    every = p["save_every_steps"]
    sums: dict = {}
    t0 = s.now()
    s.record["setup"]["staging_warmup_bytes"] = mgr.warmup(trainer.app_state())
    for i in range(p["warmup_saves"]):
        warm = _save(s, mgr, trainer, sums, keep=False)
        if i + 1 < p["warmup_saves"]:
            mgr.wait()
            trainer.step(keep=False)
    s.record["setup"]["warmup_save_s"] = s.now() - t0
    s.record["warmup_save"] = warm
    cycle_s = every * trainer.warm_step_s + (warm["t_ret"] - warm["t_call"])

    before, after = p.get("trace_steps_before", 6), p.get("trace_steps_after", 4)
    compiles0 = s.compiles()
    win0 = warm["t_ret"]
    s.record["setup"].setdefault("window_t0", win0)
    deadline = win0 + budget_s
    cycles, trace_cycle = 0, 1 if 2 * cycle_s <= budget_s else 0
    while cycles == 0 or s.now() + cycle_s <= deadline:  # the first cycle always runs
        c0 = s.now()
        for k in range(every):
            if cycles == trace_cycle and k == max(0, every - before):
                s.start_trace()
            if s.tracing and cycles > trace_cycle and k >= after and not s.watcher.pending():
                s.stop_trace()
            trainer.step()
            _scrape_committed(s)
        rec = _save(s, mgr, trainer, sums)
        cycles += 1
        cycle_s = s.now() - c0
        if not rec["ok"]:
            break
    win1 = s.now()
    committed = s.watcher.drain(timeout_s=10 * cycle_s + 60)
    s.stop_trace()
    _scrape_committed(s)
    compiles1 = s.compiles()
    s.record.setdefault("windows", []).append({"kind": "save", "t0": win0, "t1": win1, "cycles": cycles})
    s.record["window_compiles"] = s.record.get("window_compiles", 0) + compiles1["requests"] - compiles0["requests"]
    loop.check(s, "every save committed", committed and all(r["ok"] for r in s.record["saves"]),
               f"{sum('t_commit' in r for r in s.record['saves'])} of {len(s.record['saves'])}")
    return sums


def readback(s: Session, trainer: loop.Trainer, mgr, sums: dict) -> None:
    """The last committed snapshot, read to the host and compared."""
    t0 = s.now()
    mgr.wait()
    last = mgr.latest_step()
    want_step = s.record["saves"][-1]["step"] if s.record["saves"] else None
    if not loop.check(s, "last save is the latest committed step", last == want_step, f"{last} vs {want_step}"):
        return
    want = M.fetch_checksums(sums[last])
    model = trainer.model
    trainer.free()
    dst = StateDict(**s.jax.tree_util.tree_map(lambda x: np.zeros(x.shape, x.dtype), model.shapes))
    restored = mgr.restore({"train": dst})
    t1 = s.now()
    leaves = M.flat_paths(dict(dst))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:  # numpy drops the GIL
        got = dict(zip(leaves, pool.map(lambda v: M.host_checksum(np.asarray(v)), leaves.values())))
    diff = loop.same_checksums(got, want)
    loop.check(s, "read-back equals the state at its save", restored == last and not diff, diff)
    s.record["readback_s"] = s.now() - t0
    log(f"read-back of step {last}: {len(got)} leaves, restore {t1 - t0:.2f} s + checksums "
        f"{s.now() - t1:.2f} s, {diff or 'equal'}")


def run(s: Session) -> None:
    p = s.cell.traffic
    trainer = loop.build(s, "mesh_train", p.get("warm_steps", 3))
    root = s.work_dir((p["keep_last"] + 2) * trainer.model.state_bytes)
    mgr = CheckpointManager(root, save_interval_steps=1, keep_last=p["keep_last"], async_save=p["async_save"])
    sums = save_phase(s, trainer, mgr, p, s.seconds)
    readback(s, trainer, mgr, sums)
