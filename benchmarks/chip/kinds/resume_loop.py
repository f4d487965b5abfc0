"""Closed loop of resumes: fresh destination, ``mgr.restore``, first step.

Parameters: ``train_steps`` (steps taken before the snapshot is made),
``warm_steps``, ``warmup_restores``.

``mgr.restore`` may compile programs of its own (its device-side row
assembly depends on read sizes its I/O governor tunes from one operation to
the next); those are counted per restore (``restore_compiles``) and are part
of ``resume_s``, as they are for a user. Everything else in the window is
held to zero compilations.

Set-up trains ``train_steps`` steps, checksums every leaf on the device,
saves synchronously, takes one more step for the loss a resume must
reproduce, and frees the state. Each cycle then builds a destination from
another seed (so every leaf must be overwritten; outside the clocked
part), restores into it, blocks until it is on the device, and completes
one train step on it. The clocked part is what a killed job waits for
once its process is up; process start, backend start and cache loads are
the cell's ``setup_s``.
"""

from __future__ import annotations

from torchsnapshot_tpu import CheckpointManager, StateDict

from lib import loop, model as M
from lib.session import Session, log


def make_snapshot(s: Session, trainer: loop.Trainer, mgr, steps: int) -> dict:
    """Train, save synchronously, and record what a resume must reproduce."""
    for _ in range(steps):
        trainer.step(keep=False)
    sums = trainer.model.checksum(trainer.state)
    t0 = s.now()
    ok = mgr.save(trainer.n, trainer.app_state())
    mgr.wait()  # a no-op for a synchronous manager
    s.record["setup"]["sync_save_s"] = s.now() - t0
    loop.check(s, "set-up snapshot committed", ok and mgr.latest_step() == trainer.n, f"step {trainer.n}")
    want = {"step": trainer.n, "sums": M.fetch_checksums(sums), "same_layout": True}
    want["loss"] = trainer.step(keep=False)["loss"]
    return want


def resume_once(s: Session, mgr, model: M.Model, want: dict, keep: bool = True) -> dict:
    """One resume into a fresh state from another seed; returns its record."""
    jax = s.jax
    with s.note("init"):
        dst_state = jax.block_until_ready(model.init(1))
    rec = {"step": want["step"], "ok": False, "bytes": model.state_bytes,
           "bytes_per_device": M.bytes_on_fullest_device(dst_state)}
    dst = StateDict(**dst_state)
    del dst_state
    rec["t0"] = s.now()
    compiles0 = s.compiles()["requests"]
    try:
        with s.note("restore"):
            restored = mgr.restore({"train": dst}, step=want["step"])
            state = jax.block_until_ready(dict(dst))
        rec["t_restored"] = s.now()
        # What the program compiles inside its own restore is part of what
        # a resume costs, counted per restore; the harness's programs are
        # held to zero compilations in the window apart from these.
        rec["compiles"] = s.compiles()["requests"] - compiles0
        sums = model.checksum(state)  # dispatched before the step donates the state
        trainer = loop.Trainer(s, model, state, n=restored)
        first = trainer.step(keep=False)
        rec["t_first_step"] = s.now()
    except Exception as e:  # noqa: BLE001 - a failed restore is a result
        rec["error"] = repr(e)
        log(f"restore raised {e!r}")
        rec.setdefault("t_restored", s.now())
        rec.setdefault("t_first_step", s.now())
        M.free(dict(dst))
        if keep:
            s.record["restores"].append(rec)
        return rec
    rec["peak_bytes"] = max(s.peak_bytes())
    diff = loop.same_checksums(M.fetch_checksums(sums), want["sums"])
    rtol = 0.0 if want["same_layout"] else loop.RESHARD_LOSS_RTOL
    loss_ok = abs(first["loss"] - want["loss"]) <= rtol * abs(want["loss"])
    counter_ok = int(trainer.state["step"]) == restored + 1
    rec.update(loss=first["loss"], loss_want=want["loss"], loss_rtol=rtol, diff=diff)
    rec["ok"] = restored == want["step"] and not diff and loss_ok and counter_ok
    if not rec["ok"]:
        log(f"resume check failed: {rec}")
    trainer.free()
    if keep:
        s.record["restores"].append(rec)
        s.scrape_op("restore", rec["t0"], rec["t_restored"])
    return rec


def warm_up(s: Session, mgr, model: M.Model, want: dict, n: int) -> float:
    """``n`` unrecorded resumes, so that every program the window uses is
    compiled; returns the length of the last, to plan the first cycle by."""
    t0 = s.now()
    for _ in range(n):
        t0 = s.now()
        warm = resume_once(s, mgr, model, want, keep=False)
        loop.check(s, "warm-up resume", warm["ok"], str(warm.get("error") or warm.get("diff")))
    return s.now() - t0


def resume_phase(s: Session, mgr, model: M.Model, want: dict, budget_s: float, cycle_s: float) -> None:
    """Whole resume cycles for ``budget_s``; the first of them is traced."""
    compiles0 = s.compiles()
    win0 = s.now()
    s.record["setup"].setdefault("window_t0", win0)
    deadline = win0 + budget_s
    cycles = 0
    while cycles == 0 or s.now() + cycle_s <= deadline:  # the first cycle always runs
        c0 = s.now()
        if cycles == 0:
            s.start_trace()
        rec = resume_once(s, mgr, model, want)
        s.stop_trace()
        cycles += 1
        cycle_s = s.now() - c0
        if "error" in rec:
            break
    s.record.setdefault("windows", []).append({"kind": "resume", "t0": win0, "t1": s.now(), "cycles": cycles})
    inside = sum(r.get("compiles", 0) for r in s.record["restores"][-cycles:]) if cycles else 0
    s.record["window_compiles"] = (
        s.record.get("window_compiles", 0) + s.compiles()["requests"] - compiles0["requests"] - inside
    )


def run(s: Session) -> None:
    p = s.cell.traffic
    trainer = loop.build(s, "mesh_resume", p.get("warm_steps", 2))
    root = s.work_dir(2 * trainer.model.state_bytes)
    mgr = CheckpointManager(root, save_interval_steps=1, keep_last=1, async_save=False)
    want = make_snapshot(s, trainer, mgr, p["train_steps"])
    trainer.free()
    cycle_s = warm_up(s, mgr, trainer.model, want, p.get("warmup_restores", 1))
    resume_phase(s, mgr, trainer.model, want, s.seconds, cycle_s)
