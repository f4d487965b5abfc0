"""A job that saves under one layout, is stopped, and resumes under another.

Parameters: those of ``save_cadence``, and ``save_share``: the share of the
window given to saving on the train layout (``mesh_train`` of the
configuration); the rest resumes onto ``mesh_resume`` from the last snapshot
the first part committed. Each part runs at least one whole cycle.

Every program of the harness, for both layouts, is compiled in set-up:
init, batch, step and checksum of the resume layout are built and run once
on a throw-away state before the window opens. No warm-up restore is made
(it would add a restore of the whole state to every run's set-up): what
``mgr.restore`` compiles for itself is counted per restore, as in
``resume_loop``.
"""

from __future__ import annotations

from torchsnapshot_tpu import CheckpointManager

from lib import loop, model as M, spec
from lib.session import Session, log

save_cadence = spec.load_module("kinds", "save_cadence")
resume_loop = spec.load_module("kinds", "resume_loop")


def run(s: Session) -> None:
    p = s.cell.traffic
    trainer = loop.build(s, "mesh_train", p.get("warm_steps", 3))
    dst = loop.build(s, "mesh_resume", warm_steps=0, seed_offset=1)
    s.jax.block_until_ready(dst.model.checksum(dst.state))
    dst.free()
    root = s.work_dir((p["keep_last"] + 2) * trainer.model.state_bytes)
    mgr = CheckpointManager(root, save_interval_steps=1, keep_last=p["keep_last"], async_save=p["async_save"])

    sums = save_cadence.save_phase(s, trainer, mgr, p, s.seconds * p["save_share"])
    used = s.now() - s.record["setup"]["window_t0"]
    # What the resumed job must reproduce: the leaves as saved, and the loss
    # of the step after, taken here on the train layout.
    mgr.wait()
    last = s.record["saves"][-1]["step"]
    want = {"step": last, "sums": M.fetch_checksums(sums[last]), "same_layout": False}
    want["loss"] = trainer.step(keep=False)["loss"]
    trainer.free()
    log(f"save part used {used:.1f} s; resuming from step {last} under {s.cell.config.get('mesh_resume')}")
    resume_loop.resume_phase(s, mgr, dst.model, want, s.seconds - used, cycle_s=0.0)
