"""The train loop every kind shares: build, warm up, step, check a restore."""

from __future__ import annotations

from typing import Any, Dict, Optional

from torchsnapshot_tpu import StateDict

from . import model as M
from .session import Session, log

# Resumed under another layout the matmul contractions split differently
# over 'model', so f32 accumulation order and bf16 rounding points differ
# and a mean over thousands of tokens of values near ln(vocab) moves in the
# 4th digit. A wrong restore (one zeroed leaf) moves the loss by O(1).
RESHARD_LOSS_RTOL = 5e-3


class Trainer:
    """A state, the step number it is at, and the clocked step."""

    def __init__(self, s: Session, model: M.Model, state, n: int = 0) -> None:
        self.s, self.model, self.state, self.n = s, model, state, n

    def step(self, keep: bool = True) -> Dict[str, Any]:
        """One donated step, clocked around a blocking fetch of the loss."""
        s = self.s
        self.n += 1
        t0 = s.now()
        with s.note("step"):
            self.state, loss = self.model.step(self.state, self.model.batch(self.n))
            loss = float(loss)
        rec = {"n": self.n, "t0": t0, "dur": s.now() - t0, "loss": loss}
        if keep:
            s.record["steps"].append(rec)
        return rec

    def app_state(self):
        return {"train": StateDict(**self.state)}

    def free(self) -> None:
        M.free(self.state)
        self.state = None


def build(s: Session, mesh_key: Optional[str], warm_steps: int, seed_offset: int = 0) -> Trainer:
    """Model under the layout ``cfg[mesh_key]`` (None: one device), state
    from the seed, step compiled, ``warm_steps`` steps taken. Set-up."""
    jax = s.jax
    cfg = s.cell.config
    axes = cfg.get(mesh_key) if mesh_key and s.cell.chips > 1 else None
    model = M.Model(cfg, s.seed, s.devices, axes)
    setup = s.record["setup"].setdefault(mesh_key or "one_device", {})
    t0 = s.now()
    with s.note("init"):
        state = jax.block_until_ready(model.init(seed_offset))
    setup["init_s"] = s.now() - t0
    setup.update(model.compile_step(state, count_kernels=s.trace))
    setup.update(
        state_bytes=model.state_bytes, leaves=len(model.leaf_bytes),
        largest_leaf_bytes=max(model.leaf_bytes.values()), n_params=model.n_params,
        n_active_params=model.n_active, attention=model.attention(),
        mosaic_calls=model.mosaic_calls, step_memory=dict(model.step_memory),
        step_planned_bytes=model.step_planned_bytes,
    )
    s.record.setdefault("facts", {
        "state_bytes": model.state_bytes, "n_active_params": model.n_active,
        "tokens_per_step": model.tokens_per_step(), "chips": s.cell.chips,
        "device_kind": s.device_info["kind"], "dry_run": s.dry_run,
    })
    if not s.dry_run:
        flash = setup["attention"].startswith("flash") and (model.mosaic_calls >= 3 or not s.trace)
        check(s, "flash kernel in the step", flash, f"{model.mosaic_calls} Mosaic calls, attention {setup['attention']}")
    if s.trace and model.mesh is None and cfg["program"].get("reference"):
        setup["reference"] = reference_check(s, model, state)
    trainer = Trainer(s, model, state)
    warm = [trainer.step(keep=False)["dur"] for _ in range(warm_steps)]
    setup["warm_step_s"] = warm
    trainer.warm_step_s = sorted(warm)[len(warm) // 2] if warm else None
    log(f"{mesh_key or 'one device'}: {model.n_params / 1e6:.1f} M params, state {model.state_bytes / 1e9:.2f} GB "
        f"in {len(model.leaf_bytes)} leaves, step plans {model.step_planned_bytes / 1e9:.2f} GB/device, "
        f"init {setup['init_s']:.2f} s, lower {setup['lower_s']:.2f} s, compile {setup['compile_s']:.2f} s, "
        f"warm steps {[round(w, 3) for w in warm]}")
    return trainer


# The program computes in bfloat16 (8 bits of mantissa) through every
# matmul of every layer, the reference in float32 at "highest" precision;
# rounding of 2^-9 per operand accumulates over a few dozen matmuls to
# about 1e-2 of the logits' largest magnitude. fp8 compute (2^-4) or a
# wrong mask, scale or layer order is off by far more. In an expert layer
# a near-tie in the router can go the other way in bfloat16, which changes
# that token's output wholesale: a few positions may exceed the bound
# there, so the bulk (median) is held to it everywhere and the 99th
# percentile only where no router is.
REFERENCE_RTOL = 3e-2


def reference_check(s: Session, model: M.Model, state) -> Dict[str, Any]:
    """Logits of the program's forward pass against the plain reference,
    on two sequences of step 1's batch and the weights of the seed. Set-up, traced
    runs only (two more programs to compile and a float32 forward pass)."""
    import jax.numpy as jnp
    import numpy as np

    from . import spec

    jax, cfg = s.jax, s.cell.config
    ref = spec.load_module("reference", cfg["program"]["reference"])
    ref_args = model.family.reference_args(cfg)
    # On what the reference can hold: its float32 logits for two sequences
    # of 2048 over a 50304 vocabulary are 0.8 GB, beside the train state.
    tokens = model.batch(1)["tokens"][:2]
    t0 = s.now()

    @jax.jit
    def errors(params, tokens):
        got = model.family.forward(cfg, None)(params, tokens).astype(jnp.float32)
        want = ref.forward(params, tokens, **ref_args)
        return jnp.max(jnp.abs(got - want), axis=-1) / jnp.max(jnp.abs(want))

    err = np.asarray(errors(state["params"], tokens)).reshape(-1)
    out = {"positions": int(err.size), "rel_err_median": float(np.median(err)),
           "rel_err_p99": float(np.quantile(err, 0.99)), "rel_err_max": float(err.max()),
           "rtol": REFERENCE_RTOL, "seconds": s.now() - t0}
    held = out["rel_err_median"] if "num_experts" in cfg else out["rel_err_p99"]
    check(s, "forward pass agrees with the plain reference", held <= REFERENCE_RTOL and np.isfinite(err).all(), str(out))
    log(f"reference: {out}")
    return out


def check(s: Session, what: str, ok: bool, detail: str = "") -> bool:
    s.record["checks"].append({"what": what, "ok": bool(ok), "detail": detail})
    if not ok:
        log(f"CHECK FAILED: {what}: {detail}")
    return bool(ok)


def same_checksums(got: Dict[str, tuple], want: Dict[str, tuple]) -> str:
    """'' when equal leaf for leaf, else what differs."""
    if set(got) != set(want):
        return f"leaf sets differ: {sorted(set(got) ^ set(want))[:4]}"
    bad = sorted(k for k in want if got[k] != want[k])
    return f"{len(bad)} of {len(want)} leaves differ, first {bad[:3]}" if bad else ""
