"""The quickest proof that the system still starts on the chip.

Drives the product's main path once through the entry points a user
calls: a trainer takes steps, saves asynchronously at a cadence, is killed
with a save still draining, and a fresh process resumes bit-exact with the
loss continuing. The model is the repo's own transformer at the widest
configuration it supports (d_model 2048, 16 heads of 128, d_ff 8192, vocab
32768, S 2048; depth 8), weights random from a seed.

    python chip_smoke.py                  # on a machine with a TPU
    python chip_smoke.py --cpu-dry-run 1  # tiny config, CPU, tier-1 test

Exits non-zero, and prints no result line, when JAX finds no TPU, when any
phase fails or overruns its deadline, or when a Pallas kernel ran in
interpret mode. On success stdout is two lines of JSON: the report (also
written to ``<out>/report.json``), then, last, the verdict with exactly
these keys, the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The report holds observations with the device named, not benchmark metrics.

The parent imports neither jax nor the package: a chip belongs to one
process at a time, so each phase is a child that holds it alone, run in its
own process group under a deadline.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Widths are the repo's widest; depth is cut to 8 layers and, on one chip,
# the batch to 2. 469.8 M parameters, 5.6 GB of saved state (f32 params +
# adam mu + nu). The layer scan keeps every activation for the backward
# pass (no remat), so at batch 4 the step needs 18.2 GB of the v5e's
# 15.75 GB usable HBM and does not compile; at batch 2 it plans 14.9 GB
# (chip run, PR 21). The mesh phases split the same global batch of 4
# over four chips.
FULL = {
    "model": dict(
        vocab_size=32768, d_model=2048, n_heads=16, n_layers=8, d_ff=8192,
        max_seq_len=2048,
    ),
    "batch": 2,
    "mesh_batch": 4,
    "cut": "depth 8 layers; one-chip batch 2 (batch 4 needs 18.2 GB of 15.75 GB HBM)",
    "seq": 2048,
    # The step's own per-device attention shape, one chip and 2x2 mesh alike.
    "kernel_shape": (2, 2048, 16, 128),
}
# CPU dry run: control flow only. Big enough (63 MB of state) that the
# torn save is still draining when the trainer kills itself.
DRY = {
    "model": dict(
        vocab_size=8192, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq_len=128,
    ),
    "batch": 4,
    "mesh_batch": 4,
    "cut": "dry run: every size cut, control flow only",
    "seq": 128,
    "kernel_shape": (2, 128, 4, 64),
}

SAVE_EVERY = 2
TRAIN_STEPS = 6  # saves at 2, 4, 6; killed while 6 drains; resume from 4
RESUME_FROM = 4

# Seconds. The whole run must end inside the driver's 1200 s, compilation
# included, so each phase also never gets more than what is left of TOTAL.
TOTAL_DEADLINE_S = 1140
PHASE_DEADLINE_S = {"kernels": 360, "train": 420, "resume": 300}
# The trainer ends by killing itself; every other phase exits 0.
PHASE_RETURNCODE = {"kernels": 0, "train": -signal.SIGKILL, "resume": 0}

MESH_TRAIN = {"data": 2, "model": 2}
MESH_RESUME = {"data": 1, "model": 4}

# Resumed losses on the SAME layout run the same program on the same
# bytes: equality is required. Under another layout the matmul
# contractions split differently over 'model' (partial sums then
# all-reduce), so f32 accumulation order and bf16 rounding points differ;
# the loss is a mean over 8192 tokens of values near ln(32768) = 10.4 and
# moves in the 4th digit. 5e-3 relative is far below what a wrong restore
# does (a single zeroed leaf moves the loss by O(1)).
RESHARD_LOSS_RTOL = 5e-3

# Flash kernel vs dense reference at "highest" matmul precision, both on
# the same bf16 inputs: the kernel's output is rounded to bf16 (2^-9
# relative) and its softmax statistics accumulate block-wise in f32, the
# reference's in one pass. 2e-2 of the tensor's largest magnitude bounds
# both with margin; a wrong mask, scale or block offset is off by O(1).
KERNEL_RTOL = 2e-2

EXIT_NO_ACCELERATOR = 2


# --------------------------------------------------------------- parent


def _log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _kill_group(pgid: int) -> None:
    try:
        os.killpg(pgid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _run_phase(name: str, argv: list, env: dict, out: str, deadline_s: float) -> dict:
    """Run one child in its own process group; a timeout kills the group.
    Returns the child's result file merged with how the process ended."""
    result_path = os.path.join(out, f"{name}.json")
    if os.path.exists(result_path):
        os.unlink(result_path)
    log_path = os.path.join(out, f"{name}.log")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", name, *argv],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
            start_new_session=True,
        )
        timed_out = False
        try:
            proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            timed_out = True
        # Also after a clean exit: nothing the child started may outlive it.
        _kill_group(proc.pid)
        proc.wait()
    result = {}
    if os.path.exists(result_path):
        with open(result_path) as f:
            result = json.load(f)
    result.update(
        returncode=proc.returncode, timed_out=timed_out,
        wall_s=round(time.monotonic() - t0, 2),
    )
    if timed_out:
        result["ok"] = False
        result["error"] = f"overran its {deadline_s:.0f} s deadline; group killed"
    elif "ok" not in result:
        result["ok"] = False
        result["error"] = f"exited {proc.returncode} without a result"
    if not result["ok"]:
        with open(log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(log_path) - 6000))
            tail = f.read().decode("utf-8", "replace")
        _log(f"phase {name} FAILED: {result.get('error')}\n--- {log_path} (tail)\n{tail}")
    return result


def _pick_work_dir(state_bytes: int) -> str:
    """Snapshot roots live outside the checkout (two retained 5.6 GB
    snapshots inside the tree would be copied back and break the next
    check): /dev/shm when it has room for two retained snapshots plus
    the one in flight, else the default temporary directory."""
    base = None
    if os.path.isdir("/dev/shm") and shutil.disk_usage("/dev/shm").free > 4 * state_bytes:
        base = "/dev/shm"
    return tempfile.mkdtemp(prefix="chip_smoke_", dir=base)


def _state_bytes(model: dict) -> int:
    """TransformerConfig.param_count's formula, repeated because the parent
    may not import the package; only sizes the snapshot root."""
    d, f, layers, v = model["d_model"], model["d_ff"], model["n_layers"], model["vocab_size"]
    params = v * d + layers * (4 * d * d + 2 * d * f + 2 * d) + d
    return 3 * 4 * params  # f32 params + mu + nu


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run", type=int, metavar="N_DEVICES", default=0,
        help="tiny config on N virtual CPU devices (1, or 4 to include the "
        "mesh phases); labelled platform cpu, never a device measurement",
    )
    ap.add_argument("--out", default=DEFAULT_OUT, help="report and phase logs")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    ap.add_argument("--mesh", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return _child_main(args)

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    env = dict(os.environ)
    child_argv = ["--out", out]
    plan = DRY if args.cpu_dry_run else FULL
    if args.cpu_dry_run:
        # Explicit argument only; never inferred from the environment.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.cpu_dry_run}"
        child_argv += ["--cpu-dry-run", str(args.cpu_dry_run)]
    work = _pick_work_dir(_state_bytes(plan["model"]))
    child_argv += ["--work", work]

    t_start = time.monotonic()
    phases: dict = {}
    report = {"ok": False, "device": None, "dry_run": bool(args.cpu_dry_run)}

    def run(name: str, kind: str, extra=()) -> bool:
        left = TOTAL_DEADLINE_S - (time.monotonic() - t_start)
        deadline = min(PHASE_DEADLINE_S[kind], left)
        if deadline <= 0:
            phases[name] = {"ok": False, "error": "no time left before the total deadline"}
            _log(f"phase {name} not started: total deadline spent")
            return False
        _log(f"phase {name} (deadline {deadline:.0f} s) ...")
        result = phases[name] = _run_phase(name, child_argv + list(extra), env, out, deadline)
        if result["ok"] and result["returncode"] != PHASE_RETURNCODE[kind]:
            result["ok"] = False
            result["error"] = (
                f"ended with {result['returncode']}, expected {PHASE_RETURNCODE[kind]}"
            )
            _log(f"phase {name} FAILED: {result['error']}")
        _log(f"phase {name}: ok={result['ok']} wall={result['wall_s']} s")
        return result["ok"]

    try:
        ok = run("kernels", "kernels")
        first = phases["kernels"]
        if first.get("error") == "no_accelerator":
            _log(f"no TPU: jax.default_backend() is {first.get('platform')!r}")
            return EXIT_NO_ACCELERATOR
        report["device"] = first.get("device")
        ok = ok and run("train", "train") and run("resume", "resume")
        if ok and (first.get("device") or {}).get("count", 0) >= 4:
            ok = run(
                "train_mesh", "train", ["--mesh", json.dumps(MESH_TRAIN)]
            ) and run("resume_mesh", "resume", ["--mesh", json.dumps(MESH_RESUME)])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    report.update(
        ok=ok, config=plan["model"], cut=plan["cut"], seq=plan["seq"],
        snapshot_root=os.path.dirname(work),
        wall_s=round(time.monotonic() - t_start, 1), phases=phases,
    )
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if not ok:
        _log(f"FAILED; report in {out}/report.json")
        return 1
    print(json.dumps(report))
    # The driver reads the last line and accepts these keys and no others.
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


# ---------------------------------------------------------------- child
#
# Everything below runs in a child process that owns the chip.


class _Child:
    """What every phase needs: the backend check, the compile cache and
    its counters, the result file."""

    def __init__(self, args) -> None:
        self.args = args
        self.dry = bool(args.cpu_dry_run)
        self.plan = DRY if self.dry else FULL
        self.batch = self.plan["mesh_batch" if args.mesh else "batch"]
        self.result: dict = {"ok": False}
        self.t0 = time.monotonic()

        import jax

        self.jax = jax
        platform = jax.default_backend()
        self.result["platform"] = platform
        if platform != "tpu" and not self.dry:
            self.result["error"] = "no_accelerator"
            self.write()
            raise SystemExit(EXIT_NO_ACCELERATOR)
        devices = jax.devices()
        self.result["device"] = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }

        from torchsnapshot_tpu import compile_cache

        self._cc = compile_cache
        self.cache_dir = compile_cache.enable_compilation_cache()
        self._cache_events = {"hits": 0, "misses": 0}

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self._cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._cache_events["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self.result["compile_cache"] = {
            "dir": self.cache_dir,
            "entries_before": compile_cache.cache_entry_count(self.cache_dir),
        }

    def cache_counts(self) -> dict:
        return dict(self._cache_events)

    def peak_hbm(self):
        """Per-device peak bytes, or None where the backend reports none."""
        out = []
        for d in self.jax.devices():
            stats = d.memory_stats()
            if not stats:
                return None
            out.append(
                {"id": d.id, "peak_bytes": stats.get("peak_bytes_in_use"),
                 "limit_bytes": stats.get("bytes_limit")}
            )
        return out

    def write(self) -> None:
        cc = self.result.get("compile_cache")
        if cc is not None:
            cc["entries_after"] = self._cc.cache_entry_count(self.cache_dir)
            cc.update(self._cache_events)
        self.result["phase_s"] = round(time.monotonic() - self.t0, 2)
        _write_json(os.path.join(self.args.out, f"{self.args.phase}.json"), self.result)

    def finish(self, failures: list) -> None:
        self.result.update(failures=failures, ok=not failures, peak_hbm=self.peak_hbm())
        self.write()


def _child_main(args) -> int:
    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stdout)
    child = _Child(args)
    fn = {
        "kernels": _phase_kernels,
        "train": _phase_train, "train_mesh": _phase_train,
        "resume": _phase_resume, "resume_mesh": _phase_resume,
    }[args.phase]
    failures = fn(child)
    child.finish(failures)
    return 0 if not failures else 1


def _rel_err(a, b) -> float:
    """max|a-b| over max|b|, in f32 on the host."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def _mosaic_calls(lowered) -> int:
    """Mosaic kernels in a lowered program. Interpret mode lowers a
    pallas_call to plain HLO loops instead, so 0 here means interpreted
    (or no Pallas kernel at all)."""
    return lowered.as_text().count("tpu_custom_call")


def _phase_kernels(child: _Child) -> list:
    """Flash forward and both backward kernels against the dense reference
    at the step's own shape, bare and under shard_map on a ('seq',) ring
    over every device (ring-flash, zigzag-flash, ulysses with the flash
    inner). Outside any timing."""
    jax = child.jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np
    from jax.sharding import Mesh

    from torchsnapshot_tpu import _native, native_io, ops

    failures: list = []
    res = child.result
    res["versions"] = {
        "python": sys.version.split()[0], "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": _libtpu_version(),
    }
    res["native"] = _native.build_info()
    res["native"]["io_engine"] = native_io.engine_kind()
    if not res["native"]["built_from_source"] and shutil.which("g++"):
        failures.append("native extension did not build on a machine that has g++")

    B, S, H, D = child.plan["kernel_shape"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in ks[:3])
    w = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)  # cotangent

    def fwd_bwd(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

        return jax.jit(
            lambda q, k, v: (attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
        )

    with jax.default_matmul_precision("highest"):
        ref_o, ref_g = fwd_bwd(lambda q, k, v: ops.dense_attention(q, k, v, causal=True))(q, k, v)
    ref = [np.asarray(x, np.float32) for x in (ref_o, *ref_g)]

    ring = Mesh(np.array(jax.devices()), ("seq",))
    variants = {
        "flash": lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
        "ring_flash": lambda q, k, v: ops.ring_flash_attention_sharded(q, k, v, ring),
        "zigzag_flash": lambda q, k, v: ops.zigzag_ring_flash_attention_sharded(q, k, v, ring),
        "ulysses_flash": lambda q, k, v: ops.ulysses_attention_sharded(
            q, k, v, ring, causal=True, inner="flash"
        ),
    }
    res["kernels"] = {"shape": [B, S, H, D], "rtol": KERNEL_RTOL, "ring_size": len(jax.devices())}
    for name, attn in variants.items():
        fn = fwd_bwd(attn)
        calls = _mosaic_calls(fn.lower(q, k, v))
        o, g = fn(q, k, v)
        errs = [_rel_err(x, r) for x, r in zip((o, *g), ref)]
        res["kernels"][name] = {
            "mosaic_calls": calls, "interpret": calls == 0,
            "rel_err": dict(zip(("o", "dq", "dk", "dv"), (round(e, 5) for e in errs))),
        }
        if not all(np.isfinite(errs)) or max(errs) > KERNEL_RTOL:
            failures.append(f"kernel {name}: rel err {errs} above {KERNEL_RTOL}")
        if calls == 0 and not child.dry:
            failures.append(f"kernel {name} ran in interpret mode on the chip")
    return failures


def _libtpu_version():
    from importlib.metadata import PackageNotFoundError, version

    try:
        return version("libtpu")
    except PackageNotFoundError:
        return None


def _setup_model(child: _Child, seed: int):
    """Config, mesh, optimizer, state and the donated, AOT-compiled train
    step — shared by the train and resume phases so both compile the same
    program."""
    jax = child.jax
    from torchsnapshot_tpu.models import transformer as T
    from torchsnapshot_tpu.parallel import make_mesh

    cfg = T.TransformerConfig(attn_impl="auto", **child.plan["model"])
    mesh = None
    if child.args.mesh:
        mesh = make_mesh(json.loads(child.args.mesh))
    tx = T.make_optimizer()
    t0 = time.monotonic()
    state = T.init_state(jax.random.PRNGKey(seed), cfg, tx, mesh=mesh)
    jax.block_until_ready(state)
    B, S = child.batch, child.plan["seq"]
    child.result.update(
        config=child.plan["model"], param_count=cfg.param_count, batch=B, seq=S,
        mesh=json.loads(child.args.mesh) if child.args.mesh else None,
        state_bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(state)),
        init_s=round(time.monotonic() - t0, 2),
        attention=T.select_attention(cfg, mesh, B, S),
    )
    return T, cfg, mesh, tx, state


def _batch_for(child: _Child, mesh, n: int):
    """The batch that produces step ``n`` — a function of n alone, so the
    resumed process feeds exactly what the killed one did."""
    jax = child.jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, S = child.batch, child.plan["seq"]
    tokens = jax.random.randint(
        jax.random.PRNGKey(1000 + n), (B, S + 1), 0, child.plan["model"]["vocab_size"]
    )
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    if mesh is not None:
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    return batch


def _compile_step(child: _Child, T, cfg, tx, mesh, state, batch, failures: list):
    """Lower and compile the donated train step; check the Mosaic kernel
    is really in it."""
    jax = child.jax
    step = jax.jit(T.make_train_step(cfg, tx, mesh=mesh), donate_argnums=0)
    t0 = time.monotonic()
    lowered = step.lower(state, batch)
    lower_s = time.monotonic() - t0
    calls = _mosaic_calls(lowered)
    before = child.cache_counts()
    t0 = time.monotonic()
    compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    after = child.cache_counts()
    mem = compiled.memory_analysis()
    child.result["step_compile"] = {
        "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
        "mosaic_calls": calls,
        "cache_hit": after["hits"] > before["hits"],
        # Per device, as the compiler planned it (donated state aliases
        # its output, so the step's footprint is args + temps).
        "program_bytes": {
            k: getattr(mem, f"{k}_size_in_bytes", None)
            for k in ("argument", "output", "alias", "temp")
        },
    }
    flash = child.result["attention"].startswith("flash")
    if not child.dry:
        if not flash:
            failures.append(f"attention selected {child.result['attention']!r}, not the flash kernel")
        # forward, dq and dkv kernels, traced once in the layer scan
        if calls < 3:
            failures.append(f"compiled train step holds {calls} Mosaic kernels, expected >= 3")
    return compiled


def _leaf_hashes(jax, state) -> dict:
    """sha256 of every leaf's host bytes, by tree path.

    Fetched through a device-side copy: fetching the leaf itself would
    leave its host copy cached on the array, and the save that follows
    would stage from that cache instead of moving the bytes off the chip."""
    import hashlib

    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        host = np.ascontiguousarray(np.asarray(jax.numpy.copy(leaf)))
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            host.reshape(-1).view(np.uint8)
        ).hexdigest()
    return out


def _sidecar_path(child: _Child) -> str:
    return os.path.join(child.args.work, f"{child.args.phase.replace('resume', 'train')}.sidecar.json")


def _snapshot_root(child: _Child) -> str:
    return os.path.join(child.args.work, child.args.phase.replace("resume", "train") + "_ckpt")


def _new_manager(child: _Child):
    from torchsnapshot_tpu import CheckpointManager

    return CheckpointManager(
        _snapshot_root(child), save_interval_steps=SAVE_EVERY, keep_last=2, async_save=True
    )


def _phase_train(child: _Child) -> list:
    jax = child.jax
    from torchsnapshot_tpu import StateDict

    failures: list = []
    res = child.result
    T, cfg, mesh, tx, state = _setup_model(child, seed=0)
    step = _compile_step(child, T, cfg, tx, mesh, state, _batch_for(child, mesh, 1), failures)
    if failures:
        return failures

    mgr = _new_manager(child)
    res["warmup_bytes"] = mgr.warmup({"train": StateDict(**state)})
    losses, step_s, saves = {}, {}, {}
    hashes = None
    for n in range(1, TRAIN_STEPS + 1):
        t0 = time.monotonic()
        state, loss = step(state, _batch_for(child, mesh, n))
        losses[n] = float(loss)  # blocks until the step is done
        step_s[n] = round(time.monotonic() - t0, 3)
        if n == 1:
            res["peak_hbm_after_first_step"] = child.peak_hbm()
        if n % SAVE_EVERY:
            continue
        rec = saves[n] = {}
        t0 = time.monotonic()
        mgr.wait()  # what is left of the previous save's drain
        rec["drain_before_s"] = round(time.monotonic() - t0, 3)
        if n == RESUME_FROM:
            # Hashed BEFORE the save, so that the very next step donates
            # these arrays while the save is still draining: the snapshot
            # must already own every byte it will write.
            hashes = _leaf_hashes(jax, state)
        if n == TRAIN_STEPS:
            # Everything the resume needs is on disk BEFORE the last save
            # starts; the result goes out the moment it returns.
            _write_json(_sidecar_path(child), {
                "step": RESUME_FROM, "leaf_sha256": hashes,
                "losses": {str(m): losses[m] for m in range(RESUME_FROM + 1, n + 1)},
            })
        t0 = time.monotonic()
        started = mgr.save(n, {"train": StateDict(**state)})
        rec["blocked_s"] = round(time.monotonic() - t0, 3)
        if not started:
            failures.append(f"save at step {n} did not start")
        if n == SAVE_EVERY:
            # First save alone, nothing overlapping it: its whole wall.
            t0 = time.monotonic()
            mgr.wait()
            rec["save_wall_s"] = round(rec["blocked_s"] + time.monotonic() - t0, 3)
    if not all(map(_finite, losses.values())):
        failures.append(f"non-finite loss: {losses}")
    res.update(
        losses={str(n): v for n, v in losses.items()}, step_s={str(n): v for n, v in step_s.items()},
        saves={str(n): v for n, v in saves.items()}, committed=mgr.all_steps(),
        torn=not os.path.exists(
            os.path.join(mgr.path_for(TRAIN_STEPS), ".snapshot_metadata")
        ),
    )
    child.finish(failures)
    # The kill: a save is still draining on its background thread.
    os.kill(os.getpid(), signal.SIGKILL)
    raise AssertionError("unreachable")


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def _write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _phase_resume(child: _Child) -> list:
    jax = child.jax
    from torchsnapshot_tpu import StateDict

    failures: list = []
    res = child.result
    with open(_sidecar_path(child)) as f:
        sidecar = json.load(f)

    mgr = _new_manager(child)
    root = _snapshot_root(child)
    res["step_dirs"] = sorted(os.listdir(root))
    res["torn_dirs"] = [
        d for d in res["step_dirs"]
        if d.startswith("step_") and not os.path.exists(os.path.join(root, d, ".snapshot_metadata"))
    ]
    latest = mgr.latest_step()
    res["latest_step"] = latest
    if not res["torn_dirs"]:
        failures.append("the kill left no torn snapshot: the last save had already committed")
    if latest != sidecar["step"]:
        failures.append(f"latest committed step is {latest}, sidecar is for {sidecar['step']}")
        return failures

    # A different seed: every leaf must be overwritten by the restore.
    T, cfg, mesh, tx, state = _setup_model(child, seed=1)
    dst = StateDict(**state)
    del state
    t0 = time.monotonic()
    restored_step = mgr.restore({"train": dst})
    state = dict(dst)
    jax.block_until_ready(state)
    res["restore_wall_s"] = round(time.monotonic() - t0, 3)
    res["restored_step"] = restored_step
    res["peak_hbm_after_restore"] = child.peak_hbm()

    got = _leaf_hashes(jax, state)
    bad = sorted(k for k in sidecar["leaf_sha256"] if got.get(k) != sidecar["leaf_sha256"][k])
    res["leaves"] = len(got)
    res["leaves_bit_exact"] = len(got) - len(bad)
    if bad or set(got) != set(sidecar["leaf_sha256"]):
        failures.append(f"restore not bit-exact on {bad or 'a different leaf set'}")
        return failures

    step = _compile_step(
        child, T, cfg, tx, mesh, state, _batch_for(child, mesh, latest + 1), failures
    )
    if failures:
        return failures
    same_layout = child.args.phase == "resume"
    rtol = 0.0 if same_layout else RESHARD_LOSS_RTOL
    res["loss_rtol"] = rtol
    res["losses"], res["losses_expected"] = {}, sidecar["losses"]
    for n in range(latest + 1, latest + 3):
        state, loss = step(state, _batch_for(child, mesh, n))
        got_loss, want = float(loss), sidecar["losses"][str(n)]
        res["losses"][str(n)] = got_loss
        if not abs(got_loss - want) <= rtol * abs(want):
            failures.append(f"step {n}: resumed loss {got_loss!r} vs recorded {want!r} (rtol {rtol})")
    if int(state["step"]) != latest + 2:
        failures.append(f"step counter {int(state['step'])} after resume, expected {latest + 2}")
    return failures


if __name__ == "__main__":
    sys.exit(main())
