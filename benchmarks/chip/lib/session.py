"""One run of one cell: the device, the caches, the clocks and the record.

A kind (``kinds/<kind>.py``) drives the program through a ``Session`` and
leaves what it observed in ``session.record``; readers turn the record into
metrics afterwards. Nothing here knows a cell, a mix or a metric by name.
"""

from __future__ import annotations

import atexit
import hashlib
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from . import spec

EXIT_NO_ACCELERATOR = 3
METADATA_FNAME = ".snapshot_metadata"  # the program's commit marker on disk


def log(msg: str) -> None:
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


class CommitWatcher(threading.Thread):
    """Stamps each save with the time its commit marker appeared, without
    the train loop waiting for it: one thread, one ``stat`` per poll."""

    def __init__(self, poll_s: float = 0.002) -> None:
        super().__init__(name="chipbench-commit-watcher", daemon=True)
        self._poll_s = poll_s
        self._lock = threading.Lock()
        self._waiting: List[tuple] = []
        self._halt = threading.Event()

    def expect(self, marker_path: str, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._waiting.append((marker_path, rec))

    def pending(self) -> bool:
        with self._lock:
            return bool(self._waiting)

    def run(self) -> None:
        while not self._halt.is_set():
            with self._lock:
                head = self._waiting[0] if self._waiting else None
            if head is not None and os.path.exists(head[0]):
                head[1]["t_commit"] = time.monotonic()
                with self._lock:
                    self._waiting.pop(0)
                continue
            time.sleep(self._poll_s)

    def drain(self, timeout_s: float) -> bool:
        """Wait until every expected commit was seen; False on timeout."""
        deadline = time.monotonic() + timeout_s
        while self.pending():
            if time.monotonic() > deadline:
                return False
            time.sleep(self._poll_s)
        return True

    def stop(self) -> None:
        self._halt.set()
        self.join()


class Session:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float, trace: bool,
                 dry_run: bool, t_start: float, keep_trace: Optional[str] = None) -> None:
        self.cell = cell
        self.seed, self.seconds, self.trace, self.dry_run = seed, seconds, trace, dry_run
        self.keep_trace = keep_trace
        self.record: Dict[str, Any] = {
            "t_start": t_start, "steps": [], "saves": [], "restores": [], "checks": [], "setup": {}, "ops": [],
        }
        self._work: Optional[str] = None
        self._trace_dir: Optional[str] = None
        self._tracing = False

        import jax

        self.jax = jax
        platform = jax.default_backend()
        devices = jax.devices()
        if (platform != "tpu" and not dry_run) or len(devices) < cell.chips:
            log(f"cell {cell.name} needs {cell.chips} TPU chip(s); jax found {len(devices)} {platform} device(s)")
            raise SystemExit(EXIT_NO_ACCELERATOR)
        self.devices = devices[: cell.chips]
        self.device_info = {
            "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
        }

        from torchsnapshot_tpu import compile_cache

        compile_cache.enable_compilation_cache()
        self._compiles = {"hits": 0, "misses": 0, "requests": 0}

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self._compiles["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._compiles["misses"] += 1

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles["requests"] += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self.watcher = CommitWatcher()
        self.watcher.start()

    # ------------------------------------------------------------ clocks

    now = staticmethod(time.monotonic)

    def note(self, name: str):
        """A host span in the profiler's own trace (free when none is taken)."""
        return self.jax.profiler.TraceAnnotation("bench:" + name)

    def compiles(self) -> Dict[str, int]:
        return dict(self._compiles)

    # ----------------------------------------------------------- storage

    def work_dir(self, need_bytes: int) -> str:
        """A directory for this run's snapshots, emptied now and at exit.

        Snapshots go to memory-backed storage (the configuration files say
        ``"storage": "tmpfs"``): ``/dev/shm`` where it has the room, else
        the temporary directory. The name is made from the checkout's path,
        so two checkouts on one machine share nothing."""
        tag = hashlib.sha1(spec.REPO.encode()).hexdigest()[:10]
        for base in ("/dev/shm", tempfile.gettempdir()):
            if os.path.isdir(base) and shutil.disk_usage(base).free > need_bytes:
                break
        else:
            raise RuntimeError(f"no storage with {need_bytes / 1e9:.1f} GB free")
        self._work = os.path.join(base, f"chipbench-{tag}-{self.cell.name}")
        shutil.rmtree(self._work, ignore_errors=True)
        os.makedirs(self._work)
        atexit.register(self.close)
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # runs atexit
        self.record["setup"]["storage"] = base
        return self._work

    def close(self) -> None:
        if self._tracing:
            try:
                self.jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 - already failing
                pass
            self._tracing = False
        if self.watcher.is_alive():
            self.watcher.stop()
        for d in (self._work, self._trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)

    def marker(self, mgr, step: int) -> str:
        return os.path.join(mgr.path_for(step), METADATA_FNAME)

    # ------------------------------------------------------------ device

    def peak_bytes(self) -> List[int]:
        """Allocator peak per device used; 0 where the backend reports none."""
        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in self.devices]

    def dtoh_probe(self, nbytes: int) -> Dict[str, float]:
        """One ``device_get`` of one buffer: the single-stream link rate."""
        import jax.numpy as jnp
        import numpy as np

        x = self.jax.device_put(jnp.zeros((nbytes // 4,), jnp.float32), self.devices[0])
        x = (x + 1).block_until_ready()  # a buffer the runtime has not seen on the host
        t0 = self.now()
        host = np.asarray(x)
        dt = self.now() - t0
        if host[-1] != 1.0:
            raise RuntimeError("the DtoH probe read back another buffer than it wrote")
        x.delete()
        return {"bytes": nbytes, "seconds": dt, "gbps": nbytes / dt / 1e9}

    # ------------------------------------------------------------- trace

    def start_trace(self) -> None:
        if not self.trace or self._tracing or self._trace_dir:
            return
        self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the Python tracer slows the loop it watches
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = True
        self._window = self.note("window")
        self._window.__enter__()
        self.record["trace_t0"] = self.now()

    def stop_trace(self) -> None:
        if not self._tracing:
            return
        self._window.__exit__(None, None, None)
        self.record["trace_t1"] = self.now()
        self.jax.profiler.stop_trace()
        self._tracing = False
        from . import xtrace

        path = xtrace.find_xplane(self._trace_dir)
        if path is None:
            log("the profiler wrote no trace")
            return
        trace = xtrace.compact(path)
        self.record["trace"] = xtrace.reduce(trace)
        if self.keep_trace:
            os.makedirs(self.keep_trace, exist_ok=True)
            xtrace.save(trace, os.path.join(self.keep_trace, f"{self.cell.name}.trace.json.gz"))
            with open(os.path.join(self.keep_trace, f"{self.cell.name}.planes.txt"), "w") as f:
                f.write("\n".join(xtrace.describe(path)) + "\n")

    def keep_ops(self) -> None:
        """With --keep-trace, the scraped spans too: the span reduction can
        then be rerun, and looked at, without the chip."""
        if self.keep_trace and self.record["ops"]:
            import gzip
            import json

            os.makedirs(self.keep_trace, exist_ok=True)
            with gzip.open(os.path.join(self.keep_trace, f"{self.cell.name}.ops.json.gz"), "wt") as f:
                json.dump(self.record["ops"], f)

    @property
    def tracing(self) -> bool:
        return self._tracing

    # --------------------------------------------------------- telemetry

    def scrape_op(self, op: str, lo: float, hi: float) -> None:
        """Keep the program's spans and phase marks of one operation, taken
        from its telemetry bus before the next operation trims them."""
        if not self.trace:
            return
        from torchsnapshot_tpu import telemetry

        spans, phases = [], []
        for ev in telemetry.events():
            ts = ev.get("ts")
            if ts is None or ts < lo - 1e-3 or ts > hi + 1e-3:
                continue
            if ev.get("ph") == "span":
                spans.append((ev["name"], ts, ev["dur"]))
            elif ev.get("cat") == "phase":
                phases.append((ev["name"], ts, (ev.get("args") or {}).get("dur_s")))
        self.record["ops"].append({"op": op, "lo": lo, "hi": hi, "spans": spans, "phases": phases})
