"""The telemetry bus writes its spans into the profiler's trace too.

With telemetry on, every ``telemetry.span`` is also a
``jax.profiler.TraceAnnotation`` named ``tsnap:<name>``, so a trace taken
with ``jax.profiler`` shows the save and restore pipeline beside the
device on the profiler's own clock. With telemetry off nothing is written
and nothing is allocated. The staging lump is split where the work
happens: ``stage_dtoh`` and ``stage_crc`` inside ``stage_hash``, and the
device-side assembly of a streamed restore is ``consume_assemble``.

One small take + restore of jax arrays runs under an open profiler trace
once per mode (module fixtures); the cases read what it left behind.
"""

import asyncio
import glob
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.telemetry import core

PREFIX = "tsnap:"
SUB_CHUNK = 128 << 10


def _trace(tmp, body):
    """Run ``body`` under a profiler trace (host events only: the Python
    tracer would dwarf them); returns ``{line: [(name, start, end)]}`` of
    the ``tsnap:`` events, a line being one host thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(files) == 1
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for i, line in enumerate(plane.lines):
            found = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events
                if e.name.startswith(PREFIX)
            ]
            if found:
                lines[(plane.name, i)] = found
    return lines


def _spans(events):
    return [e for e in events if e["ph"] == "span"]


def _take_and_restore(tmp, enabled):
    """What one traced take + streamed restore left in the profiler's
    trace and, per operation, on the bus."""
    arr = np.arange(400_000, dtype=np.float32).reshape(400, 1000)
    state = {"app": StateDict(w=jnp.asarray(arr), b=jnp.ones((64, 64), jnp.float32))}
    dst = {"app": StateDict(w=jnp.zeros((400, 1000), jnp.float32), b=jnp.zeros((64, 64), jnp.float32))}
    bus = {}
    # Buffered writes (every leaf goes through stage_hash, as on the chip),
    # streamed reads (the large leaf goes through the device row sink).
    env = {"TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES": str(SUB_CHUNK), "TORCHSNAPSHOT_TPU_STREAM_READS": "always",
           "TORCHSNAPSHOT_TPU_STREAM_WRITES": "never",
           "TORCHSNAPSHOT_TPU_ENABLE_BATCHING": "0"}
    telemetry.reset()
    telemetry.set_enabled(enabled)
    try:
        with pytest.MonkeyPatch.context() as mp:  # a module fixture has no monkeypatch
            for k, v in env.items():
                mp.setenv(k, v)

            def body():
                # Asynchronous, as the manager saves: staging then has to copy
                # on the CPU backend, which a synchronous take is spared.
                snap = Snapshot.async_take(str(tmp / "snap"), state).wait()
                bus["take"] = _spans(telemetry.events())  # before the restore's recorder trims them
                snap.restore(dst)
                bus["restore"] = _spans(telemetry.events())

            lines = _trace(tmp / "trace", body)
    finally:
        telemetry.set_enabled(False)
        telemetry.reset()
    assert np.array_equal(np.asarray(dst["app"]["w"]), arr)
    return {"lines": lines, "bus": bus}


@pytest.fixture(scope="module")
def run_on(tmp_path_factory):
    return _take_and_restore(tmp_path_factory.mktemp("on"), True)


@pytest.fixture(scope="module")
def run_off(tmp_path_factory):
    return _take_and_restore(tmp_path_factory.mktemp("off"), False)


@pytest.fixture(autouse=True)
def _bus_off_and_empty():
    telemetry.set_enabled(False)
    telemetry.reset()
    yield
    telemetry.set_enabled(False)
    telemetry.reset()


def _traced_names(run):
    return {name for events in run["lines"].values() for name, _, _ in events}


# ------------------------------------------------------ the profiler's trace


@pytest.mark.parametrize(
    "name",
    ["stage", "stage_hash", "stage_dtoh", "stage_crc", "storage_write", "stream_read", "consume_chunk",
     "sub_chunk_htod", "consume_assemble"],
)
def test_span_lands_in_the_profilers_trace(run_on, name):
    assert PREFIX + name in _traced_names(run_on)


@pytest.mark.parametrize("child", ["stage_dtoh", "stage_crc", "stage_hostcopy"])
def test_staging_child_lies_inside_a_stage_hash_on_its_thread(run_on, child):
    seen = 0
    for events in run_on["lines"].values():
        parents = [(a, b) for name, a, b in events if name == PREFIX + "stage_hash"]
        for name, a, b in events:
            if name == PREFIX + child:
                seen += 1
                assert any(pa <= a and b <= pb for pa, pb in parents), (child, a, b, parents)
    assert seen >= 2  # one per leaf


def test_the_trace_and_the_bus_hold_the_same_spans(run_on):
    on_bus = {PREFIX + e["name"] for op in run_on["bus"].values() for e in op}
    assert on_bus == _traced_names(run_on)


def test_telemetry_off_writes_nothing(run_off):
    assert run_off["lines"] == {}
    assert run_off["bus"] == {"take": [], "restore": []}
    assert telemetry.span("stage_dtoh", cat="stager", bytes=1) is core._NULL_SPAN


# ------------------------------------------------------------------- the bus


@pytest.mark.parametrize("child", ["stage_dtoh", "stage_crc", "stage_hostcopy"])
def test_staging_child_is_a_child_of_stage_hash_on_the_bus(run_on, child):
    take = run_on["bus"]["take"]
    by_id = {e["id"]: e for e in take}
    children = [e for e in take if e["name"] == child]
    assert len(children) == 2  # one per leaf
    for e in children:
        # stage_hostcopy is the fused pass on the CPU backend: it sits
        # inside stage_crc, which sits inside stage_hash.
        parent = by_id[e["parent"]]
        if child == "stage_hostcopy" and parent["name"] == "stage_crc":
            parent = by_id[parent["parent"]]
        assert parent["name"] == "stage_hash" and parent["tid"] == e["tid"]
        assert parent["ts"] <= e["ts"] and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-9
        assert e["cat"] == "stager" and e["args"]["bytes"] in (400 * 1000 * 4, 64 * 64 * 4)


def test_consume_assemble_counts_the_streamed_leafs_blocks(run_on):
    restore = run_on["bus"]["restore"]
    assembled = [e for e in restore if e["name"] == "consume_assemble"]
    # The small leaf is under two sub-chunks and is read buffered.
    assert len(assembled) == 1 and assembled[0]["cat"] == "consumer"
    htod = [e for e in restore if e["name"] == "sub_chunk_htod"]
    assert assembled[0]["args"]["blocks"] == len(htod) > 1
    assert all(e["ts"] + e["dur"] <= assembled[0]["ts"] + 1e-9 for e in htod)


def test_the_bus_keeps_its_clock():
    assert telemetry.monotonic is time.monotonic
    telemetry.set_enabled(True)
    t0 = time.monotonic()
    with telemetry.span("x"):
        pass
    (ev,) = _spans(telemetry.events())
    assert t0 <= ev["ts"] <= ev["ts"] + ev["dur"] <= time.monotonic()
    assert core._trace_annotation is jax.profiler.TraceAnnotation


# --------------------------------------------------- one annotation per span


def test_spans_interleaved_on_one_thread_come_out_whole(tmp_path):
    """Two tasks on the event-loop thread: a opens, b opens, a closes, b
    closes. Not LIFO, so a shared or stacked annotation would cut one."""
    telemetry.set_enabled(True)

    async def task(name, delay, hold):
        await asyncio.sleep(delay)
        with telemetry.span(name):
            await asyncio.sleep(hold)

    async def both():
        await asyncio.gather(task("bridge_a", 0.0, 0.06), task("bridge_b", 0.03, 0.06))

    lines = _trace(tmp_path, lambda: asyncio.run(both()))
    (events,) = lines.values()  # one thread
    got = {name: (a, b) for name, a, b in events}
    bus = {e["name"]: e for e in _spans(telemetry.events())}
    assert set(got) == {PREFIX + "bridge_a", PREFIX + "bridge_b"}
    a, b = got[PREFIX + "bridge_a"], got[PREFIX + "bridge_b"]
    assert a[0] < b[0] < a[1] < b[1]
    for name, (start, end) in got.items():
        dur = bus[name[len(PREFIX):]]["dur"]
        assert dur >= 0.06 and (end - start) / 1e9 == pytest.approx(dur, abs=5e-3)


class _Raises:
    def __init__(self, where):
        self.where = where

    def __call__(self, name):
        if self.where == "init":
            raise RuntimeError("no annotation for you")
        return self

    def __enter__(self):
        if self.where == "enter":
            raise RuntimeError("no annotation for you")
        return self

    def __exit__(self, *exc):
        if self.where == "exit":
            raise RuntimeError("no annotation for you")


@pytest.mark.parametrize("where", ["init", "enter", "exit", "missing"])
def test_a_span_survives_a_failing_annotation(monkeypatch, where):
    monkeypatch.setattr(core, "_trace_annotation", False if where == "missing" else _Raises(where))
    telemetry.set_enabled(True)
    with telemetry.span("outer", cat="stager", bytes=3) as outer:
        with telemetry.span("inner"):
            pass
        outer.set(more=1)
    events = {e["name"]: e for e in _spans(telemetry.events())}
    assert events["inner"]["parent"] == events["outer"]["id"]
    assert events["outer"]["args"] == {"bytes": 3, "more": 1} and events["outer"]["dur"] >= events["inner"]["dur"]


def test_a_process_without_jax_is_not_made_to_import_it():
    code = (
        "import sys\n"
        "from torchsnapshot_tpu import telemetry\n"
        "telemetry.set_enabled(True)\n"
        "with telemetry.span('x'):\n"
        "    pass\n"
        "assert [e['name'] for e in telemetry.events()] == ['x']\n"
        "assert 'jax' not in sys.modules\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
