"""The telemetry bus writes its spans into the profiler's trace too.

With telemetry on, every ``telemetry.span`` is also a
``jax.profiler.TraceAnnotation`` named ``tsnap:<name>``, so a trace taken
with ``jax.profiler`` shows the save and restore pipeline beside the
device on the profiler's own clock. With telemetry off nothing is written
and nothing is allocated. The staging lump is split where the work
happens: ``stage_dtoh`` and ``stage_crc`` inside ``stage_hash``, and the
device-side assembly of a streamed restore is ``consume_assemble``. A
restore's host seconds have names of their own: ``consume_verify``,
``consume_hostcopy``, ``consume_place``, ``consume_queue`` and
``stream_read_wait``, siblings inside ``stream_read`` (a streamed entry) or
``consume`` (a buffered one).

One small take + restore of jax arrays runs under an open profiler trace
once per mode (module fixtures); the cases read what it left behind.
"""

import asyncio
import functools
import glob
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.io_preparers.array import _executor_submit
from torchsnapshot_tpu.telemetry import core

PREFIX = "tsnap:"
SUB_CHUNK = 128 << 10
# The streamed leaf: 400 rows of 4000 bytes. A row does not divide the
# sub-chunk, so every sub-chunk edge splits one and the device row sink's
# carry (hence ``consume_hostcopy``) has work; a leaf whose rows divide it
# goes to the device with no host copy and no such span.
LEAF_SHAPE = (400, 1000)
ROW_BYTES = 1000 * 4
LEAF_BYTES = 400 * ROW_BYTES
assert SUB_CHUNK % ROW_BYTES and all((k * SUB_CHUNK) % ROW_BYTES for k in range(1, LEAF_BYTES // SUB_CHUNK + 1))
# What this PR names of a restore, and the two older spans that stay their
# siblings: each lies inside one OUTER span, none inside another.
RESTORE_SPANS = ["consume_verify", "consume_hostcopy", "consume_place", "consume_queue", "stream_read_wait"]
INNER = set(RESTORE_SPANS) | {"sub_chunk_htod", "consume_assemble"}
OUTER = ("stream_read", "consume")


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
    arr = np.arange(LEAF_BYTES // 4, dtype=np.float32).reshape(LEAF_SHAPE)
    state = {"app": StateDict(w=jnp.asarray(arr), b=jnp.ones((64, 64), jnp.float32))}
    dst = {"app": StateDict(w=jnp.zeros(LEAF_SHAPE, jnp.float32), b=jnp.zeros((64, 64), jnp.float32))}
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
     "sub_chunk_htod", "consume_assemble", *RESTORE_SPANS],
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


@pytest.mark.parametrize("name", RESTORE_SPANS)
def test_telemetry_off_allocates_nothing_for_a_restore_span(name):
    assert telemetry.span(name, cat="consumer", path="0/app/w", bytes=1) is core._NULL_SPAN
    assert telemetry.handoff_span(name, cat="consumer", path="0/app/w") is core._NULL_SPAN


def test_telemetry_off_submits_to_the_executor_with_no_wrapper():
    """Off, a consumer's ``submit`` is ``run_in_executor`` itself, bound
    once a stream: nothing is allocated a chunk."""

    async def body():
        loop = asyncio.get_running_loop()
        with ThreadPoolExecutor(max_workers=1) as pool:
            submit = _executor_submit(pool, "0/app/w")
            assert isinstance(submit, functools.partial)
            assert submit.func == loop.run_in_executor and submit.args == (pool,) and not submit.keywords
            assert await submit(threading.get_ident) != threading.get_ident()

    asyncio.run(body())
    assert telemetry.events() == []


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


# ------------------------------------------- a restore's seconds, by name


@pytest.mark.parametrize("name", RESTORE_SPANS)
def test_restore_span_is_on_the_bus_with_the_path_of_its_read(run_on, name):
    restore = run_on["bus"]["restore"]
    reads = {e["args"]["path"] for e in restore if e["name"] in OUTER}
    found = [e for e in restore if e["name"] == name]
    assert found and all(e["args"]["path"] in reads for e in found)
    moved = [e for e in found if "bytes" in e["args"]]
    if name in ("consume_hostcopy", "consume_place"):
        assert moved == found
    assert all(e["args"]["bytes"] > 0 for e in moved)
    if name == "consume_hostcopy":
        # only the streamed leaf copies, and only around its carry: the
        # buffered one is placed from the buffer it was read into
        (streamed,) = {e["args"]["path"] for e in restore if e["name"] == "stream_read"}
        assert {e["args"]["path"] for e in found} == {streamed}
        # one span a chunk: the first opens a row, the last closes one, the others do both
        assert len(found) == -(-LEAF_BYTES // SUB_CHUNK) and all(e["args"]["bytes"] < 2 * ROW_BYTES for e in found)


def _by_path(spans, names):
    out = {}
    for e in spans:
        if e["name"] in names:
            out.setdefault(e["args"]["path"], []).append(e)
    return out


def _assert_siblings_inside(outer, inner):
    """Every inner span inside the one outer span, no two overlapping:
    their durations then sum to no more than the outer's."""
    lo, hi = outer["ts"], outer["ts"] + outer["dur"]
    inner = sorted(inner, key=lambda e: e["ts"])
    for e in inner:
        assert lo - 1e-9 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-9, (e["name"], outer["name"])
    for a, b in zip(inner, inner[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, (a["name"], b["name"])
    assert sum(e["dur"] for e in inner) <= outer["dur"] + 1e-9


@pytest.mark.parametrize("outer, leaf, want", [
    # the large leaf streams through the device row sink (its rows do not divide the
    # sub-chunk, so the carry copies: LEAF_SHAPE), the small one is read whole
    ("stream_read", "w", {"stream_read_wait", "consume_queue", "consume_verify", "consume_hostcopy",
                          "sub_chunk_htod", "consume_assemble"}),
    ("consume", "b", {"consume_queue", "consume_verify", "consume_place"}),
])
def test_inner_spans_are_siblings_inside_the_outer_span_of_their_path(run_on, outer, leaf, want):
    restore = run_on["bus"]["restore"]
    (outer_span,) = [e for e in restore if e["name"] == outer and ("/" + leaf + "_") in e["args"]["path"]]
    assert not [e for e in restore if e["name"] in OUTER and e is not outer_span
                and e["args"]["path"] == outer_span["args"]["path"]]
    inner = _by_path(restore, INNER)[outer_span["args"]["path"]]
    assert {e["name"] for e in inner} == want
    _assert_siblings_inside(outer_span, inner)


def test_hostcopy_and_htod_are_siblings_under_consume_chunk(run_on):
    restore = run_on["bus"]["restore"]
    by_id = {e["id"]: e for e in restore}
    htod = [e for e in restore if e["name"] == "sub_chunk_htod"]
    chunks = {e["parent"] for e in htod}
    assert len(chunks) == -(-LEAF_BYTES // SUB_CHUNK)
    for chunk in map(by_id.get, chunks):
        assert chunk["name"] == "consume_chunk"
        children = sorted((c for c in restore if c["parent"] == chunk["id"]), key=lambda c: c["ts"])
        assert all(c["tid"] == chunk["tid"] for c in children)
        # the copies into and out of the carry come first, in one span; then
        # the row they completed goes to the device, then the chunk's own rows
        names = [c["name"] for c in children]
        assert names[:2] == ["consume_verify", "consume_hostcopy"] and 1 <= len(names[2:]) <= 2
        assert set(names[2:]) == {"sub_chunk_htod"}
        for a, b in zip(children, children[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9
    # every byte of the leaf went to the device; the carry took the two parts
    # of the one row a sub-chunk edge splits, never a chunk
    copied = [e for e in restore if e["name"] == "consume_hostcopy"]
    assert sum(e["args"]["bytes"] for e in htod) == LEAF_BYTES
    assert all(0 < e["args"]["bytes"] < 2 * ROW_BYTES for e in copied)
    assert sum(e["args"]["bytes"] for e in copied) == ROW_BYTES * (len(chunks) - 1) < ROW_BYTES * len(chunks)
    # a completed row goes alone, so the blocks are the chunks and those rows
    assert len(htod) == 2 * len(chunks) - 1


def test_consume_place_says_which_thread_placed_the_leaf(run_on):
    (placed,) = [e for e in run_on["bus"]["restore"] if e["name"] == "consume_place"]
    assert placed["args"]["thread"] == "worker" and placed["args"]["bytes"] == 64 * 64 * 4
    assert placed["tid"] != threading.get_ident()


def test_a_queue_span_handed_to_a_worker_leaves_every_parent_as_it_was():
    """``consume_queue`` opens on the submitting thread and closes on the
    worker: it joins neither thread's nesting stack."""
    telemetry.set_enabled(True)
    seen = {}

    def work(waited):
        with telemetry.span("worker_before"):
            pass
        waited.__exit__(None, None, None)
        seen["closed_on"] = threading.get_ident()
        with telemetry.span("worker_after"):
            pass

    with ThreadPoolExecutor(max_workers=1) as pool, telemetry.span("outer"):
        waited = telemetry.handoff_span("consume_queue", cat="consumer", path="0/app/w")
        assert type(waited) is telemetry.Span
        with telemetry.span("submitter_while_open"):
            pass
        time.sleep(0.02)
        pool.submit(work, waited).result(timeout=30)
        with telemetry.span("submitter_after"):
            pass
    ev = {e["name"]: e for e in _spans(telemetry.events())}
    outer = ev["outer"]["id"]
    assert ev["submitter_while_open"]["parent"] == outer and ev["submitter_after"]["parent"] == outer
    assert ev["worker_before"]["parent"] is None and ev["worker_after"]["parent"] is None
    queue = ev["consume_queue"]
    assert queue["parent"] == outer and queue["args"] == {"path": "0/app/w"} and queue["cat"] == "consumer"
    assert queue["tid"] == threading.get_ident() != seen["closed_on"]
    assert queue["dur"] >= 0.02 and queue["ts"] + queue["dur"] <= ev["worker_after"]["ts"]


def test_a_queue_span_ends_where_the_work_starts_and_another_brings_the_result_back():
    """Through the consumers' ``submit``: one worker, two calls at once, so
    the second waits for the first; its span covers that wait and no more.
    The way back to the loop thread is a span of its own."""
    telemetry.set_enabled(True)

    def work(name):
        with telemetry.span(name):
            time.sleep(0.05)
        return name

    async def body():
        with ThreadPoolExecutor(max_workers=1) as pool:
            submit = _executor_submit(pool, "0/app/w")
            assert await asyncio.gather(submit(work, "first"), submit(work, "second")) == ["first", "second"]
            with telemetry.span("loop_after"):
                pass

    asyncio.run(body())
    ev = _spans(telemetry.events())
    first, second, after = (next(e for e in ev if e["name"] == n) for n in ("first", "second", "loop_after"))
    queued = sorted((e for e in ev if e["name"] == "consume_queue"), key=lambda e: e["ts"])
    assert all(e["args"]["path"] == "0/app/w" for e in queued)
    (q1, q2), back = [e for e in queued if e["args"]["thread"] == "worker"], [e for e in queued if e["args"]["thread"] == "loop"]
    assert q1["ts"] + q1["dur"] <= first["ts"] and q2["ts"] + q2["dur"] <= second["ts"]
    assert q2["dur"] >= 0.04 and q2["ts"] < first["ts"] + first["dur"] <= q2["ts"] + q2["dur"]
    assert q1["tid"] == q2["tid"] == after["tid"] != first["tid"]
    # opened on the worker when the work ends, closed on the loop thread before the caller goes on
    for work_span, b in zip((first, second), back):
        assert b["tid"] == work_span["tid"] and work_span["ts"] + work_span["dur"] <= b["ts"]
        assert b["ts"] + b["dur"] <= after["ts"]
    assert len(back) == 2 and first["parent"] is None and second["parent"] is None and after["parent"] is None


def _sharded_restore(tmp, streamed):
    """Two leaves sharded 4 x 2 over the CPU mesh, restored under 2 x 4:
    every saved shard scatters into destination boxes. Returns the
    restore's spans and the saved shards' sizes by location."""
    devs = np.array(jax.devices()[:8])
    save = NamedSharding(Mesh(devs.reshape(4, 2), ("x", "y")), P("x", "y"))
    load = NamedSharding(Mesh(devs.reshape(2, 4), ("x", "y")), P("x", "y"))
    data = {k: np.random.default_rng(i).standard_normal((512, 1024)).astype(np.float32) for i, k in enumerate("uv")}
    state = {"app": StateDict(**{k: jax.device_put(v, save) for k, v in data.items()})}
    dst = {"app": StateDict(**{k: jax.device_put(np.zeros_like(v), load) for k, v in data.items()})}
    env = {"TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES": str(64 << 10), "TORCHSNAPSHOT_TPU_ENABLE_BATCHING": "0",
           "TORCHSNAPSHOT_TPU_STREAM_READS": "always" if streamed else "never"}
    with pytest.MonkeyPatch.context() as mp:
        for k, v in env.items():
            mp.setenv(k, v)
        snap = Snapshot.take(str(tmp / "snap"), state)
        telemetry.set_enabled(True)
        snap.restore(dst)
        spans = _spans(telemetry.events())
    for k, v in data.items():
        assert np.array_equal(np.asarray(dst["app"][k]), v)
    return spans


@pytest.mark.parametrize("streamed", [False, True], ids=["buffered", "streamed"])
def test_a_sharded_restore_places_once_a_leaf_and_scatters_once_a_shard(tmp_path, streamed):
    spans = _sharded_restore(tmp_path, streamed)
    outer = {e["args"]["path"]: e for e in spans if e["name"] == ("stream_read" if streamed else "consume")}
    assert len(outer) == 16 and not [e for e in spans if e["name"] == ("consume" if streamed else "stream_read")]
    shard_bytes = 512 * 1024 * 4 // 8
    inner = _by_path(spans, INNER)
    assert set(inner) == set(outer)
    placed = [e for e in spans if e["name"] == "consume_place"]
    assert sorted(e["args"]["path"].rsplit("/", 1)[-1][0] for e in placed) == ["u", "v"]
    assert all(e["args"]["thread"] == ("loop" if streamed else "worker") for e in placed)
    assert all(e["args"]["bytes"] == 512 * 1024 * 4 for e in placed)  # the eight boxes of a leaf
    for path, found in inner.items():
        _assert_siblings_inside(outer[path], found)
        scatters = [e for e in found if e["name"] == "consume_hostcopy" and e["args"]["bytes"] == shard_bytes]
        chunks = [e for e in found if e["name"] == "consume_hostcopy" and e["args"]["bytes"] < shard_bytes]
        assert len(scatters) == 1  # the copy of the verified shard into its boxes
        # streamed, each sub-chunk is first copied into the shard's scratch; buffered, nothing else moves
        assert sum(e["args"]["bytes"] for e in chunks) == (shard_bytes if streamed else 0)
        verified = [e for e in found if e["name"] == "consume_verify"]
        assert len(verified) == (len(chunks) + 1 if streamed else 1)
        queued = [e["args"]["thread"] for e in found if e["name"] == "consume_queue"]
        assert queued.count("worker") == queued.count("loop") == len(verified)  # a wait each way, a call
        assert bool([e for e in found if e["name"] == "stream_read_wait"]) == streamed
        # the leaf is placed inside the read of its last shard, and after that shard's scatter
        for e in (e for e in found if e["name"] == "consume_place"):
            assert scatters[0]["ts"] + scatters[0]["dur"] <= e["ts"] + 1e-9


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
