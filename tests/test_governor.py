"""The I/O governor (scheduler.IOGovernor): rate smoothing, the dead band
at every ``should_*`` knee, and the one election schedule — an election
is a function of the environment override and the rates the governor
measured, nothing else."""

from __future__ import annotations

import numpy as np
import pytest

from torchsnapshot_tpu import native_io, scheduler, telemetry
from torchsnapshot_tpu.scheduler import (
    _DEFAULT_SUB_CHUNK_BYTES,
    _KNEE_MARGIN,
    _NATIVE_FALLBACK_MARGIN,
    _PREVERIFY_READ_MARGIN,
    _STREAM_READ_LATENCY_BPS,
    IOGovernor,
)
from torchsnapshot_tpu.telemetry import flightrec, history

_ELECTION_ENV = (
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES",
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_MIN_BYTES",
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_MAX_BYTES",
    "TORCHSNAPSHOT_TPU_IO_CONCURRENCY",
    "TORCHSNAPSHOT_TPU_PREVERIFY",
    "TORCHSNAPSHOT_TPU_NATIVE_IO",
    "TORCHSNAPSHOT_TPU_STREAM_READS",
)


@pytest.fixture
def clean_env(monkeypatch):
    """Elections see no ambient overrides; individual tests opt knobs
    back in with monkeypatch.setenv."""
    for var in _ELECTION_ENV:
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


def _set_read(gov, plugin, bps):
    with gov._lock:
        gov._read_bps[plugin] = bps


def _set_write(gov, plugin, bps):
    with gov._lock:
        gov._write_bps[plugin] = bps


# ------------------------------------------------------- rate smoothing


def test_ewma_first_sample_is_taken_verbatim(clean_env):
    gov = IOGovernor()
    gov.record_write("fs", 1 << 30, 1.0)
    assert gov.write_bps("fs") == pytest.approx(1 << 30)
    gov.record_read("fs", 1 << 30, 2.0)
    assert gov.read_bps("fs") == pytest.approx((1 << 30) / 2.0)
    gov.record_hash(1 << 30, 4.0)
    assert gov.hash_bps() == pytest.approx((1 << 30) / 4.0)


def test_ewma_alpha_half_smoothing(clean_env):
    gov = IOGovernor()
    gov.record_write("fs", 1 << 30, 1.0)  # 1 GiB/s
    gov.record_write("fs", 1 << 30, 0.25)  # 4 GiB/s sample
    # prev + 0.5 * (sample - prev) = 2.5 GiB/s
    assert gov.write_bps("fs") == pytest.approx(2.5 * (1 << 30))
    # One anomalous sample moves the rate halfway at most.
    gov.record_write("fs", 1 << 30, 100.0)
    assert gov.write_bps("fs") > 1.25 * (1 << 30)


def test_ewma_rejects_degenerate_samples(clean_env):
    gov = IOGovernor()
    gov.record_write("fs", 0, 1.0)
    gov.record_write("fs", 1 << 20, 0.0)
    gov.record_read("fs", -1, 1.0)
    assert gov.write_bps("fs") is None
    assert gov.read_bps("fs") is None


def test_rates_are_per_plugin(clean_env):
    gov = IOGovernor()
    gov.record_write("fs", 1 << 30, 1.0)
    gov.record_write("gcs", 1 << 27, 1.0)
    assert gov.write_bps("fs") == pytest.approx(1 << 30)
    assert gov.write_bps("gcs") == pytest.approx(1 << 27)
    assert gov.write_bps() == pytest.approx(1 << 30)  # best-known


# ------------------------------------------- gate hysteresis at the knee


def test_preverify_gate_crosses_knee_both_ways_without_flip_flop(
    clean_env,
):
    gov = IOGovernor()
    # No evidence: verify (the zero-byte path).
    assert gov.should_preverify("fs") is True
    gov.record_hash(1 << 30, 1.0 * (1 << 30) / 1e9)  # hash at 1 GB/s
    knee = 1e9 * _PREVERIFY_READ_MARGIN  # 1.25 GB/s crossover

    _set_read(gov, "fs", 2.0e9)  # reads clearly cheaper than hashing
    assert gov.should_preverify("fs") is False
    # Jitter back inside the dead band: no flip.
    _set_read(gov, "fs", knee * (1.0 - _KNEE_MARGIN / 2))
    assert gov.should_preverify("fs") is False
    # Clearly below the band: verify again.
    _set_read(gov, "fs", knee * (1.0 - 2 * _KNEE_MARGIN))
    assert gov.should_preverify("fs") is True
    # Jitter above the knee but inside the band: still no flip.
    _set_read(gov, "fs", knee * (1.0 + _KNEE_MARGIN / 2))
    assert gov.should_preverify("fs") is True
    # Clearly above: skip the verify pass.
    _set_read(gov, "fs", knee * (1.0 + 2 * _KNEE_MARGIN))
    assert gov.should_preverify("fs") is False


def test_preverify_env_overrides_beat_measurement(clean_env):
    gov = IOGovernor()
    gov.record_hash(1 << 30, 1.0)
    _set_read(gov, "fs", 100e9)  # measurement says skip
    clean_env.setenv("TORCHSNAPSHOT_TPU_PREVERIFY", "always")
    assert gov.should_preverify("fs") is True
    clean_env.setenv("TORCHSNAPSHOT_TPU_PREVERIFY", "never")
    assert gov.should_preverify("fs") is False


def test_native_write_gate_optimistic_then_deposed_then_recovers(
    clean_env,
):
    gov = IOGovernor()
    # Unmeasured: optimistic (queued SQEs are never worse than pwrite).
    assert gov.should_native_io("fs", op="write") is True
    _set_write(gov, "fs", 1.0e9)
    assert gov.should_native_io("fs", op="write") is True  # native unmeasured
    _set_write(gov, "fs.native", _NATIVE_FALLBACK_MARGIN * 1.0e9 - 1e6)
    assert gov.should_native_io("fs", op="write") is False  # clearly below
    _set_write(gov, "fs.native", 0.9e9)
    assert gov.should_native_io("fs", op="write") is True  # recovers


def test_native_read_gate_engages_only_on_latency_bound_storage(
    clean_env,
):
    gov = IOGovernor()
    # No measured base rate: no evidence, Python path.
    assert gov.should_native_io("fs", op="read") is False
    knee = _STREAM_READ_LATENCY_BPS
    _set_read(gov, "fs.native", 10e9)  # engine itself looks great
    _set_read(gov, "fs", 2 * knee)  # memcpy-speed local reads
    assert gov.should_native_io("fs", op="read") is False
    _set_read(gov, "fs", 0.5 * knee)  # latency-bound storage
    assert gov.should_native_io("fs", op="read") is True
    # Band: hovering just above the knee must not flip it off...
    _set_read(gov, "fs", knee * (1.0 + _KNEE_MARGIN / 2))
    assert gov.should_native_io("fs", op="read") is True
    # ...but clearly crossing it must.
    _set_read(gov, "fs", knee * (1.0 + 2 * _KNEE_MARGIN))
    assert gov.should_native_io("fs", op="read") is False
    # And just below the knee stays off until clearly below the band.
    _set_read(gov, "fs", knee * (1.0 - _KNEE_MARGIN / 2))
    assert gov.should_native_io("fs", op="read") is False
    _set_read(gov, "fs", knee * (1.0 - 2 * _KNEE_MARGIN))
    assert gov.should_native_io("fs", op="read") is True


def test_native_read_gate_deposes_slow_engine_even_when_latency_bound(
    clean_env,
):
    gov = IOGovernor()
    base = 0.5 * _STREAM_READ_LATENCY_BPS
    _set_read(gov, "fs", base)
    assert gov.should_native_io("fs", op="read") is True  # engine unmeasured
    _set_read(gov, "fs.native", _NATIVE_FALLBACK_MARGIN * base - 1e6)
    assert gov.should_native_io("fs", op="read") is False
    _set_read(gov, "fs.native", _NATIVE_FALLBACK_MARGIN * base + 1e6)
    assert gov.should_native_io("fs", op="read") is True


@pytest.mark.parametrize(
    "gate", ["should_coop_restore", "should_planned_reshard", "should_seed_restore"]
)
def test_latency_knee_gates_cross_both_ways_without_flip_flop(
    clean_env, gate
):
    gov = IOGovernor()
    decide = getattr(gov, gate)
    # No recorded read rate: no evidence, the status quo stays.
    assert decide("fs") is False
    knee = _STREAM_READ_LATENCY_BPS
    _set_read(gov, "fs", 0.5 * knee)
    assert decide("fs") is True  # storage-bandwidth-bound: fan out
    _set_read(gov, "fs", knee * (1.0 + _KNEE_MARGIN / 2))
    assert decide("fs") is True  # inside the dead band: no flip
    _set_read(gov, "fs", knee * (1.0 + 2 * _KNEE_MARGIN))
    assert decide("fs") is False  # clearly memcpy-speed: direct reads
    _set_read(gov, "fs", knee * (1.0 - _KNEE_MARGIN / 2))
    assert decide("fs") is False  # inside the band from below: no flip
    _set_read(gov, "fs", knee * (1.0 - 2 * _KNEE_MARGIN))
    assert decide("fs") is True


def test_knee_gate_bands_are_independent_per_gate_and_plugin(
    clean_env,
):
    gov = IOGovernor()
    knee = _STREAM_READ_LATENCY_BPS
    _set_read(gov, "fs", 0.5 * knee)
    assert gov.should_coop_restore("fs") is True
    # A different plugin at the same rate decides from scratch — and a
    # different gate on the same plugin keeps its own dead band.
    _set_read(gov, "gcs", 2 * knee)
    assert gov.should_coop_restore("gcs") is False
    _set_read(gov, "fs", knee * (1.0 + _KNEE_MARGIN / 2))
    assert gov.should_coop_restore("fs") is True  # banded (prior decision)
    # seed_restore has no prior decision for fs: first call compares the
    # raw knee, so the same rate decides False.
    assert gov.should_seed_restore("fs") is False


# ------------------------------------------------ heuristic elections


def test_sub_chunk_heuristic_defaults_without_measurement(clean_env):
    gov = IOGovernor()
    assert gov.sub_chunk_bytes("fs", op="write") == _DEFAULT_SUB_CHUNK_BYTES


def test_io_concurrency_heuristic_rates(clean_env):
    gov = IOGovernor()
    default = gov.io_concurrency("write", "fs")
    assert 1 <= default <= 16
    _set_write(gov, "fs", 5e7)  # latency-bound network storage
    assert gov.io_concurrency("write", "fs") == 16
    _set_write(gov, "fs", 5e9)  # bandwidth-bound local storage
    assert gov.io_concurrency("write", "fs") <= default




# ------------------------------------------- one schedule: env > measured rate

# Every election the governor makes, by the name its flight event carries.
_ASK = {
    "sub_chunk.write": lambda g: g.sub_chunk_bytes("fs", op="write"),
    "sub_chunk.read": lambda g: g.sub_chunk_bytes("fs", op="read"),
    "io_concurrency.write": lambda g: g.io_concurrency("write", "fs"),
    "io_concurrency.read": lambda g: g.io_concurrency("read", "fs"),
    "native.write": lambda g: g.should_native_io("fs", op="write"),
    "native.read": lambda g: g.should_native_io("fs", op="read"),
    "preverify": lambda g: g.should_preverify("fs"),
    "coop_restore": lambda g: g.should_coop_restore("fs"),
    "planned_reshard": lambda g: g.should_planned_reshard("fs"),
    "seed_restore": lambda g: g.should_seed_restore("fs"),
}

# Rates as a process would record them, one operation after another: none
# yet, a slow network backend, a climb through and around the 1 GB/s knee,
# memory-speed storage, and back down (bytes, seconds; the EWMA smooths).
_RATE_SCRIPT = (
    ("hash", None, 1 << 30, 1.0),
    ("write", "fs", 50 << 20, 1.0),
    ("read", "fs", 50 << 20, 1.0),
    ("read", "fs.native", 40 << 20, 1.0),
    ("write", "fs.native", 20 << 20, 1.0),
    ("write", "fs", 900 << 20, 1.0),
    ("read", "fs", 1800 << 20, 1.0),
    ("read", "fs", 1100 << 20, 1.0),
    ("write", "fs.native", 4 << 30, 1.0),
    ("read", "fs", 12 << 30, 1.0),
    ("write", "fs", 12 << 30, 1.0),
    ("read", "fs", 1 << 20, 1.0),
    ("read", "fs", 1 << 20, 1.0),
    ("read", "fs", 1 << 20, 1.0),
    ("read", "fs", 1 << 20, 1.0),
    ("write", "fs", 1 << 20, 1.0),
)


def _scripted_trail(dim: str, asks: int = 5):
    """What a fresh governor elects for ``dim`` before any rate and after
    each rate of the script, asked ``asks`` times each."""
    gov = IOGovernor()
    ask = _ASK[dim]
    trail = [[ask(gov) for _ in range(asks)]]
    for kind, plugin, nbytes, seconds in _RATE_SCRIPT:
        if kind == "hash":
            gov.record_hash(nbytes, seconds)
        else:
            getattr(gov, f"record_{kind}")(plugin, nbytes, seconds)
        trail.append([ask(gov) for _ in range(asks)])
    return trail


@pytest.mark.parametrize("dim", sorted(_ASK))
def test_election_is_a_function_of_env_and_rates(dim, clean_env):
    # On a host of 8 cores or more every branch of io_concurrency reads 16.
    clean_env.setattr(scheduler, "_CPU_COUNT", 2)
    first, second = _scripted_trail(dim), _scripted_trail(dim)
    # Asked again between two rate updates, an election answers the same.
    assert all(len(set(answers)) == 1 for answers in first), first
    # Two processes that measured the same rates elect the same values.
    assert first == second
    # And the script does move every dimension.
    assert len({answers[0] for answers in first}) > 1


_ENV_CASES = {
    # knob-op: (variable, pin, what the pinned election answers); the
    # rates of the test make the heuristic answer something else.
    "sub_chunk-write": ("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", "12345", 12345),
    "sub_chunk-read": ("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", "12345", 12345),
    # An explicit pin may exceed anything the heuristic would pick.
    "io_concurrency-write": ("TORCHSNAPSHOT_TPU_IO_CONCURRENCY", "64", 64),
    "io_concurrency-read": ("TORCHSNAPSHOT_TPU_IO_CONCURRENCY", "64", 64),
    "native-write": ("TORCHSNAPSHOT_TPU_NATIVE_IO", "never", False),
    "native-read": ("TORCHSNAPSHOT_TPU_NATIVE_IO", "always", True),
}


@pytest.mark.parametrize("case", sorted(_ENV_CASES))
def test_env_override_beats_heuristic(case, clean_env):
    knob, op = case.split("-")
    var, pin, pinned = _ENV_CASES[case]
    gov = scheduler.reset_io_governor()
    _set_write(gov, "fs", 2e9)  # sub-chunks of ~100 MB, few streams
    _set_read(gov, "fs", 2e9)  # memcpy-speed reads: no native reads
    if knob == "native":
        # The engine's switch is parsed beside the engine (native_io.elect);
        # the governor is only asked under ``auto``.
        clean_env.setattr(native_io, "engine_kind", lambda: "uring")
        ask = lambda: native_io.elect(op, "fs")  # noqa: E731
    elif knob == "sub_chunk":
        ask = lambda: gov.sub_chunk_bytes("fs", op=op)  # noqa: E731
    else:
        ask = lambda: gov.io_concurrency(op, "fs")  # noqa: E731
    heuristic = ask()
    assert heuristic != pinned
    clean_env.setenv(var, pin)
    assert ask() == pinned
    clean_env.delenv(var)
    assert ask() == heuristic  # the pin left nothing behind
    scheduler.reset_io_governor()


def test_retired_autotune_variable_changes_no_election(clean_env):
    """``TORCHSNAPSHOT_TPU_AUTOTUNE`` was the closed-loop tuner's switch;
    a job that still sets it elects what a job without it elects."""
    unset = {dim: _scripted_trail(dim) for dim in _ASK}
    for spelling in ("never", "pin", "fresh", "auto", "1"):
        clean_env.setenv("TORCHSNAPSHOT_TPU_AUTOTUNE", spelling)
        assert {dim: _scripted_trail(dim) for dim in _ASK} == unset, spelling


# ------------------------------------ journals written by older versions

_OLD_PROFILE = {
    "type": "profile", "ts": 1.0, "plugin": "FSStoragePlugin", "world_size": 1,
    "binding": "storage_write", "settings": {"sub_chunk.write": 16 << 20},
    "score_gbps": 1.2, "takes": 7, "op": "write",
    "trials": [{"dim": "sub_chunk.write", "from": 8 << 20, "to": 16 << 20,
                "verdict": "kept", "gbps": 1.3, "incumbent_gbps": 1.1}],
}


@pytest.mark.parametrize("reader", ["load_history", "stats_trend", "manager"])
def test_old_profile_records_in_a_history_journal_are_skipped(
    reader, clean_env, tmp_path, capsys
):
    """A root's ``.telemetry_history.jsonl`` may hold the ``type="profile"``
    records an older version appended: every reader passes over them."""
    root = str(tmp_path)
    takes = [
        {"ts": 10.0 + i, "op": "take", "snapshot": f"step_{i}", "wall_s": 2.0 + i}
        for i in range(3)
    ]
    for rec in (_OLD_PROFILE, takes[0], _OLD_PROFILE, takes[1], takes[2], _OLD_PROFILE):
        assert history.append_record(root, rec)
    if reader == "load_history":
        assert history.load_history(root) == takes
    elif reader == "stats_trend":
        from torchsnapshot_tpu.cli import main

        assert main(["stats", root, "--trend"]) == 0
        out = capsys.readouterr().out
        assert "step_2" in out and "storage_write" not in out
    else:
        from torchsnapshot_tpu import CheckpointManager, StateDict

        gov = scheduler.reset_io_governor()
        mgr = CheckpointManager(root)
        # Nothing was warm-started from the old records.
        assert gov.sub_chunk_bytes("FSStoragePlugin") == _DEFAULT_SUB_CHUNK_BYTES
        assert mgr.save(1, {"app": StateDict(w=np.arange(1000, dtype=np.float32))})
        mgr.wait()
        assert mgr.latest_step() == 1
        records = history.load_history(root)
        assert records[:3] == takes and len(records) == 4
        scheduler.reset_io_governor()


# ---------------------- the read geometry the tuner used to move, pinned


@pytest.mark.parametrize("concurrency", [1, 32])
@pytest.mark.parametrize("sub_chunk", [64 << 10, 192 << 10, 512 << 10])
def test_restore_is_bit_exact_at_every_read_geometry(
    sub_chunk, concurrency, clean_env, tmp_path
):
    """Doubling and halving ``sub_chunk.read`` and ``io_concurrency.read``
    between restores was the tuner's experiment; the pins reach the same
    geometries, and every one restores the same bytes."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict

    w = np.arange(400_000, dtype=np.float32).reshape(400, 1000)
    h = (np.arange(300_000) % 251).astype(np.uint8).reshape(300, 1000)
    path = str(tmp_path / "s")
    Snapshot.take(path, {"app": StateDict(w=jnp.asarray(w), h=h, n=np.float64(3.5))})
    clean_env.setenv("TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES", str(sub_chunk))
    clean_env.setenv("TORCHSNAPSHOT_TPU_IO_CONCURRENCY", str(concurrency))
    clean_env.setenv("TORCHSNAPSHOT_TPU_STREAM_READS", "always")
    flightrec.reset()
    dst = {"app": StateDict(w=jnp.zeros_like(w), h=np.zeros_like(h), n=np.float64(0))}
    Snapshot(path).restore(dst)
    assert np.array_equal(np.asarray(dst["app"]["w"]), w)
    assert np.array_equal(dst["app"]["h"], h)
    assert dst["app"]["n"] == 3.5
    read = [
        args for _, _, ev, args in flightrec.snapshot_ring()
        if ev == "governor.elect" and (args or {}).get("site") == "read"
    ]
    assert read and read[-1]["sub_chunk_bytes"] == sub_chunk
    assert read[-1]["io_concurrency"] == concurrency
    assert read[-1]["streamed_entries"] >= 1


# --------------------------- elections and the telemetry switch, end to end

# Measured rates an election records beside its decision: the host
# clock's, so never equal between two runs of the same operations.
_RATE_FIELDS = ("bps", "read_bps", "write_bps", "hash_bps", "native_bps", "base_bps")


@pytest.fixture
def fixed_rates(monkeypatch):
    """Every rate the governor is fed reads the same, whatever the host
    clock said: what is left to move an election between two runs of the
    same operations is the program, not the machine."""
    for name, bps in (("record_write", 4.0e8), ("record_read", 3.0e8)):
        real = getattr(IOGovernor, name)
        monkeypatch.setattr(
            IOGovernor,
            name,
            lambda self, plugin, nbytes, seconds, _real=real, _bps=bps: _real(
                self, plugin, _bps, 1.0
            ),
        )
    real_hash = IOGovernor.record_hash
    monkeypatch.setattr(
        IOGovernor,
        "record_hash",
        lambda self, nbytes, seconds: real_hash(self, 2.0e9, 1.0),
    )


def _state(layout: str, zero: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import StateDict

    w = np.arange(256 * 1024, dtype=np.float32).reshape(512, 512)
    b = np.arange(4096, dtype=np.float32)
    if zero:  # a destination: every leaf has to be overwritten
        w, b = np.zeros_like(w), np.zeros_like(b)
    if layout == "plain":
        return {"app": StateDict(w=jnp.asarray(w), b=jnp.asarray(b))}
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    return {
        "app": StateDict(
            w=jax.device_put(w, NamedSharding(mesh, P("x", "y"))),
            b=jax.device_put(b, NamedSharding(mesh, P(None))),
        )
    }


def _election_trail(op: str, layout: str, root, bus_on: bool):
    """Four operations under a fresh governor; every ``governor.elect``
    the flight recorder holds afterwards, without the measured rates."""
    from torchsnapshot_tpu import Snapshot

    scheduler.reset_io_governor()
    flightrec.reset()
    telemetry.reset()
    telemetry.set_enabled(bus_on)
    try:
        src = _state(layout)
        if op == "take":
            for i in range(4):
                Snapshot.take(str(root / f"step_{i}"), src)
        else:
            Snapshot.take(str(root / "step_0"), src)
            for _ in range(4):
                dst = _state(layout, zero=True)
                Snapshot(str(root / "step_0")).restore(dst)
                for name, leaf in dst["app"].items():
                    assert np.array_equal(np.asarray(leaf), np.asarray(src["app"][name]))
    finally:
        telemetry.reset()
        telemetry.set_enabled(False)
    return [
        {k: v for k, v in (args or {}).items() if k not in _RATE_FIELDS}
        for _, _, ev, args in flightrec.snapshot_ring()
        if ev == "governor.elect"
    ]


@pytest.mark.parametrize("layout", ["plain", "sharded-4-devices"])
@pytest.mark.parametrize("op", ["take", "restore"])
def test_elections_do_not_depend_on_the_telemetry_switch(
    op, layout, clean_env, fixed_rates, tmp_path
):
    """The traced run is the plain run: the same four operations in one
    process leave the same ``governor.elect`` trail with the bus on and
    with it off."""
    off = _election_trail(op, layout, tmp_path / "off", bus_on=False)
    on = _election_trail(op, layout, tmp_path / "on", bus_on=True)
    scheduler.reset_io_governor()
    assert off, "the operations elected nothing"
    assert {e.get("source") for e in off + on} <= {"env", "heuristic", None}
    assert on == off
