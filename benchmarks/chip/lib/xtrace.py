"""From the profiler's trace to device busy time, top operations and idle gaps.

Two halves. ``compact()`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (through ``jax.profiler.ProfileData``) and keeps what the reduction
needs as plain lists, which is also the form of the recorded fixture.
``reduce()`` is pure arithmetic on that form, checked against the fixture
by ``test_harness.py``:

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...], ...}},
     "host": [[name, start_ns, dur_ns], ...]}        # the harness's annotations (bench:*) and
                                                      # the runtime's host events of 1 ms and more

Busy is the union of the intervals in which an operation ran on a device,
inside the traced window (the ``bench:window`` annotation); idle is the
rest of the window. Each idle gap is charged to what the host was doing in
it: the shortest (innermost) event open on any host thread wins, first
among the runtime's own events (named ``host: ...``), then among the
harness's annotations, so a gap inside ``bench:save`` that the runtime
spent de-tiling a buffer is charged to the de-tiling.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from .spans import clip, merge, subtract, union_seconds

ANNOTATION_PREFIX = "bench:"
WINDOW = "bench:window"
HOST_EVENT_MIN_NS = 1_000_000  # runtime events on the host shorter than 1 ms are not kept
CONTAINER_OPCODES = ("while", "conditional", "call")  # their time is their children's
# The device line that holds one event per executed operation. Where a
# backend names it otherwise the next that exists is taken.
OP_LINES = ("XLA Ops", "XLA Modules")
SMALL_GAP_NS = 50_000  # gaps under 50 us are launch gaps, summed under one name


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def describe(path: str, per_line: int = 3) -> List[str]:
    """Planes, lines, event counts and a few events: what to look at by
    hand before trusting a reduction on a new backend."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:per_line]:
                out.append(f"    {e.name!r} start={e.start_ns:.0f} dur={e.duration_ns:.0f} {dict(e.stats)}")
    return out


def compact(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper():
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX) or e.duration_ns >= HOST_EVENT_MIN_NS
                )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def save(trace: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path: str) -> Dict[str, Any]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _op_events(lines: Dict[str, list]) -> list:
    for name in OP_LINES:
        if lines.get(name):
            return lines[name]
    return []


def _window(trace: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    for name, start, dur in trace["host"]:
        if name == WINDOW:
            return start, start + dur
    return None


def op_label(hlo: str) -> Tuple[str, str, str]:
    """('fusion.269', 'fusion', 'bf16[2,2048,8192]') from the HLO text the
    trace names an operation by: '%fusion.269 = bf16[2,2048,8192]{..}
    fusion(...), kind=...'. The shape is that of the first result."""
    name, _, rest = hlo.partition(" = ")
    rest = rest.lstrip()
    shape = rest.lstrip("(").partition("{")[0]
    if rest.startswith("("):  # a tuple type: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    return name.lstrip("%"), rest.partition("(")[0].strip(), shape


def reduce(trace: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """None when there is no window annotation or no device in the trace."""
    win = _window(trace)
    devices = {d: _op_events(lines) for d, lines in trace["devices"].items()}
    devices = {d: ev for d, ev in devices.items() if ev}
    if win is None or not devices:
        return None
    lo, hi = win
    busy_ns: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[int, int]] = []
    for d, events in devices.items():
        inside = clip(((s, s + dur) for _, s, dur in events), lo, hi)
        busy_ns[d] = union_seconds(inside)
        for hlo, s, dur in events:
            part = min(s + dur, hi) - max(s, lo)
            name, opcode, shape = op_label(hlo)
            if part > 0 and opcode not in CONTAINER_OPCODES:
                label = f"{name} [{opcode} {shape}]" if opcode else name
                op_ns[label] = op_ns.get(label, 0.0) + part
        if d == min(devices):  # gaps are named on the first device
            gaps = subtract([(lo, hi)], inside)

    # Runtime events before annotations, and of each the shortest first.
    notes = sorted(
        ((n if n.startswith(ANNOTATION_PREFIX) else "host: " + n, s, s + dur)
         for n, s, dur in trace["host"] if n != WINDOW),
        key=lambda a: (a[0].startswith(ANNOTATION_PREFIX), a[2] - a[1]),
    )
    gap_ns: Dict[str, float] = {}
    small = [g for g in gaps if g[1] - g[0] < SMALL_GAP_NS]
    rest = merge(g for g in gaps if g[1] - g[0] >= SMALL_GAP_NS)
    if small:
        gap_ns["launch gaps under 50 us"] = float(sum(b - a for a, b in small))
    for name, s, e in notes:
        if not rest:
            break
        took = union_seconds(clip(rest, s, e))
        if took > 0:
            gap_ns[name] = gap_ns.get(name, 0.0) + took
            rest = subtract(rest, [(s, e)])
    if rest:
        gap_ns["no annotation open"] = union_seconds(rest)

    def ranked(table: Dict[str, float], per: int = 1) -> list:
        pairs = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / 1e9 / per] for name, ns in pairs]

    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns.values()) / len(busy_ns) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": len(devices),
        "busy_s_per_device": {d: v / 1e9 for d, v in sorted(busy_ns.items())},
        "device_ops": ranked(op_ns, per=len(devices)),  # seconds per device, averaged
        "idle_gaps": ranked(gap_ns),  # seconds on the first device
    }
