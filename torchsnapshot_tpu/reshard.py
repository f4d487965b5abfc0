"""Minimal-movement reshard planner: pure layout changes without the
N-fold storage read.

The problem. A pure layout change (tp2->tp4, row-parallel ->
column-parallel, elastic world resize) restores through sharded.py's
overlap scatter: every rank reads every saved shard that overlaps any
of its destination boxes from STORAGE. A shard wanted by R ranks is
read R times — fleet-wide read amplification ~R on exactly the restores
where the bytes are already resident somewhere in the fleet. PR 4's
cooperative fan-out cannot help: it dedups IDENTICAL request sets
(same unit key, whole stored payload forwarded raw), while resharding
ranks each need a DIFFERENT slice of the shard.

The plan. The reshard plan is a pure function of (manifest entry,
global destination sharding, world size): ``devices_indices_map`` is
global — every rank sees every rank's destination boxes — so all ranks
compute the identical plan with ZERO extra communication (no per-key
all-gather; the only collective cost of the subsystem is one extra bool
riding the existing preverify/coop election gather, see snapshot.py).
Per saved shard, the planner intersects the shard's box with every
rank's destination boxes (box-intersection graph); a shard wanted by
``>= min_requesters`` ranks becomes a planned unit: ONE owner is
elected among the requesters with :func:`fanout.greedy_size_balanced`
(candidate restriction = the requesters), reads the shard from storage
once, decodes it (checksum -> decompress -> array), and forwards each
other requester exactly the regions its boxes need — a CRC'd bundle
over the PR 4 peer channel, generation-fenced frames, receiver-verified
before any scatter. Storage reads for the unit drop from R to 1 and
wire bytes are the minimal box intersections, not whole shards.

Failure = fall back, never fail. Each receiver's ReadReq still points
at the shard's real storage location: any peer failure (owner death,
abort, short/corrupt bundle) surfaces as IOError/IntegrityError/
PeerTransferError in the scheduler's peer read, which counts a
``fanout_fallbacks``, flips this consumer to direct mode
(``on_peer_fallback``), re-charges the budget and re-reads from
storage — per entry, no global abort, bit-exact either way. Owners that
die or error mid-key poison their keys via the session's dead-source
tracking and ``abort_incomplete``; receivers degrade promptly instead
of waiting out the coop timeout.

Election. ``TORCHSNAPSHOT_TPU_RESHARD`` = never / always / auto; auto
asks ``IOGovernor.should_planned_reshard`` (observed storage read
bandwidth below the streaming knee — on memcpy-speed local fs the
direct path wins and the planner stays off). Opt-in must be unanimous
and rides the SAME all-gather as the preverify/coop election.

This module is on the peer plane (tsalint ``peer-channel``): it MUST
NEVER import jax. Geometry comes from the manifest and from device-free
box maps the caller supplies; device work stays in io_preparers above.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import faultinject, telemetry
from .fanout import RecvRole, greedy_size_balanced
from .io_types import BufferConsumer, BufferType
from .manifest import Shard, ShardedArrayEntry

Box = Tuple[Tuple[int, int], ...]

RESHARD_ENV_VAR = "TORCHSNAPSHOT_TPU_RESHARD"
RESHARD_MIN_REQUESTERS_ENV_VAR = "TORCHSNAPSHOT_TPU_RESHARD_MIN_REQUESTERS"

# Bundle framing: one JSON header line (crc of the payload, payload
# nbytes), then the concatenated regions in the plan's deterministic
# (sorted-box) order, each ``ascontiguousarray(...).tobytes()`` in the
# shard's STORED dtype. A single generation, a single chunk frame: the
# bundle is at most the decoded shard (<= the 512 MB save-side shard
# cap), and the receiver buffers the unit anyway before its
# verify-then-scatter commit.
_HEADER_SNIFF_BYTES = 256


def reshard_mode() -> str:
    """``TORCHSNAPSHOT_TPU_RESHARD``: "never", "always", or "auto"
    (default — the IOGovernor decides per storage plugin)."""
    raw = os.environ.get(RESHARD_ENV_VAR, "auto").strip().lower()
    if raw in ("0", "false", "off", "no", "never"):
        return "never"
    if raw in ("1", "true", "on", "yes", "always", "force"):
        return "always"
    return "auto"


def reshard_min_requesters() -> int:
    """``TORCHSNAPSHOT_TPU_RESHARD_MIN_REQUESTERS``: how many ranks must
    want a saved shard before the planner claims it (default 2 — below
    that there is nothing to dedup; floored at 2)."""
    raw = os.environ.get(RESHARD_MIN_REQUESTERS_ENV_VAR, "")
    try:
        return max(2, int(raw))
    except ValueError:
        return 2


def local_opt_in(plugin_name: str, pg_wrapper: Any) -> bool:
    """This rank's planned-reshard vote. The caller enforces unanimity
    (all ranks must vote yes) and supplies the transport; the vote rides
    the preverify/coop election all-gather — never its own round trip."""
    if pg_wrapper.get_world_size() <= 1:
        return False
    mode = reshard_mode()
    read_bps = None
    if mode == "never":
        opt_in = False
    elif mode == "always":
        opt_in = True
    else:
        from .scheduler import io_governor

        gov = io_governor()
        opt_in = gov.should_planned_reshard(plugin_name)
        read_bps = gov.read_bps(plugin_name)
    telemetry.record_election(
        site="reshard",
        plugin=plugin_name,
        mode=mode,
        opt_in=opt_in,
        read_bps=read_bps,
    )
    return opt_in


# --------------------------------------------------------------------------
# The pure planner: device-free, communication-free, identical on all ranks.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class PlannedUnit:
    """One saved shard claimed by the planner: ``owner`` reads it from
    storage once and forwards minimal regions to the other
    ``requesters``."""

    shard_index: int
    owner: int
    requesters: Tuple[int, ...]  # sorted; owner is always a member
    nbytes: int  # decoded shard bytes (the balancing weight)


def plan_entry_transfers(
    entry: ShardedArrayEntry,
    boxes_by_rank: Dict[int, List[Box]],
    min_requesters: int = 2,
) -> List[PlannedUnit]:
    """The box-intersection plan for one sharded entry.

    ``boxes_by_rank`` maps EVERY rank to its sorted distinct destination
    boxes (from ``devices_indices_map`` at restore time, or from
    ``layout.LayoutSpec.boxes_by_rank`` for device-free dry runs). The
    result is deterministic: ranks iterate in sorted order, boxes in the
    caller's sorted lists, the election pool in (-nbytes, location,
    shard_index) order — byte-identical on every rank, no set iteration.

    Cost: O(shards x total_boxes) box intersections, each O(ndim) — at
    the 50k-shard / 32-way cardinality of benchmarks/manifest_scale.py
    this is a few hundred thousand integer interval tests, well under a
    second (the manifest_scale plan leg pins a wall bound on it).
    """
    from .io_preparers.sharded import _overlap
    from .serialization import array_size_bytes

    min_requesters = max(2, int(min_requesters))
    ranks = sorted(boxes_by_rank)
    world_size = (ranks[-1] + 1) if ranks else 0

    claimed: List[Tuple[int, Tuple[int, ...], int, str]] = []
    for i, shard in enumerate(entry.shards):
        requesters = []
        for rank in ranks:
            for box in boxes_by_rank[rank]:
                if _overlap(shard.offsets, shard.sizes, box) is not None:
                    requesters.append(rank)
                    break
        if len(requesters) >= min_requesters:
            claimed.append(
                (
                    i,
                    tuple(requesters),
                    array_size_bytes(shard.array.shape, shard.array.dtype),
                    shard.array.location,
                )
            )
    if not claimed:
        return []

    # Biggest units first so the greedy balance is tight; ties broken by
    # location then index for cross-rank determinism.
    order = sorted(
        range(len(claimed)),
        key=lambda j: (-claimed[j][2], claimed[j][3], claimed[j][0]),
    )
    owners = greedy_size_balanced(
        [claimed[j][2] for j in order],
        world_size,
        candidates=[list(claimed[j][1]) for j in order],
    )
    units = [
        PlannedUnit(
            shard_index=claimed[j][0],
            owner=owners[k],
            requesters=claimed[j][1],
            nbytes=claimed[j][2],
        )
        for k, j in enumerate(order)
    ]
    units.sort(key=lambda u: u.shard_index)
    return units


def plan_summary(
    entry: ShardedArrayEntry,
    boxes_by_rank: Dict[int, List[Box]],
    min_requesters: int = 2,
) -> Dict[str, int]:
    """Aggregate byte accounting for one entry's plan — the ``tstpu
    plan`` dry-run and the manifest_scale leg both report these.

    ``direct_bytes_from_storage`` is what the existing path would read
    fleet-wide (every requester reads the whole stored shard);
    ``planned_bytes_from_storage`` is what the plan reads (one owner per
    claimed unit, everyone for unclaimed shards); ``planned_peer_bytes``
    is the wire traffic (minimal region intersections)."""
    from .io_preparers.sharded import _overlap
    from .serialization import array_size_bytes

    units = plan_entry_transfers(entry, boxes_by_rank, min_requesters)
    by_index = {u.shard_index: u for u in units}
    direct = planned = peer = 0
    itemsize = None
    for i, shard in enumerate(entry.shards):
        nbytes = array_size_bytes(shard.array.shape, shard.array.dtype)
        n_elems = 1
        for s in shard.sizes:
            n_elems *= int(s)
        itemsize = nbytes // max(1, n_elems)
        requesters = []
        for rank in sorted(boxes_by_rank):
            hit = False
            for box in boxes_by_rank[rank]:
                ov = _overlap(shard.offsets, shard.sizes, box)
                if ov is None:
                    continue
                hit = True
                if i in by_index and rank != by_index[i].owner:
                    src, _dst = ov
                    vol = 1
                    for sl in src:
                        vol *= sl.stop - sl.start
                    peer += vol * itemsize
            if hit:
                requesters.append(rank)
        direct += nbytes * len(requesters)
        planned += nbytes if i in by_index else nbytes * len(requesters)
    return {
        "shards": len(entry.shards),
        "planned_units": len(units),
        "direct_bytes_from_storage": direct,
        "planned_bytes_from_storage": planned,
        "planned_peer_bytes": peer,
    }


# --------------------------------------------------------------------------
# Per-rank roles: what THIS rank owns / receives for one entry.
# --------------------------------------------------------------------------


@dataclass
class OwnerUnit:
    """This rank owns a planned unit: after decoding the shard it
    forwards each subscriber its region bundle (``bundles`` is sorted by
    subscriber rank; each entry carries the src slices into the decoded
    shard, in the subscriber's sorted-box order)."""

    ctx: "ReshardContext"
    shard_index: int
    bundles: List[Tuple[int, str, List[Tuple[slice, ...]]]]


@dataclass
class RecvUnit:
    """This rank receives a planned unit: ``regions`` lists, in the same
    sorted-box order the owner serializes, the destination box, the
    slices into that box's host buffer, and the region shape."""

    key: str
    owner: int
    shard_index: int
    regions: List[Tuple[Box, Tuple[slice, ...], Tuple[int, ...]]]


def _unit_peer_key(shard: Shard, dst_rank: int) -> str:
    """Per (saved shard, receiver) peer-channel key. Distinct receivers
    need DIFFERENT regions, so unlike coop units there is one key per
    subscriber; the ``reshard|`` prefix keeps the namespace disjoint
    from coop unit keys (which start with an origin URL or '|')."""
    br = shard.array.byte_range
    lo, hi = (int(br[0]), int(br[1])) if br is not None else (0, -1)
    origin = shard.array.origin or ""
    return f"reshard|{origin}|{shard.array.location}|{lo}|{hi}|{dst_rank}"


class ReshardContext:
    """One app-state key's planned-reshard bookkeeping for ONE rank.

    Built only after a unanimous fleet opt-in (snapshot.py's election).
    ``plan_entry`` runs the pure planner and projects out this rank's
    roles; the context tracks owned keys so ``abort_incomplete`` can
    poison whatever an erroring key never forwarded (subscribers then
    fall back to storage promptly instead of waiting out the coop
    timeout)."""

    def __init__(
        self,
        session: Any,  # fanout.CoopRestoreSession (the transport)
        rank: int,
        world_size: int,
        min_requesters: Optional[int] = None,
    ) -> None:
        self.session = session
        self.rank = rank
        self.world_size = world_size
        self.min_requesters = (
            min_requesters
            if min_requesters is not None
            else reshard_min_requesters()
        )
        self._owned: Dict[str, List[int]] = {}
        self._done: set = set()
        self.planned_units = 0
        self.owned_units = 0
        self.recv_units = 0

    def plan_entry(
        self,
        entry: ShardedArrayEntry,
        boxes_by_rank: Dict[int, List[Box]],
    ) -> Optional[Dict[int, Any]]:
        """shard_index -> OwnerUnit | RecvUnit for this rank, or None
        when the planner claims nothing (every shard below the requester
        threshold)."""
        from .io_preparers.sharded import _overlap

        with telemetry.span(
            "reshard_plan",
            cat="fanout",
            shards=len(entry.shards),
            ranks=len(boxes_by_rank),
        ):
            units = plan_entry_transfers(
                entry, boxes_by_rank, self.min_requesters
            )
        if not units:
            return None

        def regions_for(shard: Shard, dst_rank: int):
            out = []
            for box in boxes_by_rank[dst_rank]:
                ov = _overlap(shard.offsets, shard.sizes, box)
                if ov is not None:
                    src_slices, dst_slices = ov
                    shape = tuple(sl.stop - sl.start for sl in src_slices)
                    out.append((box, src_slices, dst_slices, shape))
            return out

        roles: Dict[int, Any] = {}
        for unit in units:
            self.planned_units += 1
            shard = entry.shards[unit.shard_index]
            if unit.owner == self.rank:
                bundles = []
                for sub in unit.requesters:
                    if sub == self.rank:
                        continue
                    key = _unit_peer_key(shard, sub)
                    bundles.append(
                        (
                            sub,
                            key,
                            [src for _, src, _, _ in regions_for(shard, sub)],
                        )
                    )
                    self._owned[key] = [sub]
                roles[unit.shard_index] = OwnerUnit(
                    ctx=self, shard_index=unit.shard_index, bundles=bundles
                )
                self.owned_units += 1
            elif self.rank in unit.requesters:
                roles[unit.shard_index] = RecvUnit(
                    key=_unit_peer_key(shard, self.rank),
                    owner=unit.owner,
                    shard_index=unit.shard_index,
                    regions=[
                        (box, dst, shape)
                        for box, _src, dst, shape in regions_for(
                            shard, self.rank
                        )
                    ],
                )
                self.recv_units += 1
        telemetry.flightrec.record(
            "reshard.plan",
            shards=len(entry.shards),
            planned=len(units),
            owned=self.owned_units,
            recv=self.recv_units,
        )
        return roles or None

    def mark_done(self, key: str) -> None:
        self._done.add(key)

    def abort_incomplete(self) -> None:
        """Abort every owned bundle never forwarded (key raised or was
        cancelled) so subscribers fail over to storage immediately."""
        for key, subs in self._owned.items():
            if key not in self._done:
                self.session._forward_sync(
                    subs, {"op": "abort", "key": key}, None
                )
                self._done.add(key)


# --------------------------------------------------------------------------
# Consumers: the owner/receiver ends of a planned unit.
# --------------------------------------------------------------------------


class PlannedOwnerConsumer(BufferConsumer):
    """Owner side of planned units for one saved shard. Decodes the
    stored payload exactly like the direct scatter consumer (checksum ->
    decompress -> array), FORWARDS each subscriber its region bundle
    first (they are blocked on the wire; the local scatter overlaps),
    then scatters locally.

    The scheduler gives this request NO peer role: a coop SendRole
    forwards the RAW stored payload (the identical-request dedup
    contract), whereas a planned bundle is the DECODED minimal regions —
    so forwarding lives here, after decode, via the session's
    thread-safe sync frame writer (executor-thread safe; send failures
    mark the peer dead and never raise into the restore). ``can_stream``
    stays False (the streamed consume path never materializes the whole
    decoded array this consumer must forward)."""

    def __init__(self, direct: Any, unit: OwnerUnit) -> None:
        self.direct = direct  # sharded._ShardScatterConsumer
        self.unit = unit

    def _consume_sync(self, buf: BufferType) -> None:
        arr = self.direct._decode(buf)
        _forward_bundles(self.unit, self.direct.shard, arr)
        self.direct._scatter(arr)

    async def consume_buffer(self, buf: BufferType, executor=None) -> None:
        from .io_preparers.array import _executor_submit

        submit = _executor_submit(executor, self.direct.shard.array.location)
        await submit(self._consume_sync, buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.direct.get_consuming_cost_bytes()


def _forward_bundles(unit: OwnerUnit, shard: Shard, arr: np.ndarray) -> None:
    from .integrity import compute_checksum

    for dst_rank, key, src_slices_list in unit.bundles:
        payload = b"".join(
            np.ascontiguousarray(arr[src]).tobytes()
            for src in src_slices_list
        )
        header = (
            json.dumps(
                {"crc": compute_checksum(payload), "n": len(payload)},
                separators=(",", ":"),
            ).encode()
            + b"\n"
        )
        data = faultinject.mutate("reshard.peer_xfer", header + payload)
        with telemetry.span(
            "peer_reshard", cat="fanout", key=key, bytes=len(data)
        ):
            unit.ctx.session._forward_sync(
                [dst_rank],
                {"op": "chunk", "key": key, "gen": 1, "seq": 0},
                data,
            )
            unit.ctx.session._forward_sync(
                [dst_rank],
                {
                    "op": "end",
                    "key": key,
                    "gen": 1,
                    "nbytes": len(data),
                    "nchunks": 1,
                },
                None,
            )
        telemetry.counter_add("bytes_to_peers", len(data))
        unit.ctx.mark_done(key)


class PlannedRecvConsumer(BufferConsumer):
    """Receiver side of a planned unit — dual-mode.

    Peer mode (default): the scheduler's RecvRole delivers the owner's
    region bundle; the CRC is verified BEFORE any scatter (no partial
    commit), then each region lands in its destination box buffer in the
    plan's deterministic order.

    Direct mode (after ``on_peer_fallback()``): the buffer is the raw
    stored shard — delegate to the wrapped direct consumer. The ReadReq
    carrying this consumer points at the shard's REAL storage location,
    so the scheduler's peer-fallback re-read needs no plan surgery: same
    request, re-charged budget, storage bytes, full verify/decode path.
    """

    def __init__(
        self,
        direct: Any,  # sharded._ShardScatterConsumer over the same targets
        unit: RecvUnit,
        boxes: Dict[Box, np.ndarray],
    ) -> None:
        self.direct = direct
        self.unit = unit
        self.key = unit.key
        self.owner = unit.owner
        self._peer_mode = True
        from .serialization import string_to_dtype

        self._np_dtype = string_to_dtype(direct.shard.array.dtype)
        self._regions = [
            (boxes[box], dst_slices, shape)
            for box, dst_slices, shape in unit.regions
        ]

    def on_peer_fallback(self) -> None:
        """Scheduler hook: the peer attempt failed (or the owner was
        already dead at dispatch) — the next buffer is raw storage."""
        self._peer_mode = False

    def _consume_sync(self, buf: BufferType) -> None:
        if not self._peer_mode:
            self.direct._consume_sync(buf)
            return
        from .integrity import verify_checksum

        mv = memoryview(buf)
        head = bytes(mv[:_HEADER_SNIFF_BYTES])
        idx = head.find(b"\n")
        if idx < 0:
            raise IOError(
                f"planned reshard bundle {self.key!r} has no header line"
            )
        try:
            header = json.loads(head[:idx])
            crc, nbytes = header["crc"], int(header["n"])
        except (ValueError, KeyError, TypeError) as e:
            raise IOError(
                f"planned reshard bundle {self.key!r} header unparseable: {e}"
            ) from e
        payload = mv[idx + 1 :]
        if payload.nbytes != nbytes:
            raise IOError(
                f"planned reshard bundle {self.key!r} is "
                f"{payload.nbytes} byte(s), header says {nbytes}"
            )
        # Verify-before-commit: nothing touches destination buffers until
        # the bundle checksum passes; a mismatch raises IntegrityError,
        # which the scheduler's peer-read catch converts into a counted
        # storage fallback.
        verify_checksum(payload, crc, f"peer:{self.key}")
        from .io_preparers.array import fast_copyto

        itemsize = self._np_dtype.itemsize
        pos = 0
        for dst_buf, dst_slices, shape in self._regions:
            n = itemsize
            for dim in shape:
                n *= dim
            region = np.frombuffer(
                payload[pos : pos + n], dtype=self._np_dtype
            ).reshape(shape)
            target = dst_buf[dst_slices] if dst_slices else dst_buf
            fast_copyto(target, region)
            pos += n
        if pos != payload.nbytes:
            raise IOError(
                f"planned reshard bundle {self.key!r} has {payload.nbytes - pos} "
                f"trailing byte(s) after {len(self._regions)} region(s)"
            )
        telemetry.counter_add("bytes_resharded_from_peers", pos)
        self.direct.completion.part_done(self.direct.shard.array.location)

    async def consume_buffer(self, buf: BufferType, executor=None) -> None:
        from .io_preparers.array import _executor_submit

        submit = _executor_submit(executor, self.direct.shard.array.location)
        await submit(self._consume_sync, buf)

    def get_consuming_cost_bytes(self) -> int:
        # The fallback path decodes the full stored shard; budget for it.
        return self.direct.get_consuming_cost_bytes()


# --------------------------------------------------------------------------
# The composed restore plan: reshard roles first, coop dedup second.
# --------------------------------------------------------------------------


class ComposedRestorePlan:
    """``take_role`` facade over (planned reshard, coop dedup) for one
    key. Reshard-claimed requests NEVER enter the coop gather —
    snapshot.py filters them symmetrically on every rank (the plan is a
    pure function, so the filter is too) — hence the two subsystems can
    never assign conflicting roles to one request."""

    def __init__(
        self, ctx: ReshardContext, coop_plan: Optional[Any]
    ) -> None:
        self._ctx = ctx
        self._coop = coop_plan

    def take_role(self, read_req: Any):
        consumer = getattr(read_req, "buffer_consumer", None)
        if isinstance(consumer, PlannedRecvConsumer):
            if consumer.owner in self._ctx.session._dead:
                # Known-dead owner at dispatch: skip the doomed wait.
                telemetry.counter_add("fanout_fallbacks", 1)
                telemetry.flightrec.record(
                    "fanout.fallback", key=consumer.key, owner=consumer.owner
                )
                consumer.on_peer_fallback()
                return None
            return RecvRole(self._ctx.session, consumer.key, consumer.owner)
        if isinstance(consumer, PlannedOwnerConsumer):
            # Owners read from storage like a plain request; forwarding
            # happens inside the consumer, after decode.
            return None
        if self._coop is not None:
            return self._coop.take_role(read_req)
        return None

    def mark_done(self, key: str) -> None:
        self._ctx.mark_done(key)

    def abort_incomplete(self) -> None:
        self._ctx.abort_incomplete()
        if self._coop is not None:
            self._coop.abort_incomplete()

    @property
    def n_send(self) -> int:
        base = self._coop.n_send if self._coop is not None else 0
        return base + self._ctx.owned_units

    @property
    def n_recv(self) -> int:
        base = self._coop.n_recv if self._coop is not None else 0
        return base + self._ctx.recv_units


def is_reshard_claimed(read_req: Any) -> bool:
    """True when a read request already carries a planned-reshard role —
    snapshot.py keeps these OUT of the coop unit gather."""
    consumer = getattr(read_req, "buffer_consumer", None)
    return isinstance(consumer, (PlannedRecvConsumer, PlannedOwnerConsumer))
