"""Shared cloud-storage retry machinery (reference: _RetryStrategy,
storage_plugins/gcs.py:214-270).

Transport-agnostic: used by both the GCS and S3 plugins. One
:class:`CollectiveRetryStrategy` instance is shared by every transfer
coroutine of a snapshot operation; see the class docstring for the
fleet-deadline semantics.
"""

from __future__ import annotations

import asyncio
import logging
import random
import threading
from typing import Any, Callable, Optional

from .. import telemetry

logger = logging.getLogger(__name__)

BASE_BACKOFF_S = 0.5
MAX_BACKOFF_S = 8.0
STALL_TIMEOUT_S = 120.0


def backoff_with_jitter(
    attempt: int,
    base_s: float = BASE_BACKOFF_S,
    cap_s: float = MAX_BACKOFF_S,
) -> float:
    """The retry tier's jittered exponential backoff, as a plain
    function: ``base * 2^attempt * (1 + rand)`` capped at ``cap``. Shared
    by :class:`CollectiveRetryStrategy` and the coordination store's
    connect/failover retries (dist_store) so every retry loop in the
    system jitters the same way. The exponent is capped before
    exponentiating: ``2**attempt`` overflows float conversion near
    attempt ~1076 in a long-lived retry loop."""
    raw = base_s * (2 ** min(attempt, 16)) * (1.0 + random.random())
    return min(raw, cap_s)


def named(fn: Callable[[], Any], op: str) -> Callable[[], Any]:
    """Label a transfer closure for retry telemetry: the plugins'
    ``_retrying`` wrappers read ``__name__`` as the op tag on
    ``storage_retry`` events, and lambdas built per ranged chunk would
    otherwise all report as ``<lambda>``."""
    try:
        fn.__name__ = op
        return fn
    except AttributeError:
        # Bound methods reject attribute writes — wrap instead.
        def call() -> Any:
            return fn()

        call.__name__ = op
        return call


def observe_storage_op(plugin: str, op: Optional[str], seconds: float) -> None:
    """Record one storage operation's latency into the shared
    ``storage.op_s`` histogram, labeled ``<Plugin>.<op>`` — called by
    the plugins' ``_retrying`` wrappers on every SUCCESSFUL attempt, so
    the distribution covers puts, per-part uploads, and ranged gets
    individually (the scalar rate meters only see whole-pipeline
    averages; a long tail here with a healthy mean is the throttling
    signature). One flag check when telemetry is disabled."""
    if not telemetry.enabled():
        return
    telemetry.histogram_observe(
        "storage.op_s", seconds, key=f"{plugin}.{op}" if op else plugin
    )


def is_transient_error(exc: BaseException) -> bool:
    """Classify transport errors worth retrying: 429/5xx-style service
    hiccups, connection and timeout failures. Everything else (permission
    denied, not found, invalid request) propagates immediately."""
    try:
        from google.api_core import exceptions as gexc

        transient = (
            gexc.TooManyRequests,
            gexc.InternalServerError,
            gexc.BadGateway,
            gexc.ServiceUnavailable,
            gexc.GatewayTimeout,
            gexc.DeadlineExceeded,
        )
        if isinstance(exc, transient):
            return True
    except ImportError:  # pragma: no cover
        pass
    try:
        import requests.exceptions as rexc

        # requests.exceptions.ConnectionError subclasses OSError, not the
        # builtin ConnectionError — check it explicitly.
        if isinstance(
            exc, (rexc.ConnectionError, rexc.Timeout, rexc.ChunkedEncodingError)
        ):
            return True
    except ImportError:  # pragma: no cover
        pass
    try:
        import botocore.exceptions as bexc

        if isinstance(
            exc,
            (
                bexc.ConnectionError,
                bexc.HTTPClientError,
                bexc.ReadTimeoutError,
                bexc.ConnectTimeoutError,
            ),
        ):
            return True
        if isinstance(exc, bexc.ClientError):
            code = (
                exc.response.get("ResponseMetadata", {}).get("HTTPStatusCode", 0)
                if getattr(exc, "response", None)
                else 0
            )
            if code == 429 or 500 <= code < 600:
                return True
            if exc.response.get("Error", {}).get("Code") in (
                "SlowDown",
                "RequestTimeout",
                "InternalError",
                "ServiceUnavailable",
            ):
                return True
    except ImportError:  # pragma: no cover
        pass
    return isinstance(exc, (ConnectionError, TimeoutError))


def is_not_found_error(exc: BaseException) -> bool:
    """True for any backend's flavor of not-found: the builtin types
    plus cloud-SDK types (botocore NoSuchKey, google-api NotFound)
    matched by TYPE NAME like :func:`classify_error`, so it needs none
    of the optional SDKs installed. The commit fence reader and fsck
    both classify through here — the two restore-equivalent surfaces
    must never disagree on what counts as missing. KeyError stays in
    the builtin set: KV-style fakes and stores (tests' FakeS3Client,
    dict-backed plugins) surface a missing object as the missing key."""
    if isinstance(exc, (FileNotFoundError, KeyError)):
        return True
    names = {t.__name__ for t in type(exc).__mro__}
    return any("NotFound" in n or "NoSuchKey" in n for n in names)


def classify_error(exc: BaseException) -> str:
    """Coarse error-kind label for telemetry and failure reports:
    ``throttle`` (429/SlowDown), ``server`` (5xx-style service faults),
    ``timeout``, ``connection``, or ``other``. Classification is by
    exception TYPE NAME and embedded status codes so it needs none of
    the optional cloud SDKs installed to run."""
    names = {t.__name__ for t in type(exc).__mro__}
    if "TooManyRequests" in names:
        return "throttle"
    code = None
    response = getattr(exc, "response", None)
    if isinstance(response, dict):
        code = response.get("ResponseMetadata", {}).get("HTTPStatusCode")
        err = response.get("Error", {}).get("Code")
        if code == 429 or err == "SlowDown":
            return "throttle"
        if err in ("RequestTimeout",):
            return "timeout"
        if err in ("InternalError", "ServiceUnavailable"):
            return "server"
    if code is not None and 500 <= int(code) < 600:
        return "server"
    if any(
        n in names
        for n in (
            "InternalServerError",
            "BadGateway",
            "ServiceUnavailable",
            "GatewayTimeout",
        )
    ):
        return "server"
    if "DeadlineExceeded" in names:
        return "timeout"
    if any("Timeout" in n for n in names) or isinstance(exc, TimeoutError):
        return "timeout"
    if "ChunkedEncodingError" in names:
        return "connection"
    if any("Connection" in n for n in names) or isinstance(exc, ConnectionError):
        return "connection"
    return "other"


def attach_retry_history(
    exc: BaseException,
    attempts: int,
    kind: str,
    backoff_slept_s: float,
    fleet_attempts: int,
    fleet_backoff_s: float,
) -> BaseException:
    """Record the retry history ON the exception about to propagate.

    The original exception object (and type) is preserved — callers
    catching transport-specific exceptions keep working — with the
    history attached as attributes and a ``__notes__`` line, so a
    post-mortem shows how hard the fleet tried before the
    shared deadline gave up."""
    exc.retry_attempts = attempts
    exc.retry_error_kind = kind
    exc.retry_backoff_slept_s = round(backoff_slept_s, 3)
    exc.retry_fleet_attempts = fleet_attempts
    exc.retry_fleet_backoff_s = round(fleet_backoff_s, 3)
    note = (
        f"[torchsnapshot_tpu retry] gave up after {attempts} attempt(s) on "
        f"this transfer ({backoff_slept_s:.1f}s backoff slept; error kind: "
        f"{kind}); fleet totals this operation: {fleet_attempts} retry "
        f"attempt(s), {fleet_backoff_s:.1f}s backoff"
    )
    try:
        exc.add_note(note)
    except TypeError:  # pragma: no cover - exotic BaseException subclass
        pass
    return exc


def attach_fallback_history(exc: BaseException, kind: Optional[str] = None) -> str:
    """Degraded-path accounting (mirror failover, peer-channel fallback):
    give ``exc`` the same retry-history attrs a storage-retry exhaustion
    carries — one attempt, zero backoff — UNLESS the storage layer
    already attached real history (a retried-then-exhausted transfer
    must not have its attempt counts zeroed by the fallback layer).
    Returns the classified error kind for the caller's telemetry."""
    kind = kind or classify_error(exc)
    if getattr(exc, "retry_attempts", None) is None:
        attach_retry_history(
            exc,
            attempts=1,
            kind=kind,
            backoff_slept_s=0.0,
            fleet_attempts=0,
            fleet_backoff_s=0.0,
        )
    return kind


class CollectiveRetryStrategy:
    """Shared-deadline retry for a fleet of concurrent transfer coroutines.

    One instance is shared by every transfer of a snapshot. Any coroutine
    completing a unit of work calls :meth:`report_progress`, pushing the
    shared deadline out by ``stall_timeout_s``. A coroutine hitting a
    transient error calls :meth:`backoff_or_raise`: if the fleet as a whole
    has made progress recently it sleeps (exponential backoff + jitter) and
    the caller retries; if nothing anywhere has progressed past the shared
    deadline, the error is re-raised — the service is down, fail fast
    together rather than each coroutine burning its own full retry budget
    serially.

    Not thread-safe by design: all coroutines run on one event loop
    (the scheduler's), so no locking is needed.
    """

    def __init__(
        self,
        stall_timeout_s: float = STALL_TIMEOUT_S,
        base_backoff_s: float = BASE_BACKOFF_S,
        max_backoff_s: float = MAX_BACKOFF_S,
        clock: Callable[[], float] = telemetry.monotonic,
        sleep: Optional[Callable[[float], Any]] = None,
    ) -> None:
        self._stall_timeout_s = stall_timeout_s
        self._base_backoff_s = base_backoff_s
        self._max_backoff_s = max_backoff_s
        self._clock = clock
        self._sleep = sleep or asyncio.sleep
        # Armed lazily on first use: arming at construction would count
        # pre-transfer time (staging, the gap between snapshots) against
        # the stall budget and fail the first transient error with zero
        # retries.
        self._deadline: Optional[float] = None
        # Fleet-wide retry bookkeeping for this strategy instance (one
        # instance per snapshot operation's transfer fleet): surfaced as
        # telemetry events per attempt and attached to the exception on
        # final failure — the attempt history used to vanish here.
        self.fleet_attempts = 0
        self.fleet_backoff_s = 0.0

    def report_progress(self) -> None:
        self._deadline = self._clock() + self._stall_timeout_s

    def reset(self) -> None:
        """Disarm the shared deadline for a new transfer fleet.

        An instance reused across snapshots (via storage_options) would
        otherwise carry the previous fleet's deadline: after an idle gap
        longer than the stall timeout, the first transient error of the next
        snapshot would raise with zero retries."""
        self._deadline = None
        self.fleet_attempts = 0
        self.fleet_backoff_s = 0.0

    def backoff_s(self, attempt: int) -> float:
        return backoff_with_jitter(
            attempt, base_s=self._base_backoff_s, cap_s=self._max_backoff_s
        )

    async def backoff_or_raise(
        self,
        exc: BaseException,
        attempt: int,
        op_started_at: Optional[float] = None,
        op: Optional[str] = None,
        backoff_slept_s: float = 0.0,
    ) -> float:
        """``op_started_at``: when this attempt began. An attempt that
        *started* before the deadline lapsed gets one more retry even if it
        ran long — time spent inside an active transfer is not a stall.

        ``op``: a short label for the transfer unit (e.g. "put", "get")
        carried on the telemetry events. ``backoff_slept_s``: total
        backoff THIS coroutine already slept for the current transfer —
        attached to the exception on final failure."""
        kind = classify_error(exc)
        if self._deadline is None:
            self._deadline = self._clock() + self._stall_timeout_s
        elif self._clock() > self._deadline and (
            op_started_at is None or op_started_at > self._deadline
        ):
            logger.error(
                "No transfer progressed for %.0fs; giving up: %s",
                self._stall_timeout_s,
                exc,
            )
            telemetry.event(
                "storage_retry_exhausted",
                cat="retry",
                kind=kind,
                op=op,
                attempts=attempt + 1,
                fleet_attempts=self.fleet_attempts,
                fleet_backoff_s=round(self.fleet_backoff_s, 3),
            )
            telemetry.flightrec.record(
                "retry.exhausted", kind=kind, op=op, attempts=attempt + 1
            )
            raise attach_retry_history(
                exc,
                attempts=attempt + 1,
                kind=kind,
                backoff_slept_s=backoff_slept_s,
                fleet_attempts=self.fleet_attempts,
                fleet_backoff_s=self.fleet_backoff_s,
            )
        backoff = self.backoff_s(attempt)
        self.fleet_attempts += 1
        self.fleet_backoff_s += backoff
        telemetry.counter_add("retry_attempts", 1)
        telemetry.counter_add("retry_backoff_s", backoff)
        telemetry.event(
            "storage_retry",
            cat="retry",
            kind=kind,
            op=op,
            attempt=attempt,
            backoff_s=round(backoff, 3),
        )
        telemetry.flightrec.record(
            "retry.attempt", kind=kind, op=op, attempt=attempt,
            backoff_s=round(backoff, 3),
        )
        logger.warning("Transient storage error (%s); retrying in %.1fs", exc, backoff)
        await self._sleep(backoff)
        # The slept backoff, so callers can accumulate this coroutine's
        # total and pass it back in via ``backoff_slept_s``.
        return backoff


async def ordered_window_chunks(path, spans, fetch, concurrency):
    """Drive ranged fetches through a bounded in-flight window, yielding
    chunks in offset order — the shared engine of the s3/gcs
    ``read_stream`` implementations. ``fetch(lo, hi)`` returns an
    awaitable future for the bytes of [lo, hi); the window is refilled
    BEFORE each yield so later ranges are on the wire while the consumer
    works, short responses raise (a short ranged response means the
    object changed or was truncated mid-read), and any failure cancels
    the in-flight siblings instead of leaving them running unawaited."""
    tasks = {}
    next_to_fire = 0

    def fire() -> None:
        nonlocal next_to_fire
        while next_to_fire < len(spans) and len(tasks) < concurrency:
            tasks[next_to_fire] = fetch(*spans[next_to_fire])
            next_to_fire += 1

    fire()
    try:
        for idx in range(len(spans)):
            chunk = await tasks.pop(idx)
            fire()  # keep the window full before the consumer works
            lo, hi = spans[idx]
            if len(chunk) != hi - lo:
                raise IOError(
                    f"short read on {path}: got {len(chunk)} bytes for "
                    f"range [{lo}, {hi})"
                )
            yield chunk
    except BaseException:
        for t in tasks.values():
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks.values(), return_exceptions=True)
        raise


# ---------------------------------------------------------------- executor

CLOUD_IO_THREADS_ENV_VAR = "TORCHSNAPSHOT_TPU_CLOUD_IO_THREADS"
_DEFAULT_CLOUD_IO_THREADS = 16

_executor = None
_executor_lock = threading.Lock()


def cloud_io_executor():
    """The dedicated bounded thread pool for cloud-storage transfers.

    The default asyncio loop executor is shared with everything else in
    the process and sized by CPU count; 16-way transfer concurrency
    borrowed from it competes with unrelated work and shrinks on small
    hosts. Cloud I/O threads spend their time blocked in TLS reads and
    socket writes (GIL released), so they are sized independently of
    cores (``TORCHSNAPSHOT_TPU_CLOUD_IO_THREADS``, default 16 — the
    scheduler's I/O concurrency ceiling). One pool per process, shared
    by every S3/GCS plugin instance; threads are created lazily."""
    global _executor
    with _executor_lock:
        if _executor is None:
            import concurrent.futures
            import os

            raw = os.environ.get(CLOUD_IO_THREADS_ENV_VAR, "").strip()
            try:
                workers = int(raw) if raw else _DEFAULT_CLOUD_IO_THREADS
            except ValueError:
                workers = _DEFAULT_CLOUD_IO_THREADS
            _executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(1, workers), thread_name_prefix="tsnap-cloud-io"
            )
        return _executor
