"""Checkpoint telemetry: one observability subsystem for the pipeline.

Enable with ``TORCHSNAPSHOT_TPU_TELEMETRY=1``. See core.py for the event
bus (spans/counters/gauges/rates), export.py for the Chrome-trace and
persisted-summary formats, aggregate.py for the cross-rank fleet merge,
and docs/source/telemetry.rst for the operator guide.

Typical programmatic use::

    from torchsnapshot_tpu import telemetry
    telemetry.set_enabled(True)
    Snapshot.take(path, app_state)
    summary = telemetry.last_summary()       # plain dict
    telemetry.write_chrome_trace("take.json")  # load in Perfetto
"""

from .core import (  # noqa: F401
    HISTOGRAM_BOUNDS,
    TELEMETRY_ENV_VAR,
    OpRecorder,
    Span,
    annotate_next_op,
    begin_op,
    counter_add,
    counters,
    dropped_events,
    enabled,
    event,
    events,
    gauge_set,
    gauges,
    handoff_span,
    histogram_observe,
    histogram_quantile,
    histograms,
    last_attribution,
    last_fleet,
    last_summary,
    monotonic,
    record_rate,
    refresh_from_env,
    register_rate_listener,
    reset,
    set_enabled,
    set_last_attribution,
    set_last_fleet,
    span,
)
from .export import (  # noqa: F401
    TELEMETRY_SUMMARY_FNAME,
    TRACE_DIR,
    build_summary_document,
    chrome_trace,
    chrome_trace_json,
    fmt_bytes,
    render_openmetrics,
    render_summary_document,
    trace_path_for_rank,
    write_chrome_trace,
)
from .aggregate import merge_histograms, merge_summaries  # noqa: F401
# The always-on observability planes (ISSUE 7): the flight recorder
# (bounded ring + abort dumps + blackbox merge, event registry in
# taxonomy.py), the live health plane (heartbeats over the coordination
# store), and the per-root checkpoint history (trend/regression
# detection). Imported as submodules — their APIs are namespaced
# (flightrec.record, health.update, ...), matching how the pipeline
# calls them. NOTE the registry module is named ``taxonomy`` (not
# ``events``) so it can never shadow the ``events()`` scrape function
# exported from core above.
from . import flightrec, health, history, taxonomy  # noqa: F401, E402
# The stall-forensics plane (ISSUE 13): an always-on hang watchdog that
# samples thread stacks, self-triggers on overdue collectives / slow
# storage ops / frozen progress, answers remote dump requests from
# `watch --dump`, and feeds the WEDGE finding class into `blackbox`.
# Imported after flightrec/health — it consumes both.
from . import forensics  # noqa: F401, E402
# The performance-attribution plane (ISSUE 8): critpath reconstructs the
# cross-rank critical path of a take/restore and names the binding
# resource (the `explain` CLI's engine); promexp serves the live
# OpenMetrics endpoint (TORCHSNAPSHOT_TPU_METRICS_PORT). Namespaced like
# the other planes (critpath.build_attribution, promexp.maybe_start).
from . import critpath, promexp  # noqa: F401, E402


def record_election(**fields) -> None:
    """Record one IOGovernor election on BOTH planes: the always-on
    flight recorder (so ``blackbox`` shows what the governor chose
    before an abort) and, bus permitting, a ``cat="governor"`` instant
    the OpRecorder folds into ``summary["governor"]`` (what ``explain
    -v``/``stats -v`` render and ``.snapshot_critpath`` persists).
    One helper so an election site can never wire half the pair."""
    flightrec.record("governor.elect", **fields)
    event("governor_elect", cat="governor", **fields)
