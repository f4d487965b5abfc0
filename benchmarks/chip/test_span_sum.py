"""The ``span_sum`` reader on hand-made spans, and the seven restore
metrics of PR 36 resolving to their readers. CPU only, no jax."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import spec  # noqa: E402

span_sum = spec.load_module("readers", "span_sum")

INNER = ["consume_verify", "consume_hostcopy", "consume_place", "consume_queue", "stream_read_wait",
         "sub_chunk_htod", "consume_assemble"]
OUTER = ["stream_read", "consume"]


def _record(*ops):
    return {"ops": [{"op": op, "lo": lo, "hi": hi, "spans": spans, "phases": []} for op, lo, hi, spans in ops]}


# One streamed entry (4 s) and one buffered entry (2 s) at once: 6 entry-seconds in 4 s of wall.
STREAMED = [("stream_read", 10.0, 4.0), ("stream_read_wait", 10.1, 0.4), ("consume_queue", 10.5, 0.1),
            ("consume_chunk", 10.6, 1.4), ("consume_verify", 10.6, 0.2), ("consume_hostcopy", 10.8, 0.9),
            ("sub_chunk_htod", 11.7, 0.3), ("consume_assemble", 12.5, 1.0)]
BUFFERED = [("storage_read", 9.0, 1.0), ("consume", 10.0, 2.0), ("consume_queue", 10.0, 0.5),
            ("consume_verify", 10.5, 0.25), ("consume_place", 10.75, 1.0)]


@pytest.mark.parametrize(
    "ops, names, minus, want, n",
    [
        # the plain sum: spans open at once count once each, containers not named do not count
        ([("restore", 8.0, 15.0, STREAMED + BUFFERED)], OUTER, (), 6.0, 1),
        # less the inner spans: 4.0 - 2.9 of the streamed entry, 2.0 - 1.75 of the buffered one
        ([("restore", 8.0, 15.0, STREAMED + BUFFERED)], OUTER, INNER, 1.1 + 0.25, 1),
        ([("restore", 8.0, 15.0, STREAMED)], ["stream_read"], ["stream_read_wait", "consume_assemble"], 2.6, 1),
        # clipped to the operation's wall [10, 11]: 1 + 1 outer, less 0.4 + 0.1 + 0.2 + 0.2 and 0.5 + 0.25 + 0.25
        ([("restore", 10.0, 11.0, STREAMED + BUFFERED)], OUTER, INNER, 2.0 - 0.9 - 1.0, 1),
        # a span of no length and one wholly outside the wall count for nothing
        ([("restore", 0.0, 5.0, [("consume", 1.0, 2.0), ("consume", 7.0, 1.0), ("consume", 2.5, 0.0),
                                 ("consume_verify", 1.5, 0.5), ("consume_verify", 7.1, 0.5)])],
         OUTER, INNER, 1.5, 1),
        # the median over the operations that have an outer span; the others and the other kind do not count
        ([("restore", 0.0, 9.0, [("consume", 1.0, 1.0)]), ("restore", 10.0, 19.0, [("consume", 11.0, 3.0)]),
          ("restore", 20.0, 29.0, [("storage_read", 21.0, 5.0), ("consume_verify", 22.0, 1.0)]),
          ("take", 30.0, 39.0, [("consume", 31.0, 7.0)])],
         OUTER, INNER, 2.0, 2),
        # what the parent program gives: the two older inner spans alone
        ([("restore", 8.0, 15.0, [s for s in STREAMED if s[0] in ("stream_read", "consume_chunk", "sub_chunk_htod",
                                                                 "consume_assemble")])],
         OUTER, INNER, 2.7, 1),
    ],
)
def test_span_sum_adds_the_names_and_takes_the_minus_away(ops, names, minus, want, n):
    got = span_sum.read(_record(*ops), "restore", names, minus)
    assert got["value"] == pytest.approx(want) and got["n"] == n
    assert type(got["value"]) is float  # the harness test checks the type of every metric it sees


@pytest.mark.parametrize(
    "ops",
    [
        [],  # an untraced run scrapes nothing
        [("restore", 0.0, 9.0, [])],
        [("restore", 0.0, 9.0, [("storage_read", 1.0, 1.0), ("consume_verify", 2.0, 1.0)])],  # no outer span
        [("take", 0.0, 9.0, [("consume", 1.0, 1.0)])],  # the span, under the other operation
        [("restore", 0.0, 0.5, [("consume", 1.0, 1.0)])],  # wholly clipped away
    ],
)
def test_span_sum_reads_nothing_where_no_named_span_opened(ops):
    assert span_sum.read(_record(*ops), "restore", OUTER, INNER) is None
    assert span_sum.read(_record(*ops), "restore", OUTER) is None


METRICS = [
    ("restore_verify_s", "span_stat", ["consume_verify"], "restore", ["olmo1b.resume", "olmo1b.reshard4"]),
    ("restore_hostcopy_s", "span_stat", ["consume_hostcopy"], "restore", ["olmo1b.resume", "olmo1b.reshard4"]),
    ("restore_place_s", "span_stat", ["consume_place"], "restore", ["olmo1b.resume", "olmo1b.reshard4"]),
    ("restore_htod_s", "span_stat", ["sub_chunk_htod"], "restore", ["olmo1b.resume"]),
    ("restore_queue_s", "span_stat", ["consume_queue"], "schedule", ["olmo1b.resume", "olmo1b.reshard4"]),
    ("restore_read_wait_s", "span_stat", ["storage_read", "stream_read_wait"], "storage",
     ["olmo1b.resume", "olmo1b.reshard4"]),
    ("restore_unnamed_s", "span_sum", OUTER, "restore", ["olmo1b.resume", "olmo1b.reshard4"]),
]


@pytest.mark.parametrize("name, reader, names, layer, cells", METRICS, ids=[m[0] for m in METRICS])
def test_restore_metric_resolves_to_its_reader(name, reader, names, layer, cells):
    bench = spec.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    definition = spec.load_metric(name)
    assert definition["reader"] == reader and definition["args"]["names"] == names
    assert definition["args"]["op"] == "restore" and definition["what"]
    if reader == "span_stat":
        assert definition["args"]["stat"] == "union"
    else:
        assert definition["args"]["minus"] == INNER
    assert hasattr(spec.load_module("readers", reader), "read")
    assert entry["source"] == "program_span" and entry["layer"] == layer and entry["moves"] == "first_step_ms"
    assert entry["unit"] == "s" and entry["better"] == "lower" and entry["workloads"] == cells
    assert all(entry in spec.cell_metrics(bench, w, "per_layer") for w in cells)
    # the reader takes the file's arguments as they are, and finds nothing in a record without spans
    assert spec.load_module("readers", reader).read(_record(), **definition["args"]) is None
