"""The ``span_stat`` reader on hand-made spans. CPU only, no jax."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import spec  # noqa: E402

span_stat = spec.load_module("readers", "span_stat")


def _record(*ops):
    return {"ops": [{"op": op, "lo": lo, "hi": hi, "spans": spans, "phases": []} for op, lo, hi, spans in ops]}


TWO_OVERLAPPING = [("stage_dtoh", 10.0, 1.0), ("stage_dtoh", 10.5, 1.0), ("stage_crc", 11.0, 2.0)]


@pytest.mark.parametrize(
    "ops, args, want, n",
    [
        # two 1.0 s waits offset by 0.5 s: 1.5 s with one open, 2.0 s of waiting in them
        ([("take", 9.0, 14.0, TWO_OVERLAPPING)], ("take", ["stage_dtoh"], "union"), 1.5, 1),
        ([("take", 9.0, 14.0, TWO_OVERLAPPING)], ("take", ["stage_dtoh"], "overlap"), 2.0 / 1.5, 1),
        ([("take", 9.0, 14.0, TWO_OVERLAPPING)], ("take", ["stage_dtoh", "stage_crc"], "union"), 3.0, 1),
        # clipped to the operation's wall: [10.25, 11.25] holds 0.75 s and 0.75 s, union 1.0 s
        ([("take", 10.25, 11.25, TWO_OVERLAPPING)], ("take", ["stage_dtoh"], "union"), 1.0, 1),
        ([("take", 10.25, 11.25, TWO_OVERLAPPING)], ("take", ["stage_dtoh"], "overlap"), 1.5, 1),
        # a span wholly outside the wall, and one of no length, count for nothing
        ([("take", 0.0, 5.0, [("stage_dtoh", 1.0, 1.0), ("stage_dtoh", 7.0, 1.0), ("stage_dtoh", 2.5, 0.0)])],
         ("take", ["stage_dtoh"], "overlap"), 1.0, 1),
        # median over the operations that have the span; the others and the other kind do not count
        ([("take", 0.0, 9.0, [("stage_dtoh", 1.0, 1.0)]), ("take", 10.0, 19.0, [("stage_dtoh", 11.0, 3.0)]),
          ("take", 20.0, 29.0, [("stage_hash", 21.0, 5.0)]), ("restore", 30.0, 39.0, [("stage_dtoh", 31.0, 7.0)])],
         ("take", ["stage_dtoh"], "union"), 2.0, 2),
        ([("restore", 0.0, 9.0, [("consume_assemble", 1.0, 0.5), ("consume_assemble", 3.0, 0.25)])],
         ("restore", ["consume_assemble"], "union"), 0.75, 1),
    ],
)
def test_span_stat_reads_union_and_overlap(ops, args, want, n):
    got = span_stat.read(_record(*ops), *args)
    assert got["value"] == pytest.approx(want) and got["n"] == n
    assert type(got["value"]) is float  # the harness test checks the type of every metric it sees


@pytest.mark.parametrize(
    "ops",
    [
        [],  # an untraced run scrapes nothing
        [("take", 0.0, 9.0, [])],
        [("take", 0.0, 9.0, [("stage_hash", 1.0, 1.0)])],  # a parent program: the span does not exist
        [("restore", 0.0, 9.0, [("stage_dtoh", 1.0, 1.0)])],  # the span, under another operation
        [("take", 0.0, 0.5, [("stage_dtoh", 1.0, 1.0)])],  # wholly clipped away
    ],
)
@pytest.mark.parametrize("stat", ["union", "overlap"])
def test_span_stat_reads_nothing_where_no_named_span_opened(ops, stat):
    assert span_stat.read(_record(*ops), "take", ["stage_dtoh"], stat) is None


def test_span_stat_refuses_an_unknown_statistic():
    with pytest.raises(ValueError):
        span_stat.read(_record(("take", 0.0, 9.0, TWO_OVERLAPPING)), "take", ["stage_dtoh"], "mean")


def test_the_four_metrics_resolve_to_the_reader():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, span in [("stage_dtoh_s", "stage_dtoh"), ("stage_dtoh_overlap", "stage_dtoh"),
                       ("stage_crc_s", "stage_crc"), ("restore_assemble_s", "consume_assemble")]:
        definition = spec.load_metric(name)
        assert definition["reader"] == "span_stat" and definition["args"]["names"] == [span]
        assert by_name[name]["source"] == "program_span"
        assert all(by_name[name] in spec.cell_metrics(bench, w, "per_layer") for w in by_name[name]["workloads"])
