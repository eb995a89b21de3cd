"""CPU test of the ``row_spans_busy_s`` reader on made-up spans of a
made-up run, beside the other save readers' tests."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_harness as H  # noqa: E402
from test_chip_bench_program import made_span, train_rec, two_saves  # noqa: E402


@pytest.mark.parametrize("metric,want", [
    ("row_spans_busy_s", 0.3),
    ("encode_busy_s", 3.0),
])
def test_row_spans_reader(metric, want):
    prog = two_saves()
    for step, base in ((8, 0.0), (16, 10.0)):
        prog += [made_span("cnr.save.row_spans", base + 4.5 + k,
                           base + 4.6 + k, step, rows=10) for k in range(3)]
    rec = train_rec(prog)
    assert H.load_reader(metric)(rec) == pytest.approx(want)


def test_row_spans_reader_silent_without_its_spans():
    assert H.load_reader("row_spans_busy_s")(train_rec([])) is None
    # a program whose saves open other spans but no row_spans span (one
    # from before the span existed) reads nothing, not 0
    assert H.load_reader("row_spans_busy_s")(train_rec(two_saves())) is None
