"""The metric that reads the program's own counters: known counters give
known readings, a program without them gives none, and a traced run of
the SRPT cell reports it."""

import os
import sys

import pytest

from bench_testlib import BENCH, tiny_cell

import catalog
import run
from repro.core import spans


def _read():
    path = os.path.join(BENCH, "metrics", "slot_fill_share.py")
    return catalog.load_module(path).read({}, None)


@pytest.fixture(autouse=True)
def _clean_counters():
    spans.reset()
    yield
    spans.reset()


@pytest.mark.parametrize("peaks,qs,share", [
    ([64], [256], 25.0),
    # high-water marks over calls: the largest peak over the largest Q
    ([40, 55, 12], [256, 256, 256], 100.0 * 55 / 256),
    ([7], [8], 87.5),
])
def test_slot_fill_share_reads_the_counters(peaks, qs, share):
    for p, q in zip(peaks, qs):
        spans.high("srpt_peak", p)
        spans.high("srpt_q", q)
    spans.add("fetch_bytes", 1000)
    assert _read() == pytest.approx(share)


@pytest.mark.parametrize("kept", [
    {},                                   # no call yet
    {"fetch_bytes": 10},                  # calls, but no SRPT scan
    {"srpt_peak": 3},                     # no Q
])
def test_slot_fill_share_silent_without_its_counters(kept):
    for name, v in kept.items():
        spans.add(name, v)
    assert _read() is None


def test_slot_fill_share_silent_for_a_program_without_counters(monkeypatch):
    """A program that keeps no counters (no ``repro.core.spans``) gives no
    reading, and no error."""
    spans.high("srpt_peak", 5)
    spans.high("srpt_q", 8)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _read() is None


@pytest.mark.parametrize("trace", [False, True])
def test_srpt_cell_reports_slot_fill_when_traced(trace, counter):
    res = run.run_cell(tiny_cell("sdsc-srpt"), 2**31 + 11, 0.1, trace, 0.0,
                       counter, log=lambda _: None)
    assert res["correct"]
    c = spans.counters()
    assert 0 < c["srpt_peak"] <= c["srpt_q"]
    if trace:
        got = res["metrics"]["slot_fill_share"]
        assert got == {"value": 100.0 * c["srpt_peak"] / c["srpt_q"],
                       "unit": "%"}
    else:
        assert "slot_fill_share" not in res["metrics"]
