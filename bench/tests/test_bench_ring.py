"""``ring_fill_share``, the metric of the BS helper-wait ring: known
counters give known readings, a program without them gives none, and a
traced run of each BS cell it lists reports it."""

import os
import sys

import pytest

from bench_testlib import BENCH, tiny_cell

import catalog
import run
from repro.core import spans


def _read():
    path = os.path.join(BENCH, "metrics", "ring_fill_share.py")
    return catalog.load_module(path).read({}, None)


@pytest.fixture(autouse=True)
def _clean_counters():
    spans.reset()
    yield
    spans.reset()


@pytest.mark.parametrize("peaks,caps,share", [
    ([405], [8192], 100.0 * 405 / 8192),
    # high-water marks over calls: the largest peak over the largest ring
    ([40, 55, 12], [8192, 8192, 8192], 100.0 * 55 / 8192),
    ([3], [4], 75.0),
])
def test_ring_fill_share_reads_the_counters(peaks, caps, share):
    for p, q in zip(peaks, caps):
        spans.high("bs_ring_peak", p)
        spans.high("bs_ring_cap", q)
    spans.add("bs_jobs", 1000)
    assert _read() == pytest.approx(share)


@pytest.mark.parametrize("kept", [
    {},                                   # no call yet
    {"fetch_bytes": 10, "srpt_peak": 4},  # calls, but no BS scan
    {"bs_ring_peak": 3},                  # no ring size
])
def test_ring_fill_share_silent_without_its_counters(kept):
    for name, v in kept.items():
        spans.add(name, v)
    assert _read() is None


def test_ring_fill_share_silent_for_a_program_without_counters(monkeypatch):
    """A program that keeps no counters (no ``repro.core.spans``) gives no
    reading, and no error."""
    spans.high("bs_ring_peak", 5)
    spans.high("bs_ring_cap", 8)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _read() is None


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", ["sdsc-bs", "fig1-bs"])
def test_bs_cells_report_ring_fill_when_traced(name, trace, counter):
    res = run.run_cell(tiny_cell(name), 2**31 + 13, 0.1, trace, 0.0,
                       counter, log=lambda _: None)
    assert res["correct"]
    c = spans.counters()
    assert 0 < c["bs_ring_peak"] <= c["bs_ring_cap"]
    if trace:
        got = res["metrics"]["ring_fill_share"]
        assert got == {"value": 100.0 * c["bs_ring_peak"] / c["bs_ring_cap"],
                       "unit": "%"}
    else:
        assert "ring_fill_share" not in res["metrics"]
