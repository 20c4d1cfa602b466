"""The reduction from a profiler trace to the per-layer metrics, on
hand-built traces whose numbers are known."""

import os
from types import SimpleNamespace as NS

import pytest

from bench_testlib import BENCH

import catalog
import trace_reduce as tr

CALLS = [(100, 200), (300, 400)]
HOST = [(100, 200, tr.CALL_SPAN), (100, 200, "dispatch"), (160, 175, "copy"),
        (300, 400, tr.CALL_SPAN), (300, 400, "dispatch"), (220, 280, "gen")]
CHIP_A = [(110, 150, "a"), (140, 160, "b"), (170, 190, "a"),
          (250, 260, "c"), (310, 390, "a")]
CHIP_B = [(100, 400, "x")]


def _metric(name):
    return catalog.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_union_and_overlap():
    assert tr.union([(5, 9), (1, 3), (2, 4), (9, 10), (7, 7)]) == \
        [(1, 4), (5, 10)]
    w = [(0, 10), (20, 30)]
    assert tr.overlap(5, 25, w, [0, 20]) == 10
    assert tr.overlap(10, 20, w, [0, 20]) == 0


def test_one_chip():
    red = tr.reduce({"/device:TPU:0": CHIP_A}, HOST, CALLS)
    assert red["window_ns"] == 200
    assert red["busy_ns"] == {"/device:TPU:0": 150}
    assert red["device_ops"] == [["a", pytest.approx(140e-9)],
                                 ["b", pytest.approx(20e-9)]]
    gaps = dict(red["idle_gaps"])
    assert gaps == {"between calls: gen": pytest.approx(50e-9),
                    "in call: dispatch": pytest.approx(40e-9),
                    "between calls": pytest.approx(40e-9),
                    "in call: copy": pytest.approx(10e-9)}
    # the in-call gaps are the idle part of the calls
    idle_in_calls = sum(v for k, v in gaps.items() if k.startswith("in call"))
    assert idle_in_calls == pytest.approx((200 - 150) * 1e-9)
    assert _metric("device_idle_share").read({}, red) == pytest.approx(25.0)
    assert _metric("device_ns_per_job").read({"traced_jobs": 3}, red) == \
        pytest.approx(50.0)


def test_two_chips_average():
    red = tr.reduce({"/device:TPU:0": CHIP_A, "/device:TPU:1": CHIP_B},
                    HOST, CALLS)
    assert red["busy_ns"] == {"/device:TPU:0": 150, "/device:TPU:1": 200}
    assert dict(red["device_ops"]) == {"x": pytest.approx(100e-9),
                                       "a": pytest.approx(70e-9),
                                       "b": pytest.approx(10e-9)}
    assert dict(red["idle_gaps"])["between calls: gen"] == \
        pytest.approx(25e-9)
    assert _metric("device_idle_share").read({}, red) == pytest.approx(12.5)
    assert _metric("device_ns_per_job").read({"traced_jobs": 7}, red) == \
        pytest.approx(50.0)


def test_idle_chip_and_empty_trace():
    red = tr.reduce({"/device:TPU:0": []}, HOST, CALLS)
    assert red["busy_ns"] == {"/device:TPU:0": 0}
    assert _metric("device_idle_share").read({}, red) == pytest.approx(100.0)
    assert _metric("device_ns_per_job").read({"traced_jobs": 3}, red) is None
    empty = tr.reduce({}, [], [])
    assert _metric("device_idle_share").read({}, empty) is None
    assert _metric("device_idle_share").read({}, None) is None


def test_from_xspace_planes_and_lines():
    def line(name, evs):
        return NS(name=name, events=[NS(start_ns=s, end_ns=e, name=n)
                                     for s, e, n in evs])
    def space(extra=()):
        return NS(planes=[
            NS(name="/device:TPU:0", lines=[line("XLA Modules", CHIP_A),
                                            line("XLA Ops", [(0, 5, "op")]),
                                            *extra]),
            NS(name="/device:TPU:1", lines=[line("XLA Modules", CHIP_B)]),
            NS(name="/device:TPU:0 SparseCore",
               lines=[line("XLA Modules", CHIP_B)]),
            NS(name="/host:CPU", lines=[line("python", HOST),
                                        line("other", [(0, 5, "idle")])]),
        ])
    devices, host, calls = tr.from_xspace(space())
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert devices["/device:TPU:0"] == CHIP_A
    assert devices["/device:TPU:1"] == CHIP_B
    assert sorted(calls) == CALLS and sorted(host) == sorted(HOST)
    # a chip whose trace buffers overflowed makes the trace unusable
    devices, _, calls = tr.from_xspace(
        space([line("XLA TraceMe", [(0, 400, tr.DROPPED)])]))
    assert devices == {} and sorted(calls) == CALLS


def test_from_a_real_cpu_trace():
    """A profiler trace of this process has the call spans on the host."""
    import jax
    import jax.numpy as jnp

    import run
    session = run._profiler_session()
    with jax.profiler.TraceAnnotation(tr.CALL_SPAN):
        (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    devices, host, calls = tr.from_xspace(session.stop_and_get_profile_data())
    assert len(calls) == 1 and calls[0][1] > calls[0][0]
    assert any(name == tr.CALL_SPAN for _, _, name in host)
