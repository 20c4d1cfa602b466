"""The Fig. 3 deployment, ``sdsc-sp2-fig3``: sdsc-sp2's numbers under
BS-pi, so its inputs are sdsc-sp2's and the bs-fcfs reference is exact
on its class mix."""

import json
import os

import numpy as np
import pytest

from bench_testlib import BENCH

import catalog
import gen

SEEDS = (0, 7, 2**31 + 11)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _bs_fcfs():
    return catalog.load_module(os.path.join(BENCH, "reference",
                                            "bs-fcfs.py"))


@pytest.mark.parametrize("seed", SEEDS)
def test_fig3_draws_sdsc_sp2_inputs(seed):
    """Only the policy and guarantees differ from sdsc-sp2: the mix, k,
    load and arrivals are the same numbers, so the calls are the same
    bit for bit."""
    a, b = _config("sdsc-sp2"), _config("sdsc-sp2-fig3")
    for key in ("k", "classes", "normalize_alpha", "load", "arrivals",
                "reduced"):
        assert a[key] == b[key], key
    traffic = {"jobs": 250, "reps": 4}
    for i in (0, 1):
        x = gen.Inputs(a, traffic, seed).call(i)
        y = gen.Inputs(b, traffic, seed).call(i)
        assert x.keys() == y.keys()
        for f in x:
            np.testing.assert_array_equal(x[f], y[f], err_msg=f)


def test_fig3_partition_equals_eq2():
    from repro.core.partition import balanced_partition_for
    cfg = _config("sdsc-sp2-fig3")
    slots, helpers = _bs_fcfs().partition(cfg)
    part = balanced_partition_for(cfg["k"], gen.needs(cfg), gen.demands(cfg))
    assert tuple(slots) == part.slots and helpers == part.helpers
    assert 0 in slots  # class n2 has no block slot: the helpers serve it


def test_fig3_reference_equals_python_oracle():
    """The bs-fcfs reference gives the event oracle's waits and helper
    share bit for bit on the SDSC-SP2 mix, where the helpers carry a
    large share of the jobs."""
    from repro.core import engines
    from repro.core.partition import balanced_partition_for
    from repro.core.workload import BatchTrace
    cfg = _config("sdsc-sp2-fig3")
    x = gen.Inputs(cfg, {"jobs": 300, "reps": 2}, 5).call(0)
    batch = BatchTrace(arrival=x["arrival"], cls=x["cls"],
                       service=x["service"], need=x["need"], k=x["k"],
                       C=x["C"])
    part = balanced_partition_for(cfg["k"], gen.needs(cfg), gen.demands(cfg))
    res = engines.simulate("bs-fcfs", batch, engine="python", partition=part)
    ref = _bs_fcfs()
    for r in range(2):
        out = ref.simulate(x["arrival"][r], x["cls"][r], x["need"][r],
                           x["service"][r], cfg, np.float64)
        np.testing.assert_array_equal(out["wait"], res.wait[r])
        assert out["helper"].mean() == res.p_helper[r]
        assert out["helper"].any()
