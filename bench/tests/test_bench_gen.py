"""The benchmark's own generator and references against the program.

The generator is a copy of the program's sampling arithmetic; these
tests hold the two equal bit for bit, so that the inputs a run makes are
the ones a user of the program would sample.
"""

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


def _reference(policy):
    return catalog.load_module(os.path.join(BENCH, "reference",
                                            policy + ".py"))


def _same(call: dict, batch, lo: int, hi: int) -> None:
    for f in ("arrival", "cls", "service", "need"):
        np.testing.assert_array_equal(call[f], getattr(batch, f)[lo:hi],
                                      err_msg=f)
    assert call["k"] == batch.k and call["C"] == batch.C


@pytest.mark.parametrize("seed", SEEDS)
def test_fig1_poisson_equals_sample_traces(seed):
    from repro.core.workload import figure1_workload
    cfg = _config("fig1-critical")
    wl = figure1_workload(cfg["k"], theta=cfg["load"]["theta"])
    assert gen.arrival_rate(cfg) == wl.lam
    inputs = gen.Inputs(cfg, {"jobs": 300, "reps": 3}, seed)
    batch = wl.sample_traces(300, 6, seed=seed)
    for i in (0, 1):
        _same(inputs.call(i), batch, 3 * i, 3 * i + 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_sdsc_bootstrap_equals_swf_path(seed):
    from repro.core.workload import BatchTrace
    from repro.data.swf import sdsc_sp2_trace
    cfg = _config("sdsc-sp2")
    base = sdsc_sp2_trace(250, k=cfg["k"], load=cfg["load"]["load"],
                          seed=seed)
    batch = BatchTrace.from_trace(base, 8, seed=seed, method="block")
    inputs = gen.Inputs(cfg, {"jobs": 250, "reps": 4}, seed)
    for i in (0, 1):
        _same(inputs.call(i), batch, 4 * i, 4 * i + 4)


@pytest.mark.parametrize("name", ["fig1-critical", "sdsc-sp2"])
def test_reference_partition_equals_eq2(name):
    from repro.core.partition import balanced_partition_for
    cfg = _config(name)
    slots, helpers = _reference("bs-fcfs").partition(cfg)
    part = balanced_partition_for(cfg["k"], gen.needs(cfg), gen.demands(cfg))
    assert tuple(slots) == part.slots and helpers == part.helpers


@pytest.mark.parametrize("policy,config", [
    ("fcfs", "fig1-critical"),
    ("bs-fcfs", "fig1-critical"),
    ("ff-srpt", "sdsc-sp2"),
])
def test_reference_equals_python_oracle(policy, config):
    """The plain references give the event oracle's waits bit for bit on
    the CPU (and its preemption counts, and its share of jobs the helpers
    served)."""
    from repro.core import engines
    from repro.core.partition import balanced_partition_for
    from repro.core.workload import BatchTrace
    cfg = _config(config)
    x = gen.Inputs(cfg, {"jobs": 300, "reps": 2}, 5).call(0)
    batch = BatchTrace(arrival=x["arrival"], cls=x["cls"],
                       service=x["service"], need=x["need"], k=x["k"],
                       C=x["C"])
    part = balanced_partition_for(cfg["k"], gen.needs(cfg), gen.demands(cfg))
    res = engines.simulate(policy, batch, engine="python", partition=part)
    ref = _reference(policy)
    for r in range(2):
        out = ref.simulate(x["arrival"][r], x["cls"][r], x["need"][r],
                           x["service"][r], cfg, np.float64)
        np.testing.assert_array_equal(out["wait"], res.wait[r])
        if out["preemptions"] is not None:
            assert out["preemptions"] == res.preemptions[r]
        if "helper" in out:
            assert out["helper"].mean() == res.p_helper[r]
