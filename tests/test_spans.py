"""Host spans and counters of ``engines.simulate`` (``repro.core.spans``).

Every ``jax`` and ``jax-shard`` batch core, run under a CPU profiler
session: the call's spans nest and follow each other as documented, carry
one ``call`` id, and leave the results bit-identical to a run with no
session; the counters read what the call fetched and what its SRPT scan
held.
"""

import numpy as np
import pytest

import jax

from repro.core import engines, spans
from repro.core.workload import Exp, JobClass, Workload

BATCH_ENGINES = ("jax", "jax-shard")
CORES = [(p, e) for e in BATCH_ENGINES for p in engines.policies_for(e)]
PHASES = ("repro.prep", "repro.run", "repro.fetch", "repro.assemble")
FIELDS = ("response", "wait", "p_helper", "blocked", "p_routed", "start",
          "kills", "requeues", "availability", "preemptions")


def _workload(k=32, load=0.8):
    classes = (JobClass("s", 1, Exp(1.0), 0.7),
               JobClass("m", 4, Exp(4.0), 0.2),
               JobClass("l", 8, Exp(8.0), 0.1))
    return Workload(k=k, lam=1.0, classes=classes).with_load(load)


def _simulate(policy, engine, batch, wl):
    return engines.simulate(policy, batch, engine=engine, wl=wl)


def _session():
    # the benchmark harness's session (bench/run.py): no Python tracer
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return _profiler.ProfilerSession(opts)


def _repro_events(profile):
    """(start_ns, end_ns, name, stats) of every ``repro.`` host event."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.start_ns, ev.end_ns, ev.name,
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def _assert_identical(a, b):
    for f in FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            np.testing.assert_array_equal(x, y, err_msg=f)


@pytest.mark.parametrize("policy,engine", CORES)
def test_span_tree_and_bit_identity(policy, engine):
    wl = _workload()
    batch = wl.sample_traces(300, 4, seed=11)
    plain = _simulate(policy, engine, batch, wl)  # compiles, no session
    session = _session()
    traced = _simulate(policy, engine, batch, wl)
    events = _repro_events(session.stop_and_get_profile_data())
    _assert_identical(plain, traced)

    roots = [e for e in events if e[2] == "repro.simulate"]
    assert len(roots) == 1
    s0, e0, _, meta = roots[0]
    assert meta["policy"] == policy and meta["engine"] == engine
    call = meta["call"]
    inner = [e for e in events if e[2] != "repro.simulate"]
    assert {e[2] for e in inner} == set(PHASES)
    for s, e, name, stats in inner:
        assert s0 <= s <= e <= e0, name
        assert stats == {"call": call}, name
    # one run, one fetch, one assemble; prep opens once per helper, all
    # before the run; then run, fetch and assemble, in that order
    first = {name: min(s for s, _, n, _ in inner if n == name)
             for name in PHASES}
    assert [first[p] for p in PHASES] == sorted(first[p] for p in PHASES)
    for name in PHASES[1:]:
        assert sum(n == name for _, _, n, _ in inner) == 1, name
    run = next(e for e in inner if e[2] == "repro.run")
    assert all(e <= run[0] for _, e, n, _ in inner if n == "repro.prep")


@pytest.mark.parametrize("policy,engine", CORES)
def test_fetch_bytes_are_the_outputs(policy, engine, monkeypatch):
    """``fetch_bytes`` adds up the device outputs' bytes, one transfer a
    call."""
    wl = _workload()
    batch = wl.sample_traces(200, 4, seed=5)
    fetched = []
    device_get = jax.device_get

    def recording(tree):
        fetched.append(sum(a.nbytes for a in jax.tree_util.tree_leaves(tree)))
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", recording)
    spans.reset()
    _simulate(policy, engine, batch, wl)
    assert len(fetched) == 1 and fetched[0] > 0
    assert spans.counters()["fetch_bytes"] == fetched[0]


def _peak_in_system(result, batch) -> int:
    """The largest number of jobs in the system over the replications,
    from arrivals (+1) and completions (-1) of a finished result."""
    peak = 0
    for r in range(batch.reps):
        t = np.concatenate([batch.arrival[r],
                            batch.arrival[r] + result.response[r]])
        step = np.concatenate([np.ones(batch.num_jobs, int),
                               -np.ones(batch.num_jobs, int)])
        order = np.lexsort((step, t))  # a departure first at equal times
        peak = max(peak, int(np.cumsum(step[order]).max()))
    return peak


@pytest.mark.parametrize("side", ("pairwise", "sorted"))
@pytest.mark.parametrize("policy,engine",
                         [c for c in CORES if c[0].endswith("srpt")])
def test_srpt_counters_are_the_oracle_peak(policy, engine, side,
                                           monkeypatch):
    """``srpt_peak`` is the oracle's in-system peak on either side of the
    pairwise crossover; ``srpt_pairwise_events`` counts the 2J events of
    every replication below it and is absent above it."""
    from repro.core import sim_jax
    monkeypatch.setattr(sim_jax, "_SRPT_PAIRWISE_MAX_Q",
                        {jax.default_backend(): 64 if side == "pairwise"
                         else 32})
    wl = _workload(load=0.9)
    batch = wl.sample_traces(250, 3, seed=2)
    oracle = engines.simulate(policy, batch, engine="python")
    spans.reset()
    engines.simulate(policy, batch, engine=engine, queue_cap=64)
    c = spans.counters()
    assert c["srpt_q"] == 64
    assert c["srpt_peak"] <= c["srpt_q"]
    assert c["srpt_peak"] == _peak_in_system(oracle, batch)
    if side == "pairwise":
        assert c["srpt_pairwise_events"] == 2 * 250 * 3
    else:
        assert "srpt_pairwise_events" not in c


def _bs_run(path, batch, wl):
    """One BS-FCFS call of ``batch`` on ``path``; the per-job result where
    the path returns one."""
    if path == "grid":
        return engines.simulate_grid(
            "bs-fcfs", [engines.GridCell(batch, wl=wl)], engine="jax")[0]
    if path == "stream":
        engines.simulate_stream("bs-fcfs", batch, chunk_jobs=100, wl=wl)
        return None
    return _simulate("bs-fcfs", path, batch, wl)


@pytest.mark.parametrize("path", BATCH_ENGINES + ("grid", "stream"))
def test_bs_ring_and_helper_counters(path):
    """One BS-FCFS call raises the ring counters, the peak within the ring,
    and a per-job result adds its helper-served and total jobs."""
    wl = _workload(load=0.95)
    batch = wl.sample_traces(300, 4, seed=3)
    spans.reset()
    out = _bs_run(path, batch, wl)
    c = spans.counters()
    assert 0 < c["bs_ring_peak"] <= c["bs_ring_cap"]
    if out is None:
        # a stream's ring holds the backlog cap and one chunk
        assert c["bs_ring_cap"] == 1024 + 100
        assert "bs_jobs" not in c
        return
    assert c["bs_ring_cap"] == 300
    assert c["bs_jobs"] == 300 * 4
    assert c["bs_helper_served"] == round(out.p_helper.sum() * 300)
    assert c["bs_helper_served"] > 0


def test_counters_sum_high_copy_reset():
    spans.reset()
    spans.add("n", 3)
    spans.add("n", 4)
    spans.high("m", 5)
    spans.high("m", 2)
    c = spans.counters()
    assert c == {"n": 7, "m": 5}
    c["n"] = 0
    assert spans.counters()["n"] == 7
    spans.reset()
    assert spans.counters() == {}


def test_no_call_span_outside_a_call():
    """Outside ``engines.simulate`` (the grid and stream paths) the batch
    helpers open no span, and every call gets a fresh id."""
    session = _session()
    with engines.call_span("repro.run"):
        pass
    wl = _workload()
    batch = wl.sample_traces(100, 2, seed=1)
    _simulate("fcfs", "jax", batch, wl)
    _simulate("fcfs", "jax", batch, wl)
    events = _repro_events(session.stop_and_get_profile_data())
    roots = [e for e in events if e[2] == "repro.simulate"]
    assert len(roots) == 2
    ids = [r[3]["call"] for r in roots]
    assert ids[1] > ids[0]
    runs = [e for e in events if e[2] == "repro.run"]
    assert [r[3]["call"] for r in runs] == ids
