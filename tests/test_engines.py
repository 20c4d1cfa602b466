"""Engine registry + empirical-trace (bootstrap) substrate.

The registry contract of :mod:`repro.core.engines`: one ``simulate()``
dispatch point, canonical policy names shared with the Python engine, loud
errors for unknown keys — and bit-identical (rtol=0) results across every
engine registered under a policy, including on bootstrap-resampled
empirical traces (``BatchTrace.from_trace``).
"""

import dataclasses

import numpy as np
import pytest

from repro.core import engines
from repro.core.workload import (BatchTrace, Exp, JobClass, Trace, Workload,
                                 replication_stream)


def small_workload(k=32, load=0.8):
    classes = (
        JobClass("s", 1, Exp(1.0), 0.7),
        JobClass("m", 4, Exp(4.0), 0.2),
        JobClass("l", 8, Exp(8.0), 0.1),
    )
    return Workload(k=k, lam=1.0, classes=classes).with_load(load)


# -- registry API -------------------------------------------------------------


def test_registry_covers_the_substrate_policy_grid():
    keys = set(engines.registered())
    for pol in ("fcfs", "modbs-fcfs", "bs-fcfs"):
        for eng in ("python", "jax", "jax-shard", "pallas"):
            assert (pol, eng) in keys
    # the preemptive SRPT pair runs on every scan substrate too, the
    # fused pallas kernels included (in-kernel bitonic rank/permute)
    for pol in ("sf-srpt", "ff-srpt"):
        for eng in ("python", "jax", "jax-shard", "pallas"):
            assert (pol, eng) in keys
    # the python engine also covers the paper comparison policies
    for pol in ("serverfilling", "sf-srpt", "ff-srpt", "msf"):
        assert (pol, "python") in keys
    assert engines.available_engines() == ("jax", "jax-shard", "pallas",
                                           "python")
    assert engines.policies_for("jax") == (
        "bs-fcfs", "fcfs", "ff-srpt", "modbs-fcfs", "sf-srpt")
    assert engines.policies_for("jax-shard") == (
        "bs-fcfs", "fcfs", "ff-srpt", "modbs-fcfs", "sf-srpt")


def test_registry_canonical_aliases():
    assert engines.canonical("bs") == "bs-fcfs"
    assert engines.canonical("modbs") == "modbs-fcfs"
    assert engines.canonical("fcfs") == "fcfs"
    # aliases resolve through the lookup path too
    assert engines.engines_for("bs") == engines.engines_for("bs-fcfs")
    assert engines.get("bs", "jax") is engines.get("bs-fcfs", "jax")


def test_registry_loud_errors():
    wl = small_workload()
    batch = wl.sample_traces(10, 1, seed=0)
    with pytest.raises(KeyError, match="no simulation core"):
        engines.simulate("no-such-policy", batch)
    with pytest.raises(ValueError, match="unknown engine"):
        engines.simulate("fcfs", batch, engine="tpu")
    with pytest.raises(ValueError, match="registered twice"):
        engines.register("fcfs", "jax")(lambda batch, **kw: None)


@pytest.mark.parametrize("policy", ("fcfs", "modbs-fcfs", "bs-fcfs",
                                    "sf-srpt", "ff-srpt"))
def test_pallas_refused_off_the_cpu_at_dispatch(policy, monkeypatch):
    """The kernels do not lower for the TPU: a non-CPU backend gets a
    NotImplementedError naming the roadmap items at dispatch, never a
    Mosaic error and never interpret mode on the accelerator."""
    import jax
    wl = small_workload()
    batch = wl.sample_traces(20, 1, seed=0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="S7/D3"):
        engines.simulate(policy, batch, engine="pallas", wl=wl)
    with pytest.raises(NotImplementedError, match="CPU"):
        engines.simulate_grid(policy, [engines.GridCell(batch, wl=wl)],
                              engine="pallas")


def test_python_cores_require_workload_for_bsf():
    wl = small_workload()
    batch = wl.sample_traces(50, 1, seed=0)
    with pytest.raises(ValueError, match="needs a workload"):
        engines.simulate("bs-fcfs", batch, engine="python")
    # fcfs runs without one
    res = engines.simulate("fcfs", batch, engine="python")
    assert res.response.shape == (1, 50)


def test_explicit_partition_honored_on_every_engine():
    """An explicit partition (no wl) must reach the policy on every
    engine — the python core builds BalancedSplitting from it directly,
    matching the scan cores' _partition_args path bit-for-bit."""
    from repro.core.partition import balanced_partition

    wl = small_workload()
    part = balanced_partition(wl)
    batch = wl.sample_traces(300, 1, seed=2)
    for pol in ("modbs-fcfs", "bs-fcfs"):
        ref = engines.simulate(pol, batch, engine="jax", partition=part)
        for eng in ("python", "pallas", "jax-shard"):
            out = engines.simulate(pol, batch, engine=eng, partition=part)
            assert np.array_equal(out.response, ref.response), (pol, eng)
            assert np.array_equal(out.p_helper, ref.p_helper), (pol, eng)


# -- input validation ---------------------------------------------------------


def test_simulate_rejects_malformed_batches():
    """Malformed inputs fail loudly at the dispatch point (the scan cores
    would silently fold NaNs / time-travelling arrivals into garbage),
    naming the first offending replication."""
    wl = small_workload()
    batch = wl.sample_traces(20, 2, seed=0)

    bad = batch.arrival.copy()
    bad[1, 3] = np.nan
    with pytest.raises(ValueError, match=r"arrival contains NaN.*replication 1"):
        engines.simulate("fcfs", dataclasses.replace(batch, arrival=bad))

    bad = batch.service.copy()
    bad[0, 7] = np.nan
    with pytest.raises(ValueError, match=r"service contains NaN.*replication 0"):
        engines.simulate("fcfs", dataclasses.replace(batch, service=bad))

    bad = batch.arrival.copy()
    bad[1, 5] = bad[1, 4] - 1.0           # time-travelling arrival
    with pytest.raises(ValueError, match=r"not nondecreasing.*replication 1"):
        engines.simulate("fcfs", dataclasses.replace(batch, arrival=bad))

    bad = batch.arrival - batch.arrival[:, :1] - 1.0  # negative, monotone
    with pytest.raises(ValueError, match=r"negative arrival.*replication 0"):
        engines.simulate("fcfs", dataclasses.replace(batch, arrival=bad))

    bad = batch.service.copy()
    bad[0, 2] = -0.5
    with pytest.raises(ValueError, match=r"negative service.*replication 0"):
        engines.simulate("fcfs", dataclasses.replace(batch, service=bad))

    bad = batch.need.copy()
    bad[1, 0] = 0
    with pytest.raises(ValueError, match=r"needs must be >= 1.*replication 1"):
        engines.simulate("fcfs", dataclasses.replace(batch, need=bad))


def test_simulate_rejects_class_ids_outside_partition():
    from repro.core.partition import balanced_partition

    wl = small_workload()
    part = balanced_partition(wl)
    batch = wl.sample_traces(20, 2, seed=0)
    bad = batch.cls.copy()
    bad[1, 4] = len(wl.classes)           # one past the last class
    with pytest.raises(ValueError, match=r"outside the partition.*replication 1"):
        engines.simulate("modbs-fcfs", dataclasses.replace(batch, cls=bad),
                         partition=part)


def test_simulate_rejects_mismatched_failure_batch():
    from repro.core.failures import FailureProcess

    wl = small_workload()
    batch = wl.sample_traces(20, 2, seed=0)
    proc = FailureProcess(mtbf=50.0, mttr=5.0, mode="drain")
    horizon = float(batch.arrival.max())
    with pytest.raises(ValueError, match="failures.k"):
        engines.simulate("fcfs", batch,
                         failures=proc.sample(wl.k + 1, horizon, 2, seed=0))
    with pytest.raises(ValueError, match="failures.reps"):
        engines.simulate("fcfs", batch,
                         failures=proc.sample(wl.k, horizon, 3, seed=0))


# -- BatchTrace.from_trace (bootstrap resampling) -----------------------------


def _ramp_trace(J=60, k=8):
    """Unique gaps (1, 2, ..., J) and services encoding the job index, so a
    resampled record's source index is recoverable from either field."""
    gaps = np.arange(1.0, J + 1)
    return Trace(arrival=np.cumsum(gaps), cls=np.zeros(J, dtype=np.int64),
                 service=100.0 + np.arange(J), need=np.ones(J, np.int64),
                 k=k, C=1)


def test_from_trace_philox_determinism_and_prefix_stability():
    wl = small_workload()
    trace = wl.sample_trace(500, seed=3)
    a = BatchTrace.from_trace(trace, 3, seed=11, method="iid")
    b = BatchTrace.from_trace(trace, 3, seed=11, method="iid")
    assert np.array_equal(a.arrival, b.arrival)
    assert np.array_equal(a.service, b.service)
    assert np.array_equal(a.cls, b.cls)
    # replication r draws from replication_stream(seed, r): a larger batch
    # extends a smaller one without changing the shared prefix
    big = BatchTrace.from_trace(trace, 5, seed=11, method="iid")
    assert np.array_equal(big.arrival[:3], a.arrival)
    # distinct seeds and distinct replications differ
    c = BatchTrace.from_trace(trace, 3, seed=12, method="iid")
    assert not np.array_equal(a.arrival, c.arrival)
    assert not np.array_equal(a.arrival[0], a.arrival[1])
    # workload metadata survives
    assert a.k == trace.k and a.C == trace.C
    # arrivals stay nondecreasing (scan-core invariant)
    assert (np.diff(a.arrival, axis=1) >= 0).all()


def test_from_trace_block_bootstrap_preserves_within_block_gaps():
    trace = _ramp_trace(J=60)
    L = 5
    batch = BatchTrace.from_trace(trace, 3, seed=7, method="block",
                                  block_len=L)
    for r in range(batch.reps):
        gaps = np.diff(batch.arrival[r], prepend=0.0)
        src = np.rint(batch.service[r] - 100.0).astype(int)  # source index
        # records are resampled jointly: the gap of resampled job j is the
        # source job's own interarrival gap (gap value index+1 by
        # construction)
        np.testing.assert_allclose(gaps, src + 1.0)
        # within a block, source indices are consecutive — the block copies
        # a contiguous run of the original trace, bursts intact
        for b in range(0, batch.num_jobs, L):
            blk = src[b:b + L]
            assert (np.diff(blk) == 1).all(), f"rep {r} block at {b}: {blk}"


def test_from_trace_iid_resamples_whole_records():
    trace = _ramp_trace(J=80)
    batch = BatchTrace.from_trace(trace, 2, seed=1, method="iid")
    for r in range(batch.reps):
        gaps = np.diff(batch.arrival[r], prepend=0.0)
        src = np.rint(batch.service[r] - 100.0).astype(int)
        np.testing.assert_allclose(gaps, src + 1.0)   # gap rides with record
        assert 0 <= src.min() and src.max() < trace.num_jobs


def test_from_trace_validation():
    trace = _ramp_trace(J=20)
    with pytest.raises(ValueError, match="unknown bootstrap method"):
        BatchTrace.from_trace(trace, 2, method="stationary")
    with pytest.raises(ValueError, match="at least one replication"):
        BatchTrace.from_trace(trace, 0)
    with pytest.raises(ValueError, match="block_len"):
        BatchTrace.from_trace(trace, 2, method="block", block_len=21)
    empty = dataclasses.replace(trace, arrival=np.empty(0), cls=np.empty(0, np.int64),
                                service=np.empty(0), need=np.empty(0, np.int64))
    with pytest.raises(ValueError, match="empty trace"):
        BatchTrace.from_trace(empty, 2)


# -- registry parity on a bootstrap replication -------------------------------


_RESULT_FIELDS = ("response", "wait", "start", "blocked", "p_helper",
                  "p_routed", "kills", "requeues", "availability",
                  "preemptions")


@pytest.mark.parametrize("k", (32, 256))
def test_every_registered_pair_matches_python_on_bootstrap_rep(k):
    """Iterate the registry: every (policy, engine) pair with a python
    counterpart must agree rtol=0 with the python engine on one bootstrap
    replication at k in {32, 256} — the empirical-trace substrate is
    exactly as trustworthy as the event-driven oracle, including the
    ``preemptions`` observable of the SRPT-family scan cores."""
    wl = small_workload(k=k)
    trace = wl.sample_trace(600, seed=5)
    batch = BatchTrace.from_trace(trace, 1, seed=9, method="block")
    checked = 0
    for policy, engine in engines.registered():
        if engine == "python" or (policy, "python") not in engines.registered():
            continue
        ref = engines.simulate(policy, batch, engine="python", wl=wl)
        # srpt x pallas runs the reference step in the interpreter; a
        # bounded ring keeps the bitonic width (its dominant cost) small.
        # queue_cap never changes results — a too-small cap raises.
        kw = {"queue_cap": 96} \
            if policy.endswith("srpt") and engine == "pallas" else {}
        out = engines.simulate(policy, batch, engine=engine, wl=wl, **kw)
        for f in _RESULT_FIELDS:
            a, b = getattr(out, f), getattr(ref, f)
            assert (a is None) == (b is None), (policy, engine, f)
            if a is not None:
                assert np.array_equal(a, b), (policy, engine, f)
        checked += 1
    # fcfs/modbs-fcfs/bs-fcfs x jax/jax-shard/pallas
    # + sf-srpt/ff-srpt x jax/jax-shard/pallas
    assert checked >= 15


# -- fig3 rows across engines (the acceptance pin) ----------------------------


def test_fig3_rows_bit_identical_across_engines():
    """`fig3_traces --engine jax` rows must be bit-identical (rtol=0) to
    `--engine python` on the same bootstrap replications."""
    from benchmarks import fig3_traces

    kw = dict(num_jobs=800, ks=(256,), loads=(0.7,),
              policies=("fcfs", "modbs-fcfs", "bs-fcfs", "sf-srpt",
                        "ff-srpt"), reps=2)
    rows_jax = fig3_traces.run(engine="jax", **kw)
    rows_py = fig3_traces.run(engine="python", **kw)
    assert len(rows_jax) == len(rows_py) == 2 * 5
    for a, b in zip(rows_jax, rows_py):
        assert a["engine"] == "jax" and b["engine"] == "python"
        for col in a:
            if col in ("engine", "sim_s"):
                continue
            assert a[col] == b[col], (a["policy"], col, a[col], b[col])
