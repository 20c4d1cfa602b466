"""Batched substrate: sampling determinism, sweep API, runtime pinning."""

import numpy as np
import pytest

from repro.core.sim_batch import pin_single_thread_runtime, sweep_many_server
from repro.core.workload import (Exp, JobClass, Trace, Workload,
                                 figure1_workload, replication_stream)


def small_workload(k=32, load=0.7):
    classes = (
        JobClass("s", 1, Exp(1.0), 0.7),
        JobClass("m", 4, Exp(4.0), 0.2),
        JobClass("l", 8, Exp(8.0), 0.1),
    )
    return Workload(k=k, lam=1.0, classes=classes).with_load(load)


# -- sampling determinism -----------------------------------------------------


def test_sample_traces_reps_match_derived_single_traces():
    """Replication r of a batch must be bit-identical to the single-trace
    path seeded with the derived Philox stream — so single- and
    multi-replication experiments reproduce each other."""
    wl = small_workload()
    batch = wl.sample_traces(1500, reps=4, seed=42)
    assert batch.reps == 4 and batch.num_jobs == 1500
    for r in range(4):
        single = wl.sample_trace(1500, seed=replication_stream(42, r))
        rep = batch.rep(r)
        assert np.array_equal(rep.arrival, single.arrival)
        assert np.array_equal(rep.cls, single.cls)
        assert np.array_equal(rep.service, single.service)
        assert np.array_equal(rep.need, single.need)


def test_sample_traces_is_reproducible_and_streams_independent():
    wl = small_workload()
    a = wl.sample_traces(800, reps=3, seed=7)
    b = wl.sample_traces(800, reps=3, seed=7)
    assert np.array_equal(a.arrival, b.arrival)
    assert np.array_equal(a.service, b.service)
    # distinct replications and distinct seeds give distinct streams
    assert not np.array_equal(a.arrival[0], a.arrival[1])
    c = wl.sample_traces(800, reps=3, seed=8)
    assert not np.array_equal(a.arrival, c.arrival)


def test_traces_thread_workload_num_classes():
    """A short trace that never samples the last class must still report the
    workload's C — per-class metrics and partition-backed policies rely on
    it.  Hand-built traces fall back to the observed maximum."""
    wl = small_workload()                      # C = 3, class "l" has p = 0.1
    trace = wl.sample_trace(3, seed=0)         # 3 jobs: classes undersampled
    assert trace.C == wl.C == 3
    assert trace.num_classes == 3
    batch = wl.sample_traces(3, 2, seed=0)
    assert batch.num_classes == 3
    assert batch.rep(0).num_classes == 3
    hand = Trace(arrival=np.array([0.0]), cls=np.array([0]),
                 service=np.array([1.0]), need=np.array([1]), k=2)
    assert hand.C is None and hand.num_classes == 1


def test_replication_stream_rejects_negative():
    with pytest.raises(ValueError):
        replication_stream(-1, 0)
    with pytest.raises(ValueError):
        replication_stream(0, -2)


# -- sweep API ----------------------------------------------------------------


def test_sweep_many_server_shapes_and_sanity():
    ks = (32, 64)
    sweep = sweep_many_server(lambda k: figure1_workload(k), ks,
                              num_jobs=2000, reps=3, seed=1)
    assert sweep.points == ks
    assert sweep.policies == ("fcfs", "modbs-fcfs", "bs-fcfs")
    for arr in (sweep.mean_response, sweep.ci95_response, sweep.p_wait,
                sweep.p_helper, sweep.utilization, sweep.sim_s):
        assert arr.shape == (3, len(ks))
    assert (sweep.mean_response > 0).all()
    assert ((0 <= sweep.p_wait) & (sweep.p_wait <= 1)).all()
    assert (sweep.ci95_response >= 0).all()
    # p_helper defined exactly for the BSF policies
    assert np.isnan(sweep.p_helper[0]).all()        # fcfs
    assert not np.isnan(sweep.p_helper[1]).any()    # modbs-fcfs
    assert not np.isnan(sweep.p_helper[2]).any()    # bs-fcfs
    # Cor. 1: BS-π's served fraction is bounded by ModifiedBS-π's
    assert (sweep.p_helper[2] <= sweep.p_helper[1] + 0.02).all()
    rows = sweep.rows("k", extra_cols={"regime": "critical"})
    assert len(rows) == 3 * len(ks)
    assert rows[0]["k"] == 32 and rows[0]["regime"] == "critical"
    assert rows[0]["reps"] == 3


def test_sweep_rejects_unknown_policy():
    with pytest.raises(KeyError):
        sweep_many_server(lambda k: figure1_workload(k), (32,),
                          num_jobs=100, reps=1, policies=("bs",))


def test_sweep_single_rep_has_zero_ci():
    sweep = sweep_many_server(lambda k: figure1_workload(k), (32,),
                              num_jobs=500, reps=1)
    assert (sweep.ci95_response == 0).all()


# -- runtime pinning ----------------------------------------------------------


def test_pin_runtime_noops_after_backend_init():
    """Once any JAX computation has initialized the backend, pinning the
    intra-op pool is impossible — the call must report False and leave the
    runtime fully usable, never crash on a private-API probe."""
    import jax

    jax.devices()  # force backend init (pytest has usually done so already)
    assert pin_single_thread_runtime() is False
    # runtime still works after the no-op
    assert int(jax.numpy.arange(3).sum()) == 3
    # idempotent: repeated calls stay no-ops
    assert pin_single_thread_runtime() is False


def test_backends_initialized_probe_agrees_with_reality():
    import jax

    from repro.core.sim_batch import _backends_initialized

    jax.devices()
    # after init the probe must say so (a probe that lost its API would
    # raise here instead of silently disabling the pin)
    assert _backends_initialized() is True
