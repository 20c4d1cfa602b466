"""Batched substrate: sampling determinism, sweep API, bench harness."""

import json
import os
import subprocess
import sys
import time

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


# -- bench regression guard ---------------------------------------------------


def _fake_report(jps_by_key):
    # 2-tuple keys default to the fig1 scenario; 3-tuples name one
    rows = []
    for key, v in jps_by_key.items():
        bench, (e, p) = ("fig1-critical", key) if len(key) == 2 \
            else (key[0], key[1:])
        rows.append({"bench": bench, "engine": e, "policy": p,
                     "jobs_per_sec": v})
    return {"schema": "bench_sim/v1", "config": {}, "rows": rows}


def test_check_bench_regression_device_count_cells():
    mod = pytest.importorskip(
        "benchmarks.check_bench_regression",
        reason="benchmarks package needs repo root on sys.path")

    def report(rows):
        return {"schema": "bench_sim/v1", "config": {}, "rows": rows}

    def row(engine, policy, jps, dc=None):
        r = {"bench": "fig1-critical", "engine": engine, "policy": policy,
             "jobs_per_sec": jps}
        if dc is not None:
            r["device_count"] = dc
        return r

    base = report([row("jax-shard", "fcfs", 4000.0, dc=4),
                   row("jax-shard", "fcfs", 1000.0, dc=1),
                   row("python", "fcfs", 100.0)])
    # same topology compares: a collapse of the dc=4 cell trips on a
    # >=4-cpu host ...
    slow4 = report([row("jax-shard", "fcfs", 900.0, dc=4),
                    row("python", "fcfs", 100.0)])
    failures = mod.check(slow4, base, factor=2.0, host_cpus=8)
    assert len(failures) == 1 and "[devices=4]" in failures[0]
    # ... but is skipped — not failed — when the committed topology
    # over-subscribes this host's cores
    assert mod.check(slow4, base, factor=2.0, host_cpus=2) == []
    # different topologies never compare: a slow dc=2 cell has no dc=2
    # baseline, and the dc=1 baseline must not be used against it
    slow2 = report([row("jax-shard", "fcfs", 10.0, dc=2),
                    row("python", "fcfs", 100.0)])
    assert mod.check(slow2, base, factor=2.0, host_cpus=8) == []
    # the dc=1 cell is still guarded independently
    slow1 = report([row("jax-shard", "fcfs", 400.0, dc=1),
                    row("python", "fcfs", 100.0)])
    failures = mod.check(slow1, base, factor=2.0, host_cpus=8)
    assert len(failures) == 1 and "[devices=" not in failures[0]
    # python rows are topology-pinned to dc=1: a python row measured in a
    # forced-4-device process still feeds the machine-speed ratio
    slow_host = report([row("jax-shard", "fcfs", 1800.0, dc=4),
                        row("python", "fcfs", 50.0, dc=4)])
    assert mod.check(slow_host, base, factor=2.0, host_cpus=8) == []


def test_check_bench_regression_passes_and_fails_correctly():
    mod = pytest.importorskip(
        "benchmarks.check_bench_regression",
        reason="benchmarks package needs repo root on sys.path")
    check = mod.check

    base = _fake_report({("jax-batch", "fcfs"): 1000.0,
                         ("python", "fcfs"): 100.0})
    same = _fake_report({("jax-batch", "fcfs"): 990.0,
                         ("python", "fcfs"): 95.0})
    assert check(same, base, factor=2.0) == []
    # >2x slowdown on one pair -> exactly that pair flagged
    slow = _fake_report({("jax-batch", "fcfs"): 400.0,
                         ("python", "fcfs"): 95.0})
    failures = check(slow, base, factor=2.0)
    assert len(failures) == 1 and "jax-batch/fcfs" in failures[0]
    # unseen (engine, policy) pairs are not compared
    new_engine = _fake_report({("pallas", "fcfs"): 1.0})
    assert check(new_engine, base, factor=2.0) == []
    # a uniformly 2.5x-slower CI host is NOT a regression: the python-row
    # ratio normalizes the floor (hardware speed is not a code change)
    slow_host = _fake_report({("jax-batch", "fcfs"): 400.0,
                              ("python", "fcfs"): 40.0})
    assert check(slow_host, base, factor=2.0) == []
    # ...but a jitted-engine collapse on that same slow host still trips
    slow_host_regressed = _fake_report({("jax-batch", "fcfs"): 70.0,
                                        ("python", "fcfs"): 40.0})
    failures = check(slow_host_regressed, base, factor=2.0)
    assert len(failures) == 1 and "jax-batch/fcfs" in failures[0]
    # a faster host never loosens the bar (ratio capped at 1)
    fast_host = _fake_report({("jax-batch", "fcfs"): 450.0,
                              ("python", "fcfs"): 300.0})
    assert len(check(fast_host, base, factor=2.0)) == 1
    # scenarios are guarded independently: a collapse in the traces
    # scenario trips even when the fig1 cell of the same pair is healthy
    base2 = _fake_report({("jax-batch", "fcfs"): 1000.0,
                          ("python", "fcfs"): 100.0,
                          ("traces", "jax-batch", "fcfs"): 800.0})
    tr_slow = _fake_report({("jax-batch", "fcfs"): 990.0,
                            ("python", "fcfs"): 100.0,
                            ("traces", "jax-batch", "fcfs"): 100.0})
    failures = check(tr_slow, base2, factor=2.0)
    assert len(failures) == 1 and "traces:jax-batch/fcfs" in failures[0]


def test_check_bench_regression_missing_committed_cells():
    mod = pytest.importorskip(
        "benchmarks.check_bench_regression",
        reason="benchmarks package needs repo root on sys.path")

    def report(rows, config=None):
        return {"schema": "bench_sim/v1", "config": config or {},
                "rows": rows}

    def row(bench, engine, policy, jps, dc=1):
        return {"bench": bench, "engine": engine, "policy": policy,
                "jobs_per_sec": jps, "device_count": dc}

    base = report([row("fig1-critical", "jax-batch", "fcfs", 1000.0),
                   row("fig1-critical", "jax-batch", "bs-fcfs", 800.0),
                   row("grid", "jax-batch", "fcfs", 2000.0),
                   row("fig1-critical", "jax-shard", "fcfs", 900.0, dc=4),
                   row("fig1-critical", "python", "fcfs", 100.0)])
    cfg_all = {"scenario": "all", "device_count": 1,
               "engines": ["python", "jax-batch", "jax-shard"]}
    # a full-coverage run that silently drops a committed cell fails
    # loudly (the dc=4 jax-shard cell is NOT required: this run's
    # topology is dc=1, so it could not have produced that cell)
    fresh = report([row("fig1-critical", "jax-batch", "fcfs", 1000.0),
                    row("grid", "jax-batch", "fcfs", 2000.0),
                    row("fig1-critical", "python", "fcfs", 100.0)],
                   cfg_all)
    failures = mod.check(fresh, base, factor=2.0, host_cpus=8)
    assert len(failures) == 1
    assert "missing" in failures[0] and "bs-fcfs" in failures[0]
    # scenario scoping: a fig1-only run owes no grid rows
    cfg_fig1 = dict(cfg_all, scenario="fig1")
    fresh_fig1 = report(
        [row("fig1-critical", "jax-batch", "fcfs", 1000.0),
         row("fig1-critical", "jax-batch", "bs-fcfs", 800.0),
         row("fig1-critical", "python", "fcfs", 100.0)], cfg_fig1)
    assert mod.check(fresh_fig1, base, factor=2.0, host_cpus=8) == []
    # engine scoping: a --engines jax-batch run owes no python rows
    cfg_nopy = dict(cfg_fig1, engines=["jax-batch"])
    fresh_nopy = report(
        [row("fig1-critical", "jax-batch", "fcfs", 1000.0),
         row("fig1-critical", "jax-batch", "bs-fcfs", 800.0)], cfg_nopy)
    assert mod.check(fresh_nopy, base, factor=2.0, host_cpus=8) == []
    # topology scoping: a dc=4 jax-shard run that drops its committed
    # dc=4 cell fails — unless that topology over-subscribes the host
    cfg_dc4 = dict(cfg_fig1, device_count=4, engines=["jax-shard"])
    failures = mod.check(report([], cfg_dc4), base, factor=2.0,
                         host_cpus=8)
    assert len(failures) == 1 and "jax-shard" in failures[0]
    assert mod.check(report([], cfg_dc4), base, factor=2.0,
                     host_cpus=2) == []
    # pre-config reports (no scenario recorded) skip the guard entirely
    assert mod.check(report([]), base, factor=2.0, host_cpus=8) == []


# -- bench harness ------------------------------------------------------------


@pytest.mark.slow
def test_bench_sim_smoke_emits_well_formed_json(tmp_path):
    bench_sim = pytest.importorskip(
        "benchmarks.bench_sim",
        reason="benchmarks package needs repo root on sys.path")
    out = tmp_path / "BENCH_sim.json"
    # subprocess, not in-process: pin_single_thread_runtime() must run
    # before the first JAX computation to take effect, and pytest has
    # already initialized the backend by now
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo_root, "src"), env.get("PYTHONPATH", "")])
    # the smoke budget assumes the default topology: an inherited forced
    # device count (e.g. from the CI shard job) must not leak in
    env.pop("XLA_FLAGS", None)
    t0 = time.time()
    subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sim", "--smoke",
         "--out", str(out)],
        check=True, cwd=repo_root, env=env, capture_output=True)
    wall = time.time() - t0
    # the budget grew 60 -> 75 s with the sixth (srpt) scenario, then
    # 75 -> 110 s when pallas gained drain-mode failure kernels and the
    # srpt bitonic kernels (three failure cells plus two interpret-mode
    # bitonic srpt cells, each timed twice for the cold/warm split)
    assert wall < 110, f"--smoke took {wall:.1f}s, budget is 110s"
    on_disk = json.loads(out.read_text())
    assert on_disk["schema"] == bench_sim.SCHEMA
    rows = on_disk["rows"]
    # fig1: 5 engines x 3 policies per k; traces: 4 engines x 3 policies;
    # failures: 4 engines x 3 policies (pallas runs the drain-mode fail
    # kernels); grid: 2 engines x 3 policies (jax-batch + jax-shard — no
    # python baseline, no pallas grid core); streaming: jax-batch x 3
    # policies; srpt: python x 2 policies + (jax-batch + pallas +
    # jax-shard) x 2 policies (batch cells only — smoke skips the srpt
    # grid part, whose rows would land in the same regression-guard
    # cells anyway)
    assert len(rows) == \
        15 * len(on_disk["config"]["ks"]) + 12 + 12 + 6 + 3 + 8
    assert {r["bench"] for r in rows} == {"fig1-critical", "traces",
                                          "failures", "grid", "streaming",
                                          "srpt"}
    for r in rows:
        assert set(bench_sim.ROW_KEYS) <= set(r)
        assert r["engine"] in bench_sim.ALL_ENGINES
        assert r["jobs_per_sec"] > 0 and r["wall_s"] > 0
        assert r["device_count"] >= 1
        if r["engine"] == "python" or r["bench"] in ("grid", "streaming"):
            assert r["speedup_vs_python"] is None
        elif r["bench"] == "srpt":
            # only the python_k batch cells price a baseline (full-scale
            # runs add grid-native srpt rows without one)
            assert (r["speedup_vs_python"] is None
                    or r["speedup_vs_python"] > 0)
        else:
            assert r["speedup_vs_python"] > 0
    streaming = [r for r in rows if r["bench"] == "streaming"]
    assert {r["policy"] for r in streaming} == {"fcfs", "modbs-fcfs",
                                                "bs-fcfs"}
    for r in streaming:
        assert r["chunk_jobs"] >= 1     # streaming-only extra key
        assert r["peak_rss_mb"] > 0
    grid = [r for r in rows if r["bench"] == "grid"]
    assert {r["policy"] for r in grid} == {"fcfs", "modbs-fcfs", "bs-fcfs"}
    for r in grid:
        assert r["percell_jobs_per_sec"] > 0   # grid-only extra keys
        assert r["grid_speedup"] > 0
    # the one-program-per-figure claim, asserted: the whole k-grid
    # compiles exactly one XLA program per policy on the in-process path
    assert all(r["compile_count"] == 1 for r in grid
               if r["engine"] == "jax-batch")
    srpt = [r for r in rows if r["bench"] == "srpt"]
    assert {r["policy"] for r in srpt} == {"ff-srpt", "sf-srpt"}
    # every jitted srpt row is exactly one compiled XLA program
    assert all(r["compile_count"] == 1 for r in srpt
               if r["engine"] != "python")
    # the point of the substrate: batched beats the event engine — in the
    # synthetic scenario, on the empirical bootstrap batch, and with the
    # failure branch live in every scan step.  The srpt bench is excluded
    # here: its scan-vs-oracle win needs the full-scale replication count
    # (the committed rows), not the smoke config
    batched = [r for r in rows if r["engine"] == "jax-batch"
               and r["bench"] not in ("grid", "streaming", "srpt")]
    assert {r["bench"] for r in batched} == {"fig1-critical", "traces",
                                             "failures"}
    assert all(r["speedup_vs_python"] > 1 for r in batched)
