"""Simulation-substrate benchmark — tracks the hot-path perf trajectory.

Six scenarios
(``--scenario {fig1,traces,failures,grid,streaming,srpt,all}``):
the Fig. 1 critical-regime synthetic workload (``bench="fig1-critical"``),
the Fig. 3 empirical-trace path (``bench="traces"``: an SDSC-SP2
synthesized log, moving-block-bootstrapped into replications via
``BatchTrace.from_trace`` and dispatched through the engine registry),
the degraded-capacity path (``bench="failures"``: the Fig. 1
workload with drain-mode MTBF/MTTR outages merged into the event stream
— the failure branch of every scan step is on the hot path, so a
regression there is invisible to the clean scenarios), the grid-native
path
(``bench="grid"``: a dense Fig.-1-workload k-grid as one k/J-padded
compiled program per policy via ``engines.simulate_grid``, timed
against the per-cell dispatch loop — ``compile_count`` must be 1 and
``grid_speedup`` records the whole-grid win; see :func:`bench_grid`),
and the constant-memory streaming path (``bench="streaming"``:
``engines.simulate_stream`` chunk-scanning an unbounded Poisson source
at fixed ``chunk_jobs`` — rows carry a ``peak_rss_mb`` column whose
flatness between the 10^6- and 10^7-job fcfs cells is the
O(R x chunk_jobs) memory claim; see :func:`bench_streaming`),
and the preemptive-scan path (``bench="srpt"``: the SRPT family
``ff-srpt``/``sf-srpt`` on the Fig. 3 SDSC-SP2 bootstrap batch per k,
with a python-oracle baseline at one pivot k and a dense small-k grid
whose rows pin ``compile_count == 1``; the SRPT policies are *excluded*
from the legacy scenarios so their committed cell set stays stable —
see :func:`bench_srpt`).
Each times five engines (``--engines`` selects a subset):

* ``python``    — the exact event-driven engine (the correctness oracle)
* ``jax``       — per-trace ``lax.scan`` (``repro.core.sim_jax``)
* ``jax-batch`` — vmap-over-replications (``repro.core.sim_batch``)
* ``jax-shard`` — the same scan cores with the replications axis sharded
  across the local device mesh (``repro.core.shard``).  ``--devices N``
  exposes N host-platform devices on any CPU box; the row's
  ``device_count`` column records the mesh size so
  ``check_bench_regression`` never compares cells measured on different
  topologies.
* ``pallas``    — fused step kernels (``repro.kernels.msj_scan``), one
  kernel per replication on the Pallas grid.  Off-TPU this runs in
  *interpret mode*: the grid is scanned one replication at a time with
  the kernel body executed as ordinary XLA ops, so on CPU it fuses
  nothing and trails ``jax-batch`` (which advances all replications per
  dispatched op) — the rows exist to track the engine and to pin the
  bit-exactness contract, not CPU speed; the fused win needs a TPU.

and writes ``BENCH_sim.json`` rows with jobs/sec, compile time and the
speedup over the Python engine, so every PR from here on can be compared
against the last committed numbers (``benchmarks.check_bench_regression``
does this automatically in CI).  ``--smoke`` shrinks the config to
finish in well under a minute on CPU (used by the tier-1 test).

JAX engines are timed on a steady-state call (after one compile call,
whose cost is reported separately as ``compile_s`` and whose number of
XLA program compiles — counted via ``jax.monitoring`` — lands in
``compile_count``); jobs/sec for the batched engines counts all
replications.  The persistent compilation cache is on (at
``JAX_COMPILATION_CACHE_DIR``, else ``--cache-dir``, else
``<checkout>/.jax_cache``), and each jitted cell additionally
reports ``compile_warm_s`` — the retrace-plus-cache-load cost measured by
clearing the in-memory jit caches and re-dispatching — so a compile-cache
regression (warm ≈ cold) is visible in the committed rows; on a second
sweep against the same cache dir, ``compile_s`` itself collapses to
roughly ``compile_warm_s``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from repro.core import engines
from repro.core.policies import make_policy
from repro.core.sim_jax import bs_sim, fcfs_sim, modified_bs_sim
from repro.core.simulator import simulate_trace
from repro.core.workload import BatchTrace, figure1_workload, \
    sdsc_sp2_workload
from repro.data.swf import sdsc_sp2_trace

SCHEMA = "bench_sim/v1"

#: required keys of every row — the tier-1 smoke test checks these
ROW_KEYS = ("bench", "engine", "policy", "k", "jobs", "reps", "wall_s",
            "jobs_per_sec", "compile_s", "speedup_vs_python",
            "device_count", "compile_warm_s", "peak_rss_mb",
            "compile_count")

#: process-wide XLA program-compile counter: every backend_compile event
#: jax.monitoring emits bumps it, and ``_time_engine`` samples it around
#: the first (compiling) call of each cell — the grid rows pin this at 1,
#: the "one compiled program per figure grid" claim in executable form
_COMPILES = [0]


def _count_compile(event: str, duration: float, **_) -> None:
    if "backend_compile" in event:
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)

#: row-label -> registry engine name of the timed substrates
ENGINE_LABELS = (("jax", "jax-batch"), ("pallas", "pallas"),
                 ("jax-shard", "jax-shard"))

#: every engine label a row may carry (the --engines CLI choices)
ALL_ENGINES = ("python", "jax", "jax-batch", "pallas", "jax-shard")

#: the preemptive SRPT-family scan policies — benchmarked by their own
#: ``srpt`` scenario only, so the legacy scenarios' committed cell set
#: (and their smoke wall-time) stays stable as the registry grows
SRPT_POLICIES = ("ff-srpt", "sf-srpt")


def _scan_policies(engine: str) -> tuple[str, ...]:
    """Registry policies for ``engine`` minus the SRPT family (those
    rows live in :func:`bench_srpt`, ``bench="srpt"``)."""
    return tuple(p for p in engines.policies_for(engine)
                 if p not in SRPT_POLICIES)


def _row(engine, policy, k, jobs, reps, wall_s, compile_s=None,
         python_jps=None, bench="fig1-critical", device_count=1,
         compile_warm_s=None, peak_rss_mb=None, compile_count=None):
    jps = jobs * reps / wall_s
    return {
        "bench": bench, "engine": engine, "policy": policy,
        "k": k, "jobs": jobs, "reps": reps,
        "wall_s": round(wall_s, 4),
        "jobs_per_sec": round(jps, 1),
        "compile_s": None if compile_s is None else round(compile_s, 3),
        "speedup_vs_python": None if python_jps is None
        else round(jps / python_jps, 2),
        "device_count": device_count,
        "compile_warm_s": None if compile_warm_s is None
        else round(compile_warm_s, 3),
        "peak_rss_mb": None if peak_rss_mb is None
        else round(peak_rss_mb, 1),
        "compile_count": compile_count,
    }


def _peak_rss_mb() -> float:
    """Process peak resident set in MB (ru_maxrss is KB on Linux)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _warm_compile_s(fn, wall: float) -> float | None:
    """Retrace + compile-cache-load cost of ``fn``'s executable.

    Only measured when the persistent compilation cache is enabled
    (``enable_compile_cache``): the in-memory jit caches are dropped so the next
    dispatch re-traces and reloads the executable from the cache dir —
    the steady-state ``wall`` is subtracted out.  Returns None (skipped)
    without a cache: clearing would only re-measure the cold compile.
    """
    if not (jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        return None
    jax.clear_caches()
    t0 = time.time()
    fn()
    return max(0.0, time.time() - t0 - wall)


def _time_engine(fn):
    """(wall_s, compile_s, compile_warm_s, compile_count) of a jitted
    engine call — ``compile_count`` is the number of XLA program compiles
    the first (compiling) call triggered (jax.monitoring)."""
    c0 = _COMPILES[0]
    t0 = time.time(); fn(); first = time.time() - t0
    n_compiles = _COMPILES[0] - c0
    t0 = time.time(); fn(); wall = time.time() - t0
    return wall, max(0.0, first - wall), _warm_compile_s(fn, wall), \
        n_compiles


def bench_point(k: int, jobs: int, reps: int, python_jobs: int,
                seed: int = 0, theta: float = 0.7,
                engines_sel=ALL_ENGINES) -> list[dict]:
    """All engines at one k; python runs ``python_jobs`` arrivals, 1 rep."""
    wl = figure1_workload(k, theta=theta)
    rows = []
    python_jps = {}

    if "python" in engines_sel:
        trace_py = wl.sample_trace(python_jobs, seed=seed)
        for pol in ("fcfs", "modbs", "bs"):
            t0 = time.time()
            simulate_trace(trace_py, make_policy(pol, wl=wl))
            wall = time.time() - t0
            name = make_policy(pol, wl=wl).name
            python_jps[name] = python_jobs / wall
            rows.append(_row("python", name, k, python_jobs, 1, wall))

    if "jax" in engines_sel:
        trace = wl.sample_trace(jobs, seed=seed)
        for name, fn in (("fcfs", lambda: fcfs_sim(trace)),
                         ("modbs-fcfs",
                          lambda: modified_bs_sim(trace, wl=wl)),
                         ("bs-fcfs", lambda: bs_sim(trace, wl=wl))):
            wall, compile_s, warm, nc = _time_engine(fn)
            rows.append(_row("jax", name, k, jobs, 1, wall,
                             compile_s=compile_s,
                             python_jps=python_jps.get(name),
                             device_count=jax.local_device_count(),
                             compile_warm_s=warm, compile_count=nc))

    if any(label in engines_sel for _, label in ENGINE_LABELS):
        batch = wl.sample_traces(jobs, reps, seed=seed)
        rows += _registry_rows(batch, wl, k, jobs, reps, python_jps,
                               engines_sel=engines_sel)
    return rows


def _registry_rows(batch, wl, k, jobs, reps, python_jps,
                   bench="fig1-critical", engines_sel=ALL_ENGINES,
                   failures=None):
    """Batched-substrate rows for every registry policy on one batch."""
    rows = []
    for engine, label in ENGINE_LABELS:
        if label not in engines_sel:
            continue
        # every jitted row records the process topology it was measured
        # under — a forced multi-device pool changes single-device timings
        # too (the intra-op pool is shared), and check_bench_regression
        # must never compare cells across topologies
        dc = jax.local_device_count()
        for name in _scan_policies(engine):
            def fn(e=engine, n=name):
                return engines.simulate(
                    n, batch, engine=e, wl=wl,
                    **({} if failures is None else {"failures": failures}))
            wall, compile_s, warm, nc = _time_engine(fn)
            rows.append(_row(label, name, k, jobs, reps, wall,
                             compile_s=compile_s,
                             python_jps=python_jps.get(name), bench=bench,
                             device_count=dc, compile_warm_s=warm,
                             compile_count=nc))
    return rows


def bench_traces(jobs: int, reps: int, python_jobs: int, seed: int = 0,
                 k: int = 512, load: float = 0.85,
                 engines_sel=ALL_ENGINES) -> list[dict]:
    """The empirical-trace scenario: SDSC-SP2 synthesized log,
    moving-block bootstrap (``BatchTrace.from_trace``) into ``reps``
    replications, every registry policy timed on the same batch
    (``bench="traces"`` rows)."""
    wl = sdsc_sp2_workload(k=k, load=load)
    rows = []
    python_jps = {}
    if "python" in engines_sel:
        trace_py = sdsc_sp2_trace(python_jobs, k=k, load=load, seed=seed)
        py_batch = BatchTrace.from_trace(trace_py, 1, seed=seed,
                                         method="block")
        for pol in _scan_policies("jax"):
            t0 = time.time()
            engines.simulate(pol, py_batch, engine="python", wl=wl)
            wall = time.time() - t0
            python_jps[pol] = python_jobs / wall
            rows.append(_row("python", pol, k, python_jobs, 1, wall,
                             bench="traces"))
    if any(label in engines_sel for _, label in ENGINE_LABELS):
        trace = sdsc_sp2_trace(jobs, k=k, load=load, seed=seed)
        batch = BatchTrace.from_trace(trace, reps, seed=seed,
                                      method="block")
        rows += _registry_rows(batch, wl, k, jobs, reps, python_jps,
                               bench="traces", engines_sel=engines_sel)
    return rows


def bench_failures(jobs: int, reps: int, python_jobs: int, seed: int = 0,
                   k: int = 256, theta: float = 0.7,
                   engines_sel=ALL_ENGINES) -> list[dict]:
    """The degraded-capacity scenario: the Fig. 1 workload with
    drain-mode MTBF/MTTR outages merged into the event stream
    (``bench="failures"`` rows).  Each server sees ~4 outages over the
    horizon, so the failure-event count scales with k exactly like the
    event-stream length does; repairs are short (mttr = horizon/400,
    ~1% average capacity loss) because the critical-regime workload runs
    its class blocks above unit load by design — the helper absorbs the
    overflow with only a ~(1-ρ)k margin, and heavier outages push the
    helper queue past the BS ring-buffer cap at full-scale J.  All three
    jitted engines ship rows: the pallas kernels run the same drain-mode
    merged streams (``*_fail_scan_fwd`` in :mod:`repro.kernels.msj_scan`)
    as the scan cores."""
    from repro.core.failures import FailureProcess

    wl = figure1_workload(k, theta=theta)
    rows = []
    python_jps = {}

    def proc_for(batch):
        horizon = float(batch.arrival.max())
        return FailureProcess(mtbf=horizon / 4, mttr=horizon / 400,
                              mode="drain").sample(
                                  k, horizon, batch.reps, seed=seed)

    if "python" in engines_sel:
        py_batch = wl.sample_traces(python_jobs, 1, seed=seed)
        fb_py = proc_for(py_batch)
        for pol in _scan_policies("jax"):
            t0 = time.time()
            engines.simulate(pol, py_batch, engine="python", wl=wl,
                             failures=fb_py)
            wall = time.time() - t0
            python_jps[pol] = python_jobs / wall
            rows.append(_row("python", pol, k, python_jobs, 1, wall,
                             bench="failures"))
    if any(label in engines_sel for _, label in ENGINE_LABELS):
        batch = wl.sample_traces(jobs, reps, seed=seed)
        rows += _registry_rows(batch, wl, k, jobs, reps, python_jps,
                               bench="failures", engines_sel=engines_sel,
                               failures=proc_for(batch))
    return rows


def bench_grid(ks, jobs, reps, seed=0, theta=0.7,
               engines_sel=ALL_ENGINES) -> list[dict]:
    """The grid-native scenario (``bench="grid"`` rows): a dense
    Fig.-1-workload k-grid as ONE compiled, k/J-padded program per policy
    (``engines.simulate_grid``) versus the per-cell dispatch loop the
    sweeps used to run (one ``engines.simulate`` per k — one compile per
    distinct shape).  The committed topology sits in the dispatch-bound
    regime (many small cells, few reps) where whole-grid execution pays;
    at fig1's compute-bound scale the same program is merely break-even
    on one device, so throughput-scale knobs live in ``grid_cfg``, not
    the global ``ks``/``jobs``/``reps``.  Each grid row's ``jobs_per_sec`` counts every
    cell's jobs, its ``compile_count`` must be exactly 1 (the single
    grid program — the eager-op plumbing compiles nothing), and the
    extra ``percell_jobs_per_sec``/``grid_speedup`` keys record the
    per-cell baseline measured in the same process, so the committed
    rows carry the whole-grid-vs-dispatch win alongside the regression
    floor.  Pallas ships no rows — it has no grid core (``simulate_grid``
    would just fall back to the same per-cell loop)."""
    cells = []
    for k in ks:
        wl = figure1_workload(k, theta=theta)
        cells.append((wl.sample_traces(jobs, reps, seed=seed), wl))
    gcells = [engines.GridCell(b, wl=wl) for b, wl in cells]
    grid_jobs = jobs * len(ks)
    rows = []
    for engine, label in ENGINE_LABELS:
        if label not in engines_sel or engine == "pallas":
            continue
        dc = jax.local_device_count()
        for name in _scan_policies(engine):
            def per_cell(e=engine, n=name):
                for b, wl in cells:
                    engines.simulate(n, b, engine=e, wl=wl)
            cell_wall, _, _, _ = _time_engine(per_cell)

            def fn(e=engine, n=name):
                return engines.simulate_grid(n, gcells, engine=e)
            wall, compile_s, warm, nc = _time_engine(fn)
            r = _row(label, name, max(ks), grid_jobs, reps, wall,
                     compile_s=compile_s, bench="grid", device_count=dc,
                     compile_warm_s=warm, compile_count=nc)
            r["percell_jobs_per_sec"] = round(grid_jobs * reps / cell_wall,
                                              1)
            r["grid_speedup"] = round(cell_wall / wall, 2)
            rows.append(r)
    return rows


#: srpt-scenario configs: batch cells per k on the SDSC-SP2 bootstrap
#: (python baseline at ``python_k`` only — the preemptive oracle
#: re-sorts the queue on every event, a baseline per k would dominate
#: the bench) plus a dense small-k Fig.-1 grid for the one-program row.
#: Smoke skips the grid part: its rows land in the same (bench, engine,
#: policy, device_count) guard cells as the batch rows, and the four
#: extra whole-grid compiles (~11 s) would bust the smoke wall budget —
#: grid-path correctness is pinned by tests/test_grid.py instead.
#: queue_cap=96 (-> Q=128 after the power-of-two round-up) bounds the
#: pallas rows' interpret-mode bitonic cost at smoke scale; the k=64
#: bootstrap peak in-system count stays well under it (no overflow)
SRPT_SMOKE = {"ks": (64,), "python_k": 64, "jobs": 1_200, "reps": 2,
              "python_jobs": 300, "queue_cap": 96}
#: full scale: 32 replications saturate the vmapped sort throughput on
#: one core, and queue_cap=160 trims the slot table to ~3x the measured
#: peak in-system count (~60 at k=512, load 0.85) — the per-step rank
#: sorts are the scan's whole cost, so an oversized Q is pure slowdown
#: (overflow would raise, not mis-simulate; see ``_srpt_args``).  The
#: ``pallas`` sub-config runs the fused-kernel rows at their own reduced
#: scale: the kernels run only in interpret mode (CPU), one replication
#: at a time, so the full 32-rep cells would take hours while measuring
#: only the interpreter — the committed pallas cells track the engine's
#: trajectory at a fixed small topology instead
SRPT_FULL = {"ks": (256, 512, 1024), "python_k": 512, "jobs": 3_000,
             "reps": 32, "python_jobs": 2_000, "queue_cap": 160,
             "grid": ((16, 24, 32, 48, 64, 96), 1_000, 2),
             "pallas": {"ks": (256,), "jobs": 600, "reps": 4,
                        "queue_cap": 160}}


def bench_srpt(jobs, reps, python_jobs, seed=0, ks=(256, 512, 1024),
               python_k=512, load=0.85, grid_cfg=None, queue_cap=None,
               pallas_cfg=None, engines_sel=ALL_ENGINES) -> list[dict]:
    """The preemptive-scan scenario (``bench="srpt"`` rows): the SRPT
    family (``ff-srpt``/``sf-srpt``) on the Fig. 3 empirical path — an
    SDSC-SP2 synthesized log, moving-block-bootstrapped into ``reps``
    replications (``BatchTrace.from_trace``) — timed per k on every
    registered engine.  The python oracle runs once, at ``python_k``
    only, and prices the ``speedup_vs_python`` column of the matching
    jitted rows (the committed k=512 cells carry the scan-vs-oracle
    win on the exact batch the Fig. 3 panel runs).  ``grid_cfg``
    optionally appends grid-native rows — a dense small-k Fig.-1 grid
    through ``engines.simulate_grid`` whose ``compile_count`` pins the
    one-program-per-grid claim for the SRPT cores exactly like the
    ``grid`` scenario does for the FCFS family.  ``pallas_cfg``, when
    given, moves the fused-kernel rows to their own (smaller) topology —
    see the ``SRPT_FULL["pallas"]`` comment."""
    rows = []
    python_jps = {}
    if "python" in engines_sel and python_k in ks:
        wl = sdsc_sp2_workload(k=python_k, load=load)
        trace_py = sdsc_sp2_trace(python_jobs, k=python_k, load=load,
                                  seed=seed)
        py_batch = BatchTrace.from_trace(trace_py, 1, seed=seed,
                                         method="block")
        for pol in SRPT_POLICIES:
            t0 = time.time()
            engines.simulate(pol, py_batch, engine="python", wl=wl)
            wall = time.time() - t0
            python_jps[pol] = python_jobs / wall
            rows.append(_row("python", pol, python_k, python_jobs, 1,
                             wall, bench="srpt"))
    def batch_cells(cell_ks, cell_jobs, cell_reps, cell_qc, labels):
        for k in cell_ks:
            trace = sdsc_sp2_trace(cell_jobs, k=k, load=load, seed=seed)
            batch = BatchTrace.from_trace(trace, cell_reps, seed=seed,
                                          method="block")
            for engine, label in labels:
                if label not in engines_sel:
                    continue
                dc = jax.local_device_count()
                for name in SRPT_POLICIES:
                    if (name, engine) not in engines.registered():
                        continue
                    def fn(e=engine, n=name):
                        return engines.simulate(n, batch, engine=e,
                                                queue_cap=cell_qc)
                    wall, compile_s, warm, nc = _time_engine(fn)
                    r = _row(
                        label, name, k, cell_jobs, cell_reps, wall,
                        compile_s=compile_s,
                        python_jps=(python_jps.get(name)
                                    if k == python_k
                                    and cell_reps == reps else None),
                        bench="srpt", device_count=dc,
                        compile_warm_s=warm, compile_count=nc)
                    if cell_qc is not None:
                        r["queue_cap"] = cell_qc   # srpt-only extra key
                    rows.append(r)

    main_labels = tuple((e, l) for e, l in ENGINE_LABELS
                        if not (e == "pallas" and pallas_cfg))
    batch_cells(ks, jobs, reps, queue_cap, main_labels)
    if pallas_cfg:
        batch_cells(pallas_cfg["ks"], pallas_cfg["jobs"],
                    pallas_cfg["reps"], pallas_cfg.get("queue_cap"),
                    (("pallas", "pallas"),))
    if grid_cfg:
        gks, gjobs, greps = grid_cfg
        gcells = []
        for k in gks:
            wl = figure1_workload(k, theta=0.7)
            gcells.append(engines.GridCell(
                wl.sample_traces(gjobs, greps, seed=seed), wl=wl))
        grid_jobs = gjobs * len(gks)
        for engine, label in ENGINE_LABELS:
            if label not in engines_sel:
                continue
            dc = jax.local_device_count()
            for name in SRPT_POLICIES:
                if (name, engine) not in engines.grid_registered():
                    continue
                def gfn(e=engine, n=name):
                    return engines.simulate_grid(n, gcells, engine=e)
                wall, compile_s, warm, nc = _time_engine(gfn)
                rows.append(_row(label, name, max(gks), grid_jobs,
                                 greps, wall, compile_s=compile_s,
                                 bench="srpt", device_count=dc,
                                 compile_warm_s=warm, compile_count=nc))
    return rows


#: (policy, total_jobs) streaming cells, smallest-state-first so the
#: peak-RSS high-water comparison between the two fcfs rows stays clean
STREAM_SMOKE = {"k": 64, "chunk_jobs": 20_000, "reps": 2,
                "grid": (("fcfs", 200_000), ("modbs-fcfs", 200_000),
                         ("bs-fcfs", 200_000))}
STREAM_FULL = {"k": 256, "chunk_jobs": 100_000, "reps": 2,
               # the k=256 critical-regime queue tops 1024 jobs at a
               # chunk boundary; the backlog cap only bounds *carried*
               # jobs, so raising it keeps memory O(chunk_jobs)
               "backlog_cap": 8192,
               "grid": (("fcfs", 1_000_000), ("fcfs", 10_000_000),
                        ("modbs-fcfs", 10_000_000),
                        ("bs-fcfs", 2_000_000))}


def bench_streaming(grid, reps, chunk_jobs, k, seed=0, backlog_cap=None,
                    engines_sel=ALL_ENGINES) -> list[dict]:
    """The constant-memory scenario: ``simulate_stream`` over an unbounded
    ``PoissonSource`` at fixed ``chunk_jobs`` (``bench="streaming"`` rows,
    ``engine="jax-batch"`` — the streaming cores are the vmapped registry
    scan path chunk-scanned with an explicit carry).  Each row records the
    process **peak RSS** at its completion: within a standalone
    ``--scenario streaming`` run (how the committed rows are produced and
    how the CI lane runs it) the grid goes smallest-state-first, so a flat
    ``peak_rss_mb`` between the 10^6- and 10^7-job fcfs rows *is* the
    constant-memory claim — O(R x chunk_jobs), independent of the stream
    length.  Under ``--scenario all`` the high-water is inherited from the
    monolithic scenarios and the column is not meaningful.  Streams are
    timed in one shot (per-chunk compiles amortize across the stream), so
    ``compile_s`` is None and there is no python baseline row — the
    regression guard keys these cells on their own committed minima."""
    from repro.core.workload import PoissonSource

    if "jax-batch" not in engines_sel:
        return []
    wl = figure1_workload(k, theta=0.7)
    dc = jax.local_device_count()
    rows = []
    for pol, jobs in grid:
        src = PoissonSource(wl, reps=reps, seed=seed)
        kw = {} if backlog_cap is None or pol != "bs-fcfs" \
            else {"backlog_cap": backlog_cap}
        c0 = _COMPILES[0]
        t0 = time.time()
        engines.simulate_stream(pol, src, engine="jax",
                                chunk_jobs=chunk_jobs, total_jobs=jobs,
                                wl=wl, **kw)
        wall = time.time() - t0
        r = _row("jax-batch", pol, k, jobs, reps, wall, bench="streaming",
                 device_count=dc, peak_rss_mb=_peak_rss_mb(),
                 compile_count=_COMPILES[0] - c0)
        r["chunk_jobs"] = chunk_jobs      # streaming-only extra key
        rows.append(r)
    return rows


def run(ks, jobs, reps, python_jobs, seed=0, scenario="all",
        traces_k=512, engines_sel=ALL_ENGINES, streaming_cfg=None,
        grid_cfg=None, srpt_cfg=None):
    rows = []
    if scenario in ("fig1", "all"):
        for k in ks:
            rows += bench_point(k, jobs, reps, python_jobs, seed=seed,
                                engines_sel=engines_sel)
    if scenario in ("traces", "all"):
        rows += bench_traces(jobs, reps, python_jobs, seed=seed,
                             k=traces_k, engines_sel=engines_sel)
    if scenario in ("failures", "all"):
        rows += bench_failures(jobs, reps, python_jobs, seed=seed,
                               k=min(ks), engines_sel=engines_sel)
    if scenario in ("grid", "all"):
        gks, gjobs, greps = grid_cfg or (ks, jobs, reps)
        rows += bench_grid(gks, gjobs, greps, seed=seed,
                           engines_sel=engines_sel)
    if scenario in ("streaming", "all"):
        cfg = streaming_cfg or STREAM_SMOKE
        rows += bench_streaming(cfg["grid"], cfg["reps"],
                                cfg["chunk_jobs"], cfg["k"], seed=seed,
                                backlog_cap=cfg.get("backlog_cap"),
                                engines_sel=engines_sel)
    if scenario in ("srpt", "all"):
        cfg = srpt_cfg or SRPT_SMOKE
        rows += bench_srpt(cfg["jobs"], cfg["reps"], cfg["python_jobs"],
                           seed=seed, ks=cfg["ks"],
                           python_k=cfg["python_k"],
                           grid_cfg=cfg.get("grid"),
                           queue_cap=cfg.get("queue_cap"),
                           pallas_cfg=cfg.get("pallas"),
                           engines_sel=engines_sel)
    return {"schema": SCHEMA,
            "config": {"ks": list(ks), "jobs": jobs, "reps": reps,
                       "python_jobs": python_jobs, "seed": seed,
                       "grid": (None if grid_cfg is None else
                                {"ks": list(grid_cfg[0]),
                                 "jobs": grid_cfg[1],
                                 "reps": grid_cfg[2]}),
                       "srpt": (None if srpt_cfg is None else
                                {"ks": list(srpt_cfg["ks"]),
                                 "python_k": srpt_cfg["python_k"],
                                 "jobs": srpt_cfg["jobs"],
                                 "reps": srpt_cfg["reps"],
                                 "python_jobs": srpt_cfg["python_jobs"],
                                 "queue_cap":
                                     srpt_cfg.get("queue_cap"),
                                 "pallas": srpt_cfg.get("pallas")}),
                       "scenario": scenario, "traces_k": traces_k,
                       "engines": list(engines_sel),
                       "device_count": jax.local_device_count()},
            "rows": rows}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Benchmark the simulation engines "
                    "(python | jax | jax-batch | jax-shard | pallas).",
        epilog="Engines: 'python' is the exact event-driven oracle; 'jax' "
               "is the per-trace lax.scan; 'jax-batch' is the vmapped "
               "replication batch (the production sweep path); "
               "'jax-shard' shards the replications axis across the local "
               "device mesh (pair with --devices N — any CPU box can "
               "expose N host devices); 'pallas' is the fused "
               "step-kernel family of repro.kernels.msj_scan — it runs "
               "only on the CPU, in Pallas interpret mode (one replication at a "
               "time, unfused XLA ops), so its CPU rows track correctness "
               "and trajectory, not the fused speed. "
               "fig1_critical/fig2_regimes accept the same "
               "--engine {python,jax,jax-shard,pallas} selection.")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, < 60 s on CPU")
    ap.add_argument("--scenario",
                    choices=("fig1", "traces", "failures", "grid",
                             "streaming", "srpt", "all"),
                    default="all",
                    help="fig1 = synthetic critical-regime sweep; traces "
                         "= SDSC-SP2 bootstrap batch (the Fig. 3 path); "
                         "failures = fig1 workload with drain-mode "
                         "MTBF/MTTR outages merged into the event stream; "
                         "grid = the whole fig1 k-grid as one compiled "
                         "program per policy vs the per-cell dispatch "
                         "loop (compile_count pins 1 program per grid); "
                         "streaming = simulate_stream chunked-carry rows "
                         "with the peak-RSS column (run standalone for a "
                         "meaningful RSS high-water); srpt = the "
                         "preemptive ff-srpt/sf-srpt scan cores on the "
                         "Fig. 3 SDSC-SP2 bootstrap batch per k, plus "
                         "their one-program grid rows")
    ap.add_argument("--engines", nargs="+", choices=ALL_ENGINES,
                    default=None,
                    help="subset of engines to time (default: all; rows "
                         "without python rows carry no speedup column)")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count for the jax-shard "
                         "rows (default: honor an existing XLA_FLAGS "
                         "entry, else 1); must run before JAX init")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent JAX compilation-cache dir (default: "
                         "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                         "wins when set)")
    ap.add_argument("--ks", type=int, nargs="+", default=None)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--python-jobs", type=int, default=None)
    ap.add_argument("--out", default="BENCH_sim.json")
    args = ap.parse_args(argv)
    from repro.core.shard import enable_compile_cache

    from .common import configure_scan_runtime
    # warn=True: loud if something beat us to init
    configure_scan_runtime(devices=args.devices, warn=True)
    enable_compile_cache(args.cache_dir)
    if args.smoke:
        ks, jobs, reps, pj, tk = (64,), 20_000, 4, 2_000, 256
        stream_cfg = STREAM_SMOKE
        srpt_cfg = SRPT_SMOKE
        # two cells so the smoke grid actually stacks and k-pads
        grid_cfg = ((64, 128), 2_000, 2)
    else:
        # 16 replications: the batched engines amortize the scan's fixed
        # per-step dispatch across lanes, and the CIs tighten for free
        ks, jobs, reps, pj, tk = (256, 1024), 100_000, 16, 100_000, 512
        stream_cfg = STREAM_FULL
        srpt_cfg = SRPT_FULL
        # the committed grid topology: a *dense* 12-point k-grid in the
        # dispatch-bound regime (small cells, few reps) — exactly the
        # shape the scaling-regime sweeps of ROADMAP item 5 run, and the
        # regime where one-program-per-figure pays (per-cell dispatch
        # and XLA loop trips amortize across cells; at fig1's
        # compute-bound scale the same grid is merely break-even)
        grid_cfg = ((16, 20, 24, 28, 32, 40, 48, 56, 64, 72, 80, 96),
                    2_000, 2)
    ks = tuple(args.ks) if args.ks else ks
    jobs = args.jobs or jobs
    reps = args.reps or reps
    pj = args.python_jobs or pj
    grid_cfg = (tuple(args.ks) if args.ks else grid_cfg[0],
                args.jobs or grid_cfg[1], args.reps or grid_cfg[2])
    srpt_cfg = {**srpt_cfg,
                **({"ks": tuple(args.ks)} if args.ks else {}),
                **({"jobs": args.jobs} if args.jobs else {}),
                **({"reps": args.reps} if args.reps else {}),
                **({"python_jobs": args.python_jobs}
                   if args.python_jobs else {})}
    report = run(ks, jobs, reps, pj, scenario=args.scenario, traces_k=tk,
                 engines_sel=tuple(args.engines or ALL_ENGINES),
                 streaming_cfg=stream_cfg, grid_cfg=grid_cfg,
                 srpt_cfg=srpt_cfg)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    for r in report["rows"]:
        print(f"{r['bench']:>13} {r['engine']:>9} {r['policy']:<10} "
              f"k={r['k']:<5} dc={r['device_count']} "
              f"{r['jobs_per_sec']:>12,.0f} jobs/s"
              + (f"  ({r['speedup_vs_python']}x python)"
                 if r["speedup_vs_python"] else ""), file=sys.stderr)
    print(f"wrote {args.out}", file=sys.stderr)
    return report


if __name__ == "__main__":
    main()
