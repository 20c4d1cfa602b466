"""Shared benchmark plumbing: run a policy set over traces, emit CSV."""

from __future__ import annotations

import sys
import time

import numpy as np

from repro.core.policies import make_policy
from repro.core.simulator import simulate_trace
from repro.core.workload import BatchTrace, Workload

#: the policy set the paper benchmarks against (Figures 1-3)
PAPER_POLICIES = ("bs", "fcfs", "serverfilling", "sf-srpt", "ff-srpt", "msf")

#: policies with a batched lax.scan simulator (``repro.core.sim_batch``);
#: bs-fcfs is BS-π proper (Def. 1 pull-backs) on the event-indexed scan,
#: modbs-fcfs doubles as the Cor.-1 upper bound on BS-π's P_H.
JAX_POLICIES = ("fcfs", "modbs-fcfs", "bs-fcfs")

#: the engine choices every benchmark CLI exposes
ENGINES = ("python", "jax", "jax-shard", "pallas")
ENGINE_HELP = ("jax = batched vmap scans (default); jax-shard = the same "
               "scans with replications sharded across the local device "
               "mesh (combine with --devices N on any CPU box), "
               "bit-identical to jax; pallas = fused step kernels, "
               "bit-identical but interpret-mode (slower), CPU only; "
               "python = exact event engine, full paper policy set")


def configure_scan_runtime(devices: int | None = None, *,
                           warn: bool = False) -> bool:
    """Configure the XLA runtime for the scan cores.

    Thin wrapper over :func:`repro.core.shard.configure_runtime`:
    ``devices`` host devices and a 1-thread intra-op pool per device (the
    per-op-dispatch win of the old single-thread pin, times N) on a CPU
    backend; nothing on an accelerator.  Benchmark *mains* call this
    first with ``warn=True`` so a caller that raced backend init hears
    about the dead pool loudly; the opportunistic internal calls (every
    jax-engine helper routes through here) keep ``warn=False`` and simply
    inherit whatever runtime exists.
    """
    from repro.core.shard import configure_runtime
    return configure_runtime(devices=devices, intra_op_threads=1, warn=warn)


def run_policies_jax(wl_factory, points, point_col: str, *, num_jobs: int,
                     reps: int, seed: int = 0, policies=JAX_POLICIES,
                     engine: str = "jax", grid: bool = True,
                     extra_cols=None, per_point_cols=None, failures=None,
                     ckpt_dir: str | None = None,
                     resume: bool = False) -> list[dict]:
    """Batched-substrate counterpart of :func:`run_policies`.

    One ``sweep_many_server`` call over ``wl_factory(point)``; returns CSV
    rows with mean/CI columns.  ``per_point_cols`` is an optional sequence
    (parallel to ``points``) of extra per-point column dicts.  ``engine``
    is ``"jax"`` (vmapped scans), ``"jax-shard"`` (replications sharded
    over the local device mesh) or ``"pallas"`` (fused step kernels —
    interpret mode, CPU only: bit-identical results, slower).
    ``grid=True`` (default) runs the sweep grid-natively — one
    ``engines.simulate_grid`` launch per policy over every
    not-yet-checkpointed point — and ``grid=False`` forces per-cell
    dispatch; results are bit-identical either way.
    ``failures``/``ckpt_dir``/``resume`` pass straight through to
    :func:`~repro.core.sim_batch.sweep_many_server` (fault injection and
    crash-resumable per-cell checkpointing).
    """
    from repro.core.sim_batch import sweep_many_server
    configure_scan_runtime()
    sweep = sweep_many_server(wl_factory, points, num_jobs=num_jobs,
                              reps=reps, seed=seed, policies=policies,
                              engine=engine, grid=grid, failures=failures,
                              ckpt_dir=ckpt_dir, resume=resume)
    return sweep.rows(point_col, extra_cols=extra_cols,
                      per_point_cols=per_point_cols)


def run_policies(wl: Workload, num_jobs: int, seed: int,
                 policies=PAPER_POLICIES, extra_cols=None, *,
                 engine: str = "python", reps: int = 1) -> list[dict]:
    """One CSV row per policy on a trace sampled from ``wl``.

    ``engine="python"`` (the default) keeps the original single-trace
    event-engine path.  Fast engines sample a ``reps``-replication Philox
    batch and dispatch every policy through the engine registry
    (:func:`run_policies_batch`), falling back to the python engine for
    policies the scan substrate does not cover.
    """
    if engine != "python":
        batch = wl.sample_traces(num_jobs, reps, seed=seed)
        return run_policies_batch(batch, wl, policies, engine=engine,
                                  extra_cols=extra_cols)
    trace = wl.sample_trace(num_jobs, seed=seed)
    rows = []
    for name in policies:
        pol = make_policy(name, wl=wl)
        t0 = time.time()
        try:
            res = simulate_trace(trace, pol)
            row = res.row()
        except RuntimeError as e:       # unstable on this trace
            row = {"policy": name, "jobs": num_jobs,
                   "mean_response": float("inf"), "mean_wait": float("inf"),
                   "p_wait": 1.0, "p_helper": None,
                   "p95_response": float("inf"), "utilization": 0.0,
                   "note": str(e)[:60]}
        row["sim_s"] = round(time.time() - t0, 2)
        if extra_cols:
            row.update(extra_cols)
        rows.append(row)
    return rows


def grid_precompute(cells, policies=JAX_POLICIES,
                    engine: str = "jax") -> dict:
    """One ``engines.simulate_grid`` launch per scan policy over ``cells``.

    ``cells`` is a sequence of ``(batch, wl)`` pairs (uniform ``reps``).
    Returns ``{policy: (results, wall_per_cell)}`` for every canonical
    policy with a ``(policy, engine)`` registration; the per-cell wall is
    the grid wall amortised evenly.  Policies the scan substrate does not
    cover are absent (callers dispatch them per-cell as before), and a
    grid launch that raises ``RuntimeError`` (an unstable/overflowing
    cell poisons the whole grid) is also dropped so the per-cell path's
    inf-row error handling can take over.  Feed the result to
    :func:`run_policies_batch` via ``precomputed=`` with the matching
    ``cell`` index.
    """
    from repro.core import engines
    if engine == "python" or not cells:
        return {}
    configure_scan_runtime()
    gcells = [engines.GridCell(batch, wl=wl) for batch, wl in cells]
    out = {}
    for name in dict.fromkeys(engines.canonical(p) for p in policies):
        if (name, engine) not in engines.registered():
            continue
        t0 = time.time()
        try:
            results = engines.simulate_grid(name, gcells, engine=engine)
        except RuntimeError:            # unstable cell — per-cell fallback
            continue
        out[name] = (results, (time.time() - t0) / len(gcells))
    return out


def run_policies_batch(batch: BatchTrace, wl: Workload | None,
                       policies=PAPER_POLICIES, engine: str = "jax",
                       extra_cols=None, precomputed: dict | None = None,
                       cell: int = 0) -> list[dict]:
    """Registry-dispatched rows: one per policy on a shared batch.

    Every policy goes through ``engines.simulate`` on the *same*
    :class:`BatchTrace` (synthetic or bootstrap-resampled), and every row
    is assembled from the returned per-job arrays by the same numpy ops —
    so two engines that agree bit-for-bit on the sample path produce
    bit-identical CSV rows.  Policies without a core under ``engine``
    (SF-SRPT, FF-SRPT, MSF, ... on the scan substrates) fall back to
    ``engine="python"``; the row's ``engine`` column records which core
    actually ran.  ``precomputed`` (from :func:`grid_precompute`) short-
    circuits covered policies with the grid launch's result for ``cell``
    — same numpy row assembly, so rows stay bit-identical.
    """
    from repro.core import engines
    if engine != "python":
        configure_scan_runtime()
    rows = []
    for name in policies:
        pol = engines.canonical(name)
        use = engine
        if engine != "python" and (pol, engine) not in engines.registered():
            engines.warn_fallback(pol, engine)
            use = "python"
        pre = (precomputed or {}).get(pol)
        if pre is not None:
            row = _batch_row(pol, batch, pre[0][cell])
            row["engine"] = use
            row["sim_s"] = round(pre[1], 2)
            if extra_cols:
                row.update(extra_cols)
            rows.append(row)
            continue
        t0 = time.time()
        try:
            res = engines.simulate(pol, batch, engine=use, wl=wl)
            row = _batch_row(pol, batch, res)
        except RuntimeError as e:       # unstable on this batch
            row = {"policy": pol, "jobs": batch.num_jobs,
                   "reps": batch.reps,
                   "mean_response": float("inf"), "mean_wait": float("inf"),
                   "p_wait": 1.0, "p_helper": None,
                   "p95_response": float("inf"), "utilization": 0.0,
                   "note": str(e)[:60]}
        row["engine"] = use
        row["sim_s"] = round(time.time() - t0, 2)
        if extra_cols:
            row.update(extra_cols)
        rows.append(row)
    return rows


def _batch_row(policy: str, batch: BatchTrace, res) -> dict:
    """CSV row of a BatchSimResult — identical float ops for every engine."""
    from repro.core.sim_batch import _ci95
    busy = (batch.need * batch.service).sum(axis=1)     # [R]
    completion = batch.arrival + res.response
    horizon = completion.max(axis=1)                    # [R]
    ph = res.p_helper
    return {
        "policy": policy, "jobs": batch.num_jobs, "reps": batch.reps,
        "mean_response": res.mean_response.mean(),
        "ci95_response": _ci95(res.mean_response),
        "mean_wait": res.mean_wait.mean(),
        "p_wait": res.p_wait.mean(),
        "ci95_p_wait": _ci95(res.p_wait),
        "p_helper": None if ph is None else ph.mean(),
        "p95_response": np.percentile(res.response, 95, axis=1).mean(),
        "utilization": (busy / (batch.k * horizon)).mean(),
    }


def emit(rows: list[dict], cols: list[str], file=None) -> None:
    file = file or sys.stdout
    print(",".join(cols), file=file)
    for r in rows:
        print(",".join(_fmt(r.get(c)) for c in cols), file=file)


def _fmt(v) -> str:
    if v is None:
        return ""
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)
