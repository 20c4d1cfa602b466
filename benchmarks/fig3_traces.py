"""Figure 3 — SDSC-SP2 / KIT-FH2 HPC workloads, k in {512, 1024}.

Traces are synthesized from the paper's Table-2/3 extracted parameters
(lognormal service fit; the raw archive logs are not redistributable).
``--swf <path>`` switches to a real SWF log when available (``--k`` sets
its server count).

The empirical traces run on the batched substrate: every cell bootstraps
the trace into ``--reps`` replications (``BatchTrace.from_trace``, IID or
moving-block via ``--bootstrap``) and dispatches each policy through the
engine registry.  ``--engine jax`` (default) runs fcfs/modbs-fcfs/bs-fcfs
*and* the preemptive sf-srpt/ff-srpt on the vmapped scans, with the
remaining paper policies (MSF, LSF, MaxWeight, ...) falling back to the
exact Python engine — every fallback is announced by a once-per-process
``RuntimeWarning`` plus a row summary on stderr after the sweep;
``--engine jax-shard`` shards the replications of the scan policies
across the local device mesh (pair with ``--devices N``); ``--engine
pallas`` routes all five scan policies — the preemptive srpt pair
included, via the fused bitonic rank/permute kernels — through the fused
step kernels (interpret mode, CPU only: bit-identical, not fast); ``--engine
python`` runs everything on the event engine over the *same* bootstrap
batch, so rows are bit-comparable across engines (the ``engine`` column
records the core that actually ran each row).  The persistent compilation
cache is on (``--cache-dir`` moves it; ``JAX_COMPILATION_CACHE_DIR`` wins).
"""

from __future__ import annotations

import argparse
import sys

from repro.core.workload import (BatchTrace, kit_fh2_workload,
                                 sdsc_sp2_workload)
from repro.data.swf import kit_fh2_trace, sdsc_sp2_trace

from .common import ENGINES, ENGINE_HELP, PAPER_POLICIES, emit, \
    grid_precompute, run_policies_batch

COLS = ["dataset", "k", "load", "engine", "policy", "jobs", "reps",
        "mean_response", "ci95_response", "mean_wait", "p_wait", "p_helper",
        "p95_response", "utilization", "sim_s"]

_DATASETS = (("sdsc_sp2", sdsc_sp2_trace, sdsc_sp2_workload),
             ("kit_fh2", kit_fh2_trace, kit_fh2_workload))


def run(num_jobs=15_000, seed=0, ks=(512, 1024), loads=(0.5, 0.7, 0.85),
        policies=PAPER_POLICIES, engine="jax", reps=4, bootstrap="iid",
        grid=True, ckpt_dir=None, resume=False) -> list[dict]:
    """Table-2/3 synthesized traces, bootstrapped, through the registry.

    With ``grid=True`` (default, scan engines only) every scan policy
    first runs *all* not-yet-checkpointed (dataset, k, load) cells as one
    k/J-padded compiled grid (:func:`grid_precompute`); the per-cell row
    assembly then reuses those results, so rows stay bit-identical to
    ``grid=False`` per-cell dispatch (``sim_s`` becomes the grid wall
    amortised evenly over its cells).  Python-fallback policies are
    untouched either way.

    With ``ckpt_dir`` every (dataset, k, load) cell's finished CSV rows
    are published atomically (:mod:`repro.checkpoint`, rows ride in the
    JSON manifest) and ``resume=True`` reloads completed cells instead of
    re-simulating — a killed run resumes with byte-identical output (JSON
    round-trips the float columns exactly).
    """
    done: set[int] = set()
    if resume:
        from repro.checkpoint import completed_steps
        if ckpt_dir is None:
            raise ValueError("resume=True needs a ckpt_dir")
        done = set(completed_steps(ckpt_dir))
    specs = list(enumerate([(name, trace_fn, wl_fn, k, load)
                            for name, trace_fn, wl_fn in _DATASETS
                            for k in ks for load in loads]))
    # sample every pending cell up front so the grid pre-pass can cover
    # them all in one compiled launch per scan policy
    sampled = {}
    for cell, (name, trace_fn, wl_fn, k, load) in specs:
        if cell in done:
            continue
        trace = trace_fn(num_jobs, k=k, load=load, seed=seed)
        batch = BatchTrace.from_trace(trace, reps, seed=seed,
                                      method=bootstrap)
        sampled[cell] = (batch, wl_fn(k=k, load=load))
    pre, pre_idx = {}, {}
    if grid and sampled:
        todo = sorted(sampled)
        pre = grid_precompute([sampled[c] for c in todo],
                              policies=policies, engine=engine)
        pre_idx = {c: i for i, c in enumerate(todo)}
    rows = []
    for cell, (name, trace_fn, wl_fn, k, load) in specs:
        key = f"{name}/k={k}/load={load}"
        if cell in done:
            from repro.checkpoint import restore_checkpoint
            import numpy as np
            _, _, extra = restore_checkpoint(
                ckpt_dir, {"ok": np.zeros(1)}, step=cell)
            if extra.get("cell_key") != key:
                raise ValueError(
                    f"checkpoint cell {cell} holds "
                    f"{extra.get('cell_key')!r}, sweep expects "
                    f"{key!r} — stale ckpt_dir?")
            rows += extra["rows"]
            continue
        batch, wl = sampled[cell]
        cell_rows = run_policies_batch(
            batch, wl, policies, engine=engine,
            extra_cols={"dataset": name, "k": k, "load": load},
            precomputed=pre or None, cell=pre_idx.get(cell, 0))
        if ckpt_dir is not None:
            from repro.checkpoint import save_checkpoint
            import numpy as np
            save_checkpoint(ckpt_dir, cell, {"ok": np.ones(1)},
                            extra={"cell_key": key, "rows": cell_rows})
        rows += cell_rows
    return rows


def run_swf(path: str, k: int = 512, load: float = 0.85,
            jobs: int | None = None, seed=0, policies=PAPER_POLICIES,
            engine="jax", reps=4, bootstrap="block") -> list[dict]:
    """A real SWF log on the bootstrap substrate.

    The log's own arrival/service/need columns are bootstrap-resampled
    (moving-block by default — real logs are bursty); ``load`` only feeds
    the eq.-2 partition fit via :func:`trace_to_workload`.
    """
    from repro.data.swf import parse_swf, trace_to_workload
    trace = parse_swf(path, k=k, limit=jobs)
    wl = trace_to_workload(trace, k, load)
    batch = BatchTrace.from_trace(trace, reps, seed=seed, method=bootstrap)
    return run_policies_batch(
        batch, wl, policies, engine=engine,
        extra_cols={"dataset": "swf", "k": k, "load": load})


def report_fallbacks(rows: list[dict], engine: str, file=None) -> None:
    """Name the rows that ran on the python oracle instead of ``engine``.

    The per-row ``engine`` column already records the core that ran; this
    aggregates it into one loud stderr line so a sweep log shows at a
    glance which policies were downgraded (and therefore which wall-clock
    numbers are oracle-bound).
    """
    file = file or sys.stderr
    if engine == "python":
        return
    fell = sorted({r["policy"] for r in rows
                   if r.get("engine") == "python"})
    if fell:
        print(f"# fallback: {len(fell)} polic"
              f"{'y' if len(fell) == 1 else 'ies'} ran on the python "
              f"oracle instead of engine={engine!r}: {', '.join(fell)}",
              file=file)
    else:
        print(f"# no python fallback: every row ran on engine={engine!r}",
              file=file)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=ENGINES, default="jax",
                    help=ENGINE_HELP)
    ap.add_argument("--jobs", type=int, default=15_000)
    ap.add_argument("--reps", type=int, default=4,
                    help="bootstrap replications per cell")
    ap.add_argument("--ks", type=int, nargs="+", default=[512, 1024])
    ap.add_argument("--loads", type=float, nargs="+",
                    default=[0.5, 0.7, 0.85])
    ap.add_argument("--policies", nargs="+", default=None)
    ap.add_argument("--bootstrap", choices=("iid", "block"), default=None,
                    help="job-record resampling: iid or moving-block "
                         "(default: iid for the synthesized tables, block "
                         "for --swf logs — real arrivals are bursty)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-grid", action="store_true",
                    help="dispatch each (dataset, k, load) cell "
                         "separately instead of one compiled grid per "
                         "scan policy")
    ap.add_argument("--swf", default=None, help="real SWF log path")
    ap.add_argument("--k", type=int, default=512,
                    help="server count for the --swf path")
    ap.add_argument("--load", type=float, default=0.85,
                    help="partition-fit load for the --swf path")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count (jax-shard rows)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent JAX compilation-cache dir (default: "
                         "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                         "wins when set)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="write each (dataset, k, load) cell atomically "
                         "here (crash-resumable)")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already checkpointed in --ckpt-dir")
    args = ap.parse_args(argv)
    if args.swf and (args.ckpt_dir or args.resume):
        ap.error("--ckpt-dir/--resume apply to the synthesized-table sweep")
    from repro.core.shard import enable_compile_cache

    from .common import configure_scan_runtime
    configure_scan_runtime(devices=args.devices, warn=True)
    enable_compile_cache(args.cache_dir)
    jobs = 1_000_000 if args.full else args.jobs
    pols = tuple(args.policies or PAPER_POLICIES)
    if args.swf:
        rows = run_swf(args.swf, k=args.k, load=args.load, jobs=jobs,
                       seed=args.seed, policies=pols, engine=args.engine,
                       reps=args.reps, bootstrap=args.bootstrap or "block")
    else:
        rows = run(num_jobs=jobs, seed=args.seed, ks=tuple(args.ks),
                   loads=tuple(args.loads), policies=pols,
                   engine=args.engine, reps=args.reps,
                   bootstrap=args.bootstrap or "iid",
                   grid=not args.no_grid, ckpt_dir=args.ckpt_dir,
                   resume=args.resume)
    emit(rows, COLS)
    report_fallbacks(rows, args.engine)


if __name__ == "__main__":
    main()
