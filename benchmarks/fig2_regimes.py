"""Figure 2 — (a) heavy traffic: k fixed, load -> 1; (b) subcritical sweep.

Same job classes and server needs as Figure 1 (k = 512, f_k = 6).

``--engine jax`` (default) runs both sweeps on the batched vmap substrate
(FCFS + ModifiedBS-FCFS + BS-FCFS proper with Def.-1 pull-backs, ``--reps``
replications, mean/CI columns); the heavy-traffic sweep holds k fixed, so
every load point reuses one compiled (k, R, J) executable — and the
persistent compilation cache keeps it past the process, so a re-run pays
no compile at all.
``--engine jax-shard`` shards the replications axis across the local
device mesh (pair with ``--devices N``); bit-identical to ``jax``.
``--engine pallas`` runs the same sweeps on the fused step kernels
(bit-identical; interpret mode — slower — CPU only).
``--engine python`` runs the event-driven engine over the full paper
policy set.
"""

from __future__ import annotations

import argparse

from repro.core.workload import figure2_workload, figure1_base_classes, \
    subcritical_scaling

from .common import ENGINE_HELP, ENGINES, JAX_POLICIES, PAPER_POLICIES, \
    emit, run_policies, run_policies_jax

COLS = ["regime", "k", "load", "policy", "mean_response", "ci95_response",
        "reps", "mean_wait", "p_wait", "ci95_p_wait", "p_helper",
        "p95_response", "utilization", "sim_s"]


def _subcritical_factory(load=0.85):
    base = figure1_base_classes()
    lam = load / sum(c.alpha * c.d * c.n for c in base)
    return lambda k: subcritical_scaling(base, lam, k)


def run_heavy(k=512, loads=(0.5, 0.7, 0.8, 0.9, 0.95), num_jobs=20_000,
              seed=0, policies=PAPER_POLICIES):
    rows = []
    for load in loads:
        wl = figure2_workload(k, load)
        rows += run_policies(wl, num_jobs, seed, policies,
                             extra_cols={"regime": "heavy", "k": k,
                                         "load": load})
    return rows


def run_subcritical(load=0.85, ks=(256, 512, 1024, 2048), num_jobs=20_000,
                    seed=0, policies=PAPER_POLICIES):
    factory = _subcritical_factory(load)
    rows = []
    for k in ks:
        wl = factory(k)
        rows += run_policies(wl, num_jobs, seed, policies,
                             extra_cols={"regime": "subcritical", "k": k,
                                         "load": round(wl.load, 4)})
    return rows


def run_heavy_jax(k=512, loads=(0.5, 0.7, 0.8, 0.9, 0.95),
                  num_jobs=100_000, reps=8, seed=0, policies=JAX_POLICIES,
                  engine="jax", grid=True, ckpt_dir=None, resume=False):
    return run_policies_jax(
        lambda load: figure2_workload(k, load), loads, "load",
        num_jobs=num_jobs, reps=reps, seed=seed, policies=policies,
        engine=engine, grid=grid, extra_cols={"regime": "heavy", "k": k},
        ckpt_dir=ckpt_dir, resume=resume)


def run_subcritical_jax(load=0.85, ks=(256, 512, 1024, 2048),
                        num_jobs=100_000, reps=8, seed=0,
                        policies=JAX_POLICIES, engine="jax", grid=True,
                        ckpt_dir=None, resume=False):
    factory = _subcritical_factory(load)
    return run_policies_jax(
        factory, ks, "k", num_jobs=num_jobs, reps=reps, seed=seed,
        policies=policies, engine=engine, grid=grid,
        extra_cols={"regime": "subcritical"},
        per_point_cols=[{"load": round(factory(k).load, 4)} for k in ks],
        ckpt_dir=ckpt_dir, resume=resume)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=ENGINES, default="jax",
                    help=ENGINE_HELP)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--policies", nargs="+", default=None,
                    help="subset of the engine's policy set")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-grid", action="store_true",
                    help="dispatch each sweep cell separately instead of "
                         "one compiled grid per policy")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count (jax-shard sweeps)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent JAX compilation-cache dir (default: "
                         "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                         "wins when set)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="write each sweep cell atomically under "
                         "<dir>/{heavy,subcritical} (crash-resumable; "
                         "batched engines only)")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already checkpointed in --ckpt-dir")
    args = ap.parse_args(argv)
    if args.engine == "python" and (args.ckpt_dir or args.resume):
        ap.error("--ckpt-dir/--resume need a batched engine (jax/...)")
    from repro.core.shard import enable_compile_cache

    from .common import configure_scan_runtime
    configure_scan_runtime(devices=args.devices, warn=True)
    enable_compile_cache(args.cache_dir)
    default = 20_000 if args.engine == "python" else 100_000
    jobs = args.jobs if args.jobs is not None \
        else (1_000_000 if args.full else default)
    if args.engine != "python":
        import os
        # one checkpoint namespace per sweep: cell ids are sweep-local
        sub = {r: os.path.join(args.ckpt_dir, r) if args.ckpt_dir else None
               for r in ("heavy", "subcritical")}
        pols = tuple(args.policies or JAX_POLICIES)
        rows = (run_heavy_jax(num_jobs=jobs, reps=args.reps, policies=pols,
                              engine=args.engine, grid=not args.no_grid,
                              ckpt_dir=sub["heavy"], resume=args.resume)
                + run_subcritical_jax(num_jobs=jobs, reps=args.reps,
                                      policies=pols, engine=args.engine,
                                      grid=not args.no_grid,
                                      ckpt_dir=sub["subcritical"],
                                      resume=args.resume))
    else:
        pols = tuple(args.policies or PAPER_POLICIES)
        rows = (run_heavy(num_jobs=jobs, policies=pols)
                + run_subcritical(num_jobs=jobs, policies=pols))
    emit(rows, COLS)


if __name__ == "__main__":
    main()
