"""Figure 1 — mean response time in the critical (Halfin-Whitt) regime.

k sweeps with f_k = floor((k/32)^(2/3)), (1-ρ)√(k/f_k) -> θ = 0.7;
small jobs (f_k, 1) w.p. 0.95; large (2f_k,40)/(4f_k,20)/(8f_k,10) w.p.
0.05/3 each; exponential services, Poisson arrivals (paper Fig. 1 setup).

Four engines:

* ``--engine jax`` (default) — the batched vmap substrate
  (``repro.core.sim_batch``): FCFS + ModifiedBS-FCFS + BS-FCFS proper
  (Definition 1, rule-3 pull-backs, on the event-indexed scan), ``--reps``
  independent Philox replications per k, mean/CI columns.
* ``--engine jax-shard`` — same sweeps with the replications axis sharded
  across the local device mesh (``repro.core.shard``); pair with
  ``--devices N`` to expose N host devices on any CPU box.  Bit-identical
  to ``jax``.
* ``--engine pallas`` — same sweeps on the fused step kernels
  (``repro.kernels.msj_scan``); bit-identical to ``jax``, interpret mode
  (slower), CPU only.
* ``--engine python`` — the exact event-driven engine over the full paper
  policy set (slow; use for the policies the scan substrate cannot cover).

JAX's persistent compilation cache is on (``JAX_COMPILATION_CACHE_DIR``,
else ``--cache-dir``, else ``<checkout>/.jax_cache``), so repeated sweeps
stop paying the per-(k, R, J) compile.
"""

from __future__ import annotations

import argparse

from repro.core.theory import analyze
from repro.core.workload import figure1_workload

from .common import ENGINE_HELP, ENGINES, JAX_POLICIES, PAPER_POLICIES, \
    emit, run_policies, run_policies_jax

COLS = ["k", "policy", "mean_response", "ci95_response", "reps", "mean_wait",
        "p_wait", "ci95_p_wait", "p_helper", "p95_response", "utilization",
        "ph_bound", "zero_wait_R", "sim_s"]


def _theory_cols(k: int, theta: float) -> dict:
    wl = figure1_workload(k, theta=theta)
    rep = analyze(wl)
    return {"ph_bound": rep.p_helper_modified,
            "zero_wait_R": wl.zero_wait_response_time()}


def run(ks=(256, 512, 1024, 2048), num_jobs=30_000, seed=0,
        policies=PAPER_POLICIES, theta=0.7):
    """Python-engine sweep (the full paper policy set)."""
    rows = []
    for k in ks:
        wl = figure1_workload(k, theta=theta)
        rows += run_policies(
            wl, num_jobs, seed, policies,
            extra_cols={"k": k, **_theory_cols(k, theta)})
    return rows


def run_jax(ks=(256, 512, 1024, 2048), num_jobs=100_000, reps=8, seed=0,
            theta=0.7, policies=JAX_POLICIES, engine="jax", grid=True,
            ckpt_dir=None, resume=False):
    """Batched-substrate sweep (FCFS + ModifiedBS-FCFS + BS-FCFS, CIs).

    ``grid=True`` (default) runs the whole k sweep as one k-padded
    compiled program per policy (``engines.simulate_grid``); results are
    bit-identical to the per-cell path (``grid=False``).
    """
    return run_policies_jax(
        lambda k: figure1_workload(k, theta=theta), ks, "k",
        num_jobs=num_jobs, reps=reps, seed=seed, policies=policies,
        engine=engine, grid=grid,
        per_point_cols=[_theory_cols(k, theta) for k in ks],
        ckpt_dir=ckpt_dir, resume=resume)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=ENGINES, default="jax",
                    help=ENGINE_HELP)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--ks", type=int, nargs="+",
                    default=[256, 512, 1024, 2048])
    ap.add_argument("--policies", nargs="+", default=None,
                    help="subset of the engine's policy set")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale 10^6 arrivals")
    ap.add_argument("--no-grid", action="store_true",
                    help="dispatch each (k, policy) cell separately "
                         "instead of one compiled grid per policy")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count (jax-shard sweeps)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent JAX compilation-cache dir (default: "
                         "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                         "wins when set)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="write each (k, policy) cell atomically here "
                         "(crash-resumable; batched engines only)")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already checkpointed in --ckpt-dir")
    args = ap.parse_args(argv)
    if args.engine == "python" and (args.ckpt_dir or args.resume):
        ap.error("--ckpt-dir/--resume need a batched engine (jax/...)")
    from repro.core.shard import enable_compile_cache

    from .common import configure_scan_runtime
    configure_scan_runtime(devices=args.devices, warn=True)
    enable_compile_cache(args.cache_dir)
    default = 30_000 if args.engine == "python" else 100_000
    jobs = args.jobs if args.jobs is not None \
        else (1_000_000 if args.full else default)
    if args.engine != "python":
        rows = run_jax(ks=tuple(args.ks), num_jobs=jobs, reps=args.reps,
                       policies=tuple(args.policies or JAX_POLICIES),
                       engine=args.engine, grid=not args.no_grid,
                       ckpt_dir=args.ckpt_dir, resume=args.resume)
    else:
        rows = run(ks=tuple(args.ks), num_jobs=jobs,
                   policies=tuple(args.policies or PAPER_POLICIES))
    emit(rows, COLS)


if __name__ == "__main__":
    main()
