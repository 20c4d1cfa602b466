"""Run every benchmark with CI-scale defaults.

    PYTHONPATH=src python -m benchmarks.run            # quick (CI)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweeps

One section per paper table/figure, plus the roofline table derived from
the dry-run artifacts and the kernel micro-bench.
"""

from __future__ import annotations

import argparse
import sys
import time


def _section(title):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--engine", choices=("jax", "jax-shard", "pallas"),
                    default="jax",
                    help="fast-engine selection for the batched-substrate "
                         "and fig3 sections (python-engine sections always "
                         "run the event engine)")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count (jax-shard sections)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent JAX compilation-cache dir (default: "
                         "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                         "wins when set)")
    args = ap.parse_args(argv)

    from . import (fig1_critical, fig2_regimes, fig3_traces, kernels_bench,
                   roofline, theory_tables)
    from repro.core.shard import enable_compile_cache

    from .common import configure_scan_runtime, emit

    configure_scan_runtime(devices=args.devices, warn=True)
    enable_compile_cache(args.cache_dir)

    t0 = time.time()
    jobs1 = args.jobs or (1_000_000 if args.full else 12_000)
    jobs2 = args.jobs or (1_000_000 if args.full else 8_000)

    _section("Figure 1: critical (Halfin-Whitt) many-server regime")
    emit(fig1_critical.run(ks=(256, 512, 1024) if not args.full else
                           (256, 512, 1024, 2048, 4096),
                           num_jobs=jobs1), fig1_critical.COLS)

    _section("Figure 1 (batched jax substrate): FCFS + ModifiedBS with CIs")
    jjobs = args.jobs or (1_000_000 if args.full else 50_000)
    jreps = 8 if args.full else 4
    emit(fig1_critical.run_jax(
        ks=(256, 512, 1024) if not args.full else (256, 512, 1024, 2048, 4096),
        num_jobs=jjobs, reps=jreps, engine=args.engine), fig1_critical.COLS)

    _section("Figure 2: heavy-traffic + subcritical regimes")
    emit(fig2_regimes.run_heavy(num_jobs=jobs2) +
         fig2_regimes.run_subcritical(num_jobs=jobs2), fig2_regimes.COLS)

    _section("Figure 2 (batched jax substrate)")
    emit(fig2_regimes.run_heavy_jax(num_jobs=jjobs, reps=jreps,
                                    engine=args.engine) +
         fig2_regimes.run_subcritical_jax(num_jobs=jjobs, reps=jreps,
                                          engine=args.engine),
         fig2_regimes.COLS)

    _section("Figure 3: SDSC-SP2 / KIT-FH2 HPC trace workloads (bootstrap)")
    emit(fig3_traces.run(num_jobs=jobs2,
                         ks=(512,) if not args.full else (512, 1024),
                         engine=args.engine,
                         reps=2 if not args.full else 4),
         fig3_traces.COLS)

    _section("Theorems 1-2: convergence tables (analytic + Monte-Carlo)")
    emit(theory_tables.run(mc_jobs=100_000 if not args.full else 1_000_000),
         theory_tables.COLS)

    _section("Roofline: per (arch x shape x mesh) from dry-run artifacts")
    rows = roofline.load_rows(roofline.DEFAULT_FILES)
    if rows:
        emit(rows, roofline.COLS)
    else:
        print("(no dry-run artifacts; run repro.launch.dryrun first)")

    _section("Kernel micro-benchmarks")
    emit(kernels_bench.run(), kernels_bench.COLS)

    print(f"\nall benchmarks done in {time.time() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
