"""Regenerate the committed multi-topology ``BENCH_sim.json``.

    PYTHONPATH=src python -m benchmarks.regen_bench [--out BENCH_sim.json]

The committed baseline merges rows from several *processes*, because the
XLA host-device count is frozen at backend init and so one process can
only ever measure one topology:

* ``devices=1``, every engine, full config **and** ``--smoke`` — the
  cells the ``fast``/``pallas`` CI jobs compare against.  The smoke-scale
  rows matter: smoke throughput is intrinsically lower (smaller k, fewer
  jobs/reps to amortize dispatch), and ``check_bench_regression`` takes
  the per-cell *minimum* as the floor, so without them a fast full-config
  run would set floors a legitimate smoke run cannot clear.
* ``devices=2`` and ``devices=4``, python + jax-shard, full and smoke —
  the per-topology cells the CI ``shard`` job (4 forced host devices)
  compares against.  Topologies that over-subscribe the measuring host's
  cores are still committed: they are *floors*, and hosts with that many
  real cores only beat them (hosts without skip them via the checker's
  over-subscription rule).

Regenerating with a bare ``python -m benchmarks.bench_sim`` would write a
single-topology file and silently drop the dc>1 cells — the CI shard
gate would then skip every sharded cell for lack of a baseline.  Always
regenerate through this driver (or pass ``--topologies`` to trim it on a
small box).
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import tempfile


def _run(out: str, args: list[str], cache_dir: str | None) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.bench_sim", "--out", out,
           *(["--cache-dir", cache_dir] if cache_dir else []), *args]
    print("+", " ".join(cmd[2:]), file=sys.stderr, flush=True)
    subprocess.run(cmd, check=True)
    with open(out) as f:
        return json.load(f)


def regenerate(topologies=(1, 2, 4), out="BENCH_sim.json",
               cache_dir=None) -> dict:
    """Run bench_sim once per (topology, scale) and merge the rows.

    Every run shares one persistent compilation cache: bench_sim's own
    (``JAX_COMPILATION_CACHE_DIR``, else ``cache_dir``, else the fixed
    in-checkout path).
    """
    parts = []
    with tempfile.TemporaryDirectory() as tmp:
        for n in topologies:
            # dc=1 measures every engine; dc>1 adds the sharded cells
            # (plus python rows so the machine-speed ratio always has
            # shared cells) without re-measuring single-device engines
            # under a topology they would never ship rows for
            sel = [] if n == 1 else ["--engines", "python", "jax-shard"]
            for i, scale in enumerate((["--smoke"], [])):
                parts.append(_run(f"{tmp}/bench_dc{n}_{i}.json",
                                  ["--devices", str(n), *sel, *scale],
                                  cache_dir))
        report = dict(parts[-1])  # full-config dc-max run's config block
        # the streaming scenario additionally runs standalone per scale:
        # inside a '--scenario all' process the peak_rss_mb high-water is
        # inherited from the monolithic scenarios, so the committed
        # RSS-flatness rows need a dedicated process
        for i, scale in enumerate((["--smoke"], [])):
            parts.append(_run(f"{tmp}/bench_streaming_{i}.json",
                              ["--devices", "1", "--scenario",
                               "streaming", *scale], cache_dir))
    report["rows"] = [r for p in parts for r in p["rows"]]
    report["config"]["merged_runs"] = [
        {"devices": p["config"]["device_count"],
         "engines": p["config"]["engines"],
         "scenario": p["config"]["scenario"],
         "smoke": p["config"]["ks"] == [64]} for p in parts]
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"wrote {out} ({len(report['rows'])} rows, "
          f"topologies {list(topologies)})", file=sys.stderr)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--topologies", type=int, nargs="+", default=[1, 2, 4],
                    help="host-device counts to measure (default: 1 2 4)")
    ap.add_argument("--out", default="BENCH_sim.json")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile cache shared by all runs "
                         "(default: <checkout>/.jax_cache; "
                         "JAX_COMPILATION_CACHE_DIR wins when set)")
    args = ap.parse_args(argv)
    regenerate(tuple(args.topologies), out=args.out,
               cache_dir=args.cache_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
