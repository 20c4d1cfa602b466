#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload fig1-bs --seeds 101 102 103

For each seed it makes the inputs of the run's first timed call, runs the
program once on them (the first seed's call compiles), and compares the
answers with the plain reference exactly as a run does
(``bench/compare.py``): these are the program's readings, whose largest
over the seeds is the lower reading of each number.  It then puts the
reference itself in the program's place, computed in float32 (the
precision below the configurations' float64), and compares that: the
control's readings, whose smallest is the upper reading.  One JSON line
per seed, then a summary line.  The benchmark's runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import catalog  # noqa: E402
import compare  # noqa: E402
import gen  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = catalog.cell(ROOT, args.workload)

    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 1
    run.use_compile_cache()

    path = cell.path_module().Path(cell)
    ref = cell.reference_module()
    reps = int(cell.traffic["reps"])
    program, control = [], []
    for seed in args.seeds:
        x = gen.Inputs(cell.config, cell.traffic, seed).call(1)
        sel = compare.sampled_reps(seed, 1, reps)
        t0 = time.perf_counter()
        out = path.run(path.batch(x))
        call_s = time.perf_counter() - t0
        got = path.answers(out, sel)
        del out
        rows = compare.rows(x, sel)
        horizon = rows["arrival"][:, -1]
        t1 = time.perf_counter()
        want = compare.reference_answers(ref, cell.config, rows, np.float64)
        ref_s = time.perf_counter() - t1
        line = {"seed": seed, "call_s": call_s, "reference_s": ref_s,
                "program": compare.readings(got, want, horizon)}
        # the control answers through the path, as the program does
        low = path.answers(SimpleNamespace(**compare.reference_answers(
            ref, cell.config, rows, np.float32)), list(range(len(sel))))
        line["control"] = compare.readings(low, want, horizon)
        program.append(line["program"])
        control.append(line["control"])
        print(json.dumps(line), flush=True)
    names = list(program[0])
    summary = {"workload": cell.name, "seeds": len(program),
               "lower": {k: max(p[k] for p in program) for k in names},
               "upper": {k: min(c[k] for c in control) for k in names}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
