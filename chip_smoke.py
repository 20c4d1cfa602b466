"""Run the simulator's main path once on the TPU and check what comes out.

    python3 chip_smoke.py             # phases (a)-(c) on one chip
    python3 chip_smoke.py --chips 4   # the four-chip mesh path only

One process drives everything; it starts no child.  With no option the
script runs three phases through ``engines.simulate`` /
``engines.simulate_stream`` with ``engine="jax"`` on the first chip:

(a) the Figure-1 critical-regime workload at k=1024, 1e5 jobs per
    replication, 16 replications: ``fcfs``, ``modbs-fcfs``, ``bs-fcfs``;
(b) the SDSC-SP2 bootstrap batch at k=512, 3000 jobs, 32 replications,
    ``queue_cap=160``: ``ff-srpt``, ``sf-srpt``;
(c) one ``bs-fcfs`` stream at k=256: 1e6 jobs in chunks of 1e5, two
    replications, Poisson arrivals.

Each run is repeated on the CPU backend of the same process, the path the
tests pin to the python oracle.  The chip emulates f64, so the script
reports how many per-job waits are bit-identical and fails a phase whose
per-replication ``p_wait`` or ``mean_wait`` differs by more than a
relative 1e-6.

``--chips 4`` runs only what exists across chips: phase (a) at 64
replications with ``engine="jax-shard"`` over all local chips, and an
``fcfs`` 4-cell k-grid through ``simulate_grid(..., engine="jax-shard")``,
each compared bit for bit with ``engine="jax"`` on the first chip.

Every phase prints one ``phase`` JSON line (device, compile seconds,
compiles inside the timed call, wall seconds, peak device bytes, the CPU
comparison).  The last line is ``{"ok": true, "device": {...}}``; on any
failure, or without a TPU, the script exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
#: relative bound on per-replication p_wait / mean_wait, chip vs CPU
REL_TOL = 1e-6

FIG1 = {"k": 1024, "jobs": 100_000, "reps": 16,
        "policies": ("fcfs", "modbs-fcfs", "bs-fcfs")}
SRPT = {"k": 512, "jobs": 3_000, "reps": 32, "load": 0.85, "queue_cap": 160,
        "policies": ("ff-srpt", "sf-srpt")}
STREAM = {"k": 256, "chunk_jobs": 100_000, "total_jobs": 1_000_000,
          "reps": 2, "backlog_cap": 8192, "policy": "bs-fcfs"}
MESH = {"reps": 64, "grid_ks": (256, 512, 768, 1024), "grid_jobs": 20_000,
        "grid_reps": 16}

#: [count, seconds] of XLA backend compiles, fed by jax.monitoring
_COMPILES = [0, 0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if "backend_compile" in event:
        _COMPILES[0] += 1
        _COMPILES[1] += duration


def _timed(fn):
    """(result, wall seconds, compiles, compile seconds) of one call."""
    n0, s0 = _COMPILES
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    return out, wall, _COMPILES[0] - n0, _COMPILES[1] - s0


def _rel(a, b):
    """Largest elementwise relative difference of ``a`` from ``b``."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    diff = np.abs(a - b)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(diff == 0, 0.0, diff / np.abs(b))
    return float(rel.max())


def _bits_equal(a, b):
    """Elementwise bit equality of two float64 arrays."""
    import numpy as np
    a = np.ascontiguousarray(a, np.float64)
    b = np.ascontiguousarray(b, np.float64)
    return a.view(np.uint64) == b.view(np.uint64)


def _check_batch(res, reps: int, jobs: int) -> None:
    import numpy as np
    for f in ("wait", "response"):
        a = getattr(res, f)
        if a.shape != (reps, jobs) or not np.isfinite(a).all():
            raise AssertionError(f"{f}: shape {a.shape} or non-finite values")


def _compare(out, ref, per_job: bool) -> dict:
    """Chip result against the CPU result: bit identity and the bound."""
    cmp = {"p_wait_max_rel_diff": _rel(out.p_wait, ref.p_wait),
           "mean_wait_max_rel_diff": _rel(out.mean_wait, ref.mean_wait)}
    if per_job:
        import numpy as np
        same = _bits_equal(out.wait, ref.wait)
        cmp["wait_bit_identical_share"] = float(same.mean())
        cmp["wait_max_abs_diff"] = float(np.abs(out.wait - ref.wait).max())
    else:
        cmp["folded_bit_identical"] = bool(
            _bits_equal(out.mean_wait, ref.mean_wait).all()
            and _bits_equal(out.p_wait, ref.p_wait).all())
    pre = getattr(out, "preemptions", None)
    if pre is not None:
        cmp["preemptions_equal"] = bool((pre == ref.preemptions).all())
    cmp["within_bound"] = (cmp["p_wait_max_rel_diff"] <= REL_TOL
                           and cmp["mean_wait_max_rel_diff"] <= REL_TOL)
    return cmp


def _device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _run_cell(jax, phase: str, name: str, run, warm, per_job: bool,
              check) -> dict:
    """Warm-up (compile) call, timed call on the default device, then the
    same call on the CPU backend; returns the phase line."""
    _, _, n_warm, compile_s = _timed(warm)
    out, wall, n_timed, _ = _timed(run)
    check(out)
    with jax.default_device(jax.devices("cpu")[0]):
        ref, cpu_wall, _, _ = _timed(run)
    check(ref)
    line = {"phase": phase, "policy": name, **_device_info(jax),
            "compile_s": compile_s, "compiles_warmup": n_warm,
            "compiles_timed": n_timed, "wall_s": wall,
            "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
            "cpu_wall_s": cpu_wall, **_compare(out, ref, per_job)}
    line["ok"] = line["within_bound"]
    return line


def phase_fig1(jax, cfg=FIG1) -> list[dict]:
    """(a) fig1-critical batch: the non-preemptive scan cores."""
    from repro.core import engines
    from repro.core.workload import figure1_workload
    wl = figure1_workload(cfg["k"], theta=0.7)
    batch = wl.sample_traces(cfg["jobs"], cfg["reps"], seed=SEED)
    lines = []
    for pol in cfg["policies"]:
        run = lambda p=pol: engines.simulate(p, batch, engine="jax", wl=wl,
                                           fallback=False)
        lines.append(_run_cell(
            jax, "a", pol, run, run, True,
            lambda r: _check_batch(r, cfg["reps"], cfg["jobs"])))
    return lines


def phase_srpt(jax, cfg=SRPT) -> list[dict]:
    """(b) the preemptive SRPT pair on the SDSC-SP2 bootstrap batch."""
    from repro.core import engines
    from repro.core.workload import BatchTrace
    from repro.data.swf import sdsc_sp2_trace
    trace = sdsc_sp2_trace(cfg["jobs"], k=cfg["k"], load=cfg["load"],
                           seed=SEED)
    batch = BatchTrace.from_trace(trace, cfg["reps"], seed=SEED,
                                  method="block")
    lines = []
    for pol in cfg["policies"]:
        run = lambda p=pol: engines.simulate(p, batch, engine="jax",
                                             queue_cap=cfg["queue_cap"],
                                             fallback=False)
        lines.append(_run_cell(
            jax, "b", pol, run, run, True,
            lambda r: _check_batch(r, cfg["reps"], cfg["jobs"])))
    return lines


def phase_stream(jax, cfg=STREAM) -> list[dict]:
    """(c) one constant-memory stream; the warm-up streams one chunk,
    which compiles the same chunk program."""
    from repro.core import engines
    from repro.core.workload import PoissonSource, figure1_workload
    wl = figure1_workload(cfg["k"], theta=0.7)

    def stream(total):
        return engines.simulate_stream(
            cfg["policy"], PoissonSource(wl, reps=cfg["reps"], seed=SEED),
            engine="jax", chunk_jobs=cfg["chunk_jobs"], total_jobs=total,
            wl=wl, backlog_cap=cfg["backlog_cap"])

    def check(r):
        import numpy as np
        if r.jobs != cfg["total_jobs"] or r.mean_wait.shape != (cfg["reps"],) \
                or not np.isfinite(r.mean_wait).all():
            raise AssertionError(f"stream folded {r.jobs} jobs, mean_wait "
                                 f"{r.mean_wait}")

    return [_run_cell(jax, "c", cfg["policy"],
                      lambda: stream(cfg["total_jobs"]),
                      lambda: stream(cfg["chunk_jobs"]), False, check)]


def _identical(a, b) -> bool:
    """Every array field of two BatchSimResults bit-identical."""
    import dataclasses
    import numpy as np
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if (x is None) != (y is None):
            return False
        if x is not None and not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


def phase_mesh(jax, fig1=FIG1, cfg=MESH) -> list[dict]:
    """The four-chip path: jax-shard batch and the (cells, reps) grid
    mesh, each against engine="jax" on the first chip."""
    from repro.core import engines
    from repro.core.shard import grid_mesh, local_mesh
    from repro.core.workload import figure1_workload
    mesh = local_mesh()
    wl = figure1_workload(fig1["k"], theta=0.7)
    batch = wl.sample_traces(fig1["jobs"], cfg["reps"], seed=SEED)
    lines = []

    def cell(name, shard, single):
        _, _, n_warm, compile_s = _timed(shard)
        out, wall, n_timed, _ = _timed(shard)
        ref, ref_wall, _, ref_compile_s = _timed(single)
        same = _identical(out, ref) if not isinstance(out, list) else (
            len(out) == len(ref)
            and all(_identical(o, r) for o, r in zip(out, ref)))
        lines.append({"phase": "mesh", "policy": name, **_device_info(jax),
                      "mesh": dict(mesh.shape) if name != "fcfs-grid"
                      else dict(grid_mesh(len(cfg["grid_ks"])).shape),
                      "compile_s": compile_s, "compiles_warmup": n_warm,
                      "compiles_timed": n_timed, "wall_s": wall,
                      "one_chip_wall_s": ref_wall,
                      "one_chip_compile_s": ref_compile_s,
                      "bit_identical_to_one_chip": same, "ok": same})

    for pol in fig1["policies"]:
        cell(pol,
             lambda p=pol: engines.simulate(p, batch, engine="jax-shard",
                                            wl=wl, fallback=False),
             lambda p=pol: engines.simulate(p, batch, engine="jax", wl=wl,
                                            fallback=False))
    cells = []
    for k in cfg["grid_ks"]:
        gwl = figure1_workload(k, theta=0.7)
        cells.append(engines.GridCell(
            gwl.sample_traces(cfg["grid_jobs"], cfg["grid_reps"], seed=SEED),
            wl=gwl))
    cell("fcfs-grid",
         lambda: engines.simulate_grid("fcfs", cells, engine="jax-shard",
                                       fallback=False),
         lambda: engines.simulate_grid("fcfs", cells, engine="jax",
                                       fallback=False))
    per_dev = [{"id": d.id, "peak_bytes_in_use": _peak_bytes(d),
                "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use")}
               for d in jax.devices()]
    spread = all((p["peak_bytes_in_use"] or 0) > 0 for p in per_dev)
    lines.append({"phase": "mesh-memory", **_device_info(jax),
                  "devices": per_dev, "ok": spread})
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip (default); "
                         "4: only the jax-shard / grid mesh path")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro", "core")):
        print(f"chip_smoke: no repository source under {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    from repro.core.shard import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"{devices[0].platform!r}); refusing to run on it",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} chip(s)", file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    phases = ((phase_mesh,) if args.chips == 4
              else (phase_fig1, phase_srpt, phase_stream))
    failed = []
    for phase in phases:
        try:
            lines = phase(jax)
        except Exception:          # report, run the rest, exit non-zero
            traceback.print_exc()
            failed.append(phase.__name__)
            continue
        for line in lines:
            print("phase", json.dumps(line), flush=True)
            if not line["ok"]:
                failed.append(f"{phase.__name__}:{line.get('policy')}")
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": _device_info(jax)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
