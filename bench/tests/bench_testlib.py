"""Helpers shared by the benchmark's tests."""

import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: tiny per-cell shapes for the CPU: (jobs, replications); a cell that
#: is not listed runs at ``DEFAULT_TINY``
TINY = {"fig1-bs": (400, 8), "sdsc-srpt": (200, 8), "fig1-fcfs": (400, 8),
        "fig1-bs-shard4": (400, 8)}
DEFAULT_TINY = (200, 4)


def tiny_cell(name: str, root: str = ROOT):
    """The cell ``name`` with its traffic cut to a CPU-sized call."""
    import catalog
    cell = catalog.cell(root, name)
    jobs, reps = TINY.get(name, DEFAULT_TINY)
    cell.traffic = dict(cell.traffic, jobs=jobs, reps=reps)
    return cell


def cells(root: str = ROOT) -> list[str]:
    import catalog
    return [w["name"] for w in catalog.benchmark(root)["workloads"]]
