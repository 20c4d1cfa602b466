"""Helpers shared by the benchmark's tests."""

import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

#: tiny per-cell shapes for the CPU: (jobs, replications); a cell that
#: is not listed runs at ``DEFAULT_TINY``.  fig1-bs-stream's 8400 jobs
#: fill two of the stream accumulator's 4096-job blocks and part of a
#: third, so that its fold merges blocks
TINY = {"fig1-bs": (400, 8), "sdsc-srpt": (200, 8), "fig1-fcfs": (400, 8),
        "fig1-bs-shard4": (400, 8), "fig1-bs-stream": (8400, 8)}
DEFAULT_TINY = (200, 4)
#: a streamed cell's tiny call runs in this many chunks, so that a carry
#: crosses several chunk boundaries
TINY_CHUNKS = 4


def tiny_cell(name: str, root: str = ROOT):
    """The cell ``name`` with its traffic cut to a CPU-sized call."""
    import catalog
    cell = catalog.cell(root, name)
    jobs, reps = TINY.get(name, DEFAULT_TINY)
    cell.traffic = dict(cell.traffic, jobs=jobs, reps=reps)
    if "chunk_jobs" in cell.traffic:
        cell.traffic["chunk_jobs"] = jobs // TINY_CHUNKS
    return cell


def cells(root: str = ROOT) -> list[str]:
    import catalog
    return [w["name"] for w in catalog.benchmark(root)["workloads"]]
