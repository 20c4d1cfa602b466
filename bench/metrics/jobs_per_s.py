"""Simulated job-replications completed per second of the window.

The window is the union of the timed calls: the sum of J x R over the
calls that returned, over the sum of every call's wall time (host clock,
from entering the program to its results on the host).
"""


def read(record: dict, trace: dict | None) -> float | None:
    if record["window_s"] <= 0:
        return None
    return record["jobs_done"] / record["window_s"]
