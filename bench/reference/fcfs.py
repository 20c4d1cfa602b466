"""Plain reference of multiserver-job FCFS with head-of-line blocking.

Jobs start in arrival order; a job starts as soon as it has arrived, the
job ahead of it has started, and ``need`` servers are free.  Servers are
interchangeable, so the state is the sorted list of the k servers'
free times: the job starts at the latest of its arrival, the previous
start and the ``need``-th smallest free time, and takes those servers.
Every time is computed in ``dtype``.
"""

from __future__ import annotations

import bisect

import numpy as np


def simulate(arrival, cls, need, service, config: dict, dtype) -> dict:
    """Per-job waits of one replication (float64 array of the dtype's
    values); FCFS never preempts."""
    as_list = (lambda x: np.asarray(x, np.float64).tolist()) \
        if dtype == np.float64 else (lambda x: list(np.asarray(x, dtype)))
    t, s = as_list(arrival), as_list(service)
    n = np.asarray(need).tolist()
    zero = t[0] * 0
    free = [zero] * int(config["k"])
    prev = zero
    start = []
    for j in range(len(t)):
        nj = n[j]
        st = max(t[j], prev, free[nj - 1])
        done = st + s[j]
        del free[:nj]
        pos = bisect.bisect_right(free, done)
        free[pos:pos] = [done] * nj
        start.append(st)
        prev = st
    wait = np.asarray(start, dtype) - np.asarray(arrival, dtype)
    return {"wait": wait.astype(np.float64), "preemptions": None}
