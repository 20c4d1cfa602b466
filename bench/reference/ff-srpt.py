"""Plain reference of First-Fit SRPT, preemptive-resume.

At every event the jobs in the system are ranked by remaining work
(arrival time breaks ties) and packed first-fit onto the k servers,
skipping a job that does not fit, until no server is free.  Running jobs
left out are preempted and keep their remaining work; a job's wait is
its first start minus its arrival.  Events are taken in time order, an
arrival before a completion at the same instant; a completion made stale
by a preemption is dropped.  Every time is computed in ``dtype``.
"""

from __future__ import annotations

import heapq

import numpy as np


def simulate(arrival, cls, need, service, config: dict, dtype) -> dict:
    """Per-job waits and the number of preemptions of one replication."""
    as_list = (lambda x: np.asarray(x, np.float64).tolist()) \
        if dtype == np.float64 else (lambda x: list(np.asarray(x, dtype)))
    t, rem = as_list(arrival), as_list(service)
    n = np.asarray(need).tolist()
    k = int(config["k"])
    J = len(t)
    zero = t[0] * 0
    run_start = [zero] * J
    first = [None] * J
    epoch = [0] * J
    running: set[int] = set()
    present: set[int] = set()
    done = []                             # (completion, seq, job, epoch)
    seq = 0
    preemptions = 0

    def left(j, now):
        if j in running:
            return max(zero, rem[j] - (now - run_start[j]))
        return rem[j]

    a = 0
    while a < J or done:
        if a < J and (not done or t[a] <= done[0][0]):
            now = t[a]
            present.add(a)
            a += 1
        else:
            now, _, j, ep = heapq.heappop(done)
            if ep != epoch[j]:
                continue                  # preempted since it was scheduled
            running.discard(j)
            present.discard(j)
            rem[j] = zero
        order = sorted(present, key=lambda x: (left(x, now), t[x]))
        chosen, free = [], k
        for j in order:
            if n[j] <= free:
                chosen.append(j)
                free -= n[j]
            if free == 0:
                break
        keep = set(chosen)
        out = [j for j in running if j not in keep]
        for j in out:
            rem[j] = left(j, now)
            epoch[j] += 1
        running.difference_update(out)
        preemptions += len(out)
        for j in chosen:
            if j in running:
                continue
            run_start[j] = now
            if first[j] is None:
                first[j] = now
            epoch[j] += 1
            running.add(j)
            seq += 1
            heapq.heappush(done, (now + rem[j], seq, j, epoch[j]))
    wait = np.asarray(first, dtype) - np.asarray(arrival, dtype)
    return {"wait": wait.astype(np.float64), "preemptions": preemptions}
