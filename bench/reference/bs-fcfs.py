"""Plain reference of Balanced Splitting with FCFS helpers (BS-FCFS).

Definition 1 of arXiv 2409.18557.  The servers are split once by eq. (2)
into a block A_i of ``slots_i`` whole-job slots per class and a helper
set H of ``h`` servers:

1. a class-i arrival takes a free slot of A_i, else it joins the helper
   queue;
2. the helper queue is served first come first served with head-of-line
   blocking on the ``h`` helper servers;
3. when a job completes in A_i, the oldest class-i job still waiting in
   the helper queue moves into the freed slot and starts at once.

Events are taken in time order, an arrival before a completion at the
same instant.  Every time is computed in ``dtype``.
"""

from __future__ import annotations

import heapq
import math
from collections import deque

import numpy as np


def partition(config: dict) -> tuple[list[int], int]:
    """Eq. (2): whole-job slots of each class block, and the helper count.

    a_i = floor(psi (k / n_i)(rho_i / rho)) n_i, with psi the largest x in
    [0, 1] that leaves at least max_i n_i servers to the helpers.
    """
    k = int(config["k"])
    n = [int(c["need"]) for c in config["classes"]]
    a = np.array([c["alpha"] for c in config["classes"]])
    if config["normalize_alpha"]:
        a = a / a.sum()
    rho = [float(ai) * c["mean"] * c["need"]
           for ai, c in zip(a, config["classes"])]
    total = sum(rho)
    frac = [(k / ni) * (ri / total) for ni, ri in zip(n, rho)]

    def counts(x: float) -> list[int]:
        # the guard keeps x = m / frac_i on its step despite rounding
        return [math.floor(x * f + 1e-12) for f in frac]

    def helpers(x: float) -> int:
        return k - sum(c * ni for c, ni in zip(counts(x), n))

    psi = 1.0
    if helpers(1.0) < max(n):
        steps = [m / f for f in frac if f > 0
                 for m in range(1, math.floor(f + 1e-12) + 1)]
        psi = max(x for x in [0.0] + steps
                  if x <= 1.0 and helpers(x) >= max(n))
    return counts(psi), helpers(psi)


def simulate(arrival, cls, need, service, config: dict, dtype) -> dict:
    """Per-job waits of one replication, and which jobs the helpers
    served (``helper``: started outside their class block); BS-FCFS never
    preempts."""
    slots, h = partition(config)
    as_list = (lambda x: np.asarray(x, np.float64).tolist()) \
        if dtype == np.float64 else (lambda x: list(np.asarray(x, dtype)))
    t, s = as_list(arrival), as_list(service)
    c, n = np.asarray(cls).tolist(), np.asarray(need).tolist()
    J = len(t)
    free_slots = list(slots)
    helper_free = [h]
    in_block = [False] * J
    start = [None] * J
    waiting = [deque() for _ in slots]    # helper queue, split by class
    done = []                             # (completion, seq, job)
    seq = [0]

    def begin(j, now, block: bool) -> None:
        start[j] = now
        in_block[j] = block
        seq[0] += 1
        heapq.heappush(done, (now + s[j], seq[0], j))

    def serve_helpers(now) -> None:
        while True:
            heads = [q[0] for q in waiting if q]
            if not heads:
                return
            j = min(heads)                # oldest waiting job
            if n[j] > helper_free[0]:
                return                    # head-of-line blocking
            waiting[c[j]].popleft()
            helper_free[0] -= n[j]
            begin(j, now, False)

    a = 0
    while a < J or done:
        if a < J and (not done or t[a] <= done[0][0]):
            j, now = a, t[a]
            a += 1
            i = c[j]
            if free_slots[i] > 0:
                free_slots[i] -= 1
                begin(j, now, True)
            else:
                waiting[i].append(j)
                serve_helpers(now)
            continue
        now, _, j = heapq.heappop(done)
        if in_block[j]:
            i = c[j]
            free_slots[i] += 1
            if waiting[i]:
                free_slots[i] -= 1
                begin(waiting[i].popleft(), now, True)
                serve_helpers(now)
        else:
            helper_free[0] += n[j]
            serve_helpers(now)
    wait = np.asarray(start, dtype) - np.asarray(arrival, dtype)
    return {"wait": wait.astype(np.float64), "preemptions": None,
            "helper": ~np.array(in_block)}
