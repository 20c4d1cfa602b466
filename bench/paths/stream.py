"""Execution path ``stream``: one ``engines.simulate_stream`` call.

The same [R, J] inputs as the ``batch`` path, as a ``BatchTrace`` that
``simulate_stream`` replays in chunks of the traffic's ``chunk_jobs``:
each chunk's scan resumes from the previous chunk's carry, and the
per-job waits are folded into per-replication moments on the host as
the chunks finish.  The lookahead, the overflow checks and the fold are
the program's own.  The folded result is on the host when the call
returns.
"""

from __future__ import annotations

import os

import catalog

_batch = catalog.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "batch.py"))


class Path(_batch.Path):
    def __init__(self, cell):
        from repro.core import engines

        super().__init__(cell)
        self._simulate_stream = engines.simulate_stream
        self._chunk_jobs = int(cell.traffic["chunk_jobs"])

    def run(self, batch):
        """The timed call."""
        return self._simulate_stream(
            self._policy, batch, engine=self._engine,
            chunk_jobs=self._chunk_jobs, total_jobs=batch.num_jobs,
            wl=self._wl, **self._kw)

    @staticmethod
    def answers(result, reps) -> dict:
        """What the call returned for the replications ``reps``: folded
        moments and shares, no per-job waits."""
        out = {f: getattr(result, f)[reps]
               for f in ("mean_wait", "var_wait", "p_wait")}
        helper = result.p_helper
        out["p_helper"] = None if helper is None else helper[reps]
        return out
