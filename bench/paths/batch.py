"""Execution path ``batch``: one ``engines.simulate`` call on a whole batch.

The call is the one a sweep driver makes: the [R, J] batch of one call's
inputs goes in as a ``BatchTrace``; the policy's eq.-2 partition, where it
needs one, is computed by the program from the workload built here out
of the configuration's class table.  The results are on the host when
``engines.simulate`` returns.
"""

from __future__ import annotations


class Path:
    def __init__(self, cell):
        from repro.core import engines
        from repro.core.workload import (BatchTrace, JobClass,
                                         ServiceDistribution, Workload)

        import gen

        cfg = cell.config
        classes = tuple(
            JobClass(c["name"], int(c["need"]),
                     ServiceDistribution(
                         c["law"], float(c["mean"]),
                         float(c["std"]) if c["law"] == "lognormal" else 0.0),
                     float(a))
            for c, a in zip(cfg["classes"], gen.alphas(cfg)))
        self._wl = Workload(k=int(cfg["k"]), lam=gen.arrival_rate(cfg),
                            classes=classes)
        self._simulate = engines.simulate
        self._batch_trace = BatchTrace
        self._policy = cell.traffic["policy"]
        self._engine = cell.traffic["engine"]
        self._kw = dict(cell.traffic["engine_kw"])

    def batch(self, x: dict):
        """The program's input of one call (not timed)."""
        return self._batch_trace(arrival=x["arrival"], cls=x["cls"],
                                 service=x["service"], need=x["need"],
                                 k=x["k"], C=x["C"])

    def run(self, batch):
        """The timed call."""
        return self._simulate(self._policy, batch, engine=self._engine,
                              wl=self._wl, fallback=False, **self._kw)

    @staticmethod
    def answers(result, reps) -> dict:
        """What the call returned for the replications ``reps``."""
        pre = result.preemptions
        return {"wait": result.wait[reps], "p_wait": result.p_wait[reps],
                "mean_wait": result.mean_wait[reps],
                "preemptions": None if pre is None else pre[reps]}
