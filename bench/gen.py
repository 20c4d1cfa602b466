"""The benchmark's one input generator: a cell's calls, made from the seed.

A configuration file gives the class table (need, service law, mean, std,
alpha), the machine size ``k``, the load and the arrival process; a
traffic file gives the jobs per replication ``J`` and the replications
``R`` of one call.  Call ``i`` of a run with seed ``s`` is a pure function
of ``(s, i)``: replication ``r`` of it draws from the Philox stream keyed
``[s, i * R + r]``.  The program receives only the arrays made here.

The arithmetic is a copy of the simulator's sampling (per-replication
Philox streams, the class draw, the service laws, the moving-block
bootstrap), kept here so that a change to the program's sampling cannot
move the yardstick; ``bench/tests/test_bench_gen.py`` holds the two equal.
"""

from __future__ import annotations

import math

import numpy as np

_U64 = 1 << 64


def seed_key(seed: int) -> int:
    """The seed as an unsigned 64-bit key (any whole number is accepted)."""
    return int(seed) % _U64


def rep_stream(seed: int, rep: int) -> np.random.Philox:
    """Counter-based stream of one replication: key ``[seed, rep]``."""
    return np.random.Philox(key=np.array([seed_key(seed), rep],
                                         dtype=np.uint64))


def alphas(config: dict) -> np.ndarray:
    a = np.array([c["alpha"] for c in config["classes"]])
    return a / a.sum() if config["normalize_alpha"] else a


def needs(config: dict) -> np.ndarray:
    return np.array([c["need"] for c in config["classes"]], dtype=np.int64)


def demands(config: dict) -> list[float]:
    """Relative demand alpha_i * mean_i * need_i of each class."""
    return [float(a) * c["mean"] * c["need"]
            for a, c in zip(alphas(config), config["classes"])]


def load(config: dict) -> float:
    """Offered load rho: fixed, or eq. (8)'s 1 - theta sqrt(f_k / k)."""
    rule = config["load"]
    if rule["rule"] == "fixed":
        return float(rule["load"])
    if rule["rule"] == "halfin-whitt":
        return 1.0 - rule["theta"] * math.sqrt(rule["f_k"] / config["k"])
    raise ValueError(f"unknown load rule {rule['rule']!r}")


def arrival_rate(config: dict) -> float:
    """lambda = rho k / sum_i alpha_i mean_i need_i (eq. 1 solved)."""
    return load(config) * config["k"] / sum(demands(config))


def _service(rng: np.random.Generator, cls: dict, size: int) -> np.ndarray:
    if cls["law"] == "exponential":
        return rng.exponential(cls["mean"], size=size)
    if cls["law"] == "lognormal":
        mean, std = cls["mean"], cls["std"]
        sigma2 = math.log(1.0 + std * std / (mean * mean))
        mu = math.log(mean) - 0.5 * sigma2
        return rng.lognormal(mu, math.sqrt(sigma2), size=size)
    raise ValueError(f"unknown service law {cls['law']!r}")


def poisson_rep(config: dict, jobs: int, seed) -> dict[str, np.ndarray]:
    """One replication: Poisson arrivals, i.i.d. classes and services.

    ``seed`` is anything ``numpy.random.default_rng`` takes.
    """
    rng = np.random.default_rng(seed)
    arrival = np.cumsum(rng.exponential(1.0 / arrival_rate(config),
                                        size=jobs))
    cls = rng.choice(len(config["classes"]), size=jobs, p=alphas(config))
    service = np.empty(jobs)
    for i, c in enumerate(config["classes"]):
        mask = cls == i
        service[mask] = _service(rng, c, int(mask.sum()))
    return {"arrival": arrival, "cls": cls.astype(np.int64),
            "service": service, "need": needs(config)[cls]}


def block_bootstrap(base: dict, jobs: int, bitgen) -> dict[str, np.ndarray]:
    """Moving-block bootstrap of a base trace into one replication.

    Whole (gap, class, service, need) records are resampled in blocks of
    ``ceil(J^(1/3))`` consecutive jobs; arrivals are the cumulative sum
    of the resampled gaps.
    """
    n = len(base["arrival"])
    block = min(n, max(1, math.ceil(n ** (1.0 / 3.0))))
    rng = np.random.default_rng(bitgen)
    starts = rng.integers(0, n - block + 1, size=-(-jobs // block))
    idx = (starts[:, None] + np.arange(block)[None, :]).ravel()[:jobs]
    gaps = np.diff(base["arrival"], prepend=0.0)
    return {"arrival": np.cumsum(gaps[idx]), "cls": base["cls"][idx],
            "service": base["service"][idx], "need": base["need"][idx]}


class Inputs:
    """The calls of one run: ``call(i)`` gives the [R, J] arrays of call i."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        self.jobs, self.reps = int(traffic["jobs"]), int(traffic["reps"])
        process = config["arrivals"]["process"]
        if process == "poisson":
            self._base = None
        elif process == "block-bootstrap":
            # one synthesized base trace per run, bootstrapped per call
            self._base = poisson_rep(config, self.jobs, seed_key(seed))
        else:
            raise ValueError(f"unknown arrival process {process!r}")

    def call(self, i: int) -> dict:
        reps = []
        for r in range(self.reps):
            stream = rep_stream(self.seed, i * self.reps + r)
            reps.append(poisson_rep(self.config, self.jobs, stream)
                        if self._base is None
                        else block_bootstrap(self._base, self.jobs, stream))
        out = {f: np.stack([x[f] for x in reps])
               for f in ("arrival", "cls", "service", "need")}
        out["k"] = int(self.config["k"])
        out["C"] = len(self.config["classes"])
        return out
