"""The comparison that decides ``correct``.

After the window, one call of the window is drawn from the seed, and up
to 16 of its replications, as many from each quarter of the batch (so
that every quarter, and every chip of a four-chip mesh, is looked at).
The plain reference of the cell's policy runs each of them again, from
the same inputs, and four numbers are compared, each with its limit
(``bench/limits/<cell>.json``):

* ``wait_gap``: the largest gap between a job's wait as the program
  returned it and as the reference computes it, over the replication's
  horizon (its last arrival time): a relative error of the times;
* ``mean_wait_gap``: the largest relative gap of a replication's mean
  wait;
* ``p_wait_gap``: the largest gap of a replication's share of jobs that
  wait (an exact count, so its limit is 0);
* ``preempt_gap``: the largest gap of a replication's preemption count
  (preemptive policies only; exact, limit 0).

A number that is not finite reads as infinite, and so fails.
"""

from __future__ import annotations

import math

import numpy as np

#: wait above which a job counts as having waited, as in P[wait > 0]
WAIT_EPS = 1e-9
#: replications compared per run
SAMPLE = 16
STRATA = 4


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def sampled_reps(seed: int, call: int, reps: int) -> list[int]:
    """Replications of ``call`` to compare: all of them up to ``SAMPLE``,
    else ``SAMPLE / STRATA`` drawn from each quarter of the batch."""
    if reps <= SAMPLE:
        return list(range(reps))
    rng = _rng(seed, call, 1)
    out = []
    for q in range(STRATA):
        lo, hi = q * reps // STRATA, (q + 1) * reps // STRATA
        out.extend(sorted(rng.choice(np.arange(lo, hi), SAMPLE // STRATA,
                                     replace=False).tolist()))
    return out


def rows(inputs: dict, reps: list[int]) -> dict:
    """The inputs of the replications ``reps`` of one call."""
    return {f: inputs[f][reps] for f in ("arrival", "cls", "need", "service")}


def drawn_call(seed: int, calls: list[int]) -> int:
    """The call of the window whose answers are compared."""
    return calls[int(_rng(seed, 0).integers(len(calls)))]


def _gap(a, b, scale=1.0) -> float:
    with np.errstate(invalid="ignore", over="ignore"):
        d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    m = float(np.max(d)) if d.size else 0.0
    return m / float(scale) if math.isfinite(m) else math.inf


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)) or b == 0:
        return math.inf
    return abs(a - b) / abs(b)


def reference_answers(reference, config: dict, rows: dict, dtype) -> dict:
    """The reference's answers for the replications in ``rows`` (arrays of
    the inputs, one row per replication)."""
    waits, pre, helper = [], [], []
    for r in range(len(rows["arrival"])):
        out = reference.simulate(rows["arrival"][r], rows["cls"][r],
                                 rows["need"][r], rows["service"][r],
                                 config, dtype)
        waits.append(out["wait"])
        pre.append(out["preemptions"])
        helper.append(out.get("helper"))
    wait = np.stack(waits)
    return {"wait": wait, "mean_wait": wait.mean(axis=1),
            "var_wait": wait.var(axis=1),
            "p_wait": (wait > WAIT_EPS).mean(axis=1),
            "p_helper": (None if helper[0] is None
                         else np.stack(helper).mean(axis=1)),
            "preemptions": None if pre[0] is None else np.array(pre)}


def _max_rel(got, ref) -> float:
    return max(_rel(float(g), float(r)) for g, r in zip(got, ref))


def readings(got: dict, ref: dict, horizon: np.ndarray) -> dict:
    """The numbers compared: the program's answers ``got`` against the
    reference's ``ref``, row by row.  Each number is read only where
    ``got`` holds its answer (and the reference gives one)."""
    out = {}
    if "wait" in got:
        out["wait_gap"] = max(_gap(g, r, h) for g, r, h in
                              zip(got["wait"], ref["wait"], horizon))
    if "mean_wait" in got:
        out["mean_wait_gap"] = _max_rel(got["mean_wait"], ref["mean_wait"])
    if "var_wait" in got:
        out["var_wait_gap"] = _max_rel(got["var_wait"], ref["var_wait"])
    if "p_wait" in got:
        out["p_wait_gap"] = _gap(got["p_wait"], ref["p_wait"])
    if "p_helper" in got and ref["p_helper"] is not None:
        out["p_helper_gap"] = (_gap(got["p_helper"], ref["p_helper"])
                               if got["p_helper"] is not None
                               else math.inf)
    if "preemptions" in got and ref["preemptions"] is not None:
        out["preempt_gap"] = (_gap(got["preemptions"], ref["preemptions"])
                              if got["preemptions"] is not None
                              else math.inf)
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) of the numbers compared.  A
    limit with no reading reads infinite: a path never passes a check by
    answering less."""
    checks = {}
    for name, v in values.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        checks[name] = {"value": v, "limit": limits[name]}
    for name, limit in limits.items():
        checks.setdefault(name, {"value": math.inf, "limit": limit})
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
