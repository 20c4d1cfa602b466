"""A run with the timed path broken underneath reads ``correct`` false.

Each fault is planted in what the program returns, as it would produce
it, or in the program's own code, and the rest of the run (warm-up,
window, comparison) is the harness's own.  A path that returns per-job
waits (``batch``):

* ``state_unchanged``: the scan's state never advances, so every job
  starts at its arrival;
* ``half_batch``: the second half of the replications is left out and
  filled with copies of the first;
* ``mean_over_half``: each replication's mean wait and share of waiting
  jobs are taken over the first half of its jobs only;
* ``answer_altered``: one job's wait in each replication is off by a
  millionth of the horizon;
* ``chip_left_out`` (cells on several chips): the replications of every
  chip but the first never come back, and read 0.

A path that streams a call (``stream``) and returns folded moments:

* ``state_unchanged``: no job waits;
* ``half_batch``: as above, on the folded answers;
* ``mean_nudged``: each replication's mean wait is off by a millionth;
* ``helpers_reset``: the helpers' state does not cross a chunk boundary
  (``_bs_extract`` hands on idle helpers), so later chunks start jobs
  too early;
* ``m2_cross_term_dropped``: the accumulator's merge leaves out the
  delta**2 * n_a * n_b / n term, so the mean stays right and the variance
  goes wrong.

``carry_dropped``, every chunk starting from an empty system
(``_bs_extract`` returning the initial state), loses the jobs in service
and in the queue; the program's own end-of-stream count refuses it, so
such a run ends in an error and prints no result.
"""

import dataclasses

import numpy as np
import pytest

from bench_testlib import ROOT, cells, tiny_cell

import catalog
import run


class _MeanOverHalf:
    @property
    def mean_wait(self):
        return self.wait[:, : self.wait.shape[1] // 2].mean(axis=1)

    @property
    def p_wait(self):
        return (self.wait[:, : self.wait.shape[1] // 2] > 1e-9).mean(axis=1)


def _broken(res, fault: str, chips: int):
    R, J = res.wait.shape
    wait = res.wait.copy()
    if fault == "state_unchanged":
        return dataclasses.replace(res, wait=np.zeros_like(wait))
    if fault == "half_batch":
        half = (R + 1) // 2
        wait[half:] = wait[: R - half]
        return dataclasses.replace(res, wait=wait)
    if fault == "mean_over_half":
        cls = type("MeanOverHalf", (_MeanOverHalf, type(res)), {})
        return cls(**{f.name: getattr(res, f.name)
                      for f in dataclasses.fields(res)})
    if fault == "answer_altered":
        horizon = res.response.max(axis=1)
        wait[:, J // 2] += 1e-6 * horizon
        return dataclasses.replace(res, wait=wait)
    if fault == "chip_left_out":
        wait[R // chips:] = 0.0
        return dataclasses.replace(res, wait=wait)
    raise ValueError(fault)


def _broken_stream(res, fault: str):
    R = res.reps
    if fault == "state_unchanged":
        zero = np.zeros(R)
        return dataclasses.replace(res, mean_wait=zero, var_wait=zero,
                                   p_wait=zero)
    if fault == "half_batch":
        half = (R + 1) // 2
        out = {}
        for f in ("mean_wait", "var_wait", "p_wait", "p_helper"):
            a = getattr(res, f).copy()
            a[half:] = a[: R - half]
            out[f] = a
        return dataclasses.replace(res, **out)
    if fault == "mean_nudged":
        return dataclasses.replace(res, mean_wait=res.mean_wait * (1 + 1e-6))
    raise ValueError(fault)


def _merge_without_cross_term(cnt, mean, m2, buf, b):
    blk = buf[:, :, :b]
    bm = blk.mean(axis=2)
    bm2 = ((blk - bm[:, :, None]) ** 2).sum(axis=2)
    tot = cnt + b
    return tot, mean + (bm - mean) * (b / tot), m2 + bm2


def _plant(monkeypatch, cell, fault: str) -> None:
    from repro.core import engines, sim_batch
    if cell.traffic["path"] != "stream":
        real = engines.simulate
        monkeypatch.setattr(engines, "simulate", lambda *a, **kw: _broken(
            real(*a, **kw), fault, cell.chips))
    elif fault == "helpers_reset":
        extract = sim_batch._bs_extract

        def idle_helpers(*a):
            canon = extract(*a)
            return dict(canon, **{k: np.zeros_like(canon[k])
                                  for k in ("W", "t_prev", "t_hol")})
        monkeypatch.setattr(sim_batch, "_bs_extract", idle_helpers)
    elif fault == "m2_cross_term_dropped":
        monkeypatch.setattr(sim_batch.StreamAccumulator, "_merge",
                            staticmethod(_merge_without_cross_term))
    else:
        real = engines.simulate_stream
        monkeypatch.setattr(engines, "simulate_stream",
                            lambda *a, **kw: _broken_stream(real(*a, **kw),
                                                            fault))


FAULTS = {
    "batch": ["state_unchanged", "half_batch", "mean_over_half",
              "answer_altered"],
    "stream": ["state_unchanged", "half_batch", "mean_nudged",
               "helpers_reset", "m2_cross_term_dropped"],
}


def _cases():
    for c in cells():
        cell = catalog.cell(ROOT, c)
        faults = FAULTS[cell.traffic["path"]]
        if cell.chips > 1:
            faults = faults + ["chip_left_out"]
        yield from ((c, f) for f in faults)


@pytest.mark.parametrize("name,fault", list(_cases()))
def test_fault_is_caught(name, fault, counter, monkeypatch):
    cell = tiny_cell(name)
    _plant(monkeypatch, cell, fault)
    res = run.run_cell(cell, 2**31 + 77, 0.0, False, 0.0, counter,
                       log=lambda _: None)
    assert res["correct"] is False, (fault, res["checks"])
    assert res["failed"] == 0


@pytest.mark.parametrize("name", [c for c in cells() if catalog.cell(
    ROOT, c).traffic["path"] == "stream"])
def test_stream_carry_dropped_ends_the_run(name, counter, monkeypatch):
    from repro.core import sim_batch
    first = {}
    canon0 = sim_batch._bs_canon0

    def initial(*a):
        first["canon"] = canon0(*a)
        return first["canon"]
    monkeypatch.setattr(sim_batch, "_bs_canon0", initial)
    monkeypatch.setattr(sim_batch, "_bs_extract", lambda *a: {
        k: v.copy() for k, v in first["canon"].items()})
    with pytest.raises(RuntimeError, match="unprocessed events"):
        run.run_cell(tiny_cell(name), 2**31 + 77, 0.0, False, 0.0, counter,
                     log=lambda _: None)
