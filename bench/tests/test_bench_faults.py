"""A run with the timed path broken underneath reads ``correct`` false.

Each fault is planted in what ``engines.simulate`` returns, as the
program would produce it, and the rest of the run (warm-up, window,
comparison) is the harness's own:

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


FAULTS = ["state_unchanged", "half_batch", "mean_over_half",
          "answer_altered"]
CASES = [(c, f) for c in cells() for f in FAULTS
         + (["chip_left_out"] if catalog.cell(ROOT, c).chips > 1 else [])]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_caught(name, fault, counter, monkeypatch):
    from repro.core import engines
    real = engines.simulate
    cell = tiny_cell(name)
    monkeypatch.setattr(engines, "simulate", lambda *a, **kw: _broken(
        real(*a, **kw), fault, cell.chips))
    res = run.run_cell(cell, 2**31 + 77, 0.0, False, 0.0, counter,
                       log=lambda _: None)
    assert res["correct"] is False, (fault, res["checks"])
    assert res["failed"] == 0
