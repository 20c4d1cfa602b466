"""The control: the plain reference put in the program's place.

Computed in float64, as the configurations state, it passes every
cell's comparison; computed in float32, the nearest precision below, it
has to fail it.  The chip readings that set each limit are in PERF.md.
"""

from types import SimpleNamespace

import numpy as np
import pytest

from bench_testlib import cells, tiny_cell

import compare
import run


def _reference_in_place(cell, dtype):
    """A ``Path.run`` that answers with the reference in ``dtype``."""
    ref = cell.reference_module()

    def fake_run(self, batch):
        rows = {"arrival": batch.arrival, "cls": batch.cls,
                "need": batch.need, "service": batch.service}
        out = compare.reference_answers(ref, cell.config, rows, dtype)
        return SimpleNamespace(**out)
    return fake_run


@pytest.mark.parametrize("dtype,correct", [(np.float64, True),
                                           (np.float32, False)])
@pytest.mark.parametrize("name", cells())
def test_control(name, dtype, correct, counter, monkeypatch):
    cell = tiny_cell(name)
    monkeypatch.setattr(cell.path_module().Path, "run",
                        _reference_in_place(cell, dtype))
    res = run.run_cell(cell, 2**31 + 21, 0.0, False, 0.0, counter,
                       log=lambda _: None)
    assert res["correct"] is correct, res["checks"]
    if not correct:
        assert any(c["value"] > c["limit"] for c in res["checks"].values())
