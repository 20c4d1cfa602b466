"""The comparison that decides ``correct``, on hand-built answers.

A path is judged by what it answers: per-job waits give ``wait_gap``,
folded moments give ``var_wait_gap`` and ``p_helper_gap``; a limit that
no answer reads fails.
"""

import math

import numpy as np
import pytest

import compare


def _per_job():
    """Fixed answers of a per-job path, and the reference's."""
    rng = np.random.default_rng(20261018)
    ref = rng.exponential(3.0, size=(4, 64))
    ref[:, ::3] = 0.0
    wait = ref + rng.normal(0.0, 1e-9, size=ref.shape) * (ref > 0)
    wait[2, 5] = 0.0
    got = {"wait": wait, "mean_wait": wait.mean(axis=1),
           "p_wait": (wait > 1e-9).mean(axis=1),
           "preemptions": np.array([3, 0, 6, 2])}
    want = {"wait": ref, "mean_wait": ref.mean(axis=1),
            "var_wait": ref.var(axis=1), "p_wait": (ref > 1e-9).mean(axis=1),
            "p_helper": np.full(4, 0.25),
            "preemptions": np.array([3, 0, 7, 2])}
    return got, want, np.array([50.0, 60.0, 70.0, 80.0])


def test_per_job_readings_are_unchanged():
    """The readings of a per-job path, bit for bit as the comparison gave
    them before it read folded answers."""
    got, want, horizon = _per_job()
    assert {k: float(v).hex() for k, v in
            compare.readings(got, want, horizon).items()} == {
        "wait_gap": "0x1.a94ec6ea1d088p-4",
        "mean_wait_gap": "0x1.8e4efacca8f1cp-5",
        "p_wait_gap": "0x1.0000000000000p-6",
        "preempt_gap": "0x1.0000000000000p+0"}


def test_folded_readings():
    _, want, horizon = _per_job()
    got = {"mean_wait": want["mean_wait"] * (1 + 1e-12),
           "var_wait": want["var_wait"] * (1 - 1e-10),
           "p_wait": want["p_wait"], "p_helper": want["p_helper"] + 1 / 64}
    out = compare.readings(got, want, horizon)
    assert set(out) == {"mean_wait_gap", "var_wait_gap", "p_wait_gap",
                        "p_helper_gap"}
    assert out["mean_wait_gap"] == pytest.approx(1e-12, rel=1e-3)
    assert out["var_wait_gap"] == pytest.approx(1e-10, rel=1e-3)
    assert out["p_wait_gap"] == 0.0
    assert out["p_helper_gap"] == 1 / 64
    # a path that has no helper share where the reference gives one
    assert compare.readings(dict(got, p_helper=None), want,
                            horizon)["p_helper_gap"] == math.inf
    # a reference without helpers reads no helper share
    assert "p_helper_gap" not in compare.readings(
        got, dict(want, p_helper=None), horizon)


@pytest.mark.parametrize("values,correct", [
    ({"mean_wait_gap": 0.0, "wait_gap": 0.0}, True),
    ({"mean_wait_gap": 0.0}, False),             # wait_gap has no reading
    ({"mean_wait_gap": 0.0, "wait_gap": math.nan}, False),
    ({"mean_wait_gap": 2e-8, "wait_gap": 0.0}, False),
])
def test_verdict(values, correct):
    limits = {"wait_gap": 1e-8, "mean_wait_gap": 1e-8}
    ok, checks = compare.verdict(values, limits)
    assert ok is correct
    assert set(checks) == set(limits)
    if "wait_gap" not in values:
        assert checks["wait_gap"] == {"value": math.inf, "limit": 1e-8}


def test_reading_without_limit_is_an_error():
    with pytest.raises(KeyError, match="p_helper_gap"):
        compare.verdict({"p_helper_gap": 0.0}, {"mean_wait_gap": 1e-8})


def test_reference_folds_in_float64():
    """``var_wait`` is the population variance, ``p_helper`` the share of
    jobs the reference reports as served by the helpers."""
    class Ref:
        @staticmethod
        def simulate(arrival, cls, need, service, config, dtype):
            wait = np.asarray(service, dtype) - np.asarray(arrival, dtype)
            return {"wait": wait.astype(np.float64), "preemptions": None,
                    "helper": np.asarray(cls) == 1}
    rows = {"arrival": np.zeros((2, 4)), "cls": np.array([[0, 1, 1, 0],
                                                          [0, 0, 0, 1]]),
            "need": np.ones((2, 4), int),
            "service": np.array([[1.0, 2.0, 3.0, 6.0], [0.0, 0.0, 0.0, 4.0]])}
    out = compare.reference_answers(Ref, {}, rows, np.float64)
    np.testing.assert_array_equal(out["var_wait"], [3.5, 3.0])
    np.testing.assert_array_equal(out["p_helper"], [0.5, 0.25])
    np.testing.assert_array_equal(out["mean_wait"], [3.0, 1.0])
    assert out["preemptions"] is None
