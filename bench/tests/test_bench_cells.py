"""Every cell's pieces load by name, and every cell runs end to end
through the harness's own functions, on the CPU at a tiny size."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_testlib import BENCH, ROOT, cells, tiny_cell

import catalog
import run

#: the numbers a cell's limits must hold, by what its path answers:
#: per-job waits, or moments folded over each replication
READINGS = {
    "batch": {"wait_gap", "mean_wait_gap", "p_wait_gap"},
    "stream": {"mean_wait_gap", "var_wait_gap", "p_wait_gap",
               "p_helper_gap"},
}


@pytest.mark.parametrize("name", cells())
def test_cell_pieces_load(name):
    cell = catalog.cell(ROOT, name)
    assert cell.chips in (1, 4)
    assert cell.config["k"] >= max(c["need"] for c in cell.config["classes"])
    path = cell.path_module()
    assert hasattr(path, "Path")
    assert hasattr(cell.reference_module(), "simulate")
    names = [m["name"] for m in cell.end_to_end + cell.per_layer]
    assert {"jobs_per_s", "setup_s"} <= set(names)
    for m in names:
        assert callable(cell.metric_module(m).read)
    assert READINGS[cell.traffic["path"]] <= set(cell.limits)


@pytest.mark.parametrize("name", cells())
def test_cell_runs_and_is_correct(name, counter):
    cell = tiny_cell(name)
    lines = []
    res = run.run_cell(cell, 2**31 + 3, 0.5, False, 0.0, counter,
                       log=lines.append)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"jobs_per_s", "setup_s"}
    assert res["metrics"]["jobs_per_s"]["unit"] == "jobs/s"
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.limits)
    assert any("0 compiles and 0 cache loads inside the window" in ln
               for ln in lines)
    assert res["device"]["count"] >= 1
    json.dumps(run._finite(res))


def test_traced_run_has_breakdown(counter):
    res = run.run_cell(tiny_cell("fig1-fcfs"), 5, 0.1, True, 0.0, counter,
                       log=lambda _: None)
    assert res["correct"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # no device plane in a CPU trace: the device metrics stay silent
    assert "device_ns_per_job" not in res["metrics"]


def test_same_seed_same_inputs():
    import gen
    cell = tiny_cell("sdsc-srpt")
    a = gen.Inputs(cell.config, cell.traffic, 2**33 + 1).call(3)
    b = gen.Inputs(cell.config, cell.traffic, 2**33 + 1).call(3)
    c = gen.Inputs(cell.config, cell.traffic, 2**33 + 2).call(3)
    assert all((a[f] == b[f]).all() for f in ("arrival", "service"))
    assert not (a["arrival"] == c["arrival"]).all()


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def test_new_pieces_are_picked_up_by_name(tmp_path, counter):
    """A new mix, metric and cell are new files and new entries; no file
    that is there changes."""
    _copy_benchmark(tmp_path)
    before = {p: open(os.path.join(tmp_path, p), "rb").read()
              for p in ("bench/run.py", "bench/catalog.py",
                        "bench/traffic/fcfs-batch.json")}
    (tmp_path / "bench/traffic/fcfs-tiny.json").write_text(json.dumps({
        "policy": "fcfs", "engine": "jax", "path": "batch", "jobs": 300,
        "reps": 4, "engine_kw": {}}))
    (tmp_path / "bench/metrics/calls_done.py").write_text(
        "def read(record, trace):\n"
        "    return float(sum(c['ok'] for c in record['calls']))\n")
    (tmp_path / "bench/limits/fig1-fcfs-tiny.json").write_text(
        (tmp_path / "bench/limits/fig1-fcfs.json").read_text())
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "fig1-fcfs-tiny",
                            "config": "fig1-critical",
                            "traffic": "fcfs-tiny", "chips": 1,
                            "why": "test"})
    bm["end_to_end"].append({"name": "calls_done", "unit": "calls",
                             "better": "higher", "bound": 0.01,
                             "source": "host_clock",
                             "workloads": ["fig1-fcfs-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = catalog.cell(str(tmp_path), "fig1-fcfs-tiny")
    res = run.run_cell(cell, 9, 0.2, False, 0.0, counter, log=lambda _: None)
    assert res["correct"]
    assert res["metrics"]["calls_done"]["value"] == res["attempted"]
    assert {"jobs_per_s", "setup_s"} <= set(res["metrics"])
    for p, data in before.items():
        assert open(os.path.join(tmp_path, p), "rb").read() == data


def test_main_refuses_a_cpu():
    assert run.main(["--workload", "fig1-fcfs", "--seed", "1",
                     "--seconds", "1"]) == 1


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program: the run fails and prints no result."""
    _copy_benchmark(tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fig1-bs", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_cell_exits_nonzero():
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"]) == 2


def test_sample_covers_every_quarter():
    import compare
    sel = compare.sampled_reps(2**31 + 5, 3, 64)
    assert sel == compare.sampled_reps(2**31 + 5, 3, 64)
    assert len(sel) == 16 and len(set(sel)) == 16
    assert [sum(q * 16 <= r < (q + 1) * 16 for r in sel)
            for q in range(4)] == [4, 4, 4, 4]
    assert compare.sampled_reps(1, 1, 16) == list(range(16))
    assert compare.drawn_call(7, [1, 2, 3]) in (1, 2, 3)
