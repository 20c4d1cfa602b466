"""Replication-sharded engine (``jax-shard``) + device-aware runtime.

Three layers:

* pure helpers — XLA_FLAGS editing, replication padding, mesh bounds —
  tested in-process;
* ``configure_runtime`` — the replacement for the silent
  ``pin_single_thread_runtime`` no-op: a call that lost the race with
  backend init must warn loudly (once), not quietly keep the default
  pool;
* the rtol=0 engine contract — ``jax-shard`` is pinned against ``jax``
  in-process on whatever topology the session has (the registry parity
  tests in ``test_engines.py`` / ``test_sim_cross.py`` pick the engine up
  automatically too), and the real multi-device matrix (4 forced host
  devices, padding, R < device_count, sub-mesh) runs in a subprocess
  because the device-count flag is frozen at backend init.
"""

import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax

from repro.core import engines, shard
from repro.core.shard import (_flag_device_count, _pad_reps,
                              configure_runtime, enable_compile_cache,
                              ensure_host_devices, local_mesh)
from repro.core.workload import figure1_workload

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- XLA_FLAGS parsing / ensure_host_devices ----------------------------------


def test_flag_device_count_parsing():
    F = "--xla_force_host_platform_device_count"
    assert _flag_device_count("") is None
    assert _flag_device_count("--xla_cpu_foo=1") is None
    assert _flag_device_count(f"{F}=4") == 4
    assert _flag_device_count(f"--xla_cpu_foo=1 {F}=8 --bar=2") == 8
    # the last occurrence wins (mirrors how XLA parses repeated flags)
    assert _flag_device_count(f"{F}=4 {F}=2") == 2
    assert _flag_device_count(f"{F}=banana") is None


def test_ensure_host_devices_validates_after_init():
    jax.devices()  # force backend init (pytest usually has already)
    have = jax.local_device_count()
    # enough devices exist: validated no-op, nothing rewritten
    assert ensure_host_devices(have) is False
    assert ensure_host_devices(1) is False
    # more than exist: loud error, never a silently smaller mesh
    with pytest.raises(RuntimeError, match="already initialized"):
        ensure_host_devices(have + 1)
    with pytest.raises(ValueError):
        ensure_host_devices(0)


# -- configure_runtime ---------------------------------------------------------


def test_configure_runtime_warns_once_after_backend_init(monkeypatch):
    """The old pin silently no-op'ed when a caller touched jax.devices()
    first; configure_runtime must say so — loudly, once."""
    jax.devices()
    monkeypatch.setattr(shard, "_warned", False)
    monkeypatch.setattr(shard, "_configured_devices", None)
    with pytest.warns(RuntimeWarning, match="after the JAX backend"):
        assert configure_runtime(devices=1) is False
    # once per process: the second late call stays quiet (and still False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert configure_runtime(devices=1) is False
    # runtime is untouched and fully usable after the failed call
    assert int(jax.numpy.arange(3).sum()) == 3


def test_configure_runtime_silent_when_request_already_covered(monkeypatch):
    """Opportunistic re-calls (every benchmark helper) after a successful
    main-entry configuration are idempotent successes, not warnings."""
    jax.devices()
    monkeypatch.setattr(shard, "_warned", False)
    monkeypatch.setattr(shard, "_configured_devices",
                        jax.local_device_count())
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert configure_runtime() is True
        assert configure_runtime(devices=1) is True


def test_configure_runtime_rejects_bad_args():
    with pytest.raises(ValueError):
        configure_runtime(devices=0)
    with pytest.raises(ValueError):
        configure_runtime(devices=1, intra_op_threads=0)


def test_configure_runtime_leaves_affinity_alone_off_cpu(monkeypatch):
    """On an accelerator host the pin would confine the runtime's own
    threads for good: configure_runtime must not touch the affinity (nor
    the host-device flag) when the platform is not the CPU."""
    calls = []
    monkeypatch.setattr(os, "sched_setaffinity",
                        lambda *a: calls.append(a))
    monkeypatch.setattr(shard, "_backends_initialized", lambda: False)
    monkeypatch.setattr(shard, "_configured_devices", None)
    monkeypatch.setenv("XLA_FLAGS", "")
    old = jax.config.jax_platforms
    try:
        for plats in ("tpu", "tpu,cpu", ""):
            jax.config.update("jax_platforms", plats)
            assert configure_runtime(devices=2) is True
        assert calls == [] and os.environ["XLA_FLAGS"] == ""
        # the CPU backend is still pinned, and restored after init
        jax.config.update("jax_platforms", "cpu")
        assert configure_runtime(devices=2) is True
        assert len(calls) == 2 and calls[-1][1] == os.sched_getaffinity(0)
        assert _flag_device_count(os.environ["XLA_FLAGS"]) == 2
    finally:
        jax.config.update("jax_platforms", old)


@pytest.fixture
def compile_cache_on(monkeypatch):
    """The persistent cache switched on for one test (the suite runs with
    it off), restored afterwards."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", old)


def test_enable_compile_cache_creates_dir_and_sets_config(tmp_path,
                                                          compile_cache_on):
    target = tmp_path / "cache" / "nested"
    got = enable_compile_cache(target)
    assert got == str(target) and target.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(target)


def test_enable_compile_cache_env_var_wins(tmp_path, monkeypatch,
                                           compile_cache_on):
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    assert enable_compile_cache(tmp_path / "from_arg") == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == str(env_dir)
    assert env_dir.is_dir() and not (tmp_path / "from_arg").exists()


def test_enable_compile_cache_defaults_to_fixed_checkout_path(
        tmp_path, monkeypatch, compile_cache_on):
    assert shard.DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    monkeypatch.setattr(shard, "DEFAULT_CACHE_DIR", str(tmp_path / "fixed"))
    assert enable_compile_cache() == str(tmp_path / "fixed")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")


def test_enable_compile_cache_stays_off_when_switched_off(tmp_path):
    assert not jax.config.jax_enable_compilation_cache  # the suite's state
    old = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(tmp_path / "c") is None
    assert jax.config.jax_compilation_cache_dir == old
    assert not (tmp_path / "c").exists()


# -- padding / mesh helpers ----------------------------------------------------


def test_pad_reps_repeats_last_lane_and_roundtrips():
    a = np.arange(12.0).reshape(3, 4)
    b = np.arange(3)
    (pa, pb), R = _pad_reps(2, a, b)
    assert R == 3 and pa.shape == (4, 4) and pb.shape == (4,)
    assert np.array_equal(pa[:3], a) and np.array_equal(pb[:3], b)
    assert np.array_equal(pa[3], a[2]) and pb[3] == b[2]
    # already divisible: arrays pass through untouched (same objects)
    (qa, qb), R = _pad_reps(3, a, b)
    assert R == 3 and qa is a and qb is b
    # more devices than replications: pad 1 -> n_dev
    (ra,), R = _pad_reps(4, a[:1])
    assert R == 1 and ra.shape == (4, 4)
    assert (ra == a[0]).all()


def test_local_mesh_bounds():
    n = jax.local_device_count()
    assert local_mesh().size == n
    assert local_mesh(1).size == 1
    assert local_mesh(1).axis_names == ("r",)
    with pytest.raises(ValueError, match="devices"):
        local_mesh(n + 1)
    with pytest.raises(ValueError, match="devices"):
        local_mesh(0)


# -- engine contract (current topology) ----------------------------------------


def test_jax_shard_bit_identical_to_jax_in_process():
    """rtol=0 vs the vmapped scans on whatever mesh this session has
    (1 device in a plain pytest run; 4 under the CI shard job's
    XLA_FLAGS) — including an R that does not divide any device count
    > 1, so the padding path is live whenever the topology is."""
    wl = figure1_workload(32, theta=0.7)
    batch = wl.sample_traces(500, 3, seed=11)
    for pol in ("fcfs", "modbs-fcfs", "bs-fcfs"):
        ref = engines.simulate(pol, batch, engine="jax", wl=wl)
        out = engines.simulate(pol, batch, engine="jax-shard", wl=wl)
        for f in ("response", "wait", "start", "blocked", "p_helper",
                  "p_routed"):
            a, b = getattr(out, f), getattr(ref, f)
            assert (a is None) == (b is None), (pol, f)
            if a is not None:
                assert np.array_equal(a, b), (pol, f)
        assert out.response.shape == (3, 500)


def test_jax_shard_registered_for_the_substrate_policies():
    assert engines.policies_for("jax-shard") == ("bs-fcfs", "fcfs",
                                                 "ff-srpt", "modbs-fcfs",
                                                 "sf-srpt")
    assert "jax-shard" in engines.available_engines()


def test_jax_shard_rejects_oversized_mesh():
    wl = figure1_workload(32, theta=0.7)
    batch = wl.sample_traces(50, 2, seed=0)
    with pytest.raises(ValueError, match="devices"):
        engines.simulate("fcfs", batch, engine="jax-shard", wl=wl,
                         devices=jax.local_device_count() + 1)


# -- the real multi-device matrix (subprocess: flag frozen at init) -------------


@pytest.mark.slow
def test_jax_shard_four_device_cross_validation_subprocess():
    """k in {32, 256} x {fcfs, modbs-fcfs, bs-fcfs} on 4 forced host
    devices, R=5 (padding) and R=2 (< device count) plus a 3-device
    sub-mesh — bit-identical to engine="jax" throughout.  Runs in a
    subprocess because the pytest process initialized its backend long
    ago; the script sets XLA_FLAGS itself before importing jax."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO_ROOT, "src"), env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tests",
                                      "_shard_check.py")],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "OK checked=14" in proc.stdout, proc.stdout
