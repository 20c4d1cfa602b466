"""Replication-sharded execution (``engine="jax-shard"``) and device topology.

The vmapped scan cores of :mod:`repro.core.sim_batch` advance every
replication on **one** device: fast per dispatched op, but a k-sweep with
many replications leaves every other device idle.  This module owns the
device side of the substrate:

* :func:`ensure_host_devices` — force N XLA host-platform devices (the
  ``--xla_force_host_platform_device_count`` flag) *before* backend init,
  so multi-device execution needs no accelerator: any CPU box exposes N
  devices today, and the identical mesh/``shard_map`` code path is what a
  real TPU mesh will compile.
* :func:`local_mesh` — a 1-D :class:`jax.sharding.Mesh` over the local
  devices with a single ``"r"`` (replications) axis.
* the ``engine="jax-shard"`` simulation cores: the same scan cores as
  ``engine="jax"`` (:mod:`repro.core.sim_jax` — FCFS roll-and-insert,
  ModBS slot-counter, the hand-vectorized BS-π event scan), wrapped in
  ``shard_map`` so the replications axis is split across the mesh.  Every
  per-lane step is lane-independent by construction (the BS-π scan
  vectorizes its lane axis with per-lane gather/scatter indices and no
  cross-lane reductions), so sharding the lane axis is legal and the
  results are **bit-identical** to every other engine of the policy — the
  registry contract (rtol=0) pins this in ``tests/test_sim_cross.py`` /
  ``tests/test_engines.py`` the moment the cores register.
* R-padding: replication counts need not divide the device count.  Batches
  are padded up to the next multiple of the mesh size by repeating the
  last replication (always a valid lane — no sentinel values to thread
  through the scan cores) and the padded lanes are dropped before
  :class:`~repro.core.sim_batch.BatchSimResult` assembly.
* :func:`configure_runtime` — the device-aware successor of
  ``pin_single_thread_runtime()``: forces the device count *and* sizes the
  XLA:CPU intra-op pool to ``devices * intra_op_threads`` threads (PJRT
  sizes the pool from the CPUs visible at backend init, so the pool is
  restricted via process affinity around the init call).  The single-core
  1-thread pin that bought 3-4x on the dispatch-bound BS scan is the
  ``devices=1`` special case.  Unlike the old pin, a call that comes too
  late (backend already initialized by someone else) **warns loudly once**
  instead of silently keeping the default pool.  It configures only a CPU
  backend (``JAX_PLATFORMS=cpu``); on an accelerator host it touches
  nothing.
* :func:`enable_compile_cache` — persistent JAX compilation cache at
  ``JAX_COMPILATION_CACHE_DIR`` or a fixed in-checkout path, so repeated
  k-sweeps stop paying XLA compilation per (k, R, J) cell.

CPU caveat (measured, 2-core host): XLA:CPU backs all host-platform
devices of a process with **one shared intra-op thread pool**, so the
wide data-parallel scans (FCFS/ModBS: every op touches all lanes x k
entries) gain from sharding, while the dispatch-bound BS-π event scan —
whose single-thread pin exists precisely to avoid per-op cross-thread
handoffs — can lose a little to pool contention until each device really
owns a core.  On a TPU mesh each device is a physically separate core and
the same ``shard_map`` program shards without that contention.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64
from jax.sharding import Mesh, PartitionSpec as P

from . import engines
from . import failures as flr
from .partition import balanced_partition
from .sim_batch import (_backends_initialized, _bs_fail_args,
                        _bs_fail_grid_plan, _bs_grid_carry, _bs_grid_extract,
                        _bs_grid_plan, _bs_result, _BS_CARRY_DTYPES,
                        _bs_stream_args, _bs_stream_drive, _call,
                        _class_inputs, _dev, _fcfs_fail_grid_extract,
                        _fcfs_fail_grid_plan, _fcfs_grid_extract,
                        _fcfs_grid_plan, _fcfs_inputs, _fcfs_result,
                        _fcfs_stream_init, _fetch, _merged_fcfs_inputs,
                        _modbs_fail_grid_extract, _modbs_fail_grid_plan,
                        _modbs_grid_extract, _modbs_grid_plan, _modbs_result,
                        _modbs_stream_init, _partition_args, _puts,
                        _scan_stream, _slice_stream_result, _srpt_grid_carry,
                        _srpt_grid_extract, _srpt_grid_plan, _srpt_inputs,
                        _srpt_k_mult, _srpt_no_failures, _srpt_nu,
                        _srpt_result, _stream_partition, _with_drain_obs)
from .sim_jax import (_bs_args, _bs_core, _bs_fail_core,
                      _bs_fail_stream_core, _bs_stream_core, _fcfs_core,
                      _fcfs_fail_core, _fcfs_fail_stream_core,
                      _fcfs_stream_core, _modbs_core, _modbs_fail_core,
                      _modbs_fail_stream_core, _modbs_stream_core,
                      _srpt_args, _srpt_core, _srpt_pairwise,
                      _srpt_stream_core)
from .workload import BatchTrace

_FLAG = "--xla_force_host_platform_device_count"

#: Every sharded body below is strictly per-lane: no collective crosses a
#: shard.  The scan carries start from constants, which the
#: varying-manual-axes check types as replicated while the carried-out
#: values vary over the mesh, so the check would refuse a correct program
#: and has nothing else to verify here.
shard_map = partial(jax.shard_map, check_vma=False)


# --------------------------------------------------------------------------
# Device topology.
# --------------------------------------------------------------------------


def _flag_device_count(flags: str) -> int | None:
    """The forced host-platform device count in an XLA_FLAGS string."""
    for tok in reversed(flags.split()):
        if tok.startswith(_FLAG + "="):
            try:
                return int(tok.split("=", 1)[1])
            except ValueError:
                return None
    return None


def ensure_host_devices(n: int) -> bool:
    """Force at least ``n`` XLA host-platform (CPU) devices.

    Must run before the first JAX computation: the flag only takes effect
    at backend init.  Before init this sets (or raises) the
    ``--xla_force_host_platform_device_count`` entry of ``XLA_FLAGS`` and
    returns True.  After init it validates instead: no-op returning False
    when ``n`` devices already exist, ``RuntimeError`` otherwise — a
    too-late call must never silently hand back a smaller mesh.
    """
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if _backends_initialized():
        # too late for the flag: validate against the real topology — a
        # too-small mesh must raise, not silently shrink
        have = jax.local_device_count()
        if have < n:
            raise RuntimeError(
                f"JAX backend already initialized with {have} device(s), "
                f"cannot expose {n}; set XLA_FLAGS={_FLAG}={n} (or call "
                f"configure_runtime) before the first JAX computation")
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    cur = _flag_device_count(flags)
    if cur is not None and cur >= n:
        return True
    toks = [t for t in flags.split() if not t.startswith(_FLAG + "=")]
    toks.append(f"{_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(toks)
    return True


def local_mesh(devices: int | None = None) -> Mesh:
    """A 1-D mesh over the local devices, replications axis ``"r"``.

    ``devices`` takes the first N local devices (default: all of them);
    asking for more than exist is a loud error, not a silent shrink.
    """
    avail = jax.devices()
    n = len(avail) if devices is None else devices
    if not 1 <= n <= len(avail):
        raise ValueError(f"requested {devices} devices, "
                         f"{len(avail)} available")
    return Mesh(np.array(avail[:n]), ("r",))


def grid_mesh(n_cells: int, devices: int | None = None) -> Mesh:
    """A 2-D ``("c", "r")`` mesh over the local devices for grid sweeps.

    The cell axis gets the largest divisor of the device count that does
    not exceed ``n_cells`` (a grid smaller than the device count still
    uses every device — the remainder shards replications), the
    replications axis the rest.  Grid and replication counts need not
    divide the mesh sizes: callers pad both axes (repeating the last
    cell / replication) and slice the outputs back.
    """
    if n_cells < 1:
        raise ValueError(f"need at least one grid cell, got {n_cells}")
    avail = jax.devices()
    n = len(avail) if devices is None else devices
    if not 1 <= n <= len(avail):
        raise ValueError(f"requested {devices} devices, "
                         f"{len(avail)} available")
    dc = max(d for d in range(1, n + 1) if n % d == 0 and d <= n_cells)
    return Mesh(np.array(avail[:n]).reshape(dc, n // dc), ("c", "r"))


# --------------------------------------------------------------------------
# Runtime configuration (successor of pin_single_thread_runtime).
# --------------------------------------------------------------------------

#: devices configured by a successful configure_runtime() call, else None
_configured_devices: int | None = None
_warned = False


def _warn_once(msg: str) -> None:
    global _warned
    if not _warned:
        _warned = True
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


#: the persistent compilation cache's fixed home inside the checkout
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache(cache_dir: str | os.PathLike | None = None
                         ) -> str | None:
    """Switch JAX's persistent compilation cache on; every entry point
    calls this once.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set (no
    argument overrides it), else ``cache_dir``, else the fixed
    :data:`DEFAULT_CACHE_DIR` — a fixed path, so a later run hits what an
    earlier one wrote.  Every executable compiled from here on is written
    to (and on later runs loaded from) the directory, so a repeated
    k-sweep pays tracing but not XLA compilation per (k, R, J) cell.
    Returns the directory, or None
    when the cache is switched off (``JAX_ENABLE_COMPILATION_CACHE=false``,
    as the test suite runs).  Callable before or after backend init.
    """
    if not jax.config.jax_enable_compilation_cache:
        return None
    cache_dir = os.fspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or cache_dir or DEFAULT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)  # the cache never mkdirs itself
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every hit: the scan executables compile fast but recompile often
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _cpu_backend() -> bool:
    """Whether the backend that comes up is XLA:CPU, decided before init
    from ``jax_platforms`` (``JAX_PLATFORMS``): unset lets JAX pick an
    accelerator."""
    plats = {p.strip() for p in (jax.config.jax_platforms or "").split(",")}
    return plats == {"cpu"}


def configure_runtime(devices: int | None = None, intra_op_threads: int = 1,
                      *, warn: bool = True) -> bool:
    """Device-aware XLA:CPU runtime setup — replaces
    ``pin_single_thread_runtime``.

    Forces ``devices`` host-platform devices (default: whatever an
    existing ``XLA_FLAGS`` entry requests, else 1) and initializes the
    backend with the process affinity restricted to
    ``devices * intra_op_threads`` CPUs, so PJRT sizes its intra-op pool
    to exactly that many threads — ``intra_op_threads=1`` keeps the
    per-op-dispatch win of the old single-thread pin (3-4x on the BS event
    scan) per device.

    Only a CPU backend is configured (``JAX_PLATFORMS=cpu``): any other
    backend starts its own runtime threads during init, and the pin would
    confine them for the life of the process (restoring the mask resets
    only the calling thread).  There the call touches nothing and returns
    True.

    Returns True when the runtime is configured as requested.  When the
    backend was **already initialized** by an earlier JAX call the pool
    cannot be resized: the call warns loudly once (``RuntimeWarning``,
    suppressed by ``warn=False`` for opportunistic library callers) and
    returns False — unless a previous ``configure_runtime`` already set up
    a runtime that covers the request, which is an idempotent success.
    Where process affinity is unavailable (non-Linux), the device count
    still takes effect but the pool keeps its default size: the call
    returns False without warning, and later calls treat the topology as
    configured.
    """
    global _configured_devices
    if devices is None:
        devices = _flag_device_count(os.environ.get("XLA_FLAGS", "")) or 1
    if devices < 1 or intra_op_threads < 1:
        raise ValueError("devices and intra_op_threads must be >= 1")
    if not _cpu_backend():
        return True
    if _backends_initialized():
        # subsumed iff a previous call really configured the runtime (the
        # pool was pinned) AND the live topology covers the request — the
        # recorded count can understate reality when an env XLA_FLAGS
        # asked for more devices than that call did
        if (_configured_devices is not None
                and jax.local_device_count() >= devices):
            return True
        if warn:
            _warn_once(
                f"configure_runtime(devices={devices}) called after the JAX "
                "backend was initialized: the intra-op thread pool and "
                "device count are frozen at backend init, so this call "
                "cannot take effect. Call configure_runtime (or set "
                f"XLA_FLAGS={_FLAG}=N) before the first JAX computation.")
        return False
    ensure_host_devices(devices)
    # the device topology is now committed (the flag applies at first JAX
    # use even if pool pinning below is unavailable) — record it so later
    # calls are recognized as subsumed instead of spuriously warning
    _configured_devices = devices
    try:
        cpus = os.sched_getaffinity(0)
        pool = min(devices * intra_op_threads, len(cpus))
        os.sched_setaffinity(0, set(sorted(cpus)[:pool]))
        try:
            jax.devices()  # backend init sees exactly `pool` CPUs
        finally:
            os.sched_setaffinity(0, cpus)
    except (AttributeError, OSError):  # non-Linux or restricted:
        return False  # devices take effect, the pool stays default-sized
    return True


# --------------------------------------------------------------------------
# Replication padding.
# --------------------------------------------------------------------------


def _pad_reps(n_dev: int, *arrays: np.ndarray):
    """Pad the leading replications axis up to a multiple of ``n_dev``.

    Padding repeats the last replication — always a valid sample path, so
    the scan cores need no sentinel handling and a padded BS-π lane can
    never overflow a ring buffer its source lane did not.  Returns the
    (possibly shared-memory) padded arrays and the true replication count;
    callers slice outputs back to ``[:R]`` before result assembly.
    """
    R = arrays[0].shape[0]
    pad = (-R) % n_dev
    if pad == 0:
        return arrays, R
    return tuple(np.concatenate(
        [a, np.broadcast_to(a[-1:], (pad,) + a.shape[1:])], axis=0)
        for a in arrays), R


def _pad_batch(batch: BatchTrace, n_dev: int) -> tuple[BatchTrace, int]:
    """``batch`` with its replications padded to a multiple of ``n_dev``.

    Delegates to :meth:`BatchTrace.pad_reps` (repeat the last replication
    — always a valid sample path) and returns a :class:`BatchTrace` so the
    sharded cores feed the *same* input-prep helpers
    (``_fcfs_inputs``/``_class_inputs``) as every other engine —
    bit-identical dtype handling by construction.
    """
    R = batch.reps
    return batch.pad_reps(R + (-R) % n_dev), R


# --------------------------------------------------------------------------
# Sharded scan entry points (replications axis split over the mesh).
# --------------------------------------------------------------------------
#
# The mesh is a static jit argument (Mesh is hashable): one executable per
# (shape, k/partition statics, mesh), exactly like the single-device cores
# compile per (k, R, J).  Inputs shard along their leading axis (P("r"));
# the eq.-2 slots vector is replicated (P(None)).


@partial(jax.jit, static_argnums=(3, 4))
def _fcfs_shard_call(arrival, need, service, k: int, mesh: Mesh):
    body = lambda a, n, v: jax.vmap(
        lambda a1, n1, v1: _fcfs_core(a1, n1, v1, k))(a, n, v)
    return shard_map(body, mesh=mesh,
                     in_specs=(P("r"), P("r"), P("r")),
                     out_specs=P("r"))(arrival, need, service)


@partial(jax.jit, static_argnums=(5, 6, 7))
def _modbs_shard_call(arrival, cls, need, service, slots, s_max: int, h: int,
                      mesh: Mesh):
    body = lambda a, c, n, v, s: jax.vmap(
        lambda a1, c1, n1, v1: _modbs_core(a1, c1, n1, v1, s, s_max, h))(
        a, c, n, v)
    return shard_map(body, mesh=mesh,
                     in_specs=(P("r"),) * 4 + (P(),),
                     out_specs=(P("r"), P("r")))(
        arrival, cls, need, service, slots)


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _bs_shard_call(arrival, cls, need, service, slots, s_max: int, h: int,
                   q_cap: int, mesh: Mesh):
    # _bs_core carries the lane axis natively (per-lane gather/scatter
    # indices, no cross-lane ops) — each mesh shard runs it on its slice.
    body = lambda a, c, n, v, s: _bs_core(a, c, n, v, s, s_max, h, q_cap)
    return shard_map(body, mesh=mesh,
                     in_specs=(P("r"),) * 4 + (P(),),
                     out_specs=(P("r"), P("r"), P("r")))(
        arrival, cls, need, service, slots)


@partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _srpt_shard_call(arrival, need, service, kk, Q: int, NU: tuple,
                     sf: bool, k_mult: bool, pairwise: bool, mesh: Mesh):
    # _srpt_core carries the lane axis natively (per-lane sorts and
    # 1-entry scatters, no cross-lane ops) — each shard runs its slice.
    body = lambda a, n, v, k: _srpt_core(a, n, v, k, Q, NU, sf, k_mult,
                                         pairwise)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 4,
                     out_specs=(P("r"),) * 7)(arrival, need, service, kk)


# Failure-aware variants: identical scan cores as engine="jax"
# (sim_jax._*_fail_core), merged streams built host-side from the UNPADDED
# batch, then replication-padded like every other input.


@partial(jax.jit, static_argnums=(5, 6))
def _fcfs_fail_shard_call(t, n, svc, t_up, is_fail, k: int, mesh: Mesh):
    body = lambda a, b, c, d, e: jax.vmap(
        lambda a1, b1, c1, d1, e1: _fcfs_fail_core(a1, b1, c1, d1, e1, k))(
        a, b, c, d, e)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 5,
                     out_specs=P("r"))(t, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnums=(7, 8, 9))
def _modbs_fail_shard_call(t, c, n, svc, t_up, is_fail, slots, s_max: int,
                           h: int, mesh: Mesh):
    body = lambda a, b, cc, d, e, f, s: jax.vmap(
        lambda a1, b1, c1, d1, e1, f1: _modbs_fail_core(
            a1, b1, c1, d1, e1, f1, s, s_max, h))(a, b, cc, d, e, f)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 6 + (P(),),
                     out_specs=(P("r"), P("r")))(
        t, c, n, svc, t_up, is_fail, slots)


@partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _bs_fail_shard_call(arrival, cls, need, service, ft, ftgt, fup, slots,
                        s_max: int, h: int, q_cap: int, length: int,
                        mesh: Mesh):
    body = lambda a, c, n, v, t, g, u, s: _bs_fail_core(
        a, c, n, v, t, g, u, s, s_max, h, q_cap, length)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 7 + (P(),),
                     out_specs=(P("r"), P("r"), P("r")))(
        arrival, cls, need, service, ft, ftgt, fup, slots)


# --------------------------------------------------------------------------
# engine="jax-shard" registry cores.
# --------------------------------------------------------------------------
#
# Same input prep, same scan cores, same result assembly as engine="jax" —
# the only difference is the mesh between them.  `devices` (extra keyword,
# forwarded by engines.simulate) bounds the mesh; default all local.


@engines.register("fcfs", "jax-shard")
def _fcfs_jax_shard(batch, *, partition=None, wl=None, devices=None,
                    failures=None):
    """FCFS with the replications axis sharded across the local mesh."""
    mesh = local_mesh(devices)
    if failures is None:
        padded, R = _pad_batch(batch, mesh.size)
        with enable_x64():
            starts = _fetch(_call(_fcfs_shard_call, *_fcfs_inputs(padded),
                                  batch.k, mesh), R)
        return _fcfs_result(batch, starts)
    flr.require_drain(failures, "jax-shard")
    ms = _merged_fcfs_inputs(batch, failures)
    (t, n, svc, t_up, isf), R = _pad_reps(mesh.size, ms.t, ms.need,
                                          ms.service, ms.t_up, ms.is_fail)
    with enable_x64():
        starts_m = _fetch(_call(
            _fcfs_fail_shard_call,
            *_puts((t, jnp.float64), (n, jnp.int32), (svc, jnp.float64),
                   (t_up, jnp.float64), (isf != 0, jnp.bool_)),
            batch.k, mesh), R)
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    return _with_drain_obs(_fcfs_result(batch, starts), batch, failures)


@engines.register("modbs-fcfs", "jax-shard")
def _modbs_jax_shard(batch, *, partition=None, wl=None, devices=None,
                     failures=None):
    """ModifiedBS-FCFS (Definition 2), replication-sharded."""
    slots, s_max, h = _partition_args(batch, partition, wl)
    mesh = local_mesh(devices)
    if failures is None:
        padded, R = _pad_batch(batch, mesh.size)
        with enable_x64():
            blocked, starts = _fetch(_call(
                _modbs_shard_call, *_class_inputs(padded), jnp.asarray(slots),
                s_max, h, mesh), R)
        return _modbs_result(batch, blocked, starts)
    flr.require_drain(failures, "jax-shard")
    part = partition if partition is not None else balanced_partition(wl)
    ft, ftgt, fup, count = flr.partition_targets(failures, part)
    ms = flr.merge_failure_stream(batch, ft, ftgt, fup, count,
                                  pad_cls=len(part.a))
    (t, c, n, svc, t_up, isf), R = _pad_reps(
        mesh.size, ms.t, ms.cls, ms.need, ms.service, ms.t_up, ms.is_fail)
    with enable_x64():
        blocked_m, starts_m = _fetch(_call(
            _modbs_fail_shard_call,
            *_puts((t, jnp.float64), (c, jnp.int32), (n, jnp.int32),
                   (svc, jnp.float64), (t_up, jnp.float64),
                   (isf != 0, jnp.bool_)),
            jnp.asarray(slots), s_max, h, mesh), R)
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    blocked = np.take_along_axis(blocked_m, ms.job_pos, axis=1)
    return _with_drain_obs(_modbs_result(batch, blocked, starts), batch,
                           failures)


@engines.register("bs-fcfs", "jax-shard")
def _bs_jax_shard(batch, *, partition=None, wl=None, queue_cap=None,
                  devices=None, failures=None):
    """BS-FCFS (Definition 1) event scan, replication-sharded."""
    slots, s_max, h, q_cap = _bs_args(batch, partition, wl, queue_cap)
    mesh = local_mesh(devices)
    if failures is None:
        padded, R = _pad_batch(batch, mesh.size)
        with enable_x64():
            tagged, rec_t, rpk = _fetch(_call(
                _bs_shard_call, *_class_inputs(padded), jnp.asarray(slots),
                s_max, h, q_cap, mesh), R)
        return _bs_result(batch, tagged, rec_t, rpk, q_cap)
    flr.require_drain(failures, "jax-shard")
    ft, ftgt, fup, length = _bs_fail_args(batch, failures, partition, wl)
    padded, R = _pad_batch(batch, mesh.size)
    (ft, ftgt, fup), _ = _pad_reps(mesh.size, ft, ftgt, fup)
    with enable_x64():
        tagged, rec_t, rpk = _fetch(_call(
            _bs_fail_shard_call, *_class_inputs(padded),
            *_puts((ft, jnp.float64), (ftgt, jnp.int32), (fup, jnp.float64)),
            jnp.asarray(slots), s_max, h, q_cap, length, mesh), R)
    return _with_drain_obs(_bs_result(batch, tagged, rec_t, rpk, q_cap),
                           batch, failures)


def _srpt_jax_shard(sf: bool, batch, *, partition=None, wl=None,
                    queue_cap=None, devices=None, failures=None):
    policy = "sf-srpt" if sf else "ff-srpt"
    _srpt_no_failures(failures, policy)
    q_cap = _srpt_args(batch, queue_cap)
    NU, pairwise = _srpt_nu(batch), _srpt_pairwise(q_cap)
    mesh = local_mesh(devices)
    padded, R = _pad_batch(batch, mesh.size)
    with enable_x64():
        job_ev, t_ev, fs_ev, ovf, npre, ne, peak = _fetch(_call(
            _srpt_shard_call, *_srpt_inputs(padded),
            q_cap, NU, sf, _srpt_k_mult(NU, batch), pairwise, mesh), R)
    return _srpt_result(batch, job_ev, t_ev, fs_ev, ovf, npre, ne, q_cap,
                        peak=peak, pairwise=pairwise)


@engines.register("sf-srpt", "jax-shard")
def _sf_srpt_jax_shard(batch, **kw):
    """ServerFilling-SRPT preemptive event scan, replication-sharded."""
    return _srpt_jax_shard(True, batch, **kw)


@engines.register("ff-srpt", "jax-shard")
def _ff_srpt_jax_shard(batch, **kw):
    """FirstFit-SRPT preemptive event scan, replication-sharded."""
    return _srpt_jax_shard(False, batch, **kw)


# --------------------------------------------------------------------------
# Streaming (chunked-carry) execution over the mesh.
# --------------------------------------------------------------------------
#
# The same chunk loop as engine="jax" (the drivers of sim_batch are reused
# verbatim), with the per-chunk scan dispatched through shard_map: the
# carry and the chunk job buffers all shard along the replications axis.
# The chunk source is wrapped so every chunk arrives pre-padded to a
# mesh-size multiple (repeating the last lane — a valid sample path), the
# drivers run at the padded lane count, and the folded StreamResult is
# sliced back to the true replication count at the end.  Checkpoint
# layouts record the *padded* count: a stream checkpointed under one mesh
# size resumes on another only when the padded counts agree — anything
# else fails loudly via require_layout.


@partial(jax.jit, static_argnums=(4,))
def _fcfs_stream_shard_call(carry, arrival, need, service, mesh: Mesh):
    body = lambda c, a, n, v: jax.vmap(_fcfs_stream_core)(c, a, n, v)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 4,
                     out_specs=(P("r"), P("r")))(carry, arrival, need,
                                                 service)


@partial(jax.jit, static_argnums=(5, 6))
def _modbs_stream_shard_call(carry, arrival, cls, need, service, s_max: int,
                             mesh: Mesh):
    body = lambda c, a, cc, n, v: jax.vmap(
        lambda c1, a1, cc1, n1, v1: _modbs_stream_core(
            c1, a1, cc1, n1, v1, s_max))(c, a, cc, n, v)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 5,
                     out_specs=(P("r"), P("r")))(
        carry, arrival, cls, need, service)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _bs_stream_shard_call(carry, arrival, cls, need, service, horizon,
                          C: int, s_max: int, h: int, q_cap: int,
                          length: int, mesh: Mesh):
    body = lambda c, a, cc, n, v, hz: _bs_stream_core(
        a, cc, n, v, hz, c, C, s_max, h, q_cap, length)
    return shard_map(body, mesh=mesh, in_specs=(P("r"),) * 6,
                     out_specs=(P("r"), P("r"), P("r")))(
        carry, arrival, cls, need, service, horizon)


class _PaddedChunkSource:
    """A chunk source whose lanes are padded to a mesh-size multiple.

    Every emitted chunk repeats its last replication lane up to the next
    multiple of ``n_dev`` (``_pad_batch``); state handling passes through
    to the inner source, so determinism and resume semantics are
    untouched — the padded lanes are exact copies of a real lane.
    """

    def __init__(self, inner, n_dev: int):
        self._inner = inner
        self._n_dev = int(n_dev)
        R = int(inner.reps)
        self.reps = R + (-R) % self._n_dev

    @property
    def k(self):
        return self._inner.k

    @property
    def C(self):
        return self._inner.C

    @property
    def total_jobs(self):
        return self._inner.total_jobs

    def init_state(self):
        return self._inner.init_state()

    def next_chunk(self, state, n: int):
        batch, state = self._inner.next_chunk(state, n)
        padded, _ = _pad_batch(batch, self._n_dev)
        return padded, state


@engines.register_stream("fcfs", "jax-shard")
def _fcfs_stream_shard(source, *, chunk_jobs, total_jobs, partition=None,
                       wl=None, policy="fcfs", devices=None, block=4096,
                       ckpt_dir=None, resume=False):
    """Streaming FCFS with the replications axis sharded over the mesh."""
    mesh = local_mesh(devices)
    R = int(source.reps)
    psrc = _PaddedChunkSource(source, mesh.size)

    def chunk_fn(carry, batch):
        with enable_x64():
            carry, starts = _call(_fcfs_stream_shard_call, carry,
                                  *_fcfs_inputs(batch), mesh)
        starts = np.asarray(starts)
        return (carry, starts + batch.service - batch.arrival,
                starts - batch.arrival, None, None)

    sr = _scan_stream(
        psrc, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        n_carry=2, init_fn=partial(_fcfs_stream_init, k=int(source.k)),
        chunk_fn=chunk_fn, has_helper=False, block=block,
        ckpt_dir=ckpt_dir, resume=resume)
    return _slice_stream_result(sr, R)


@engines.register_stream("modbs-fcfs", "jax-shard")
def _modbs_stream_shard(source, *, chunk_jobs, total_jobs, partition=None,
                        wl=None, policy="modbs-fcfs", devices=None,
                        block=4096, ckpt_dir=None, resume=False):
    """Streaming ModifiedBS-FCFS, replication-sharded."""
    part = _stream_partition(partition, wl)
    slots = np.asarray(part.slots, np.int32)
    s_max = int(slots.max())
    h = int(part.helpers)
    mesh = local_mesh(devices)
    R = int(source.reps)
    psrc = _PaddedChunkSource(source, mesh.size)

    def chunk_fn(carry, batch):
        if h < int(batch.need.max()):
            raise ValueError("helper set smaller than the largest "
                             "server need")
        with enable_x64():
            carry, (blocked, starts) = _call(
                _modbs_stream_shard_call, carry, *_class_inputs(batch),
                s_max, mesh)
        blocked = np.asarray(blocked)
        starts = np.asarray(starts)
        return (carry, starts + batch.service - batch.arrival,
                starts - batch.arrival, blocked, blocked)

    sr = _scan_stream(
        psrc, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        n_carry=3,
        init_fn=partial(_modbs_stream_init, slots=slots, s_max=s_max, h=h),
        chunk_fn=chunk_fn, has_helper=True, part=part, block=block,
        ckpt_dir=ckpt_dir, resume=resume,
        layout_extra={"C": int(slots.shape[0]), "s_max": s_max, "h": h})
    return _slice_stream_result(sr, R)


def _bs_chunk_scan_shard(C: int, s_max: int, h: int, q_cap: int,
                         mesh: Mesh):
    def scan(carry, rec, horizon, length):
        arr, cl, nd, svc = rec
        with enable_x64():
            dev = tuple(jnp.asarray(c, d)
                        for c, d in zip(carry, _BS_CARRY_DTYPES))
            out, tagged, rec_t = _call(
                _bs_stream_shard_call, dev,
                _dev(arr, jnp.float64), _dev(cl, jnp.int32),
                _dev(nd, jnp.int32), _dev(svc, jnp.float64),
                _dev(horizon, jnp.float64), C, s_max, h, q_cap, length,
                mesh)
        return ([np.asarray(x) for x in out], np.asarray(tagged),
                np.asarray(rec_t))
    return scan


@engines.register_stream("bs-fcfs", "jax-shard")
def _bs_stream_shard(source, *, chunk_jobs, total_jobs, partition=None,
                     wl=None, policy="bs-fcfs", queue_cap=None,
                     backlog_cap=1024, devices=None, block=4096,
                     ckpt_dir=None, resume=False):
    """Streaming BS-FCFS (Definition 1), replication-sharded."""
    part, slots, s_max, h, q_cap, B = _bs_stream_args(
        partition, wl, chunk_jobs, queue_cap, backlog_cap)
    mesh = local_mesh(devices)
    R = int(source.reps)
    psrc = _PaddedChunkSource(source, mesh.size)
    sr = _bs_stream_drive(
        psrc, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        part=part, slots=slots, s_max=s_max, h=h, q_cap=q_cap, B=B,
        scan_fn=_bs_chunk_scan_shard(int(slots.shape[0]), s_max, h, q_cap,
                                     mesh),
        block=block, ckpt_dir=ckpt_dir, resume=resume)
    return _slice_stream_result(sr, R)


# --------------------------------------------------------------------------
# Grid-native sharded execution: the 2-D (cells, reps) mesh.
# --------------------------------------------------------------------------
#
# The ``engine="jax-shard"`` grid cores reuse the host-side grid plans and
# extraction helpers of :mod:`repro.core.sim_batch` verbatim — the only
# difference from the ``engine="jax"`` grid cores is the execution layout:
# instead of flattening (cells x reps) to one lane axis on one device, the
# [G, R, ...] stacks keep both axes and shard them over the
# :func:`grid_mesh` ``("c", "r")`` mesh.  Each device block vmaps the same
# per-lane stream cores over its (G/dc_c, R/dc_r) tile; lanes never
# interact, so the results are bit-identical to every other engine of the
# policy.  Neither axis needs to divide its mesh size: :func:`_pad_gr`
# edge-repeats the last cell / replication (always valid lanes) and the
# outputs are sliced back to [:G, :R] before extraction.


def _pad_gr(a: np.ndarray, g_pad: int, r_pad: int) -> np.ndarray:
    """Edge-repeat the leading (cells, reps) axes up to (g_pad, r_pad)."""
    G, R = a.shape[:2]
    if g_pad > G:
        a = np.concatenate(
            [a, np.broadcast_to(a[-1:], (g_pad - G,) + a.shape[1:])], axis=0)
    if r_pad > R:
        a = np.concatenate(
            [a, np.broadcast_to(a[:, -1:],
                                (a.shape[0], r_pad - R) + a.shape[2:])],
            axis=1)
    return np.ascontiguousarray(a)


@partial(jax.jit, static_argnums=(4,))
def _fcfs_grid_shard_call(carry, arrival, need, service, mesh: Mesh):
    body = lambda c, a, n, v: jax.vmap(jax.vmap(_fcfs_stream_core))(
        c, a, n, v)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 4,
                     out_specs=(P("c", "r"), P("c", "r")))(
        carry, arrival, need, service)


@partial(jax.jit, static_argnums=(5, 6))
def _modbs_grid_shard_call(carry, arrival, cls, need, service, s_max: int,
                           mesh: Mesh):
    body = lambda c, a, cc, n, v: jax.vmap(jax.vmap(
        lambda c1, a1, cc1, n1, v1: _modbs_stream_core(
            c1, a1, cc1, n1, v1, s_max)))(c, a, cc, n, v)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 5,
                     out_specs=(P("c", "r"), P("c", "r")))(
        carry, arrival, cls, need, service)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _bs_grid_shard_call(carry, arrival, cls, need, service, j_live,
                        C: int, s_max: int, h: int, q_cap: int, length: int,
                        mesh: Mesh):
    # _bs_stream_core carries its lane (reps) axis natively; vmap adds the
    # per-tile cell axis on top.
    def body(c, a, cc, n, v, jl):
        f = lambda c1, a1, cc1, n1, v1, jl1: _bs_stream_core(
            a1, cc1, n1, v1, jnp.full(a1.shape[0], jnp.inf, a1.dtype), c1,
            C, s_max, h, q_cap, length, j_live=jl1)
        return jax.vmap(f)(c, a, cc, n, v, jl)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 6,
                     out_specs=(P("c", "r"),) * 3)(
        carry, arrival, cls, need, service, j_live)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _srpt_grid_shard_call(carry, arrival, need, service, kk, j_live,
                          Q: int, NU: tuple, sf: bool, length: int,
                          k_mult: bool, pairwise: bool, mesh: Mesh):
    def body(c, a, n, v, k, jl):
        f = lambda c1, a1, n1, v1, k1, jl1: _srpt_stream_core(
            a1, n1, v1, k1, c1, Q, NU, sf, length, j_live=jl1,
            k_mult=k_mult, pairwise=pairwise)
        return jax.vmap(f)(c, a, n, v, k, jl)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 6,
                     out_specs=(P("c", "r"),) * 4)(
        carry, arrival, need, service, kk, j_live)


@partial(jax.jit, static_argnums=(6,))
def _fcfs_fail_grid_shard_call(carry, t, n, svc, t_up, is_fail, mesh: Mesh):
    body = lambda c, a, b, d, e, f: jax.vmap(jax.vmap(
        _fcfs_fail_stream_core))(c, a, b, d, e, f)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 6,
                     out_specs=(P("c", "r"), P("c", "r")))(
        carry, t, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnums=(7, 8, 9))
def _modbs_fail_grid_shard_call(carry, t, c, n, svc, t_up, is_fail,
                                s_max: int, C: int, mesh: Mesh):
    body = lambda cr, a, b, nn, v, tu, isf: jax.vmap(jax.vmap(
        lambda cr1, a1, b1, n1, v1, tu1, isf1: _modbs_fail_stream_core(
            cr1, a1, b1, n1, v1, tu1, isf1, s_max, C)))(
        cr, a, b, nn, v, tu, isf)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 7,
                     out_specs=(P("c", "r"), P("c", "r")))(
        carry, t, c, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnums=(9, 10, 11, 12, 13, 14))
def _bs_fail_grid_shard_call(carry, arrival, cls, need, service, ft, ftgt,
                             fup, j_live, C: int, s_max: int, h: int,
                             q_cap: int, length: int, mesh: Mesh):
    def body(c, a, cc, n, v, t, g, u, jl):
        f = lambda c1, a1, cc1, n1, v1, t1, g1, u1, jl1: \
            _bs_fail_stream_core(a1, cc1, n1, v1, t1, g1, u1, c1,
                                 C, s_max, h, q_cap, length, j_live=jl1)
        return jax.vmap(f)(c, a, cc, n, v, t, g, u, jl)
    return shard_map(body, mesh=mesh, in_specs=(P("c", "r"),) * 9,
                     out_specs=(P("c", "r"),) * 3)(
        carry, arrival, cls, need, service, ft, ftgt, fup, j_live)


def _grid_mesh_pads(cells, devices):
    """(mesh, G, R, G_pad, R_pad) for a grid of ``cells``."""
    G, R = len(cells), cells[0].batch.reps
    mesh = grid_mesh(G, devices)
    return (mesh, G, R, G + (-G) % mesh.shape["c"],
            R + (-R) % mesh.shape["r"])


@engines.register_grid("fcfs", "jax-shard")
def _fcfs_grid_shard(cells, devices=None):
    mesh, G, R, Gp, Rp = _grid_mesh_pads(cells, devices)
    pg = lambda a: _pad_gr(a, Gp, Rp)
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax-shard")
        p = _fcfs_fail_grid_plan(cells)
        with enable_x64():
            carry = (_dev(pg(p["W0"]), jnp.float64),
                     _dev(pg(p["t0"]), jnp.float64))
            _, starts_m = _call(
                _fcfs_fail_grid_shard_call, carry,
                _dev(pg(p["t"]), jnp.float64), _dev(pg(p["n"]), jnp.int32),
                _dev(pg(p["svc"]), jnp.float64),
                _dev(pg(p["t_up"]), jnp.float64),
                _dev(pg(p["isf"]), jnp.bool_), mesh)
        return _fcfs_fail_grid_extract(cells, p["mss"],
                                       np.asarray(starts_m)[:G, :R])
    p = _fcfs_grid_plan(cells)
    with enable_x64():
        carry = (_dev(pg(p["W0"]), jnp.float64),
                 _dev(pg(p["t0"]), jnp.float64))
        _, starts = _call(
            _fcfs_grid_shard_call, carry,
            _dev(pg(p["arrival"]), jnp.float64),
            _dev(pg(p["need"]), jnp.int32),
            _dev(pg(p["service"]), jnp.float64), mesh)
    return _fcfs_grid_extract(cells, np.asarray(starts)[:G, :R])


@engines.register_grid("modbs-fcfs", "jax-shard")
def _modbs_grid_shard(cells, devices=None):
    mesh, G, R, Gp, Rp = _grid_mesh_pads(cells, devices)
    pg = lambda a: _pad_gr(a, Gp, Rp)
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax-shard")
        p = _modbs_fail_grid_plan(cells)
        with enable_x64():
            carry = (_dev(pg(p["comp0"]), jnp.float64),
                     _dev(pg(p["W0"]), jnp.float64),
                     _dev(pg(p["t0"]), jnp.float64))
            _, (blocked_m, starts_m) = _call(
                _modbs_fail_grid_shard_call, carry,
                _dev(pg(p["t"]), jnp.float64),
                _dev(pg(p["cls"]), jnp.int32),
                _dev(pg(p["need"]), jnp.int32),
                _dev(pg(p["svc"]), jnp.float64),
                _dev(pg(p["t_up"]), jnp.float64),
                _dev(pg(p["isf"]), jnp.bool_),
                p["s_max_pad"], p["C_pad"], mesh)
        return _modbs_fail_grid_extract(
            cells, p["mss"], np.asarray(blocked_m)[:G, :R],
            np.asarray(starts_m)[:G, :R])
    p = _modbs_grid_plan(cells)
    with enable_x64():
        carry = (_dev(pg(p["comp0"]), jnp.float64),
                 _dev(pg(p["W0"]), jnp.float64),
                 _dev(pg(p["t0"]), jnp.float64))
        _, (blocked, starts) = _call(
            _modbs_grid_shard_call, carry,
            _dev(pg(p["arrival"]), jnp.float64),
            _dev(pg(p["cls"]), jnp.int32),
            _dev(pg(p["need"]), jnp.int32),
            _dev(pg(p["service"]), jnp.float64), p["s_max_pad"], mesh)
    return _modbs_grid_extract(cells, np.asarray(blocked)[:G, :R],
                               np.asarray(starts)[:G, :R])


@engines.register_grid("bs-fcfs", "jax-shard")
def _bs_grid_shard(cells, devices=None):
    mesh, G, R, Gp, Rp = _grid_mesh_pads(cells, devices)
    pg = lambda a: _pad_gr(a, Gp, Rp)
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax-shard")
        p = _bs_fail_grid_plan(cells)
        pp = dict(p, **{k: pg(p[k])
                        for k in ("st0", "comp0", "ring0", "heads0", "W0")})
        with enable_x64():
            c0 = _bs_grid_carry(pp, (Gp, Rp))
            carry = (c0[0], _dev(np.zeros((Gp, Rp)), jnp.int32)) + c0[1:]
            carry, tagged, rec_t = _call(
                _bs_fail_grid_shard_call, carry,
                _dev(pg(p["arrival"]), jnp.float64),
                _dev(pg(p["cls"]), jnp.int32),
                _dev(pg(p["need"]), jnp.int32),
                _dev(pg(p["service"]), jnp.float64),
                _dev(pg(p["ft"]), jnp.float64),
                _dev(pg(p["ftgt"]), jnp.int32),
                _dev(pg(p["fup"]), jnp.float64),
                _dev(pg(p["j_live"]), jnp.int32),
                p["C_pad"], p["s_max_pad"], p["h_pad"], p["q_cap_pad"],
                p["length"], mesh)
            rpk = carry[9]
        return _bs_grid_extract(cells, p, np.asarray(tagged)[:G, :R],
                                np.asarray(rec_t)[:G, :R],
                                np.asarray(rpk)[:G, :R])
    p = _bs_grid_plan(cells)
    pp = dict(p, **{k: pg(p[k])
                    for k in ("st0", "comp0", "ring0", "heads0", "W0")})
    with enable_x64():
        c0 = _bs_grid_carry(pp, (Gp, Rp))
        carry = c0 + (_dev(np.zeros((Gp, Rp)), jnp.int32),)
        carry, tagged, rec_t = _call(
            _bs_grid_shard_call, carry,
            _dev(pg(p["arrival"]), jnp.float64),
            _dev(pg(p["cls"]), jnp.int32),
            _dev(pg(p["need"]), jnp.int32),
            _dev(pg(p["service"]), jnp.float64),
            _dev(pg(p["j_live"]), jnp.int32),
            p["C_pad"], p["s_max_pad"], p["h_pad"], p["q_cap_pad"],
            2 * p["J_pad"], mesh)
        rpk, ne = carry[8], carry[9]
    assert (np.asarray(ne) == 2 * pg(p["j_live"])).all(), \
        "BS grid scan under-ran its event budget"
    return _bs_grid_extract(cells, p, np.asarray(tagged)[:G, :R],
                            np.asarray(rec_t)[:G, :R],
                            np.asarray(rpk)[:G, :R])


def _srpt_grid_shard(sf: bool, cells, devices=None):
    policy = "sf-srpt" if sf else "ff-srpt"
    _srpt_no_failures(cells[0].failures, policy)
    mesh, G, R, Gp, Rp = _grid_mesh_pads(cells, devices)
    pg = lambda a: _pad_gr(a, Gp, Rp)
    p = _srpt_grid_plan(cells)
    with enable_x64():
        carry = _srpt_grid_carry((Gp, Rp), p["Q_pad"])
        carry, job_ev, t_ev, fs_ev = _call(
            _srpt_grid_shard_call, carry,
            _dev(pg(p["arrival"]), jnp.float64),
            _dev(pg(p["need"]), jnp.float64),
            _dev(pg(p["service"]), jnp.float64),
            _dev(pg(p["kk"]), jnp.float64),
            _dev(pg(p["j_live"]), jnp.int32),
            p["Q_pad"], p["NU"], sf, 2 * p["J_pad"], p["k_mult"],
            p["pairwise"], mesh)
    return _srpt_grid_extract(
        cells, p, np.asarray(job_ev)[:G, :R], np.asarray(t_ev)[:G, :R],
        np.asarray(fs_ev)[:G, :R], np.asarray(carry[2])[:G, :R],
        np.asarray(carry[3])[:G, :R], np.asarray(carry[4])[:G, :R],
        np.asarray(carry[5])[:G, :R])


@engines.register_grid("sf-srpt", "jax-shard")
def _sf_srpt_grid_shard(cells, devices=None):
    return _srpt_grid_shard(True, cells, devices)


@engines.register_grid("ff-srpt", "jax-shard")
def _ff_srpt_grid_shard(cells, devices=None):
    return _srpt_grid_shard(False, cells, devices)
