"""Batched (vmap-over-replications) simulation substrate — the sweep fast path.

The Thm-1/2 validations sweep k -> infinity with many arrivals and many
independent replications per point.  Running those replications one
``lax.scan`` at a time leaves the machine idle between traces and pays the
Python dispatch per replication.  This module vmaps the un-jitted scan cores
of :mod:`repro.core.sim_jax` over a leading replications axis:

* ``loss_queue_sim_batch`` / ``fcfs_sim_batch`` / ``modified_bs_sim_batch``
  / ``bs_sim_batch`` consume a :class:`~repro.core.workload.BatchTrace`
  ([R, J] arrays sampled with per-replication Philox streams) and return
  per-replication metrics.  Each is compiled once per (k, R, J) shape with
  donated input buffers, so a whole k-sweep at fixed (R, J) pays one
  compile per k and zero per-trace Python overhead.  ``bs_sim_batch`` is
  BS-π proper (Definition 1 rule-3 pull-backs) on the event-indexed 2J-step
  scan of :func:`repro.core.sim_jax._bs_core` — per-class ring buffers and
  the sorted helper free-time vector ride in the scan carry, so the Thm-1/2
  zero-wait validations now cover the paper's headline policy at full
  k-sweep scale.
* ``sweep_many_server`` drives the Fig. 1/2-style sweeps: one workload per
  swept point, ``reps`` replications each, returning mean/CI arrays ready
  for the benchmark CSVs.
* engine dispatch goes through the registry of :mod:`repro.core.engines`:
  this module registers the vmapped scan cores under ``engine="jax"``,
  :mod:`repro.kernels.msj_scan` registers the fused step kernels under
  ``engine="pallas"`` (one kernel per replication on the Pallas grid;
  interpret mode, CPU only), and :mod:`repro.core.simulator` registers the
  exact event engine under ``engine="python"`` — all behind the same
  ``engines.simulate(policy, batch, engine=...)`` entry point.  The
  engines are pinned bit-for-bit against each other in
  ``tests/test_sim_cross.py`` / ``tests/test_engines.py``.

Replication r of a batch is bit-identical to the single-trace path on
``sample_trace(J, seed=replication_stream(seed, r))`` — cross-validated in
``tests/test_sim_batch.py``.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from . import engines
from . import failures as flr
from . import spans
from .partition import BalancedPartition, balanced_partition
from .sim_jax import (_BIG, _SRPT_COLS, _bs_args, _bs_core, _bs_fail_core,
                      _bs_fail_stream_core, _bs_scatter_events,
                      _bs_stream_core, _fcfs_core, _fcfs_fail_core,
                      _fcfs_fail_stream_core, _fcfs_stream_core, _loss_core,
                      _modbs_core, _modbs_fail_core,
                      _modbs_fail_stream_core, _modbs_stream_core,
                      _srpt_args, _srpt_core, _srpt_pairwise,
                      _srpt_scatter_events, _srpt_stream_core)
from .workload import BatchTrace, Workload

#: waiting-time epsilon for P[wait > 0] — matches ``Simulation.wait_eps``
WAIT_EPS = 1e-9


def _call(fn, *args):
    """Run a jitted call to completion, silencing the donation no-op warning
    XLA emits on backends (CPU) that cannot alias the donated buffers.
    The outputs stay on the device; inside a ``simulate`` call this is its
    ``repro.run`` span."""
    with engines.call_span("repro.run"), warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return jax.block_until_ready(fn(*args))


def _fetch(out, reps: int | None = None):
    """The outputs of :func:`_call` on the host, in one transfer (the
    ``repro.fetch`` span; their bytes count to ``fetch_bytes``), cut back
    to the first ``reps`` replications when the sharded engines padded
    them."""
    with engines.call_span("repro.fetch"):
        host = jax.device_get(out)
        spans.add("fetch_bytes",
                  sum(a.nbytes for a in jax.tree_util.tree_leaves(host)))
    if reps is None:
        return host
    return jax.tree_util.tree_map(lambda a: a[:reps], host)


def _backends_initialized() -> bool:
    """Whether any XLA backend has already been created, without creating
    one.  JAX 0.9 has no public predicate (``jax.extend.backend`` lacks
    one), so this reads ``xla_bridge``'s."""
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def pin_single_thread_runtime() -> bool:
    """Init the XLA:CPU backend with a single-thread intra-op pool.

    The scan cores are inherently sequential: every op in a scan body is
    microseconds of work, and XLA's thunk executor pays a cross-core
    handoff per op when its intra-op pool has more than one thread — on a
    2-core host that synchronization is 3-4x the entire runtime of the
    BS-FCFS event scan (measured: 101k -> 339k jobs/s at k=256, R=8).

    Kept as the single-device special case of the device-aware successor,
    :func:`repro.core.shard.configure_runtime` — this shim delegates to
    ``configure_runtime(devices=1, intra_op_threads=1)`` with the
    after-init warning suppressed (opportunistic callers may run after
    the backend exists and just keep whatever pool is there).  New code
    and the benchmark mains should call ``configure_runtime`` directly.
    """
    from .shard import configure_runtime  # local: shard imports this module
    return configure_runtime(devices=1, intra_op_threads=1, warn=False)


# --------------------------------------------------------------------------
# Batched scans: vmap the sim_jax cores over the replications axis.
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("s",), donate_argnums=(0, 1))
def _loss_scan_batch(arrival, service, s: int):
    return jax.vmap(lambda a, v: _loss_core(a, v, s))(arrival, service)


@partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1, 2))
def _fcfs_scan_batch(arrival, need, service, k: int):
    return jax.vmap(lambda a, n, v: _fcfs_core(a, n, v, k))(
        arrival, need, service)


@partial(jax.jit, static_argnames=("s_max", "h"),
         donate_argnums=(0, 1, 2, 3))
def _modbs_scan_batch(arrival, cls, need, service, slots, s_max: int, h: int):
    return jax.vmap(
        lambda a, c, n, v: _modbs_core(a, c, n, v, slots, s_max, h))(
        arrival, cls, need, service)


@partial(jax.jit, static_argnames=("s_max", "h", "q_cap"),
         donate_argnums=(0, 1, 2, 3))
def _bs_scan_batch(arrival, cls, need, service, slots, s_max: int, h: int,
                   q_cap: int):
    # _bs_core carries the replications axis natively (hand-vectorized
    # scatters with per-lane indices) — no vmap; see its docstring.
    return _bs_core(arrival, cls, need, service, slots, s_max, h, q_cap)


# failure-aware variants: scans over the chronologically merged
# arrival+failure streams of repro.core.failures (drain semantics)

@partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1, 2, 3, 4))
def _fcfs_fail_scan_batch(t, n, svc, t_up, is_fail, k: int):
    return jax.vmap(
        lambda a, b, c, d, e: _fcfs_fail_core(a, b, c, d, e, k))(
        t, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnames=("s_max", "h"),
         donate_argnums=(0, 1, 2, 3, 4, 5))
def _modbs_fail_scan_batch(t, c, n, svc, t_up, is_fail, slots, s_max: int,
                           h: int):
    return jax.vmap(
        lambda a, b, cc, d, e, f: _modbs_fail_core(a, b, cc, d, e, f, slots,
                                                   s_max, h))(
        t, c, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnames=("s_max", "h", "q_cap", "length"),
         donate_argnums=(0, 1, 2, 3))
def _bs_fail_scan_batch(arrival, cls, need, service, ft, ftgt, fup, slots,
                        s_max: int, h: int, q_cap: int, length: int):
    return _bs_fail_core(arrival, cls, need, service, ft, ftgt, fup, slots,
                         s_max, h, q_cap, length)


# --------------------------------------------------------------------------
# Host wrappers.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BatchSimResult:
    """Per-replication sample-path metrics of a batched simulation."""

    response: np.ndarray        # [R, J] response time per job
    wait: np.ndarray            # [R, J] waiting time per job
    p_helper: np.ndarray | None # [R] fraction served on helpers (BSF only)
    blocked: np.ndarray | None  # [R, J] bool (loss queue / BSF routing)
    p_routed: np.ndarray | None = None  # [R] fraction routed to H on arrival
                                        # (> p_helper under Def.-1 pull-backs)
    start: np.ndarray | None = None     # [R, J] raw start times
    # failure-scenario observables (None without fault injection):
    kills: np.ndarray | None = None         # [R] jobs killed mid-service
    requeues: np.ndarray | None = None      # [R] killed jobs requeued
    availability: np.ndarray | None = None  # [R] time-avg live fraction
    # preempt-resume observable (None for nonpreemptive policies):
    preemptions: np.ndarray | None = None   # [R] preemption events

    @property
    def reps(self) -> int:
        return self.response.shape[0]

    @property
    def mean_response(self) -> np.ndarray:
        """[R] mean response time of each replication."""
        return self.response.mean(axis=1)

    @property
    def mean_wait(self) -> np.ndarray:
        return self.wait.mean(axis=1)

    @property
    def p_wait(self) -> np.ndarray:
        """[R] queueing probability P[wait > 0] of each replication."""
        return (self.wait > WAIT_EPS).mean(axis=1)

    def rep(self, r: int):
        """Replication ``r`` as a single-trace :class:`JaxSimResult`."""
        from .sim_jax import JaxSimResult
        return JaxSimResult(
            response=self.response[r],
            p_helper=None if self.p_helper is None else float(self.p_helper[r]),
            blocked=None if self.blocked is None else self.blocked[r],
            p_routed=None if self.p_routed is None
            else float(self.p_routed[r]),
            start=None if self.start is None else self.start[r])


def _dev(x, dtype) -> jnp.ndarray:
    """Device array that never aliases caller-owned memory.

    ``jnp.asarray`` zero-copies suitably aligned numpy float64/int
    buffers on CPU (alignment depends on the allocator — run to run!),
    and the batched entry points below *donate* their input buffers:
    XLA writing into a donated zero-copy alias silently corrupts the
    caller's ``BatchTrace`` arrays in place.  ``np.array`` copies
    unconditionally, which breaks the alias for the cost of one host
    memcpy — noise next to the scan itself — and ``jax.device_put`` of
    the private copy transfers without compiling anything (``jnp.array``
    builds a tiny per-shape convert executable, which would pollute the
    one-program-per-grid ``compile_count`` the bench rows pin).  The
    put must run under ``enable_x64`` — outside it the dtype is
    canonicalized — and every caller already is.
    """
    return jax.device_put(np.array(x, dtype))


def _puts(*pairs) -> tuple:
    """:func:`_dev` of each ``(array, dtype)`` pair, on the device when
    this returns: the transfer ends a call's ``repro.prep`` span rather
    than running into ``repro.run``."""
    with engines.call_span("repro.prep"):
        return jax.block_until_ready(tuple(_dev(x, dt) for x, dt in pairs))


def loss_queue_sim_batch(arrival: np.ndarray, service: np.ndarray,
                         s: int) -> BatchSimResult:
    """Batched M/GI/s/s: [R, J] arrival/service arrays, R independent paths."""
    with enable_x64():
        blocked = np.asarray(_call(
            _loss_scan_batch,
            _dev(arrival, jnp.float64),
            _dev(service, jnp.float64), s))
    resp = np.where(blocked, 0.0, service)
    return BatchSimResult(response=resp, wait=np.zeros_like(resp),
                          p_helper=None, blocked=blocked)


# -- shared input-prep / result-assembly helpers (every engine's cores use
# these, so results are bit-identical across engines by construction) -------


def _fcfs_inputs(batch: BatchTrace) -> tuple:
    """(arrival f64, need i32, service f64) device arrays of a batch."""
    return _puts((batch.arrival, jnp.float64), (batch.need, jnp.int32),
                 (batch.service, jnp.float64))


def _class_inputs(batch: BatchTrace) -> tuple:
    """(arrival f64, cls i32, need i32, service f64) device arrays."""
    return _puts((batch.arrival, jnp.float64), (batch.cls, jnp.int32),
                 (batch.need, jnp.int32), (batch.service, jnp.float64))


def _srpt_inputs(batch: BatchTrace) -> tuple:
    """(arrival, need, service, k per lane) f64 device arrays of an SRPT
    scan."""
    return _puts((batch.arrival, jnp.float64), (batch.need, jnp.float64),
                 (batch.service, jnp.float64),
                 (np.full(batch.reps, float(batch.k)), jnp.float64))


def _partition_args(batch: BatchTrace, partition: BalancedPartition | None,
                    wl: Workload | None) -> tuple[np.ndarray, int, int]:
    """(slots, s_max, h) of the eq.-2 partition, validated for the batch."""
    with engines.call_span("repro.prep"):
        if partition is None:
            if wl is None:
                raise ValueError("need a partition or a workload")
            partition = balanced_partition(wl)
        slots = np.asarray(partition.slots, dtype=np.int32)
        s_max = int(slots.max())
        h = int(partition.helpers)
        if h < int(batch.need.max()):
            raise ValueError("helper set smaller than the largest server need")
    return slots, s_max, h


# The ``_*_result`` helpers, ``_with_drain_obs`` included, are a call's
# ``repro.assemble`` span: overflow checks, event-to-job scatters and the
# BatchSimResult, from outputs already on the host (``_fetch``).


def _fcfs_result(batch: BatchTrace, starts) -> BatchSimResult:
    # same op order as the single-trace path so replications bit-match it
    with engines.call_span("repro.assemble"):
        starts = np.asarray(starts)
        return BatchSimResult(response=starts + batch.service - batch.arrival,
                              wait=starts - batch.arrival,
                              p_helper=None, blocked=None, start=starts)


def _modbs_result(batch: BatchTrace, blocked, starts) -> BatchSimResult:
    with engines.call_span("repro.assemble"):
        blocked = np.asarray(blocked)
        starts = np.asarray(starts)
        return BatchSimResult(response=starts + batch.service - batch.arrival,
                              wait=starts - batch.arrival,
                              p_helper=blocked.mean(axis=1), blocked=blocked,
                              p_routed=blocked.mean(axis=1), start=starts)


def _bs_check_ring(rpk, q_cap: int, cell: str = "") -> None:
    """Raise the ``bs_ring_peak`` and ``bs_ring_cap`` counters from a
    scan's per-lane peak ring occupancy ``rpk``, and raise an error where
    it passed the ring's ``q_cap`` slots (the ring then overwrote queued
    jobs, so the path is not exact)."""
    rpk = np.asarray(rpk)
    spans.high("bs_ring_peak", rpk.max(initial=0))
    spans.high("bs_ring_cap", q_cap)
    over = rpk > q_cap
    if over.any():
        raise RuntimeError(
            f"helper-wait ring buffer overflow in {cell}replication(s) "
            f"{np.flatnonzero(over).tolist()}: up to {int(rpk.max())} jobs "
            f"of one class waited at once, against queue_cap={q_cap} — "
            f"the ring was too small for this run; pass queue_cap >= "
            f"{int(rpk.max())}")


def _bs_assemble(batch: BatchTrace, starts, served,
                 routed) -> BatchSimResult:
    """Per-job event arrays -> BatchSimResult (one shared op order); the
    jobs the helpers served add to the ``bs_helper_served`` counter, all
    of them to ``bs_jobs``."""
    spans.add("bs_helper_served", served.sum())
    spans.add("bs_jobs", served.size)
    return BatchSimResult(response=starts + batch.service - batch.arrival,
                          wait=starts - batch.arrival,
                          p_helper=served.mean(axis=1), blocked=None,
                          p_routed=routed.mean(axis=1), start=starts)


def _bs_result(batch: BatchTrace, tagged, rec_t, rpk,
               q_cap: int) -> BatchSimResult:
    with engines.call_span("repro.assemble"):
        _bs_check_ring(rpk, q_cap)
        # one vectorized event->job scatter for the whole batch (no per-rep
        # Python loop: host post-processing must not scale with R)
        starts, served, routed = _bs_scatter_events(batch.num_jobs, tagged,
                                                    rec_t, batch.arrival)
        return _bs_assemble(batch, starts, served, routed)


# -- engine="jax" cores (the vmapped lax.scan substrate) --------------------


def _with_drain_obs(res: BatchSimResult, batch: BatchTrace,
                    fb) -> BatchSimResult:
    with engines.call_span("repro.assemble"):
        return dataclasses.replace(
            res, **flr.drain_observables(fb, batch, res.response))


def _merged_fcfs_inputs(batch: BatchTrace, fb) -> flr.MergedStream:
    ft, ftgt, fup, count = flr.fcfs_targets(fb)
    return flr.merge_failure_stream(batch, ft, ftgt, fup, count, pad_cls=0)


@engines.register("fcfs", "jax")
def _fcfs_jax(batch: BatchTrace, *, partition=None, wl=None, failures=None):
    """Batched multiserver-job FCFS over all replications at once."""
    if failures is None:
        with enable_x64():
            starts = _fetch(_call(_fcfs_scan_batch, *_fcfs_inputs(batch),
                                  batch.k))
        return _fcfs_result(batch, starts)
    flr.require_drain(failures, "jax")
    ms = _merged_fcfs_inputs(batch, failures)
    with enable_x64():
        starts_m = _fetch(_call(
            _fcfs_fail_scan_batch,
            *_puts((ms.t, jnp.float64), (ms.need, jnp.int32),
                   (ms.service, jnp.float64), (ms.t_up, jnp.float64),
                   (ms.is_fail != 0, jnp.bool_)), batch.k))
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    return _with_drain_obs(_fcfs_result(batch, starts), batch, failures)


@engines.register("modbs-fcfs", "jax")
def _modbs_jax(batch: BatchTrace, *, partition=None, wl=None, failures=None):
    """Batched ModifiedBS-FCFS (Definition 2) over all replications."""
    slots, s_max, h = _partition_args(batch, partition, wl)
    if failures is None:
        with enable_x64():
            blocked, starts = _fetch(_call(
                _modbs_scan_batch, *_class_inputs(batch), jnp.asarray(slots),
                s_max, h))
        return _modbs_result(batch, blocked, starts)
    flr.require_drain(failures, "jax")
    part = partition if partition is not None else balanced_partition(wl)
    ft, ftgt, fup, count = flr.partition_targets(failures, part)
    ms = flr.merge_failure_stream(batch, ft, ftgt, fup, count,
                                  pad_cls=len(part.a))
    with enable_x64():
        blocked_m, starts_m = _fetch(_call(
            _modbs_fail_scan_batch,
            *_puts((ms.t, jnp.float64), (ms.cls, jnp.int32),
                   (ms.need, jnp.int32), (ms.service, jnp.float64),
                   (ms.t_up, jnp.float64), (ms.is_fail != 0, jnp.bool_)),
            jnp.asarray(slots), s_max, h))
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    blocked = np.take_along_axis(blocked_m, ms.job_pos, axis=1)
    return _with_drain_obs(_modbs_result(batch, blocked, starts), batch,
                           failures)


def _bs_fail_args(batch: BatchTrace, failures, partition, wl):
    """(ft, ftgt, fup, scan length) of a BS drain run.

    Length = 2J + F + F_A: every failure event consumes a step, and each
    *class-targeted* event may claim a free slot, adding one future
    repair-completion event.
    """
    part = partition if partition is not None else balanced_partition(wl)
    ft, ftgt, fup, count = flr.partition_targets(failures, part)
    C = len(part.a)
    F = max(1, ft.shape[1])
    if ft.shape[1] == 0:
        ft = np.full((batch.reps, 1), np.inf)
        ftgt = np.full((batch.reps, 1), C, dtype=np.int32)
        fup = np.zeros((batch.reps, 1))
    fa = int((ftgt < C).sum(axis=1).max()) if ft.size else 0
    return ft, ftgt, fup, 2 * batch.num_jobs + F + fa


@engines.register("bs-fcfs", "jax")
def _bs_jax(batch: BatchTrace, *, partition=None, wl=None, queue_cap=None,
            failures=None):
    """Batched BS-FCFS (Definition 1, rule-3 pull-backs) over all reps.

    Runs the event-indexed 2J-step scan of ``sim_jax._bs_core`` with the
    replications axis carried natively; replication ``r`` is bit-identical
    to ``bs_sim(batch.rep(r))``.  Raises if any replication overflowed the
    per-class helper-wait ring buffers (``queue_cap``, default
    ``min(J, 8192)``): the error names the peak occupancy, and no
    approximate result is returned.  With ``failures`` the scan
    runs the drain-mode variant (``sim_jax._bs_fail_core``).
    """
    slots, s_max, h, q_cap = _bs_args(batch, partition, wl, queue_cap)
    if failures is None:
        with enable_x64():
            tagged, rec_t, rpk = _fetch(_call(
                _bs_scan_batch, *_class_inputs(batch), jnp.asarray(slots),
                s_max, h, q_cap))
        return _bs_result(batch, tagged, rec_t, rpk, q_cap)
    flr.require_drain(failures, "jax")
    ft, ftgt, fup, length = _bs_fail_args(batch, failures, partition, wl)
    with enable_x64():
        tagged, rec_t, rpk = _fetch(_call(
            _bs_fail_scan_batch, *_class_inputs(batch),
            *_puts((ft, jnp.float64), (ftgt, jnp.int32),
                   (fup, jnp.float64)), jnp.asarray(slots), s_max, h,
            q_cap, length))
    return _with_drain_obs(_bs_result(batch, tagged, rec_t, rpk, q_cap),
                           batch, failures)


# -- preemptive SRPT-family cores (sf-srpt / ff-srpt) -----------------------


@partial(jax.jit, static_argnames=("Q", "NU", "sf", "k_mult", "pairwise"),
         donate_argnums=(0, 1, 2))
def _srpt_scan_batch(arrival, need, service, kk, Q: int, NU: tuple,
                     sf: bool, k_mult: bool, pairwise: bool):
    # _srpt_core carries the replications axis natively (per-lane sorts
    # and 1-entry scatters) — no vmap; see the sim_jax section comment.
    return _srpt_core(arrival, need, service, kk, Q, NU, sf, k_mult,
                      pairwise)


def _srpt_nu(*batches) -> tuple:
    """Static ascending tuple of distinct server needs — the unroll set of
    the vectorized first-fit walk.  A superset is always correct, so grid
    plans pass the union across cells."""
    return tuple(sorted({int(v) for b in batches for v in np.unique(b.need)}))


def _srpt_k_mult(NU: tuple, *batches) -> bool:
    """Static "every k is an integer multiple of max(NU)" flag — the
    closed-form ServerFilling walk gate of ``_srpt_fast_make_step``
    (computed host-side from numpy so it never traces)."""
    m = max(NU)
    return all(float(b.k) % m == 0 for b in batches)


def _srpt_check_ovf(ovf, q_cap: int, cell: str = "", peak=None) -> None:
    ovf = np.asarray(ovf)
    if ovf.any():
        hint = ""
        if peak is not None:
            need = int(np.asarray(peak).max())
            # the peak stops counting dropped arrivals after the first
            # overflow, so it is a lower bound on the required capacity
            q_next = max(1 << max(need - 1, 1).bit_length(), 2 * q_cap)
            hint = (f"; measured peak occupancy >= {need} jobs — pass "
                    f"queue_cap={q_next} (the next power of two) or more")
        raise RuntimeError(
            f"SRPT slot table overflow (queue_cap={q_cap}) in "
            f"{cell}replication(s) {np.flatnonzero(ovf).tolist()} — "
            f"workload unstable at this load, or raise queue_cap{hint}")


def _srpt_no_failures(failures, policy: str) -> None:
    if failures is not None:
        raise NotImplementedError(
            f"policy {policy!r} has no fault-injection scan core — use "
            f"engine='python' (mode='kill' kill-and-requeue)")


def _srpt_result(batch: BatchTrace, job_ev, t_ev, fs_ev, ovf, npre, ne,
                 q_cap: int, peak=None,
                 pairwise: bool = False) -> BatchSimResult:
    """Event streams -> BatchSimResult, the `_python_core` op order
    (response = completion - arrival, wait = first start - arrival).
    The scan's in-system ``peak`` and ``q_cap`` raise the ``srpt_peak``
    and ``srpt_q`` counters; a ``pairwise`` scan (the fast step ordered
    its slot table by precedence counts) adds its 2J events per
    replication to ``srpt_pairwise_events``."""
    with engines.call_span("repro.assemble"):
        _srpt_check_ovf(ovf, q_cap, peak=peak)
        assert (np.asarray(ne) == 2 * batch.num_jobs).all(), \
            "SRPT event scan under-ran its 2J event budget"
        if peak is not None:
            spans.high("srpt_peak", np.max(peak))
            spans.high("srpt_q", q_cap)
            if pairwise:
                spans.add("srpt_pairwise_events",
                          2 * batch.num_jobs * batch.reps)
        comp, fstart = _srpt_scatter_events(batch.num_jobs, job_ev, t_ev,
                                            fs_ev)
        return BatchSimResult(response=comp - batch.arrival,
                              wait=fstart - batch.arrival,
                              p_helper=None, blocked=None, start=fstart,
                              preemptions=np.asarray(npre).astype(np.int64))


def _srpt_jax(sf: bool, batch: BatchTrace, *, partition=None, wl=None,
              queue_cap=None, failures=None) -> BatchSimResult:
    policy = "sf-srpt" if sf else "ff-srpt"
    _srpt_no_failures(failures, policy)
    q_cap = _srpt_args(batch, queue_cap)
    NU, pairwise = _srpt_nu(batch), _srpt_pairwise(q_cap)
    with enable_x64():
        job_ev, t_ev, fs_ev, ovf, npre, ne, peak = _fetch(_call(
            partial(_srpt_scan_batch, Q=q_cap, NU=NU, sf=sf,
                    k_mult=_srpt_k_mult(NU, batch), pairwise=pairwise),
            *_srpt_inputs(batch)))
    return _srpt_result(batch, job_ev, t_ev, fs_ev, ovf, npre, ne, q_cap,
                        peak=peak, pairwise=pairwise)


@engines.register("sf-srpt", "jax")
def _sf_srpt_jax(batch: BatchTrace, **kw) -> BatchSimResult:
    """Batched preemptive ServerFilling-SRPT event scan, all reps at once.

    Rank = remaining work x need, the DONE-SRPT candidate prefix, packed
    largest-need-first — bit-identical to the python oracle's
    ``ServerFillingSRPT`` per replication, including the ``preemptions``
    observable.  ``queue_cap`` bounds the in-system slot table (default
    ``min(J, max(4k, 256))``); overflow raises loudly.
    """
    return _srpt_jax(True, batch, **kw)


@engines.register("ff-srpt", "jax")
def _ff_srpt_jax(batch: BatchTrace, **kw) -> BatchSimResult:
    """Batched preemptive FirstFit-SRPT event scan, all reps at once.

    Rank = remaining work, greedy first-fit over the whole in-system set —
    bit-identical to the python oracle's ``FirstFitSRPT``.
    """
    return _srpt_jax(False, batch, **kw)


# -- public batched entry points (thin shims over the registry) -------------


def fcfs_sim_batch(batch: BatchTrace, engine: str = "jax") -> BatchSimResult:
    """Batched FCFS via the engine registry (:mod:`repro.core.engines`)."""
    return engines.simulate("fcfs", batch, engine=engine)


def modified_bs_sim_batch(batch: BatchTrace,
                          partition: BalancedPartition | None = None,
                          wl: Workload | None = None,
                          engine: str = "jax") -> BatchSimResult:
    """Batched ModifiedBS-FCFS via the engine registry."""
    return engines.simulate("modbs-fcfs", batch, engine=engine,
                            partition=partition, wl=wl)


def bs_sim_batch(batch: BatchTrace,
                 partition: BalancedPartition | None = None,
                 wl: Workload | None = None,
                 queue_cap: int | None = None,
                 engine: str = "jax") -> BatchSimResult:
    """Batched BS-FCFS (Definition 1) via the engine registry."""
    return engines.simulate("bs-fcfs", batch, engine=engine,
                            partition=partition, wl=wl, queue_cap=queue_cap)


# --------------------------------------------------------------------------
# Grid-native execution: a whole figure grid as ONE compiled program.
#
# A grid stacks heterogeneous (k, load) cells — each its own BatchTrace,
# partition, and failure batch — onto one flattened (cells x reps) lane
# axis and runs a single jitted scan program per policy.  Two padding
# mechanisms make the shapes uniform without changing any cell's result:
#
# * J-padding: per-cell batches pad to the grid max J with the sentinel
#   no-op jobs of ``BatchTrace.pad_jobs``.  The arrival-indexed scans
#   (FCFS, ModBS) process them strictly after every real job, so slicing
#   outputs to [:J_cell] recovers the unpadded path bit-for-bit; the
#   event-indexed BS cores instead carry a per-lane ``j_live`` admission
#   guard so padding never enters the rings.
# * k-padding (dead capacity): heterogeneous k / C / s_max / h share one
#   static shape by moving every per-cell size into the *initial carry* —
#   dead servers are ``_BIG`` entries at the tail of the sorted free-time
#   vectors (no finite completion ever undercuts them, so insertion
#   counts and n-th-smallest reads see exactly the live prefix), and
#   dead A-slots are permanently-busy ``_BIG`` completion entries (the
#   same masking ``_modbs_init`` uses for ragged slot counts, and the
#   drain-mode failure machinery uses for outages).
#
# The plans below build the stacked [G, R, ...] host arrays + per-lane
# carries; the jax cores flatten to [G*R, ...] lanes and call the jitted
# chunk entries; :mod:`repro.core.shard` reuses the same plans over a 2-D
# (cells, reps) device mesh.  Every cell extracts through the same
# ``_*_result`` helpers as the per-cell path — bit-identity (rtol=0) is
# by construction and pinned in ``tests/test_grid.py``.
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10),
         donate_argnums=(1, 2, 3, 4))
def _bs_grid_chunk(carry, arrival, cls, need, service, j_live,
                   C: int, s_max: int, h: int, q_cap: int, length: int):
    horizon = jnp.full(arrival.shape[0], jnp.inf, arrival.dtype)
    return _bs_stream_core(arrival, cls, need, service, horizon, carry,
                           C, s_max, h, q_cap, length, j_live=j_live)


@partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
def _fcfs_fail_grid_chunk(carry, t, n, svc, t_up, is_fail):
    return jax.vmap(_fcfs_fail_stream_core)(carry, t, n, svc, t_up,
                                            is_fail)


@partial(jax.jit, static_argnums=(7, 8), donate_argnums=(1, 2, 3, 4, 5, 6))
def _modbs_fail_grid_chunk(carry, t, c, n, svc, t_up, is_fail,
                           s_max: int, C: int):
    return jax.vmap(
        lambda cr, a, b, nn, v, tu, isf: _modbs_fail_stream_core(
            cr, a, b, nn, v, tu, isf, s_max, C))(
        carry, t, c, n, svc, t_up, is_fail)


@partial(jax.jit, static_argnums=(9, 10, 11, 12, 13),
         donate_argnums=(1, 2, 3, 4, 5, 6, 7))
def _bs_fail_grid_chunk(carry, arrival, cls, need, service, ft, ftgt, fup,
                        j_live, C: int, s_max: int, h: int, q_cap: int,
                        length: int):
    return _bs_fail_stream_core(arrival, cls, need, service, ft, ftgt,
                                fup, carry, C, s_max, h, q_cap, length,
                                j_live=j_live)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11),
         donate_argnums=(1, 2, 3))
def _srpt_grid_chunk(carry, arrival, need, service, kk, j_live,
                     Q: int, NU: tuple, sf: bool, length: int,
                     k_mult: bool, pairwise: bool):
    return _srpt_stream_core(arrival, need, service, kk, carry, Q, NU,
                             sf, length, j_live=j_live, k_mult=k_mult,
                             pairwise=pairwise)


# -- host-side grid plans: stacked [G, R, ...] inputs + per-lane carries ----


def _grid_jobs(cells):
    """Stacked [G, R, J_pad] job arrays (``pad_jobs`` to the grid max J)."""
    J_pad = max(c.batch.num_jobs for c in cells)
    pads = [c.batch.pad_jobs(J_pad) for c in cells]
    return (np.stack([p.arrival for p in pads]),
            np.stack([p.cls for p in pads]),
            np.stack([p.service for p in pads]),
            np.stack([p.need for p in pads]), J_pad)


def _grid_cell_parts(cells):
    """Each cell's eq.-2 partition (explicit or derived from its wl)."""
    parts = []
    for g, cell in enumerate(cells):
        if cell.partition is None and cell.wl is None:
            raise ValueError(f"grid cell {g}: need a partition or a "
                             f"workload")
        parts.append(cell.partition if cell.partition is not None
                     else balanced_partition(cell.wl))
    return parts


def _fcfs_grid_plan(cells) -> dict:
    G, R = len(cells), cells[0].batch.reps
    arrival, _, service, need, J_pad = _grid_jobs(cells)
    k_pad = max(c.batch.k for c in cells)
    W0 = np.zeros((G, R, k_pad))
    for g, c in enumerate(cells):
        W0[g, :, c.batch.k:] = _BIG      # dead servers: never free
    return dict(arrival=arrival, need=need, service=service, W0=W0,
                t0=np.zeros((G, R)), J_pad=J_pad)


def _fcfs_grid_extract(cells, starts) -> list:
    starts = np.asarray(starts)
    return [_fcfs_result(c.batch, starts[g][:, :c.batch.num_jobs])
            for g, c in enumerate(cells)]


def _fcfs_fail_grid_plan(cells) -> dict:
    """Merged arrival+failure streams, L-padded with identity drain rows
    (``is_fail`` with ``t_up = 0`` — ``_kw_drain`` is then a no-op)."""
    G, R = len(cells), cells[0].batch.reps
    mss = [_merged_fcfs_inputs(c.batch, c.failures) for c in cells]
    L_pad = max(ms.t.shape[1] for ms in mss)
    t = np.zeros((G, R, L_pad))
    n = np.ones((G, R, L_pad), np.int64)
    svc = np.zeros((G, R, L_pad))
    t_up = np.zeros((G, R, L_pad))
    isf = np.ones((G, R, L_pad), bool)
    for g, ms in enumerate(mss):
        L = ms.t.shape[1]
        t[g, :, :L] = ms.t
        n[g, :, :L] = ms.need
        svc[g, :, :L] = ms.service
        t_up[g, :, :L] = ms.t_up
        isf[g, :, :L] = ms.is_fail != 0
    k_pad = max(c.batch.k for c in cells)
    W0 = np.zeros((G, R, k_pad))
    for g, c in enumerate(cells):
        W0[g, :, c.batch.k:] = _BIG
    return dict(t=t, n=n, svc=svc, t_up=t_up, isf=isf, W0=W0,
                t0=np.zeros((G, R)), mss=mss)


def _fcfs_fail_grid_extract(cells, mss, starts_m) -> list:
    starts_m = np.asarray(starts_m)
    out = []
    for g, (c, ms) in enumerate(zip(cells, mss)):
        starts = np.take_along_axis(starts_m[g], ms.job_pos, axis=1)
        out.append(_with_drain_obs(_fcfs_result(c.batch, starts), c.batch,
                                   c.failures))
    return out


def _modbs_grid_statics(cells, parts):
    """(per-cell (slots, s_max, h), C_pad, s_max_pad, h_pad)."""
    args = [_partition_args(c.batch, part, None)
            for c, part in zip(cells, parts)]
    return (args, max(len(a[0]) for a in args), max(a[1] for a in args),
            max(a[2] for a in args))


def _modbs_grid_carry(args, C_pad: int, s_max_pad: int, h_pad: int,
                      R: int):
    """Per-lane (comp0, W0, t0): padded classes/slots permanently busy,
    padded helper servers dead ``_BIG`` tail entries."""
    G = len(args)
    comp0 = np.full((G, R, C_pad, s_max_pad), _BIG)
    W0 = np.zeros((G, R, h_pad))
    for g, (slots, _, h) in enumerate(args):
        live = np.arange(s_max_pad)[None, :] < slots[:, None]
        comp0[g, :, :len(slots), :] = np.where(live, 0.0, _BIG)
        W0[g, :, h:] = _BIG
    return comp0, W0, np.zeros((G, R))


def _modbs_grid_plan(cells) -> dict:
    G, R = len(cells), cells[0].batch.reps
    arrival, cls_, service, need, J_pad = _grid_jobs(cells)
    parts = _grid_cell_parts(cells)
    args, C_pad, s_max_pad, h_pad = _modbs_grid_statics(cells, parts)
    comp0, W0, t0 = _modbs_grid_carry(args, C_pad, s_max_pad, h_pad, R)
    return dict(arrival=arrival, cls=cls_, need=need, service=service,
                comp0=comp0, W0=W0, t0=t0, s_max_pad=s_max_pad,
                J_pad=J_pad)


def _modbs_grid_extract(cells, blocked, starts) -> list:
    blocked = np.asarray(blocked)
    starts = np.asarray(starts)
    out = []
    for g, c in enumerate(cells):
        J = c.batch.num_jobs
        out.append(_modbs_result(c.batch, blocked[g][:, :J],
                                 starts[g][:, :J]))
    return out


def _modbs_fail_grid_plan(cells) -> dict:
    """Merged streams with the helper-drain class marker remapped from the
    per-cell C to the grid C_pad, L-padded with identity helper drains."""
    G, R = len(cells), cells[0].batch.reps
    parts = _grid_cell_parts(cells)
    args, C_pad, s_max_pad, h_pad = _modbs_grid_statics(cells, parts)
    mss = []
    for cell, part in zip(cells, parts):
        ft, ftgt, fup, count = flr.partition_targets(cell.failures, part)
        mss.append(flr.merge_failure_stream(cell.batch, ft, ftgt, fup,
                                            count, pad_cls=len(part.a)))
    L_pad = max(ms.t.shape[1] for ms in mss)
    t = np.zeros((G, R, L_pad))
    c_ = np.full((G, R, L_pad), C_pad, np.int64)
    n = np.ones((G, R, L_pad), np.int64)
    svc = np.zeros((G, R, L_pad))
    t_up = np.zeros((G, R, L_pad))
    isf = np.ones((G, R, L_pad), bool)
    for g, (ms, part) in enumerate(zip(mss, parts)):
        L = ms.t.shape[1]
        C_cell = len(part.a)
        t[g, :, :L] = ms.t
        # the helper-drain marker is "class == C" with C a static of the
        # step: remap the per-cell marker to the grid's C_pad
        c_[g, :, :L] = np.where(ms.cls == C_cell, C_pad, ms.cls)
        n[g, :, :L] = ms.need
        svc[g, :, :L] = ms.service
        t_up[g, :, :L] = ms.t_up
        isf[g, :, :L] = ms.is_fail != 0
    comp0, W0, t0 = _modbs_grid_carry(args, C_pad, s_max_pad, h_pad, R)
    return dict(t=t, cls=c_, need=n, svc=svc, t_up=t_up, isf=isf,
                comp0=comp0, W0=W0, t0=t0, s_max_pad=s_max_pad,
                C_pad=C_pad, mss=mss)


def _modbs_fail_grid_extract(cells, mss, blocked_m, starts_m) -> list:
    blocked_m = np.asarray(blocked_m)
    starts_m = np.asarray(starts_m)
    out = []
    for g, (c, ms) in enumerate(zip(cells, mss)):
        starts = np.take_along_axis(starts_m[g], ms.job_pos, axis=1)
        blocked = np.take_along_axis(blocked_m[g], ms.job_pos, axis=1)
        out.append(_with_drain_obs(_modbs_result(c.batch, blocked, starts),
                                   c.batch, c.failures))
    return out


def _bs_grid_plan(cells) -> dict:
    G, R = len(cells), cells[0].batch.reps
    arrival, cls_, service, need, J_pad = _grid_jobs(cells)
    args = [_bs_args(c.batch, c.partition, c.wl, c.queue_cap)
            for c in cells]                  # (slots, s_max, h, q_cap)
    C_pad = max(len(a[0]) for a in args)
    s_max_pad = max(a[1] for a in args)
    h_pad = max(a[2] for a in args)
    q_cap_pad = max(a[3] for a in args)
    st0 = np.zeros((G, R, 3 * C_pad), np.int32)
    W0 = np.zeros((G, R, h_pad))
    for g, (slots, _, h, _) in enumerate(args):
        st0[g, :, :len(slots)] = slots       # free counters; padded C = 0
        W0[g, :, h:] = _BIG                  # dead helper servers
    j_live = np.broadcast_to(
        np.array([c.batch.num_jobs for c in cells],
                 np.int32)[:, None], (G, R))
    return dict(arrival=arrival, cls=cls_, need=need, service=service,
                st0=st0, W0=W0, j_live=np.ascontiguousarray(j_live),
                comp0=np.full((G, R, C_pad * s_max_pad), _BIG),
                ring0=np.zeros((G, R, C_pad * q_cap_pad), np.int32),
                heads0=np.full((G, R, C_pad), J_pad, np.int32),
                C_pad=C_pad, s_max_pad=s_max_pad, h_pad=h_pad,
                q_cap_pad=q_cap_pad, J_pad=J_pad,
                q_caps=[a[3] for a in args])


def _bs_grid_carry(plan, lead: tuple):
    """The BS event-scan carry of a grid plan with leading shape ``lead``
    (``(L,)`` flattened lanes, or ``(G, R)`` for the 2-D sharded mesh; no
    ``fi``/``ne`` — callers append the variant-specific counters)."""
    rs = lambda a: a.reshape(lead + a.shape[2:])
    return (_dev(np.zeros(lead), jnp.int32),
            _dev(rs(plan["st0"]), jnp.int32),
            _dev(rs(plan["comp0"]), jnp.float64),
            _dev(rs(plan["ring0"]), jnp.int32),
            _dev(rs(plan["heads0"]), jnp.int32),
            _dev(rs(plan["W0"]), jnp.float64),
            _dev(np.zeros(lead), jnp.float64),
            _dev(np.zeros(lead), jnp.float64),
            _dev(np.zeros(lead), jnp.int32))


def _bs_grid_extract(cells, plan, tagged, rec_t, rpk) -> list:
    tagged = np.asarray(tagged)
    rec_t = np.asarray(rec_t)
    rpk = np.asarray(rpk)
    J_pad = plan["J_pad"]
    out = []
    for g, c in enumerate(cells):
        # every cell's lanes ran the padded ring of q_cap_pad slots
        _bs_check_ring(rpk[g], plan["q_cap_pad"], cell=f"grid cell {g} ")
        starts, served, routed = _bs_scatter_events(
            J_pad, tagged[g], rec_t[g], plan["arrival"][g])
        J = c.batch.num_jobs
        res = _bs_assemble(c.batch, starts[:, :J], served[:, :J],
                           routed[:, :J])
        if c.failures is not None:
            res = _with_drain_obs(res, c.batch, c.failures)
        out.append(res)
    return out


def _bs_fail_grid_plan(cells) -> dict:
    """BS plan plus F-padded failure records (``t_down = inf`` rows never
    fire) with the helper marker remapped from per-cell C to C_pad."""
    plan = _bs_grid_plan(cells)
    G, R = len(cells), cells[0].batch.reps
    C_pad, J_pad = plan["C_pad"], plan["J_pad"]
    frecs = [_bs_fail_args(c.batch, c.failures, c.partition, c.wl)
             for c in cells]                 # (ft, ftgt, fup, length)
    F_pad = max(fr[0].shape[1] for fr in frecs)
    ft = np.full((G, R, F_pad), np.inf)
    ftgt = np.full((G, R, F_pad), C_pad, np.int32)
    fup = np.zeros((G, R, F_pad))
    length = 0
    parts = _grid_cell_parts(cells)
    for g, (fr, part) in enumerate(zip(frecs, parts)):
        F = fr[0].shape[1]
        C_cell = len(part.a)
        ft[g, :, :F] = fr[0]
        ftgt[g, :, :F] = np.where(fr[1] == C_cell, C_pad, fr[1])
        fup[g, :, :F] = fr[2]
        # per-cell event budget at the grid J/F: 2*J_pad covers every
        # job's two events, F_pad every failure, fa the repair
        # completions of free-slot drains
        fa = fr[3] - 2 * cells[g].batch.num_jobs - max(1, F)
        length = max(length, 2 * J_pad + F_pad + fa)
    plan.update(ft=ft, ftgt=ftgt, fup=fup, length=length)
    return plan


def _srpt_grid_plan(cells) -> dict:
    """SRPT grid plan: per-lane capacity ``kk`` is data (no dead-server
    masking needed — the walk budget F simply starts lower), the slot
    table is Q-padded to the grid max, and ``NU`` is the union of every
    cell's distinct needs (a superset is walk-equivalent per cell)."""
    G, R = len(cells), cells[0].batch.reps
    arrival, _, service, need, J_pad = _grid_jobs(cells)
    q_caps = [_srpt_args(c.batch, c.queue_cap) for c in cells]
    kk = np.broadcast_to(
        np.array([float(c.batch.k) for c in cells])[:, None], (G, R))
    j_live = np.broadcast_to(
        np.array([c.batch.num_jobs for c in cells], np.int32)[:, None],
        (G, R))
    NU = _srpt_nu(*[c.batch for c in cells])
    return dict(arrival=arrival, need=need, service=service,
                kk=np.ascontiguousarray(kk),
                j_live=np.ascontiguousarray(j_live),
                NU=NU, k_mult=_srpt_k_mult(NU, *[c.batch for c in cells]),
                Q_pad=max(q_caps), pairwise=_srpt_pairwise(max(q_caps)),
                q_caps=q_caps, J_pad=J_pad)


def _srpt_grid_carry(lead: tuple, Q: int):
    """Per-lane empty fast carry (``_srpt_fast_init`` layout), built
    host-side through ``_dev`` so the grid path compiles exactly one
    program (``jnp`` constructors would add per-shape convert
    executables to the pinned ``compile_count``)."""
    zq = lambda dt: _dev(np.zeros(lead + (Q,)), dt)
    z = lambda dt: _dev(np.zeros(lead), dt)
    cols = (_dev(np.full(lead + (Q,), -1), jnp.int32),  # every slot empty
            zq(jnp.int32), zq(jnp.int32), zq(jnp.float64), zq(jnp.float64),
            zq(jnp.bool_), zq(jnp.bool_), zq(jnp.float64))
    return (z(jnp.int32), cols, z(jnp.bool_), z(jnp.int32), z(jnp.int32),
            z(jnp.int32))


def _srpt_grid_extract(cells, plan, job_ev, t_ev, fs_ev, ovf, npre,
                       ne, peak=None) -> list:
    ovf, npre, ne = np.asarray(ovf), np.asarray(npre), np.asarray(ne)
    J_pad = plan["J_pad"]
    out = []
    for g, c in enumerate(cells):
        _srpt_check_ovf(ovf[g], plan["q_caps"][g], cell=f"grid cell {g} ",
                        peak=None if peak is None else peak[g])
        assert (ne[g] == 2 * c.batch.num_jobs).all(), \
            "SRPT grid scan under-ran its event budget"
        comp, fstart = _srpt_scatter_events(J_pad, job_ev[g], t_ev[g],
                                            fs_ev[g])
        J = c.batch.num_jobs
        out.append(BatchSimResult(
            response=comp[:, :J] - c.batch.arrival,
            wait=fstart[:, :J] - c.batch.arrival,
            p_helper=None, blocked=None, start=fstart[:, :J],
            preemptions=npre[g].astype(np.int64)))
    return out


# -- grid cores, engine="jax": flatten (cells, reps) -> one lane axis -------


@engines.register_grid("fcfs", "jax")
def _fcfs_grid_jax(cells):
    G, R = len(cells), cells[0].batch.reps
    L = G * R
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax")
        p = _fcfs_fail_grid_plan(cells)
        with enable_x64():
            carry = (_dev(p["W0"].reshape(L, -1), jnp.float64),
                     _dev(p["t0"].reshape(L), jnp.float64))
            _, starts_m = _call(
                _fcfs_fail_grid_chunk, carry,
                _dev(p["t"].reshape(L, -1), jnp.float64),
                _dev(p["n"].reshape(L, -1), jnp.int32),
                _dev(p["svc"].reshape(L, -1), jnp.float64),
                _dev(p["t_up"].reshape(L, -1), jnp.float64),
                _dev(p["isf"].reshape(L, -1), jnp.bool_))
        return _fcfs_fail_grid_extract(
            cells, p["mss"], np.asarray(starts_m).reshape(G, R, -1))
    p = _fcfs_grid_plan(cells)
    with enable_x64():
        carry = (_dev(p["W0"].reshape(L, -1), jnp.float64),
                 _dev(p["t0"].reshape(L), jnp.float64))
        _, starts = _call(
            _fcfs_stream_chunk, carry,
            _dev(p["arrival"].reshape(L, -1), jnp.float64),
            _dev(p["need"].reshape(L, -1), jnp.int32),
            _dev(p["service"].reshape(L, -1), jnp.float64))
    return _fcfs_grid_extract(cells, np.asarray(starts).reshape(G, R, -1))


@engines.register_grid("modbs-fcfs", "jax")
def _modbs_grid_jax(cells):
    G, R = len(cells), cells[0].batch.reps
    L = G * R
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax")
        p = _modbs_fail_grid_plan(cells)
        with enable_x64():
            carry = (_dev(p["comp0"].reshape(L, *p["comp0"].shape[2:]),
                          jnp.float64),
                     _dev(p["W0"].reshape(L, -1), jnp.float64),
                     _dev(p["t0"].reshape(L), jnp.float64))
            _, (blocked_m, starts_m) = _call(
                _modbs_fail_grid_chunk, carry,
                _dev(p["t"].reshape(L, -1), jnp.float64),
                _dev(p["cls"].reshape(L, -1), jnp.int32),
                _dev(p["need"].reshape(L, -1), jnp.int32),
                _dev(p["svc"].reshape(L, -1), jnp.float64),
                _dev(p["t_up"].reshape(L, -1), jnp.float64),
                _dev(p["isf"].reshape(L, -1), jnp.bool_),
                p["s_max_pad"], p["C_pad"])
        return _modbs_fail_grid_extract(
            cells, p["mss"], np.asarray(blocked_m).reshape(G, R, -1),
            np.asarray(starts_m).reshape(G, R, -1))
    p = _modbs_grid_plan(cells)
    with enable_x64():
        carry = (_dev(p["comp0"].reshape(L, *p["comp0"].shape[2:]),
                      jnp.float64),
                 _dev(p["W0"].reshape(L, -1), jnp.float64),
                 _dev(p["t0"].reshape(L), jnp.float64))
        _, (blocked, starts) = _call(
            _modbs_stream_chunk, carry,
            _dev(p["arrival"].reshape(L, -1), jnp.float64),
            _dev(p["cls"].reshape(L, -1), jnp.int32),
            _dev(p["need"].reshape(L, -1), jnp.int32),
            _dev(p["service"].reshape(L, -1), jnp.float64),
            p["s_max_pad"])
    return _modbs_grid_extract(cells,
                               np.asarray(blocked).reshape(G, R, -1),
                               np.asarray(starts).reshape(G, R, -1))


@engines.register_grid("bs-fcfs", "jax")
def _bs_grid_jax(cells):
    G, R = len(cells), cells[0].batch.reps
    L = G * R
    if cells[0].failures is not None:
        for c in cells:
            flr.require_drain(c.failures, "jax")
        p = _bs_fail_grid_plan(cells)
        with enable_x64():
            c0 = _bs_grid_carry(p, (L,))
            carry = (c0[0], _dev(np.zeros(L), jnp.int32)) + c0[1:]
            carry, tagged, rec_t = _call(
                _bs_fail_grid_chunk, carry,
                _dev(p["arrival"].reshape(L, -1), jnp.float64),
                _dev(p["cls"].reshape(L, -1), jnp.int32),
                _dev(p["need"].reshape(L, -1), jnp.int32),
                _dev(p["service"].reshape(L, -1), jnp.float64),
                _dev(p["ft"].reshape(L, -1), jnp.float64),
                _dev(p["ftgt"].reshape(L, -1), jnp.int32),
                _dev(p["fup"].reshape(L, -1), jnp.float64),
                _dev(p["j_live"].reshape(L), jnp.int32),
                p["C_pad"], p["s_max_pad"], p["h_pad"], p["q_cap_pad"],
                p["length"])
            rpk = carry[9]
        return _bs_grid_extract(cells, p,
                                np.asarray(tagged).reshape(G, R, -1),
                                np.asarray(rec_t).reshape(G, R, -1),
                                np.asarray(rpk).reshape(G, R))
    p = _bs_grid_plan(cells)
    with enable_x64():
        c0 = _bs_grid_carry(p, (L,))
        carry = c0 + (_dev(np.zeros(L), jnp.int32),)  # + ne
        carry, tagged, rec_t = _call(
            _bs_grid_chunk, carry,
            _dev(p["arrival"].reshape(L, -1), jnp.float64),
            _dev(p["cls"].reshape(L, -1), jnp.int32),
            _dev(p["need"].reshape(L, -1), jnp.int32),
            _dev(p["service"].reshape(L, -1), jnp.float64),
            _dev(p["j_live"].reshape(L), jnp.int32),
            p["C_pad"], p["s_max_pad"], p["h_pad"], p["q_cap_pad"],
            2 * p["J_pad"])
        rpk, ne = carry[8], carry[9]
    assert (np.asarray(ne) == 2 * p["j_live"].reshape(L)).all(), \
        "BS grid scan under-ran its event budget"
    return _bs_grid_extract(cells, p,
                            np.asarray(tagged).reshape(G, R, -1),
                            np.asarray(rec_t).reshape(G, R, -1),
                            np.asarray(rpk).reshape(G, R))


def _srpt_grid(sf: bool, cells):
    policy = "sf-srpt" if sf else "ff-srpt"
    _srpt_no_failures(cells[0].failures, policy)
    G, R = len(cells), cells[0].batch.reps
    L = G * R
    p = _srpt_grid_plan(cells)
    with enable_x64():
        carry = _srpt_grid_carry((L,), p["Q_pad"])
        carry, job_ev, t_ev, fs_ev = _call(
            _srpt_grid_chunk, carry,
            _dev(p["arrival"].reshape(L, -1), jnp.float64),
            _dev(p["need"].reshape(L, -1), jnp.float64),
            _dev(p["service"].reshape(L, -1), jnp.float64),
            _dev(p["kk"].reshape(L), jnp.float64),
            _dev(p["j_live"].reshape(L), jnp.int32),
            p["Q_pad"], p["NU"], sf, 2 * p["J_pad"], p["k_mult"],
            p["pairwise"])
    return _srpt_grid_extract(
        cells, p, np.asarray(job_ev).reshape(G, R, -1),
        np.asarray(t_ev).reshape(G, R, -1),
        np.asarray(fs_ev).reshape(G, R, -1),
        np.asarray(carry[2]).reshape(G, R),
        np.asarray(carry[3]).reshape(G, R),
        np.asarray(carry[4]).reshape(G, R),
        np.asarray(carry[5]).reshape(G, R))


@engines.register_grid("sf-srpt", "jax")
def _sf_srpt_grid_jax(cells):
    return _srpt_grid(True, cells)


@engines.register_grid("ff-srpt", "jax")
def _ff_srpt_grid_jax(cells):
    return _srpt_grid(False, cells)


# --------------------------------------------------------------------------
# k-sweeps.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Mean/CI arrays of a batched sweep, shaped [policies, points].

    ``ci95_*`` is the half-width of the normal 95% confidence interval over
    the per-replication means (0 when ``reps == 1``).
    """

    points: tuple                  # the swept values (k, or load, ...)
    policies: tuple[str, ...]
    num_jobs: int
    reps: int
    mean_response: np.ndarray      # [P, N]
    ci95_response: np.ndarray      # [P, N]
    mean_wait: np.ndarray          # [P, N]
    p_wait: np.ndarray             # [P, N]
    ci95_p_wait: np.ndarray        # [P, N]
    p_helper: np.ndarray           # [P, N], nan where not a BSF policy
    p95_response: np.ndarray       # [P, N] (mean of per-rep 95th pctiles)
    utilization: np.ndarray        # [P, N] busy server-time / (k * horizon)
    sim_s: np.ndarray              # [P, N] simulator wall time incl. compile

    def rows(self, point_col: str, extra_cols: dict | None = None,
             per_point_cols: Sequence[dict] | None = None) -> list[dict]:
        """Benchmark CSV rows, one per (point, policy)."""
        out = []
        for j, pt in enumerate(self.points):
            for i, pol in enumerate(self.policies):
                ph = self.p_helper[i, j]
                row = {
                    point_col: pt, "policy": pol,
                    "jobs": self.num_jobs, "reps": self.reps,
                    "mean_response": self.mean_response[i, j],
                    "ci95_response": self.ci95_response[i, j],
                    "mean_wait": self.mean_wait[i, j],
                    "p_wait": self.p_wait[i, j],
                    "ci95_p_wait": self.ci95_p_wait[i, j],
                    "p_helper": None if np.isnan(ph) else ph,
                    "p95_response": self.p95_response[i, j],
                    "utilization": self.utilization[i, j],
                    "sim_s": round(float(self.sim_s[i, j]), 2),
                }
                if extra_cols:
                    row.update(extra_cols)
                if per_point_cols:
                    row.update(per_point_cols[j])
                out.append(row)
        return out


def _ci95(per_rep: np.ndarray) -> float:
    if per_rep.size < 2:
        return 0.0
    return float(1.96 * per_rep.std(ddof=1) / np.sqrt(per_rep.size))


def _sweep_failures(failures, wl: Workload, batch: BatchTrace, seed: int):
    """Materialize the per-point FailureBatch of a faulty sweep.

    ``failures`` is either a :class:`repro.core.failures.FailureProcess`
    (sampled here with the point's k and the batch's arrival horizon, same
    seed discipline as the traces) or a callable ``(wl, batch) ->
    FailureBatch`` for full control.
    """
    if hasattr(failures, "sample"):
        horizon = float(batch.arrival.max())
        return failures.sample(wl.k, horizon, batch.reps, seed=seed)
    return failures(wl, batch)


def sweep_many_server(wl_factory: Callable[..., Workload], points: Sequence,
                      *, num_jobs: int = 100_000, reps: int = 8,
                      seed: int = 0,
                      policies: Sequence[str] = ("fcfs", "modbs-fcfs",
                                                 "bs-fcfs"),
                      engine: str = "jax",
                      grid: bool = True,
                      failures=None,
                      ckpt_dir: str | None = None,
                      resume: bool = False,
                      ) -> SweepResult:
    """Run the batched simulators over ``wl_factory(point)`` for each point.

    One batch of ``reps`` Philox replications x ``num_jobs`` arrivals is
    sampled per point.  With ``grid=True`` (the default) the sweep is
    **grid-native**: per policy, every not-yet-checkpointed point becomes
    one :class:`~repro.core.engines.GridCell` and a single
    :func:`engines.simulate_grid` launch runs the whole grid as one
    compiled program (cells k/J-padded onto one lane axis — see the grid
    section of this module; on ``engine="jax-shard"`` the (cells, reps)
    plane shards over the 2-D mesh of :func:`repro.core.shard.grid_mesh`).
    Every cell is bit-identical to the per-cell path, so ``grid`` only
    changes wall-clock: ``sim_s`` then records the grid launch wall time
    amortized uniformly over its cells.  ``grid=False`` keeps the
    point-major per-cell dispatch (one ``engines.simulate`` per cell with
    exact per-cell timing — the baseline ``bench="grid"`` benchmarks
    compare against).  Engines without a registered grid core (python,
    pallas) fall back to per-cell dispatch inside ``simulate_grid``.

    ``engine`` selects the substrate via the registry of
    :mod:`repro.core.engines`: ``"jax"`` (vmapped lax.scan, the default),
    ``"jax-shard"`` (device-mesh sharding — see :mod:`repro.core.shard`;
    use ``configure_runtime(devices=N)`` before the first JAX call to
    expose N host devices), ``"pallas"`` (fused step kernels, interpret
    mode on the CPU only — bit-identical, slower), or ``"python"`` (the
    exact event engine — slow, but the same interface).  Any ``(policy,
    engine)`` registry pair sweeps; unknown policies raise ``KeyError``.
    Returns mean/CI arrays [policies, points].

    ``failures`` injects degraded-capacity scenarios (see
    :func:`_sweep_failures`).  ``ckpt_dir`` makes the sweep crash-
    resumable: every (point, policy) cell is written atomically
    (:mod:`repro.checkpoint`) as its own checkpoint step the moment its
    results exist — per cell in the per-cell path, extracted per cell
    right after each grid launch returns — and ``resume=True`` restores
    completed cells — including their recorded ``sim_s`` — instead of
    re-simulating.  The cell-step numbering (``point * P + policy``) is
    identical in both modes, so a sweep checkpointed per-cell resumes
    forward under ``grid=True`` and vice versa, with bit-identical
    output.
    """
    if engine not in engines.available_engines():
        raise ValueError(f"unknown engine {engine!r}; registered engines: "
                         f"{list(engines.available_engines())}")
    avail = engines.policies_for(engine)
    unknown = set(policies) - set(avail)
    if unknown:
        raise KeyError(f"no {engine!r} simulator for {sorted(unknown)}; "
                       f"available: {list(avail)}")
    if resume and ckpt_dir is None:
        raise ValueError("resume=True needs a ckpt_dir")
    P, N = len(policies), len(points)
    shape = (P, N)
    mean_r = np.zeros(shape); ci_r = np.zeros(shape)
    mean_w = np.zeros(shape); p_wait = np.zeros(shape)
    ci_pw = np.zeros(shape)
    p_help = np.full(shape, np.nan)
    p95 = np.zeros(shape); util = np.zeros(shape); sim_s = np.zeros(shape)
    cells = (mean_r, ci_r, mean_w, p_wait, ci_pw, p_help, p95, util, sim_s)
    done: set[int] = set()
    if resume:
        from repro.checkpoint import completed_steps
        done = set(completed_steps(ckpt_dir))

    # a fully checkpointed point restores without sampling: the traces are
    # only needed to simulate, not to read back cell metrics.  Sampling is
    # per-point Philox (order-independent), so the grid path sampling
    # points policy-by-policy is bit-identical to the point-major path.
    sampled: dict[int, tuple] = {}

    def _point_data(j: int) -> tuple:
        if j not in sampled:
            wl = wl_factory(points[j])
            batch = wl.sample_traces(num_jobs, reps, seed=seed)
            busy = (batch.need * batch.service).sum(axis=1)    # [R]
            fb = (_sweep_failures(failures, wl, batch, seed)
                  if failures is not None else None)
            sampled[j] = (wl, batch, busy, fb)
        return sampled[j]

    def _restore_cell(i: int, j: int, pol: str) -> None:
        from repro.checkpoint import require_layout, restore_checkpoint
        cell = j * P + i
        tree, _, extra = restore_checkpoint(
            ckpt_dir, {"cell": np.zeros(len(cells))}, step=cell)
        require_layout(extra, {"policy": pol}, context=f"cell {cell}")
        for arr, v in zip(cells, tree["cell"]):
            arr[i, j] = v

    def _record_cell(i: int, j: int, pol: str, res, wall: float) -> None:
        wl, batch, busy, _ = sampled[j]
        sim_s[i, j] = wall
        mean_r[i, j] = res.mean_response.mean()
        ci_r[i, j] = _ci95(res.mean_response)
        mean_w[i, j] = res.mean_wait.mean()
        p_wait[i, j] = res.p_wait.mean()
        ci_pw[i, j] = _ci95(res.p_wait)
        if res.p_helper is not None:
            p_help[i, j] = res.p_helper.mean()
        p95[i, j] = np.percentile(res.response, 95, axis=1).mean()
        completion = batch.arrival + res.response
        horizon = completion.max(axis=1)                       # [R]
        util[i, j] = (busy / (wl.k * horizon)).mean()
        if ckpt_dir is not None:
            from repro.checkpoint import save_checkpoint
            save_checkpoint(
                ckpt_dir, j * P + i,
                {"cell": np.array([a[i, j] for a in cells])},
                extra={"point": repr(points[j]), "policy": pol})

    if grid:
        for i, pol in enumerate(policies):
            todo = []
            for j in range(N):
                if j * P + i in done:
                    _restore_cell(i, j, pol)
                else:
                    todo.append(j)
            if not todo:
                continue
            gcells = []
            for j in todo:
                wl, batch, _, fb = _point_data(j)
                gcells.append(engines.GridCell(batch=batch, wl=wl,
                                               failures=fb))
            t0 = time.time()
            results = engines.simulate_grid(pol, gcells, engine=engine)
            wall = (time.time() - t0) / len(todo)
            for j, res in zip(todo, results):
                _record_cell(i, j, pol, res, wall)
    else:
        for j in range(N):
            for i, pol in enumerate(policies):
                if j * P + i in done:
                    _restore_cell(i, j, pol)
                    continue
                _, batch, _, fb = _point_data(j)
                wl = sampled[j][0]
                t0 = time.time()
                res = engines.simulate(pol, batch, engine=engine, wl=wl,
                                       **({} if fb is None
                                          else {"failures": fb}))
                _record_cell(i, j, pol, res, time.time() - t0)
    return SweepResult(points=tuple(points), policies=tuple(policies),
                       num_jobs=num_jobs, reps=reps,
                       mean_response=mean_r, ci95_response=ci_r,
                       mean_wait=mean_w, p_wait=p_wait, ci95_p_wait=ci_pw,
                       p_helper=p_help, p95_response=p95,
                       utilization=util, sim_s=sim_s)


# --------------------------------------------------------------------------
# Streaming chunked execution: constant-memory unbounded traces.
#
# A stream is a sequence of chunk scans, each resumed from the previous
# chunk's carry (the stream cores of sim_jax), with per-job observables
# folded into an online accumulator the moment they are final — peak memory
# is O(R * chunk_jobs), independent of the stream length.  Every fold
# below is arranged so the chunked path is *bit-identical* to running the
# monolithic batch and folding its per-job arrays once (`stream_fold`):
# block boundaries fall on fixed global job indices, block means use the
# same contiguous-buffer reductions, and the probability observables are
# exact integer counts divided once at the end.
# --------------------------------------------------------------------------


class StreamAccumulator:
    """Online per-replication observables of a job stream.

    Response and wait fold through a fixed-size [2, R, block] buffer:
    full blocks merge into running (count, mean, M2) via the Chan
    parallel-variance update.  Because blocks are cut at fixed *global*
    job indices (multiples of ``block``) regardless of push granularity,
    the folded moments are bit-identical however the stream was chunked.
    The probability observables (P[wait>0], helper-served, routed) are
    kept as exact int64 counts — order-independent by construction.
    """

    def __init__(self, reps: int, block: int = 4096):
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        self.reps = int(reps)
        self.block = int(block)
        self.count = 0              # jobs observed (incl. still-buffered)
        self._cnt = 0               # jobs merged into the running moments
        self._fill = 0
        self._mean = np.zeros((2, self.reps))    # rows: response, wait
        self._m2 = np.zeros((2, self.reps))
        self._buf = np.zeros((2, self.reps, self.block))
        self.n_wait = np.zeros(self.reps, np.int64)
        self.n_served = np.zeros(self.reps, np.int64)
        self.n_routed = np.zeros(self.reps, np.int64)

    def push(self, response, wait, served=None, routed=None) -> None:
        """Fold [R, m] per-job arrays (m may be any size, incl. 0)."""
        resp = np.asarray(response)
        wt = np.asarray(wait)
        m = resp.shape[1]
        if m == 0:
            return
        self.n_wait += (wt > WAIT_EPS).sum(axis=1, dtype=np.int64)
        if served is not None:
            self.n_served += np.asarray(served).sum(axis=1, dtype=np.int64)
        if routed is not None:
            self.n_routed += np.asarray(routed).sum(axis=1, dtype=np.int64)
        data = np.stack([resp, wt])              # [2, R, m]
        pos = 0
        while pos < m:
            take = min(self.block - self._fill, m - pos)
            self._buf[:, :, self._fill:self._fill + take] = \
                data[:, :, pos:pos + take]
            self._fill += take
            pos += take
            if self._fill == self.block:
                self._cnt, self._mean, self._m2 = self._merge(
                    self._cnt, self._mean, self._m2, self._buf, self.block)
                self._fill = 0
        self.count += m

    @staticmethod
    def _merge(cnt, mean, m2, buf, b):
        """Chan merge of the first ``b`` buffered jobs; returns new state."""
        blk = buf[:, :, :b]
        bm = blk.mean(axis=2)
        bm2 = ((blk - bm[:, :, None]) ** 2).sum(axis=2)
        delta = bm - mean
        tot = cnt + b
        mean = mean + delta * (b / tot)
        m2 = m2 + bm2 + delta * delta * (cnt * b / tot)
        return tot, mean, m2

    def finalize(self):
        """(count, mean [2, R], M2 [2, R]) including the partial buffer.

        Non-destructive: the accumulator remains valid for further pushes
        (the partial block is merged into *copies* of the running state).
        """
        cnt, mean, m2 = self._cnt, self._mean.copy(), self._m2.copy()
        if self._fill:
            cnt, mean, m2 = self._merge(cnt, mean, m2, self._buf,
                                        self._fill)
        return cnt, mean, m2

    def state(self) -> dict:
        """Checkpointable state (the buffer saved at its exact fill)."""
        return {"count": np.asarray(self.count, np.int64),
                "cnt": np.asarray(self._cnt, np.int64),
                "mean": self._mean.copy(), "m2": self._m2.copy(),
                "buf": self._buf[:, :, :self._fill].copy(),
                "n_wait": self.n_wait.copy(),
                "n_served": self.n_served.copy(),
                "n_routed": self.n_routed.copy()}

    def load_state(self, d: dict) -> None:
        self.count = int(d["count"])
        self._cnt = int(d["cnt"])
        self._mean = np.asarray(d["mean"], np.float64).copy()
        self._m2 = np.asarray(d["m2"], np.float64).copy()
        fill = int(d["buf"].shape[2])
        self._fill = fill
        self._buf[:, :, :fill] = d["buf"]
        self.n_wait = np.asarray(d["n_wait"], np.int64).copy()
        self.n_served = np.asarray(d["n_served"], np.int64).copy()
        self.n_routed = np.asarray(d["n_routed"], np.int64).copy()


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Folded per-replication observables of a streamed simulation.

    The constant-memory counterpart of :class:`BatchSimResult`: per-job
    arrays are never materialized, so the result carries the folded
    moments instead — ``var_*`` is the population variance (M2/n) of the
    per-job values within each replication.
    """

    jobs: int                      # jobs folded per replication
    reps: int
    mean_response: np.ndarray      # [R]
    var_response: np.ndarray       # [R]
    mean_wait: np.ndarray          # [R]
    var_wait: np.ndarray           # [R]
    p_wait: np.ndarray             # [R] P[wait > WAIT_EPS]
    p_helper: np.ndarray | None = None   # [R] (BSF policies only)
    p_routed: np.ndarray | None = None   # [R]


def _stream_result(acc: StreamAccumulator, jobs: int,
                   has_helper: bool) -> StreamResult:
    cnt, mean, m2 = acc.finalize()
    if cnt != jobs:
        raise RuntimeError(f"internal error: accumulator folded {cnt} "
                           f"jobs, stream fed {jobs}")
    var = m2 / cnt
    return StreamResult(
        jobs=jobs, reps=acc.reps,
        mean_response=mean[0], var_response=var[0],
        mean_wait=mean[1], var_wait=var[1],
        p_wait=acc.n_wait / cnt,
        p_helper=(acc.n_served / cnt) if has_helper else None,
        p_routed=(acc.n_routed / cnt) if has_helper else None)


def stream_fold(res: BatchSimResult, block: int = 4096) -> StreamResult:
    """Fold a monolithic :class:`BatchSimResult` into a StreamResult.

    The reference the streaming path is pinned against: pushing the full
    per-job arrays through a fresh accumulator cuts blocks at the same
    global indices as any chunked schedule, so ``simulate_stream`` must
    match this bit-for-bit (``tests/test_stream.py``).
    """
    acc = StreamAccumulator(res.reps, block=block)
    flags = res.blocked  # ModBS: served == routed == blocked flags
    acc.push(res.response, res.wait, served=flags, routed=flags)
    cnt, mean, m2 = acc.finalize()
    var = m2 / cnt
    if res.p_helper is None:
        p_h = p_r = None
    elif flags is not None:
        p_h = acc.n_served / cnt
        p_r = acc.n_routed / cnt
    else:
        # bs-fcfs keeps no per-job flags on the result; its per-rep
        # fractions are the same exact count/J in f64 (0/1 partial sums
        # are exact integers, one final division) as the count route
        p_h = res.p_helper
        p_r = res.p_routed
    return StreamResult(jobs=res.response.shape[1], reps=res.reps,
                        mean_response=mean[0], var_response=var[0],
                        mean_wait=mean[1], var_wait=var[1],
                        p_wait=acc.n_wait / cnt, p_helper=p_h, p_routed=p_r)


# -- jitted chunk entries (carry in, carry out; the carry is NEVER donated
# — the driver owns it across chunks — only the per-chunk job buffers are)


@partial(jax.jit, donate_argnums=(1, 2, 3))
def _fcfs_stream_chunk(carry, arrival, need, service):
    return jax.vmap(_fcfs_stream_core)(carry, arrival, need, service)


@partial(jax.jit, static_argnums=(5,), donate_argnums=(1, 2, 3, 4))
def _modbs_stream_chunk(carry, arrival, cls, need, service, s_max: int):
    return jax.vmap(
        lambda c, a, cc, n, v: _modbs_stream_core(c, a, cc, n, v, s_max))(
        carry, arrival, cls, need, service)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10),
         donate_argnums=(1, 2, 3, 4))
def _bs_stream_chunk(carry, arrival, cls, need, service, horizon,
                     C: int, s_max: int, h: int, q_cap: int, length: int):
    # _bs_stream_core carries the replications axis natively (see _bs_core)
    return _bs_stream_core(arrival, cls, need, service, horizon, carry,
                           C, s_max, h, q_cap, length)


# -- checkpoint plumbing -----------------------------------------------------


class _StreamCkpt:
    """Per-chunk checkpoint plumbing of a streaming driver.

    Synchronous atomic saves (:mod:`repro.checkpoint`), last two steps
    kept; restore validates the manifest's layout dict against the
    resuming run (:func:`repro.checkpoint.require_layout`) so a changed
    ``chunk_jobs``/J layout fails loudly instead of mixing carries.
    """

    def __init__(self, ckpt_dir: str | None, layout: dict):
        self.mgr = None
        self.layout = layout
        if ckpt_dir is not None:
            from repro.checkpoint import CheckpointManager
            self.mgr = CheckpointManager(ckpt_dir, keep=2)

    def save(self, step: int, tree) -> None:
        if self.mgr is not None:
            self.mgr.save(step, tree, extra=self.layout)

    def restore(self, tree_like, context: str):
        """(tree, step) of the latest checkpoint, or None when fresh."""
        if self.mgr is None:
            raise ValueError("resume=True needs a ckpt_dir")
        from repro.checkpoint import latest_step, require_layout
        if latest_step(self.mgr.directory) is None:
            return None
        tree, step, extra = self.mgr.restore(tree_like)
        require_layout(extra, self.layout, context=context)
        return tree, step


def _fetch_chunk(source, state, n: int, total: int):
    batch, state = source.next_chunk(state, n)
    if batch.num_jobs != n:
        raise ValueError(
            f"chunk source returned {batch.num_jobs} jobs, the driver "
            f"asked for {n} — source exhausted before total_jobs={total}?")
    return batch, state


# -- the scan-carry driver (fcfs / modbs: one event per job, no horizon) ----


def _scan_stream(source, *, policy, chunk_jobs, total_jobs, n_carry,
                 init_fn, chunk_fn, has_helper, part=None, block=4096,
                 ckpt_dir=None, resume=False, layout_extra=None):
    """Drive a scan-carry policy over a chunk source.

    ``chunk_fn(carry, batch) -> (carry, response, wait, served, routed)``
    runs one chunk resumed from ``carry``; ``init_fn(R)`` builds the
    empty-system carry.  The carry plus accumulator plus source state is
    checkpointed after every chunk, so a SIGKILL mid-stream resumes
    byte-identically (the saved source state is the *pre-fetch* state of
    the next chunk — re-fetching it is exact because sources are pure
    functions of their state).
    """
    R = int(source.reps)
    total = int(total_jobs)
    chunk_jobs = int(chunk_jobs)
    layout = {"policy": policy, "chunk_jobs": chunk_jobs,
              "total_jobs": total, "reps": R, "k": int(source.k),
              "block": int(block)}
    if layout_extra:
        layout.update(layout_extra)
    ck = _StreamCkpt(ckpt_dir, layout)
    acc = StreamAccumulator(R, block=block)
    src_state = source.init_state()
    carry_np = None
    fed = 0
    step = 0
    if resume:
        like = {"sim": {"carry": [np.zeros(0)] * n_carry,
                        "fed": np.zeros((), np.int64)},
                "acc": acc.state(), "src": src_state}
        got = ck.restore(like, f"of stream {policy!r}")
        if got is not None:
            tree, step = got
            carry_np = tree["sim"]["carry"]
            fed = int(tree["sim"]["fed"])
            acc.load_state(tree["acc"])
            src_state = tree["src"]
    with enable_x64():
        carry = (init_fn(R) if carry_np is None
                 else tuple(jnp.asarray(c) for c in carry_np))
    while fed < total:
        n = min(chunk_jobs, total - fed)
        batch, src_state = _fetch_chunk(source, src_state, n, total)
        engines.validate_batch(batch, partition=part)
        carry, resp, wait, served, routed = chunk_fn(carry, batch)
        acc.push(resp, wait, served=served, routed=routed)
        fed += n
        step += 1
        ck.save(step, {"sim": {"carry": [np.asarray(c) for c in carry],
                               "fed": np.asarray(fed, np.int64)},
                       "acc": acc.state(), "src": src_state})
    return _stream_result(acc, total, has_helper)


# -- the BS event driver: bounded backlog, start-time reorder window --------


class _StreamWindow:
    """Start-time reorder window of the streaming BS driver (host side).

    BS start events arrive out of job order (the event scan interleaves
    A starts, routings and helper commits), so finished observables are
    folded only up to the oldest job whose start is still unknown.  The
    window holds per-global-job (arrival, service, start, flags) records
    for gids [base, base+used); capacity doubles on demand and the
    occupied prefix shifts left after each fold.
    """

    def __init__(self, reps: int, cap: int):
        self.reps = int(reps)
        self.base = 0
        self._used = 0
        self._alloc(max(1, int(cap)))

    def _alloc(self, cap: int) -> None:
        self.cap = cap
        R = self.reps
        self.arr = np.zeros((R, cap))
        self.svc = np.zeros((R, cap))
        self.start = np.zeros((R, cap))
        self.known = np.zeros((R, cap), bool)
        self.served = np.zeros((R, cap), bool)
        self.routed = np.zeros((R, cap), bool)

    def _arrays(self):
        return (self.arr, self.svc, self.start, self.known, self.served,
                self.routed)

    def extend(self, fed: int, chunk: BatchTrace) -> None:
        """Cover gids [fed, fed + Jc) and record the chunk's arr/svc."""
        Jc = chunk.num_jobs
        need = fed + Jc - self.base
        if need > self.cap:
            old = self._arrays()
            u = self._used
            self._alloc(max(need, 2 * self.cap))
            for dst, src in zip(self._arrays(), old):
                dst[:, :u] = src[:, :u]
        lo = fed - self.base
        self.arr[:, lo:lo + Jc] = chunk.arrival
        self.svc[:, lo:lo + Jc] = chunk.service
        self._used = need

    def scatter(self, tagged, rec_t, idmap, J_l: int) -> None:
        """Scatter one chunk's [R, L] event streams (local ids -> gids).
        A job that starts at its own arrival (never routed, or a j + 3J
        helper commit) takes its start from the window's arrival, as in
        ``sim_jax._bs_scatter_events``; a job's routing record never comes
        after its start record, so routing is scattered first."""
        rows = np.broadcast_to(np.arange(self.reps)[:, None], tagged.shape)
        m_r = (tagged >= J_l) & (tagged < 2 * J_l)
        col = idmap[rows[m_r], tagged[m_r] - J_l] - self.base
        self.routed[rows[m_r], col] = True
        m_a = (tagged >= 0) & (tagged < J_l)
        r, col = rows[m_a], idmap[rows[m_a], tagged[m_a]] - self.base
        self.start[r, col] = np.where(self.routed[r, col], rec_t[m_a],
                                      self.arr[r, col])
        self.known[r, col] = True
        for lo, at_arrival in ((2 * J_l, False), (3 * J_l, True)):
            m_h = (tagged >= lo) & (tagged < lo + J_l)
            r, col = rows[m_h], idmap[rows[m_h], tagged[m_h] - lo] - self.base
            self.start[r, col] = self.arr[r, col] if at_arrival else rec_t[m_h]
            self.known[r, col] = True
            self.served[r, col] = True

    def fold_into(self, acc: StreamAccumulator) -> None:
        """Fold every job below the oldest still-unknown start."""
        n = self._used
        unk = ~self.known[:, :n]
        first = np.where(unk.any(axis=1), unk.argmax(axis=1), n)
        adv = int(first.min())
        if adv == 0:
            return
        a = self.arr[:, :adv]
        v = self.svc[:, :adv]
        s = self.start[:, :adv]
        # same elementwise op order as _bs_result
        acc.push(s + v - a, s - a, served=self.served[:, :adv],
                 routed=self.routed[:, :adv])
        rem = n - adv
        for x in self._arrays():
            x[:, :rem] = x[:, adv:n].copy()
        for x in (self.known, self.served, self.routed):
            x[:, rem:n] = False
        self.base += adv
        self._used = rem

    def state(self) -> dict:
        u = self._used
        return {"base": np.asarray(self.base, np.int64),
                "arr": self.arr[:, :u].copy(), "svc": self.svc[:, :u].copy(),
                "start": self.start[:, :u].copy(),
                "known": self.known[:, :u].copy(),
                "served": self.served[:, :u].copy(),
                "routed": self.routed[:, :u].copy()}

    def load_state(self, d: dict) -> None:
        u = int(d["arr"].shape[1])
        if u > self.cap:
            self._alloc(max(u, 2 * self.cap))
        self.base = int(d["base"])
        self._used = u
        for name in ("arr", "svc", "start", "known", "served", "routed"):
            dst = getattr(self, name)
            dst[:, :u] = d[name]
            if dst.dtype == bool:
                dst[:, u:] = False


def _bs_canon0(R: int, C: int, s_max: int, h: int, B: int,
               slots) -> dict:
    """Empty-system canonical BS stream state (matches ``_bs_init``)."""
    return {"pend_gid": np.full((R, B), -1, np.int64),
            "pend_arr": np.zeros((R, B)),
            "pend_svc": np.zeros((R, B)),
            "pend_cls": np.zeros((R, B), np.int64),
            "pend_need": np.ones((R, B), np.int64),
            "pend_n": np.zeros(R, np.int64),
            "free": np.broadcast_to(np.asarray(slots, np.int32),
                                    (R, C)).copy(),
            "comp": np.full((R, C * s_max), _BIG),
            "W": np.zeros((R, h)),
            "t_prev": np.zeros(R),
            "t_hol": np.zeros(R)}


def _bs_inflate(canon: dict, chunk: BatchTrace, fed: int, slots,
                s_max: int, h: int, q_cap: int, B: int):
    """Canonical state + chunk -> (carry, local job arrays, idmap).

    Local layout: the still-queued jobs of earlier chunks re-based to
    local indices [0, P_r) in global-gid order (= FIFO — gids increment
    in feed order, so local index order mirrors the monolithic job index
    order the scan's min-of-heads FIFO selection relies on), zero padding
    up to B, the chunk's jobs at [B, B + Jc).  Per-class rings rebuild
    from the pending set (head counter 0), the arrival cursor starts at B
    (pending arrivals were consumed in earlier chunks), and the ring peak
    and ne reset per chunk.
    """
    R, Jc = chunk.arrival.shape
    C = int(slots.shape[0])
    J_l = B + Jc
    arr = np.zeros((R, J_l))
    svc = np.zeros((R, J_l))
    cl = np.zeros((R, J_l), np.int64)
    nd = np.ones((R, J_l), np.int64)
    arr[:, :B] = canon["pend_arr"]
    svc[:, :B] = canon["pend_svc"]
    cl[:, :B] = canon["pend_cls"]
    nd[:, :B] = canon["pend_need"]
    arr[:, B:] = chunk.arrival
    svc[:, B:] = chunk.service
    cl[:, B:] = chunk.cls
    nd[:, B:] = chunk.need
    idmap = np.concatenate(
        [canon["pend_gid"],
         np.broadcast_to(fed + np.arange(Jc), (R, Jc))], axis=1)
    st = np.zeros((R, 3 * C), np.int32)
    st[:, :C] = canon["free"]
    ring = np.zeros((R, C * q_cap), np.int32)
    heads = np.full((R, C), J_l, np.int32)
    for r in range(R):
        pcls = canon["pend_cls"][r, :int(canon["pend_n"][r])]
        for c in range(C):
            loc = np.flatnonzero(pcls == c)
            if loc.size:
                ring[r, c * q_cap + np.arange(loc.size)] = loc
                st[r, 2 * C + c] = loc.size
                heads[r, c] = loc[0]
    carry = (np.full(R, B, np.int32), st, canon["comp"], ring, heads,
             canon["W"], canon["t_prev"], canon["t_hol"],
             np.zeros(R, np.int32), np.zeros(R, np.int32))
    return carry, (arr, cl, nd, svc), idmap


def _bs_extract(carry, idmap, rec, B: int, C: int, q_cap: int) -> dict:
    """Post-chunk carry -> canonical state (the checkpoint/resume unit).

    Walks the per-class rings, maps survivors back to gids, and re-sorts
    them into global-FIFO order.  More than ``B`` still-queued jobs in
    any lane means the bounded local layout cannot represent the backlog
    — raised loudly rather than silently dropping jobs.
    """
    ai, st, comp, ring, heads, W, t_prev, t_hol, rpk, ne = carry
    arr_l, cl_l, nd_l, svc_l = rec
    R = st.shape[0]
    canon = {"pend_gid": np.full((R, B), -1, np.int64),
             "pend_arr": np.zeros((R, B)),
             "pend_svc": np.zeros((R, B)),
             "pend_cls": np.zeros((R, B), np.int64),
             "pend_need": np.ones((R, B), np.int64),
             "pend_n": np.zeros(R, np.int64),
             "free": np.asarray(st[:, :C], np.int32).copy(),
             "comp": np.asarray(comp),
             "W": np.asarray(W),
             "t_prev": np.asarray(t_prev),
             "t_hol": np.asarray(t_hol)}
    for r in range(R):
        locs = []
        for c in range(C):
            hd, tl = int(st[r, C + c]), int(st[r, 2 * C + c])
            if tl > hd:
                pos = c * q_cap + (hd + np.arange(tl - hd)) % q_cap
                locs.append(ring[r, pos])
        if not locs:
            continue
        loc = np.concatenate(locs).astype(np.int64)
        gid = idmap[r, loc]
        order = np.argsort(gid)
        loc, gid = loc[order], gid[order]
        if loc.size > B:
            raise RuntimeError(
                f"streaming backlog overflow: replication {r} has "
                f"{loc.size} jobs still queued at a chunk boundary but "
                f"backlog_cap={B} — raise backlog_cap, or the workload "
                f"is unstable at this load")
        p = loc.size
        canon["pend_gid"][r, :p] = gid
        canon["pend_arr"][r, :p] = arr_l[r, loc]
        canon["pend_svc"][r, :p] = svc_l[r, loc]
        canon["pend_cls"][r, :p] = cl_l[r, loc]
        canon["pend_need"][r, :p] = nd_l[r, loc]
        canon["pend_n"][r] = p
    return canon


def _bs_stream_drive(source, *, policy, chunk_jobs, total_jobs, part, slots,
                     s_max, h, q_cap, B, scan_fn, block=4096,
                     ckpt_dir=None, resume=False):
    """Drive BS-FCFS over a chunk source with a one-chunk lookahead.

    Each chunk scans with ``horizon`` = the next chunk's first arrival
    (events at or past it defer to the next chunk's scan, which replays
    them first — see ``sim_jax._bs_stream_make_step``), runs ``length =
    2*Jc + B + C*s_max`` steps (arrivals + chunk-job second events +
    pending second events + in-flight A completions: every event that can
    legally fall before the horizon), and hands the carry to
    ``_bs_extract``.  The last chunk runs with horizon = inf, so its scan
    *is* the drain — afterwards every lane must have processed exactly
    two events per fed job.  ``scan_fn(carry, rec, horizon, length)`` is
    the engine-specific jitted chunk call.
    """
    R = int(source.reps)
    C = int(slots.shape[0])
    total = int(total_jobs)
    chunk_jobs = int(chunk_jobs)
    layout = {"policy": policy, "chunk_jobs": chunk_jobs,
              "total_jobs": total, "reps": R, "k": int(source.k),
              "block": int(block), "C": C, "s_max": int(s_max),
              "h": int(h), "q_cap": int(q_cap), "backlog_cap": int(B)}
    ck = _StreamCkpt(ckpt_dir, layout)
    acc = StreamAccumulator(R, block=block)
    win = _StreamWindow(R, B + 2 * chunk_jobs)
    canon = _bs_canon0(R, C, s_max, h, B, slots)
    src_state = source.init_state()
    fed = 0
    step = 0
    done = np.zeros(R, np.int64)
    if resume:
        like = {"sim": {**{key: np.zeros(0) for key in canon},
                        "fed": np.zeros((), np.int64),
                        "done": np.zeros(0, np.int64)},
                "acc": acc.state(), "src": src_state, "win": win.state()}
        got = ck.restore(like, f"of stream {policy!r}")
        if got is not None:
            tree, step = got
            fed = int(tree["sim"]["fed"])
            done = np.asarray(tree["sim"]["done"], np.int64).copy()
            canon = {key: tree["sim"][key] for key in canon}
            acc.load_state(tree["acc"])
            src_state = tree["src"]
            win.load_state(tree["win"])
    pending = None             # pre-fetched (chunk, post-fetch src state)
    while fed < total:
        n = min(chunk_jobs, total - fed)
        if pending is None:
            cur, src_after = _fetch_chunk(source, src_state, n, total)
        else:
            cur, src_after = pending
            pending = None
        rem = total - fed - n
        if rem > 0:
            pending = _fetch_chunk(source, src_after,
                                   min(chunk_jobs, rem), total)
            horizon = pending[0].arrival[:, 0].copy()
        else:
            horizon = np.full(R, np.inf)
        engines.validate_batch(cur, partition=part)
        if h < int(cur.need.max()):
            raise ValueError("helper set smaller than the largest "
                             "server need")
        win.extend(fed, cur)
        carry, rec, idmap = _bs_inflate(canon, cur, fed, slots, s_max, h,
                                        q_cap, B)
        J_l = B + n
        length = 2 * n + B + C * s_max
        carry, tagged, rec_t = scan_fn(carry, rec, horizon, length)
        _bs_check_ring(carry[8], q_cap)
        if not np.all(carry[0] == J_l):
            raise RuntimeError("internal error: chunk scan left arrivals "
                               "unprocessed")
        done += np.asarray(carry[9], np.int64)
        win.scatter(tagged, rec_t, idmap, J_l)
        fed += n
        win.fold_into(acc)
        canon = _bs_extract(carry, idmap, rec, B, C, q_cap)
        step += 1
        src_state = src_after
        ck.save(step, {"sim": {**canon, "fed": np.asarray(fed, np.int64),
                               "done": done.copy()},
                       "acc": acc.state(), "src": src_state,
                       "win": win.state()})
    if not np.all(done == 2 * total):
        raise RuntimeError("internal error: stream ended with unprocessed "
                           "events")
    return _stream_result(acc, total, True)


# -- engine="jax" stream cores ----------------------------------------------


def _stream_partition(partition, wl) -> BalancedPartition:
    if partition is None:
        if wl is None:
            raise ValueError("need a partition or a workload")
        partition = balanced_partition(wl)
    return partition


def _fcfs_stream_init(R: int, *, k: int):
    return (jnp.zeros((R, k), jnp.float64), jnp.zeros(R, jnp.float64))


def _fcfs_chunk_jax(carry, batch):
    with enable_x64():
        carry, starts = _call(_fcfs_stream_chunk, carry,
                              *_fcfs_inputs(batch))
    starts = np.asarray(starts)
    return (carry, starts + batch.service - batch.arrival,
            starts - batch.arrival, None, None)


@engines.register_stream("fcfs", "jax")
def _fcfs_stream_jax(source, *, chunk_jobs, total_jobs, partition=None,
                     wl=None, policy="fcfs", block=4096, ckpt_dir=None,
                     resume=False):
    """Streaming FCFS: the Kiefer–Wolfowitz carry rides across chunks."""
    return _scan_stream(
        source, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        n_carry=2, init_fn=partial(_fcfs_stream_init, k=int(source.k)),
        chunk_fn=_fcfs_chunk_jax, has_helper=False, block=block,
        ckpt_dir=ckpt_dir, resume=resume)


def _modbs_stream_init(R: int, *, slots, s_max: int, h: int):
    # bit-matches vmap-of-_modbs_init: the per-lane carry is identical
    pad = jnp.arange(s_max)[None, :] >= jnp.asarray(slots)[:, None]
    comp0 = jnp.where(pad, _BIG, 0.0).astype(jnp.float64)
    return (jnp.broadcast_to(comp0[None], (R,) + comp0.shape),
            jnp.zeros((R, h), jnp.float64), jnp.zeros(R, jnp.float64))


def _modbs_chunk_jax(carry, batch, *, s_max: int, h: int):
    if h < int(batch.need.max()):
        raise ValueError("helper set smaller than the largest server need")
    with enable_x64():
        carry, (blocked, starts) = _call(_modbs_stream_chunk, carry,
                                         *_class_inputs(batch), s_max)
    blocked = np.asarray(blocked)
    starts = np.asarray(starts)
    return (carry, starts + batch.service - batch.arrival,
            starts - batch.arrival, blocked, blocked)


@engines.register_stream("modbs-fcfs", "jax")
def _modbs_stream_jax(source, *, chunk_jobs, total_jobs, partition=None,
                      wl=None, policy="modbs-fcfs", block=4096,
                      ckpt_dir=None, resume=False):
    """Streaming ModifiedBS-FCFS: (comp, W, t_prev) rides across chunks."""
    part = _stream_partition(partition, wl)
    slots = np.asarray(part.slots, np.int32)
    s_max = int(slots.max())
    h = int(part.helpers)
    return _scan_stream(
        source, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        n_carry=3,
        init_fn=partial(_modbs_stream_init, slots=slots, s_max=s_max, h=h),
        chunk_fn=partial(_modbs_chunk_jax, s_max=s_max, h=h),
        has_helper=True, part=part, block=block, ckpt_dir=ckpt_dir,
        resume=resume,
        layout_extra={"C": int(slots.shape[0]), "s_max": s_max, "h": h})


#: dtypes of the BS stream carry (ai, st, comp, ring, heads, W, t_prev,
#: t_hol, ring peak, ne) — the host keeps the carry as numpy for extract /
#: checkpoint; chunk calls re-device it with these.
_BS_CARRY_DTYPES = (jnp.int32, jnp.int32, jnp.float64, jnp.int32,
                    jnp.int32, jnp.float64, jnp.float64, jnp.float64,
                    jnp.int32, jnp.int32)


def _bs_chunk_scan_jax(C: int, s_max: int, h: int, q_cap: int):
    def scan(carry, rec, horizon, length):
        arr, cl, nd, svc = rec
        with enable_x64():
            dev = tuple(jnp.asarray(c, d)
                        for c, d in zip(carry, _BS_CARRY_DTYPES))
            out, tagged, rec_t = _call(
                _bs_stream_chunk, dev,
                _dev(arr, jnp.float64), _dev(cl, jnp.int32),
                _dev(nd, jnp.int32), _dev(svc, jnp.float64),
                _dev(horizon, jnp.float64), C, s_max, h, q_cap, length)
        return ([np.asarray(x) for x in out], np.asarray(tagged),
                np.asarray(rec_t))
    return scan


def _bs_stream_args(partition, wl, chunk_jobs, queue_cap, backlog_cap):
    """(part, slots, s_max, h, q_cap, B) of a BS stream, validated.

    ``queue_cap`` defaults to ``backlog_cap + chunk_jobs`` — the within-
    chunk queue occupancy (carried backlog + every chunk arrival) can
    never exceed it, so the default never overflows.
    """
    part = _stream_partition(partition, wl)
    slots = np.asarray(part.slots, np.int32)
    s_max = max(1, int(slots.max()))
    h = int(part.helpers)
    B = int(backlog_cap)
    if B < 1:
        raise ValueError(f"backlog_cap must be >= 1, got {backlog_cap}")
    if queue_cap is None:
        q_cap = B + int(chunk_jobs)
    elif queue_cap < 1:
        raise ValueError(f"queue_cap must be >= 1, got {queue_cap}")
    else:
        q_cap = int(queue_cap)
    return part, slots, s_max, h, q_cap, B


@engines.register_stream("bs-fcfs", "jax")
def _bs_stream_jax(source, *, chunk_jobs, total_jobs, partition=None,
                   wl=None, policy="bs-fcfs", queue_cap=None,
                   backlog_cap=1024, block=4096, ckpt_dir=None,
                   resume=False):
    """Streaming BS-FCFS (Definition 1) via the bounded-backlog driver.

    ``backlog_cap`` bounds how many still-queued jobs may cross a chunk
    boundary (exceeding it raises — raise the cap, or the workload is
    unstable); ``queue_cap`` defaults to ``backlog_cap + chunk_jobs``,
    which the within-chunk queue occupancy can never exceed.
    """
    part, slots, s_max, h, q_cap, B = _bs_stream_args(
        partition, wl, chunk_jobs, queue_cap, backlog_cap)
    return _bs_stream_drive(
        source, policy=policy, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
        part=part, slots=slots, s_max=s_max, h=h, q_cap=q_cap, B=B,
        scan_fn=_bs_chunk_scan_jax(int(slots.shape[0]), s_max, h, q_cap),
        block=block, ckpt_dir=ckpt_dir, resume=resume)


def _slice_stream_result(sr: StreamResult, R: int) -> StreamResult:
    """Drop padded replication lanes from a StreamResult (jax-shard)."""
    if sr.reps == R:
        return sr
    opt = lambda a: None if a is None else a[:R]
    return dataclasses.replace(
        sr, reps=R, mean_response=sr.mean_response[:R],
        var_response=sr.var_response[:R], mean_wait=sr.mean_wait[:R],
        var_wait=sr.var_wait[:R], p_wait=sr.p_wait[:R],
        p_helper=opt(sr.p_helper), p_routed=opt(sr.p_routed))
