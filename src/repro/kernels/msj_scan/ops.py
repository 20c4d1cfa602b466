"""Public wrappers for the fused event-scan kernels.

The kernels run only in interpret mode on the CPU backend: interpret mode
scans the grid one replication at a time with the kernel body executed as
ordinary XLA ops, so it fuses nothing — it exists for bit-level
cross-validation and the ``engine="pallas"`` benchmark rows, not speed.
They do not lower for the TPU yet: their ``(1, J)`` blocks break Mosaic's
(8, 128) tiling rule and their state is f64 (see ``kernel.py``).  So every
other backend is refused at dispatch, before anything is compiled.

This module also registers the kernels as the ``engine="pallas"`` cores of
the :mod:`repro.core.engines` registry — the cores reuse the input-prep and
result-assembly helpers of :mod:`repro.core.sim_batch`, so pallas results
are bit-identical to the scan cores by construction everywhere outside the
kernel bodies (and the bodies execute the same hoisted step functions; see
``tests/test_sim_cross.py``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import engines
from repro.core import failures as flr
from repro.core.partition import balanced_partition
from repro.core.sim_batch import (_bs_fail_args, _bs_result, _call,
                                  _class_inputs, _fcfs_inputs, _fcfs_result,
                                  _fetch, _merged_fcfs_inputs,
                                  _modbs_result, _partition_args, _puts,
                                  _srpt_inputs, _srpt_no_failures, _srpt_nu,
                                  _srpt_result, _with_drain_obs)
from repro.core.sim_jax import _bs_args, _srpt_args

from .kernel import (bs_fail_scan_fwd, bs_scan_fwd, fcfs_fail_scan_fwd,
                     fcfs_scan_fwd, modbs_fail_scan_fwd, modbs_scan_fwd)
from .srpt import srpt_scan_fwd


def _interpret() -> bool:
    """True on the CPU backend; any other backend is refused."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise NotImplementedError(
            f'engine="pallas" runs only in interpret mode on the CPU '
            f'backend, not on {backend!r}: the kernels do not lower for the '
            f'TPU yet (ROADMAP.md S7/D3). Use engine="jax" there.')
    return True


def fcfs_scan(arrival, need, service, *, k: int):
    """Fused FCFS Kiefer–Wolfowitz scan: [R, J] arrays -> starts [R, J]."""
    return fcfs_scan_fwd(arrival, need, service, k=k,
                         interpret=_interpret())


def modbs_scan(arrival, cls, need, service, *, slots, s_max: int, h: int):
    """Fused ModifiedBS-π scan -> (blocked [R, J], starts [R, J])."""
    return modbs_scan_fwd(arrival, cls, need, service,
                          jnp.asarray(slots, jnp.int32),
                          s_max=s_max, h=h, interpret=_interpret())


def bs_scan(arrival, cls, need, service, *, slots, s_max: int, h: int,
            q_cap: int):
    """Fused BS-π (Def. 1) event scan -> (tagged, rec_t, ring peak)."""
    return bs_scan_fwd(arrival, cls, need, service,
                       jnp.asarray(slots, jnp.int32),
                       s_max=s_max, h=h, q_cap=q_cap,
                       interpret=_interpret())


def srpt_scan(arrival, need, service, kk, *, Q: int, NU: tuple, sf: bool):
    """Fused preemptive SRPT event scan (bitonic in-kernel sort) ->
    (job_ev, t_ev, fs_ev, ovf, npre, ne, peak)."""
    return srpt_scan_fwd(arrival, need, service, kk, Q=Q, NU=NU, sf=sf,
                         interpret=_interpret())


# -- engine="pallas" registry cores -----------------------------------------
#
# The failure branches mirror the engine="jax" drain flows exactly (host-side
# merge of the failure stream, fused-kernel scan, unmerge via
# ``MergedStream.job_pos``) — only the scan call differs, so drain results
# are bit-identical to engine="jax" by construction outside the kernel body.


@engines.register("fcfs", "pallas")
def _fcfs_pallas(batch, *, partition=None, wl=None, failures=None):
    """Fused-kernel FCFS core (replications axis = Pallas grid)."""
    if failures is None:
        with enable_x64():
            a, n, v = _fcfs_inputs(batch)
            starts = _fetch(_call(
                lambda a, n, v: fcfs_scan(a, n, v, k=batch.k), a, n, v))
        return _fcfs_result(batch, starts)
    flr.require_drain(failures, "pallas")
    ms = _merged_fcfs_inputs(batch, failures)
    with enable_x64():
        starts_m = _fetch(_call(
            lambda t, n, v, tu, isf: fcfs_fail_scan_fwd(
                t, n, v, tu, isf, k=batch.k, interpret=_interpret()),
            *_puts((ms.t, jnp.float64), (ms.need, jnp.int32),
                   (ms.service, jnp.float64), (ms.t_up, jnp.float64),
                   (ms.is_fail != 0, jnp.bool_))))
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    return _with_drain_obs(_fcfs_result(batch, starts), batch, failures)


@engines.register("modbs-fcfs", "pallas")
def _modbs_pallas(batch, *, partition=None, wl=None, failures=None):
    """Fused-kernel ModifiedBS-FCFS core."""
    slots, s_max, h = _partition_args(batch, partition, wl)
    if failures is None:
        with enable_x64():
            blocked, starts = _fetch(_call(
                lambda a, c, n, v: modbs_scan(a, c, n, v, slots=slots,
                                              s_max=s_max, h=h),
                *_class_inputs(batch)))
        return _modbs_result(batch, blocked, starts)
    flr.require_drain(failures, "pallas")
    part = partition if partition is not None else balanced_partition(wl)
    ft, ftgt, fup, count = flr.partition_targets(failures, part)
    ms = flr.merge_failure_stream(batch, ft, ftgt, fup, count,
                                  pad_cls=len(part.a))
    with enable_x64():
        blocked_m, starts_m = _fetch(_call(
            lambda t, c, n, v, tu, isf: modbs_fail_scan_fwd(
                t, c, n, v, tu, isf, jnp.asarray(slots, jnp.int32),
                s_max=s_max, h=h, interpret=_interpret()),
            *_puts((ms.t, jnp.float64), (ms.cls, jnp.int32),
                   (ms.need, jnp.int32), (ms.service, jnp.float64),
                   (ms.t_up, jnp.float64), (ms.is_fail != 0, jnp.bool_))))
    starts = np.take_along_axis(starts_m, ms.job_pos, axis=1)
    blocked = np.take_along_axis(blocked_m, ms.job_pos, axis=1)
    return _with_drain_obs(_modbs_result(batch, blocked, starts), batch,
                           failures)


@engines.register("bs-fcfs", "pallas")
def _bs_pallas(batch, *, partition=None, wl=None, queue_cap=None,
               failures=None):
    """Fused-kernel BS-FCFS (Definition 1) event-step core."""
    slots, s_max, h, q_cap = _bs_args(batch, partition, wl, queue_cap)
    if failures is None:
        with enable_x64():
            tagged, rec_t, rpk = _fetch(_call(
                lambda a, c, n, v: bs_scan(a, c, n, v, slots=slots,
                                           s_max=s_max, h=h, q_cap=q_cap),
                *_class_inputs(batch)))
        return _bs_result(batch, tagged, rec_t, rpk, q_cap)
    flr.require_drain(failures, "pallas")
    ft, ftgt, fup, length = _bs_fail_args(batch, failures, partition, wl)
    with enable_x64():
        tagged, rec_t, rpk = _fetch(_call(
            lambda a, c, n, v, t1, t2, t3: bs_fail_scan_fwd(
                a, c, n, v, t1, t2, t3, jnp.asarray(slots, jnp.int32),
                s_max=s_max, h=h, q_cap=q_cap, length=length,
                interpret=_interpret()),
            *_class_inputs(batch),
            *_puts((ft, jnp.float64), (ftgt, jnp.int32),
                   (fup, jnp.float64))))
    return _with_drain_obs(_bs_result(batch, tagged, rec_t, rpk, q_cap),
                           batch, failures)


def _srpt_pallas(sf: bool, batch, *, partition=None, wl=None,
                 queue_cap=None, failures=None):
    policy = "sf-srpt" if sf else "ff-srpt"
    _srpt_no_failures(failures, policy)
    q_cap = _srpt_args(batch, queue_cap)
    NU = _srpt_nu(batch)
    with enable_x64():
        job_ev, t_ev, fs_ev, ovf, npre, ne, peak = _fetch(_call(
            lambda a, n, v, k: srpt_scan(a, n, v, k, Q=q_cap, NU=NU, sf=sf),
            *_srpt_inputs(batch)))
    return _srpt_result(batch, job_ev, t_ev, fs_ev, ovf, npre, ne, q_cap,
                        peak=peak)


@engines.register("sf-srpt", "pallas")
def _sf_srpt_pallas(batch, **kw):
    """Fused-kernel ServerFilling-SRPT core: the reference event step with
    the in-kernel stable bitonic rank/permute of ``sort.bitonic_sort`` —
    bit-identical to every other sf-srpt engine, ``preemptions`` included."""
    return _srpt_pallas(True, batch, **kw)


@engines.register("ff-srpt", "pallas")
def _ff_srpt_pallas(batch, **kw):
    """Fused-kernel FirstFit-SRPT core (see ``_sf_srpt_pallas``)."""
    return _srpt_pallas(False, batch, **kw)
