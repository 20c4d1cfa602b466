"""Fused Pallas step kernels for the preemptive SRPT-family scans.

The sf-srpt / ff-srpt event step is sort-bound: the reference step
(:func:`repro.core.sim_jax._srpt_make_step`) stable-sorts the [R, Q] slot
table twice per event, and ``jax.lax.sort`` is exactly the kind of opaque
library call a fused kernel body cannot contain.  These kernels run the
*same reference step* — bit-exactness by construction, the contract of
every kernel in this package — with the sort swapped for the in-kernel
stable bitonic rank/permute network of :mod:`.sort`, which is built from
plain compare-exchange ``where``/``reshape`` stages and therefore traces
inside a Pallas kernel body.  Rank computation, the bitonic permute, the
NU-phase first-fit walk (``_srpt_first_fit`` — a statically unrolled
len(NU)-round walk, no data-dependent trip count), the inverse-scatter
``unsort`` and the occupancy update all live in one kernel per grid cell,
so the slot table never round-trips through HBM-resolution XLA ops between
sub-steps.

Grid layout matches the other kernels: one replication per Pallas grid
cell, the whole 2J-event loop as an in-kernel ``fori_loop``, interpret
mode on the CPU only (see ``ops.py``).  ``Q`` must be a power of two — guaranteed
by ``_srpt_args``, which rounds the slot-table capacity up (the bitonic
network and the fast path's slot-index pack keys both need it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sim_jax import _srpt_init, _srpt_make_step

from .sort import bitonic_sort

_row2 = lambda r: (r, 0)


def _srpt_kernel(a_ref, n_ref, v_ref, k_ref, job_ref, t_ref, fs_ref,
                 ovf_ref, npre_ref, ne_ref, peak_ref, *, Q: int, NU: tuple,
                 sf: bool):
    # one replication per grid cell: run the reference step with R = 1 and
    # the bitonic network as its stable sort
    arrival = a_ref[0, :][None]
    need = n_ref[0, :][None]
    service = v_ref[0, :][None]
    kk = k_ref[:]
    dt = arrival.dtype
    J = arrival.shape[1]
    jobrec = jnp.stack([arrival, service, need], axis=2)   # [1, J, 3]
    step = _srpt_make_step(jobrec, kk, Q, NU, sf, sort=bitonic_sort)
    carry0 = _srpt_init(1, Q, dt)

    def body(e, state):
        carry, job_ev, t_ev, fs_ev = state
        carry, (jo, to, fo) = step(carry, None)
        return (carry, job_ev.at[e].set(jo[0]), t_ev.at[e].set(to[0]),
                fs_ev.at[e].set(fo[0]))

    carry, job_ev, t_ev, fs_ev = jax.lax.fori_loop(
        0, 2 * J, body,
        (carry0, jnp.full(2 * J, -1.0, dt), jnp.zeros(2 * J, dt),
         jnp.zeros(2 * J, dt)))
    job_ref[0, :] = job_ev
    t_ref[0, :] = t_ev
    fs_ref[0, :] = fs_ev
    ovf_ref[0] = carry[2][0]
    npre_ref[0] = carry[3][0]
    ne_ref[0] = carry[4][0]
    peak_ref[0] = carry[5][0]


@functools.partial(jax.jit, static_argnames=("Q", "NU", "sf", "interpret"))
def srpt_scan_fwd(arrival, need, service, kk, *, Q: int, NU: tuple,
                  sf: bool, interpret: bool = False):
    """[R, J] trace arrays + kk [R] -> SRPT event streams and counters.

    Returns (job_ev, t_ev, fs_ev) [R, 2J] — the raw departure-event
    streams of ``sim_jax._srpt_core`` (-1 job ids mark non-departure
    steps) — plus the per-lane (ovf, npre, ne, peak) counters.
    """
    R, J = arrival.shape
    dt = arrival.dtype
    lane = pl.BlockSpec((1,), lambda r: (r,))
    return pl.pallas_call(
        functools.partial(_srpt_kernel, Q=Q, NU=NU, sf=sf),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, J), _row2)] * 3 + [lane],
        out_specs=(pl.BlockSpec((1, 2 * J), _row2),) * 3 + (lane,) * 4,
        out_shape=(jax.ShapeDtypeStruct((R, 2 * J), dt),) * 3
        + (jax.ShapeDtypeStruct((R,), jnp.bool_),
           jax.ShapeDtypeStruct((R,), jnp.int32),
           jax.ShapeDtypeStruct((R,), jnp.int32),
           jax.ShapeDtypeStruct((R,), jnp.int32)),
        interpret=interpret,
    )(arrival, need, service, kk)
