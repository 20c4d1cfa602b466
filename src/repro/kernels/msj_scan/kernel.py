"""Fused Pallas step kernels for the multiserver-job event scans.

The ``lax.scan`` cores of :mod:`repro.core.sim_jax` are dispatch-bound on
XLA:CPU: the BS-π event step alone is ~19 gather/scatter ops that XLA stops
fusing, so every event pays fixed per-op dispatch.  These kernels fuse each
per-event step body into a single Pallas kernel with the **replications axis
as the grid dimension** — grid cell r simulates replication r end-to-end,
with the whole scan state (the sorted Kiefer–Wolfowitz free-time vector W,
the ModBS per-class completion matrix, the BS-π ring buffers / outstanding
A-completion matrix / counters) living in the kernel's ``fori_loop`` carry
instead of round-tripping through ~19 dispatched XLA ops per event.

Bit-exactness by construction: the kernels do not re-implement the queueing
steps — they import and run the *same* module-level step functions the scan
cores use (``_fcfs_sorted_step``, ``_modbs_step``, ``_bs_make_step`` with
R = 1), so interpret mode executes the identical op sequence and the outputs
are pinned rtol=0 against the jax-batch engines in
``tests/test_sim_cross.py``.

Execution modes: ``interpret=True`` (the CPU/CI path — the grid is scanned
by the Pallas interpreter, one replication at a time, so it fuses nothing on
CPU and exists for correctness + the CPU benchmark rows).  ``interpret=False``
does not lower for the TPU: the ``(1, J)`` blocks break Mosaic's (8, 128)
tiling rule and the state is f64.  A TPU path needs f32 state, replications
on the lane axis and J streamed through VMEM (ROADMAP S7) — ``ops.py``
refuses every backend other than the CPU until then.

Inputs are [R, J] trace arrays plus the eq.-2 partition's ``slots`` vector
([C], replicated to every grid cell — Pallas kernels cannot capture array
constants); ``s_max``/``h``/``q_cap`` are static, matching the
one-compile-per-partition-shape behavior of the scan cores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sim_jax import (_bs_fail_make_step, _bs_init, _bs_make_step,
                                _fcfs_fail_step, _fcfs_sorted_step,
                                _modbs_fail_step, _modbs_init, _modbs_step)

_row2 = lambda r: (r, 0)


# --------------------------------------------------------------------------
# FCFS — O(k) sorted roll-and-insert Kiefer–Wolfowitz step.
# --------------------------------------------------------------------------


def _fcfs_kernel(a_ref, n_ref, s_ref, out_ref, *, k: int):
    arrival = a_ref[0, :]
    need = n_ref[0, :]
    service = s_ref[0, :]

    def body(j, carry):
        W, t_prev, starts = carry
        W, start = _fcfs_sorted_step(W, t_prev, arrival[j], need[j],
                                     service[j])
        return W, start, starts.at[j].set(start)

    _, _, starts = jax.lax.fori_loop(
        0, arrival.shape[0], body,
        (jnp.zeros(k, arrival.dtype), jnp.zeros((), arrival.dtype),
         jnp.zeros_like(arrival)))
    out_ref[0, :] = starts


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fcfs_scan_fwd(arrival, need, service, *, k: int,
                  interpret: bool = False):
    """arrival/need/service: [R, J] -> start times [R, J]."""
    R, J = arrival.shape
    return pl.pallas_call(
        functools.partial(_fcfs_kernel, k=k),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, J), _row2)] * 3,
        out_specs=pl.BlockSpec((1, J), _row2),
        out_shape=jax.ShapeDtypeStruct((R, J), arrival.dtype),
        interpret=interpret,
    )(arrival, need, service)


def _fcfs_fail_kernel(t_ref, n_ref, s_ref, tu_ref, if_ref, out_ref, *,
                      k: int):
    # the merged arrival+failure stream of the drain-mode scan core — runs
    # the same hoisted _fcfs_fail_step, rows with is_fail drain W
    t = t_ref[0, :]
    n = n_ref[0, :]
    svc = s_ref[0, :]
    tu = tu_ref[0, :]
    isf = if_ref[0, :]

    def body(j, state):
        W, t_prev, starts = state
        (W, t_prev), start = _fcfs_fail_step(
            (W, t_prev), (t[j], n[j], svc[j], tu[j], isf[j]))
        return W, t_prev, starts.at[j].set(start)

    _, _, starts = jax.lax.fori_loop(
        0, t.shape[0], body,
        (jnp.zeros(k, t.dtype), jnp.zeros((), t.dtype), jnp.zeros_like(t)))
    out_ref[0, :] = starts


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fcfs_fail_scan_fwd(t, n, svc, t_up, is_fail, *, k: int,
                       interpret: bool = False):
    """Merged [R, L] arrival+failure stream -> start times [R, L].

    Start outputs of failure rows are garbage; the host gathers arrival
    positions via ``MergedStream.job_pos`` (same contract as
    ``sim_jax._fcfs_fail_core``).
    """
    R, L = t.shape
    return pl.pallas_call(
        functools.partial(_fcfs_fail_kernel, k=k),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, L), _row2)] * 5,
        out_specs=pl.BlockSpec((1, L), _row2),
        out_shape=jax.ShapeDtypeStruct((R, L), t.dtype),
        interpret=interpret,
    )(t, n, svc, t_up, is_fail)


# --------------------------------------------------------------------------
# ModifiedBS-π (Definition 2) — per-class loss queues + helper FCFS.
# --------------------------------------------------------------------------


def _modbs_kernel(a_ref, c_ref, n_ref, s_ref, sl_ref, blk_ref, out_ref, *,
                  s_max: int, h: int):
    arrival = a_ref[0, :]
    cls = c_ref[0, :]
    need = n_ref[0, :]
    service = s_ref[0, :]
    dt = arrival.dtype
    carry0 = _modbs_init(sl_ref[:], s_max, h, dt)

    def body(j, state):
        carry, blocked, starts = state
        carry, (b, s) = _modbs_step(
            carry, (arrival[j], cls[j], need[j], service[j]), s_max=s_max)
        return carry, blocked.at[j].set(b), starts.at[j].set(s)

    J = arrival.shape[0]
    _, blocked, starts = jax.lax.fori_loop(
        0, J, body, (carry0, jnp.zeros(J, bool), jnp.zeros(J, dt)))
    blk_ref[0, :] = blocked
    out_ref[0, :] = starts


@functools.partial(jax.jit, static_argnames=("s_max", "h", "interpret"))
def modbs_scan_fwd(arrival, cls, need, service, slots, *, s_max: int,
                   h: int, interpret: bool = False):
    """[R, J] trace arrays + slots [C] -> (blocked [R, J], starts [R, J])."""
    R, J = arrival.shape
    C = slots.shape[0]
    return pl.pallas_call(
        functools.partial(_modbs_kernel, s_max=s_max, h=h),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, J), _row2)] * 4
        + [pl.BlockSpec((C,), lambda r: (0,))],
        out_specs=(pl.BlockSpec((1, J), _row2), pl.BlockSpec((1, J), _row2)),
        out_shape=(jax.ShapeDtypeStruct((R, J), jnp.bool_),
                   jax.ShapeDtypeStruct((R, J), arrival.dtype)),
        interpret=interpret,
    )(arrival, cls, need, service, slots)


def _modbs_fail_kernel(t_ref, c_ref, n_ref, s_ref, tu_ref, if_ref, sl_ref,
                       blk_ref, out_ref, *, s_max: int, h: int):
    t = t_ref[0, :]
    cls = c_ref[0, :]
    n = n_ref[0, :]
    svc = s_ref[0, :]
    tu = tu_ref[0, :]
    isf = if_ref[0, :]
    dt = t.dtype
    C = sl_ref.shape[0]
    carry0 = _modbs_init(sl_ref[:], s_max, h, dt)

    def body(j, state):
        carry, blocked, starts = state
        carry, (b, s) = _modbs_fail_step(
            carry, (t[j], cls[j], n[j], svc[j], tu[j], isf[j]),
            s_max=s_max, C=C)
        return carry, blocked.at[j].set(b), starts.at[j].set(s)

    L = t.shape[0]
    _, blocked, starts = jax.lax.fori_loop(
        0, L, body, (carry0, jnp.zeros(L, bool), jnp.zeros(L, dt)))
    blk_ref[0, :] = blocked
    out_ref[0, :] = starts


@functools.partial(jax.jit, static_argnames=("s_max", "h", "interpret"))
def modbs_fail_scan_fwd(t, cls, need, svc, t_up, is_fail, slots, *,
                        s_max: int, h: int, interpret: bool = False):
    """Merged [R, L] stream + slots [C] -> (blocked [R, L], starts [R, L]).

    Failure rows target the class column (``cls == C`` drains the helper
    W) — identical contract to ``sim_jax._modbs_fail_core``.
    """
    R, L = t.shape
    C = slots.shape[0]
    return pl.pallas_call(
        functools.partial(_modbs_fail_kernel, s_max=s_max, h=h),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, L), _row2)] * 6
        + [pl.BlockSpec((C,), lambda r: (0,))],
        out_specs=(pl.BlockSpec((1, L), _row2), pl.BlockSpec((1, L), _row2)),
        out_shape=(jax.ShapeDtypeStruct((R, L), jnp.bool_),
                   jax.ShapeDtypeStruct((R, L), t.dtype)),
        interpret=interpret,
    )(t, cls, need, svc, t_up, is_fail, slots)


# --------------------------------------------------------------------------
# BS-π proper (Definition 1, rule-3 pull-backs) — 2J-step event scan.
# --------------------------------------------------------------------------


def _bs_kernel(a_ref, c_ref, n_ref, s_ref, sl_ref, tag_ref, rect_ref,
               ovf_ref, *, s_max: int, h: int, q_cap: int):
    # one replication per grid cell: run the batched step with R = 1
    arrival = a_ref[0, :][None]
    cls = c_ref[0, :][None]
    need = n_ref[0, :][None]
    service = s_ref[0, :][None]
    dt = arrival.dtype
    J = arrival.shape[1]
    C = sl_ref.shape[0]
    jobrec = jnp.stack([arrival, service, cls.astype(dt), need.astype(dt)],
                       axis=2)                            # [1, J, 4]
    step = _bs_make_step(jobrec, C, s_max, h, q_cap)
    carry0 = _bs_init(1, J, C, s_max, h, q_cap, sl_ref[:], dt)

    def body(e, state):
        carry, tagged, rec_t = state
        carry, (tg, rt) = step(carry, None)
        return carry, tagged.at[e].set(tg[0]), rec_t.at[e].set(rt[0])

    carry, tagged, rec_t = jax.lax.fori_loop(
        0, 2 * J, body,
        (carry0, jnp.zeros(2 * J, jnp.int32), jnp.zeros(2 * J, dt)))
    tag_ref[0, :] = tagged
    rect_ref[0, :] = rec_t
    ovf_ref[0] = carry[-1][0]                             # ring overflow


@functools.partial(
    jax.jit, static_argnames=("s_max", "h", "q_cap", "interpret"))
def bs_scan_fwd(arrival, cls, need, service, slots, *, s_max: int,
                h: int, q_cap: int, interpret: bool = False):
    """[R, J] trace arrays -> (tagged [R, 2J] i32, rec_t [R, 2J], ovf [R]).

    Same raw event-stream encoding as ``sim_jax._bs_core``: tagged j = job
    j started in its A_i, j + J = routed to H on arrival, j + 2J = helper
    commit, -1 = non-recording event.
    """
    R, J = arrival.shape
    C = slots.shape[0]
    return pl.pallas_call(
        functools.partial(_bs_kernel, s_max=s_max, h=h, q_cap=q_cap),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, J), _row2)] * 4
        + [pl.BlockSpec((C,), lambda r: (0,))],
        out_specs=(pl.BlockSpec((1, 2 * J), _row2),
                   pl.BlockSpec((1, 2 * J), _row2),
                   pl.BlockSpec((1,), lambda r: (r,))),
        out_shape=(jax.ShapeDtypeStruct((R, 2 * J), jnp.int32),
                   jax.ShapeDtypeStruct((R, 2 * J), arrival.dtype),
                   jax.ShapeDtypeStruct((R,), jnp.bool_)),
        interpret=interpret,
    )(arrival, cls, need, service, slots)


def _bs_fail_kernel(a_ref, c_ref, n_ref, s_ref, ft_ref, ftgt_ref, fup_ref,
                    sl_ref, tag_ref, rect_ref, ovf_ref, *, s_max: int,
                    h: int, q_cap: int, length: int):
    arrival = a_ref[0, :][None]
    cls = c_ref[0, :][None]
    need = n_ref[0, :][None]
    service = s_ref[0, :][None]
    dt = arrival.dtype
    J = arrival.shape[1]
    C = sl_ref.shape[0]
    jobrec = jnp.stack([arrival, service, cls.astype(dt), need.astype(dt)],
                       axis=2)                            # [1, J, 4]
    failrec = jnp.stack([ft_ref[0, :][None],
                         ftgt_ref[0, :][None].astype(dt),
                         fup_ref[0, :][None]], axis=2)    # [1, F, 3]
    step = _bs_fail_make_step(jobrec, failrec, C, s_max, h, q_cap)
    c0 = _bs_init(1, J, C, s_max, h, q_cap, sl_ref[:], dt)
    carry0 = (c0[0], jnp.zeros(1, jnp.int32)) + c0[1:]    # + failure cursor

    def body(e, state):
        carry, tagged, rec_t = state
        carry, (tg, rt) = step(carry, None)
        return carry, tagged.at[e].set(tg[0]), rec_t.at[e].set(rt[0])

    carry, tagged, rec_t = jax.lax.fori_loop(
        0, length, body,
        (carry0, jnp.zeros(length, jnp.int32), jnp.zeros(length, dt)))
    tag_ref[0, :] = tagged
    rect_ref[0, :] = rec_t
    ovf_ref[0] = carry[9][0]                              # ring overflow


@functools.partial(
    jax.jit, static_argnames=("s_max", "h", "q_cap", "length", "interpret"))
def bs_fail_scan_fwd(arrival, cls, need, service, ft, ftgt, fup, slots, *,
                     s_max: int, h: int, q_cap: int, length: int,
                     interpret: bool = False):
    """Drain-mode BS-π scan: trace [R, J] + failures [R, F] -> event
    streams (tagged [R, length] i32, rec_t [R, length], ovf [R]).

    ``length`` = 2J + F + F_A, per ``sim_batch._bs_fail_args``; failure
    events win ties and claim the earliest-free capacity unit of their
    target block, exactly as ``sim_jax._bs_fail_core``.
    """
    R, J = arrival.shape
    F = ft.shape[1]
    C = slots.shape[0]
    return pl.pallas_call(
        functools.partial(_bs_fail_kernel, s_max=s_max, h=h, q_cap=q_cap,
                          length=length),
        grid=(R,),
        in_specs=[pl.BlockSpec((1, J), _row2)] * 4
        + [pl.BlockSpec((1, F), _row2)] * 3
        + [pl.BlockSpec((C,), lambda r: (0,))],
        out_specs=(pl.BlockSpec((1, length), _row2),
                   pl.BlockSpec((1, length), _row2),
                   pl.BlockSpec((1,), lambda r: (r,))),
        out_shape=(jax.ShapeDtypeStruct((R, length), jnp.int32),
                   jax.ShapeDtypeStruct((R, length), arrival.dtype),
                   jax.ShapeDtypeStruct((R,), jnp.bool_)),
        interpret=interpret,
    )(arrival, cls, need, service, ft, ftgt, fup, slots)
