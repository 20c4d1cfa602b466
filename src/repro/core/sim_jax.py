"""JAX (``lax.scan``) vectorized simulators — the per-trace fast path.

The event-driven reference simulator is exact but Python-speed.  For the
policies whose dynamics are *arrival-indexed* — loss queues and FCFS — the
whole simulation is expressible as a ``lax.scan`` over jobs with O(k) state,
which jit-compiles and runs millions of arrivals in seconds, and is used by
the theory-validation benchmarks (Thms 1-2 need large k and many arrivals).

Covered exactly (cross-validated event-for-event against the Python engine
in ``tests/test_sim_cross.py``):

* ``loss_queue_sim``      — M/GI/s/s (the Property-1 building block)
* ``fcfs_sim``            — multiserver-job FCFS with head-of-line blocking
* ``modified_bs_sim``     — ModifiedBS-π with π = FCFS (Definition 2)
* ``bs_sim``              — BS-π proper with π = FCFS (Definition 1)

BS-π proper (Definition 1) pulls helper jobs back at A-system *completion*
times, which breaks arrival indexing.  ``_bs_core`` therefore scans an
*event-indexed* merged stream instead: every sample path has exactly 2J
events — J arrivals plus, per job, either its A-system completion (jobs
that run in an A_i, whether routed there on arrival or pulled back by
rule 3) or its helper start ("commit", jobs that run in H).  The scan
carries per-class free-slot counts, the matrix of outstanding A-completion
times, fixed-capacity per-class helper-wait ring buffers (rule 3 pops the
class head, π = FCFS pops the global head = smallest waiting job index),
the sorted helper free-time vector W, the last helper start (in-order
clamp), and the time of the last head-of-line pull-back (a job promoted to
the head by a rule-3 pull cannot start before the pull).  Each step
processes the chronologically next event; rule 3 executes inside
A-completion events, and helper starts are evaluated lazily via the same
Kiefer–Wolfowitz W-vector recursion as the FCFS core, so helper
completions never need events of their own.

FCFS recursion (multiserver-need Kiefer–Wolfowitz):  keep the multiset W of
server free-times.  Job j with need n starts at

    T_j = max(A_j, T_{j-1}, n-th smallest of W)

(the clamp T_{j-1} enforces in-order starts = head-of-line blocking), then
the n smallest entries of W are set to T_j + S_j.  Idle servers are
interchangeable, so this multiset recursion is exact.

O(k) sorted-invariant step.  W is kept sorted ascending as a scan invariant
instead of re-sorted every arrival (O(k log k) per job).  Each of the n
retired entries satisfies W[i] <= W[n-1] <= T_j <= T_j + S_j, so removing
the n smallest and inserting n copies of comp = T_j + S_j is a roll-and-
insert:  with p = searchsorted(W, comp, 'right') - n, the new sorted vector
is  [W[n:n+p], comp * n, W[n+p:]] — a single O(k) gather.  The pre-fix
full-sort step is retained as ``_fcfs_scan_reference`` and the two paths
are cross-validated bit-for-bit in ``tests/test_sim_cross.py``.

Batch layer.  :mod:`repro.core.sim_batch` vmaps the ``*_core`` functions in
this module over a replications axis (``Workload.sample_traces``) — that is
the benchmark fast path for the Fig. 1/2 k-sweeps; the wrappers here remain
the single-trace entry points and the cross-validation anchors.

Fused-kernel layer.  The per-event step bodies (``_fcfs_sorted_step``,
``_modbs_step``, ``_bs_make_step``) are module-level functions rather than
scan closures so that :mod:`repro.kernels.msj_scan` can run the *identical*
step inside a fused Pallas kernel (one kernel launch per replication instead
of ~19 dispatched XLA ops per event).  Engine selection goes through the
registry of :mod:`repro.core.engines`: the wrappers here wrap the trace as
a one-replication batch and dispatch ``engine={"python","jax","pallas"}``
to whichever core is registered — the engines are pinned bit-for-bit
against each other in ``tests/test_sim_cross.py`` / ``tests/test_engines.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64

from . import engines
from .partition import BalancedPartition, balanced_partition
from .workload import BatchTrace, Trace, Workload

_BIG = 1e30


@dataclasses.dataclass(frozen=True)
class JaxSimResult:
    response: np.ndarray       # [J] response time per job
    p_helper: float | None     # fraction SERVED on helpers (BSF only)
    blocked: np.ndarray | None # [J] bool, loss-queue only
    p_routed: float | None = None  # fraction routed to H on arrival (BSF);
                                   # > p_helper under Def.-1 pull-backs
    start: np.ndarray | None = None  # [J] raw start times (BS-FCFS only)

    @property
    def mean_response(self) -> float:
        return float(self.response.mean())


# --------------------------------------------------------------------------
# M/GI/s/s loss queue
# --------------------------------------------------------------------------


def _loss_core(arrival, service, s: int):
    """Blocked mask of one M/GI/s/s sample path (un-jitted scan core)."""
    def step(comp, inp):
        t, svc = inp
        busy = jnp.sum(comp > t)
        blocked = busy >= s
        idx = jnp.argmin(comp)
        new_comp = comp.at[idx].set(jnp.where(blocked, comp[idx], t + svc))
        return new_comp, blocked

    comp0 = jnp.zeros(s, dtype=arrival.dtype)
    _, blocked = jax.lax.scan(step, comp0, (arrival, service))
    return blocked


_loss_scan = partial(jax.jit, static_argnames=("s",))(_loss_core)


def loss_queue_sim(arrival: np.ndarray, service: np.ndarray, s: int) -> JaxSimResult:
    """Exact M/GI/s/s sample path; returns the per-job blocked mask."""
    with enable_x64():
        blocked = np.asarray(_loss_scan(jnp.asarray(arrival, jnp.float64),
                                        jnp.asarray(service, jnp.float64), s))
    resp = np.where(blocked, 0.0, service)
    return JaxSimResult(response=resp, p_helper=None, blocked=blocked)


# --------------------------------------------------------------------------
# Multiserver-job FCFS
# --------------------------------------------------------------------------


def _fcfs_sorted_step(W, t_prev, t, n, svc):
    """One Kiefer–Wolfowitz arrival on a sorted free-time vector, O(k).

    Requires W sorted ascending; returns (W', start) with W' sorted.
    """
    k = W.shape[0]
    nth = W[jnp.maximum(n - 1, 0)]
    start = jnp.maximum(jnp.maximum(t, t_prev), nth)
    comp = start + svc
    # All n retired entries are <= comp, so the remainder W[n:] shifted left
    # with n copies of comp inserted at offset p stays sorted.
    p = jnp.searchsorted(W, comp, side="right") - n
    i = jnp.arange(k)
    W_new = jnp.where((i >= p) & (i < p + n), comp,
                      W[jnp.where(i < p, i + n, i)])
    return W_new, start


def _fcfs_carry0(k: int, dt):
    """Empty-system FCFS carry: (W sorted free times, last start)."""
    return jnp.zeros(k, dtype=dt), jnp.zeros((), dt)


def _fcfs_stream_core(carry, arrival, need, service):
    """One FCFS chunk scan resumed from ``carry`` (un-jitted, single lane).

    The carry is the complete Kiefer–Wolfowitz state ``(W, t_prev)``: a
    simulation over any trace is a sequence of these chunk scans, each
    resumed from the previous chunk's carry — ``lax.scan`` is sequential,
    so the chunked path is bit-identical to one monolithic scan by
    construction.  :func:`_fcfs_core` is the one-chunk special case;
    :mod:`repro.core.sim_batch` drives multi-chunk streams.
    """
    def step(c, inp):
        W, t_prev = c
        t, n, svc = inp
        W_new, start = _fcfs_sorted_step(W, t_prev, t, n, svc)
        return (W_new, start), start

    return jax.lax.scan(step, carry, (arrival, need, service))


def _fcfs_core(arrival, need, service, k: int):
    """Start times of one FCFS sample path (un-jitted scan core)."""
    _, starts = _fcfs_stream_core(_fcfs_carry0(k, arrival.dtype),
                                  arrival, need, service)
    return starts


_fcfs_scan = partial(jax.jit, static_argnames=("k",))(_fcfs_core)


@partial(jax.jit, static_argnames=("k",))
def _fcfs_scan_reference(arrival, need, service, k: int):
    """Pre-optimization full-sort step — kept as the bit-for-bit oracle."""
    def step(carry, inp):
        W, t_prev = carry
        t, n, svc = inp
        Ws = jnp.sort(W)
        nth = Ws[jnp.maximum(n - 1, 0)]
        start = jnp.maximum(jnp.maximum(t, t_prev), nth)
        comp = start + svc
        mask = jnp.arange(k) < n
        W_new = jnp.where(mask, comp, Ws)
        return (W_new, start), start

    W0 = jnp.zeros(k, dtype=arrival.dtype)
    (_, _), starts = jax.lax.scan(step, (W0, jnp.zeros((), arrival.dtype)),
                                  (arrival, need, service))
    return starts


def _kw_drain(W, t_up):
    """One drain event on a sorted Kiefer–Wolfowitz free-time vector.

    A server breakdown claims the earliest-free capacity unit until
    ``t_up``: the multiset update is ``W[0] := max(W[0], t_up)``, realized
    as the same O(k) roll-and-insert as ``_fcfs_sorted_step`` with n = 1.
    ``t_up = 0`` is the identity — the no-op padding row of the merged
    failure stream.
    """
    k = W.shape[0]
    comp_f = jnp.maximum(W[0], t_up)
    p = jnp.searchsorted(W, comp_f, side="right") - 1
    i = jnp.arange(k)
    return jnp.where(i == p, comp_f, W[jnp.where(i < p, i + 1, i)])


def _fcfs_fail_step(carry, inp):
    """One merged arrival-or-failure row of the FCFS drain scan.

    Rows with ``is_fail`` drain W (``_kw_drain``); arrival rows are the
    ordinary Kiefer–Wolfowitz step.  Failures never touch ``t_prev`` —
    running jobs are not preempted, a breakdown only defers future starts.
    Module-level (not a scan closure) so the fused Pallas kernel
    (:mod:`repro.kernels.msj_scan`) executes the exact same step body.
    """
    W, t_prev = carry
    tt, nn, ss, tu, isf = inp
    W_a, start = _fcfs_sorted_step(W, t_prev, tt, nn, ss)
    W_new = jnp.where(isf, _kw_drain(W, tu), W_a)
    return (W_new, jnp.where(isf, t_prev, start)), start


def _fcfs_fail_stream_core(carry, t, n, svc, t_up, is_fail):
    """FCFS merged arrival+failure scan resumed from ``carry`` (one lane).

    Start outputs of failure rows are garbage; the host gathers arrival
    positions via ``MergedStream.job_pos``.  The carry is the plain
    ``(W, t_prev)`` FCFS state, so per-lane grid carries (dead ``_BIG``
    tail entries in W for k-padding) plug in directly, and padding rows
    (``is_fail`` with ``t_up = 0``) are the identity.
    """
    return jax.lax.scan(_fcfs_fail_step, carry, (t, n, svc, t_up, is_fail))


def _fcfs_fail_core(t, n, svc, t_up, is_fail, k: int):
    """FCFS over a merged arrival+failure stream, from an empty system."""
    _, starts = _fcfs_fail_stream_core(_fcfs_carry0(k, t.dtype),
                                       t, n, svc, t_up, is_fail)
    return starts


def _as_batch(trace: Trace) -> BatchTrace:
    """The trace as a one-replication batch (the registry cores' input)."""
    return BatchTrace(arrival=trace.arrival[None], cls=trace.cls[None],
                      service=trace.service[None], need=trace.need[None],
                      k=trace.k, C=trace.C)


def fcfs_sim(trace: Trace, engine: str = "jax") -> JaxSimResult:
    """Multiserver-job FCFS (head-of-line blocking), exact sample path.

    ``engine`` selects any registered substrate ("jax" scan, "pallas"
    fused kernel, "python" event engine) via :mod:`repro.core.engines` —
    all bit-identical, see ``tests/test_sim_cross.py``.
    """
    return engines.simulate("fcfs", _as_batch(trace), engine=engine).rep(0)


# --------------------------------------------------------------------------
# ModifiedBS-π with π = FCFS
# --------------------------------------------------------------------------


def _modbs_step(carry, inp, *, s_max: int):
    """One ModifiedBS-π arrival (single lane).

    Module-level (not a scan closure) so the fused Pallas kernel
    (:mod:`repro.kernels.msj_scan`) executes the exact same step body.
    """
    comp, W, t_prev = carry           # comp: [C, s_max], W: [h] sorted
    t, c, n, svc = inp
    row = comp[c]
    busy = jnp.sum(row > t)           # padding counts as busy
    blocked = busy >= s_max
    # --- A-system path: replace min completion in class row
    idx = jnp.argmin(row)
    new_row = row.at[idx].set(jnp.where(blocked, row[idx], t + svc))
    comp = comp.at[c].set(new_row)
    # --- helper path: FCFS on h servers, engaged only when blocked
    W_upd, start_h = _fcfs_sorted_step(W, t_prev, t, n, svc)
    W_new = jnp.where(blocked, W_upd, W)
    t_prev_new = jnp.where(blocked, start_h, t_prev)
    start = jnp.where(blocked, start_h, t)
    return (comp, W_new, t_prev_new), (blocked, start)


def _modbs_init(slots, s_max: int, h: int, dt):
    """Initial (comp, W, t_prev) carry; padding slots are permanently busy."""
    pad = jnp.arange(s_max)[None, :] >= slots[:, None]
    comp0 = jnp.where(pad, _BIG, 0.0).astype(dt)
    return comp0, jnp.zeros(h, dtype=dt), jnp.zeros((), dt)


def _modbs_stream_core(carry, arrival, cls, need, service, s_max: int):
    """One ModBS-FCFS chunk scan resumed from ``carry`` (single lane).

    ``carry = (comp, W, t_prev)`` — per-class A-completion matrix, helper
    free-time vector, last helper start — is the complete state, so chunked
    resumption is bit-identical to the monolithic scan (:func:`_modbs_core`
    is the one-chunk special case over the :func:`_modbs_init` carry).
    """
    return jax.lax.scan(partial(_modbs_step, s_max=s_max), carry,
                        (arrival, cls, need, service))


def _modbs_core(arrival, cls, need, service, slots, s_max: int, h: int):
    """Per-class loss queues (padded to s_max) + helper FCFS on h servers."""
    carry0 = _modbs_init(slots, s_max, h, arrival.dtype)
    (_, _, _), (blocked, starts) = _modbs_stream_core(
        carry0, arrival, cls, need, service, s_max)
    return blocked, starts


def _modbs_fail_step(carry, inp, *, s_max: int, C: int):
    """One merged arrival-or-failure row of the ModBS drain scan.

    Failure rows carry the target block in the class column: ``c < C``
    extends the argmin completion entry of class row c to ``t_up`` (a
    free slot has entry <= t, so argmin is the earliest-free unit either
    way); ``c == C`` drains the helper W vector.  Padding rows are
    helper drains with ``t_up = 0`` — the identity.
    """
    comp, W, t_prev = carry
    t, c, n, svc, tu, isf = inp
    helper_fail = isf & (c == C)
    class_fail = isf & ~helper_fail
    cc = jnp.minimum(c, C - 1)
    row = comp[cc]
    busy = jnp.sum(row > t)
    blocked = busy >= s_max
    idx = jnp.argmin(row)
    new_val = jnp.where(class_fail, jnp.maximum(row[idx], tu),
                        jnp.where(blocked, row[idx], t + svc))
    touch = class_fail | ~isf
    comp = comp.at[cc].set(row.at[idx].set(
        jnp.where(touch, new_val, row[idx])))
    W_upd, start_h = _fcfs_sorted_step(W, t_prev, t, n, svc)
    engage = (~isf) & blocked
    W_new = jnp.where(helper_fail, _kw_drain(W, tu),
                      jnp.where(engage, W_upd, W))
    t_prev_new = jnp.where(engage, start_h, t_prev)
    start = jnp.where(blocked, start_h, t)
    return (comp, W_new, t_prev_new), (blocked & ~isf, start)


def _modbs_fail_stream_core(carry, t, c, n, svc, t_up, is_fail,
                            s_max: int, C: int):
    """ModBS merged arrival+failure scan resumed from ``carry`` (one lane).

    The carry is the plain ``(comp, W, t_prev)`` ModBS state, so per-lane
    grid carries (permanently-busy ``_BIG`` padding in comp for class/slot
    padding, dead tail entries in W for helper padding) plug in directly;
    padding rows — helper drains (``c == C``) with ``t_up = 0`` — are the
    identity.
    """
    return jax.lax.scan(partial(_modbs_fail_step, s_max=s_max, C=C), carry,
                        (t, c, n, svc, t_up, is_fail))


def _modbs_fail_core(t, c, n, svc, t_up, is_fail, slots, s_max: int,
                     h: int):
    """ModBS-FCFS over a merged arrival+failure stream (single lane)."""
    C = slots.shape[0]
    carry0 = _modbs_init(slots, s_max, h, t.dtype)
    (_, _, _), (blocked, starts) = _modbs_fail_stream_core(
        carry0, t, c, n, svc, t_up, is_fail, s_max, C)
    return blocked, starts




def modified_bs_sim(trace: Trace, partition: BalancedPartition | None = None,
                    wl: Workload | None = None,
                    engine: str = "jax") -> JaxSimResult:
    """ModifiedBS-FCFS (Definition 2) — exact sample path via the registry."""
    return engines.simulate("modbs-fcfs", _as_batch(trace), engine=engine,
                            partition=partition, wl=wl).rep(0)


# --------------------------------------------------------------------------
# BS-π proper (Definition 1, rule-3 pull-backs) with π = FCFS
# --------------------------------------------------------------------------


def _bs_make_step(jobrec, C: int, s_max: int, h: int, q_cap: int):
    """Build the batched BS-FCFS event-step function over ``jobrec``.

    ``jobrec`` is the packed [R, J, 4] (arrival, service, class, need)
    record array.  Module-level factory (not a scan closure inside
    ``_bs_core``) so the fused Pallas kernel of
    :mod:`repro.kernels.msj_scan` runs the *identical* step body with
    R = 1 per grid cell — the bit-level cross-validation between the two
    engines rests on this sharing.  See ``_bs_core`` for the event
    semantics.
    """
    R, J, _ = jobrec.shape
    dt = jobrec.dtype
    INF = jnp.asarray(jnp.inf, dt)
    lanes = jnp.arange(R)
    lanes1 = lanes[:, None]
    ar = jnp.arange(h)[None, :]

    def taa(a, idx):
        """a[lane, idx[lane]] for every lane (single gather)."""
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def rec(idx):
        """One job's packed attributes per lane: [R, 4]."""
        return jnp.take_along_axis(jobrec, idx[:, None, None], axis=1)[:, 0]

    def step(carry, _):
        (ai, st, comp, ring, heads, W, t_prev, t_hol, rpk) = carry
        # st packs the per-class int32 counters: [0:C] free A slots,
        # [C:2C] ring heads, [2C:3C] ring tails.

        j_arr = jnp.minimum(ai, J - 1)
        rec_a = rec(j_arr)
        Ta = jnp.where(ai < J, rec_a[:, 0], INF)
        cm = jnp.argmin(comp, axis=1).astype(jnp.int32)
        Tc = taa(comp, cm)
        gh_job = jnp.min(heads, axis=1)       # global FIFO head (= min index)
        has_head = gh_job < J
        jh = jnp.minimum(gh_job, J - 1)
        rec_h = rec(jh)
        nh = rec_h[:, 3].astype(jnp.int32)
        Wn = taa(W, nh - 1)                   # n-th smallest free time
        Th = jnp.where(has_head,
                       jnp.maximum(jnp.maximum(rec_h[:, 0], t_hol),
                                   jnp.maximum(t_prev, Wn)),
                       INF)

        is_commit = (Th <= Tc) & (Th <= Ta)
        # arrivals precede departures at equal times (engine heap order)
        is_comp = (~is_commit) & (Tc < Ta)
        is_arr = (~is_commit) & (~is_comp)

        # --- arrival (rule 1): free A_i slot -> start in A, else enqueue.
        # Disabled updates scatter to a dropped out-of-bounds index.
        c_arr = rec_a[:, 2].astype(jnp.int32)
        g = jnp.take_along_axis(
            st, jnp.stack([c_arr, C + c_arr, 2 * C + c_arr], 1), axis=1)
        free_c, head_c, tail_c = g[:, 0], g[:, 1], g[:, 2]
        has_slot = is_arr & (free_c > 0)
        enq = is_arr & ~has_slot
        ring = ring.at[lanes,
                       jnp.where(enq, c_arr * q_cap + tail_c % q_cap,
                                 C * q_cap)].set(j_arr, mode="drop")
        # ring occupancy only grows at an enqueue: the running max over
        # enqueues is the exact peak over every class
        rpk = jnp.maximum(rpk, jnp.where(enq, tail_c + 1 - head_c, 0))
        ai = ai + jnp.where(is_arr, 1, 0)

        # --- A-completion: rule-3 pull the class head into the freed slot
        c_comp = cm // s_max
        pull = taa(heads, c_comp)
        can_pull = is_comp & (pull < J)
        jp = jnp.minimum(pull, J - 1)
        # head-of-line pull-back: the new head cannot start in H before Tc
        t_hol = jnp.where(can_pull & (pull == gh_job),
                          jnp.maximum(t_hol, Tc), t_hol)

        # --- comp update, one 2-entry scatter with disjoint indices:
        # clear the completed slot (completion without pull), insert the
        # next A start (arrival with a free slot, at an empty-_BIG slot of
        # its class row, or pull-back, reusing the freed slot cm).
        ins = has_slot | can_pull
        j_ins = jnp.where(is_arr, j_arr, jp)
        t_ins = jnp.where(is_arr, Ta, Tc)
        svc_ins = rec(j_ins)[:, 1]
        row = jnp.take_along_axis(
            comp, c_arr[:, None] * s_max + jnp.arange(s_max)[None, :],
            axis=1)
        pos = jnp.argmax(row, axis=1).astype(jnp.int32)
        OOBC = C * s_max
        idx2 = jnp.stack(
            [jnp.where(is_comp & ~can_pull, cm, OOBC),
             jnp.where(has_slot, c_arr * s_max + pos,
                       jnp.where(can_pull, cm, OOBC))], 1)
        val2 = jnp.stack([jnp.full(R, _BIG, dt), t_ins + svc_ins], 1)
        comp = comp.at[lanes1, idx2].set(val2, mode="drop")

        # --- helper commit: global head starts on H at Th (π = FCFS).
        # Batched O(h) sorted Kiefer-Wolfowitz step (_fcfs_sorted_step):
        # retire the nh smallest entries of W, roll-and-insert nh copies
        # of comp_h at p = searchsorted(W, comp_h, "right") - nh.
        comp_h = Th + rec_h[:, 1]
        p = (jnp.sum(W <= comp_h[:, None], axis=1).astype(jnp.int32)
             - nh)[:, None]
        nh_ = nh[:, None]
        W_roll = jnp.take_along_axis(
            W, jnp.minimum(jnp.where(ar < p, ar + nh_, ar), h - 1), axis=1)
        W2 = jnp.where((ar >= p) & (ar < p + nh_), comp_h[:, None], W_roll)
        W = jnp.where(is_commit[:, None], W2, W)
        t_prev = jnp.where(is_commit, Th, t_prev)

        # --- counter updates, one 3-entry scatter-add (duplicates add):
        # free A slots at the touched class, ring tail on enqueue, ring
        # head on pop (rule-3 pull xor commit).
        did_pop = can_pull | is_commit
        pop_c = jnp.where(can_pull, c_comp, rec_h[:, 2].astype(jnp.int32))
        OOBS = 3 * C
        idx3 = jnp.stack(
            [jnp.where(is_arr, c_arr, jnp.where(is_comp, c_comp, OOBS)),
             jnp.where(enq, 2 * C + c_arr, OOBS),
             jnp.where(did_pop, C + pop_c, OOBS)], 1)
        val3 = jnp.stack(
            [jnp.where(has_slot, -1, 0) +
             jnp.where(is_comp & ~can_pull, 1, 0),
             jnp.ones(R, jnp.int32), jnp.ones(R, jnp.int32)], 1)
        st = st.at[lanes1, idx3].add(val3, mode="drop")

        # --- refresh the materialized per-class head jobs, one 2-entry
        # scatter: an enqueue into an empty queue sets the head, a pop
        # promotes the next ring entry (J sentinel when empty).
        gp = jnp.take_along_axis(
            st, jnp.stack([C + pop_c, 2 * C + pop_c], 1), axis=1)
        nxt = jnp.where(gp[:, 0] < gp[:, 1],
                        taa(ring, pop_c * q_cap + gp[:, 0] % q_cap), J)
        hidx = jnp.stack([jnp.where(enq & (head_c == tail_c), c_arr, C),
                          jnp.where(did_pop, pop_c, C)], 1)
        hval = jnp.stack([j_arr, nxt], 1)
        heads = heads.at[lanes1, hidx].set(hval, mode="drop")

        # one tagged int per event (fewer scan outputs = fewer per-step
        # ops): j = A start, j + J = routed to H, j + 2J = helper commit,
        # j + 3J = helper commit at the job's own arrival (a zero wait the
        # host takes from its exact arrival; see _bs_scatter_events)
        tagged = jnp.where(is_commit,
                           jh + jnp.where(Th == rec_h[:, 0], 3 * J, 2 * J),
                           jnp.where(ins, j_ins,
                                     jnp.where(enq, j_arr + J, -1)))
        rec_t = jnp.where(is_commit, Th, t_ins)
        out = (tagged, rec_t)
        return (ai, st, comp, ring, heads, W, t_prev, t_hol, rpk), out

    return step


def _bs_init(R: int, J: int, C: int, s_max: int, h: int, q_cap: int,
             slots, dt):
    """Initial BS-FCFS event-scan carry (shared with the Pallas kernel)."""
    st0 = jnp.concatenate([
        jnp.broadcast_to(slots.astype(jnp.int32), (R, C)),  # free slots
        jnp.zeros((R, 2 * C), jnp.int32)], axis=1)          # head/tail = 0
    return (jnp.zeros(R, jnp.int32),                    # ai
            st0,                                        # free/head/tail
            jnp.full((R, C * s_max), _BIG, dt),         # A completion times
            jnp.zeros((R, C * q_cap), jnp.int32),       # helper-wait rings
            jnp.full((R, C), J, jnp.int32),             # per-class heads
            jnp.zeros((R, h), dt),                      # W, sorted asc.
            jnp.zeros(R, dt),                           # t_prev
            jnp.zeros(R, dt),                           # t_hol
            jnp.zeros(R, jnp.int32))                    # ring peak


def _bs_core(arrival, cls, need, service, slots, s_max: int, h: int,
             q_cap: int):
    """BS-FCFS (Definition 1) sample paths as a 2J-step event scan, batched.

    All inputs carry an explicit leading replications axis ([R, J] arrays);
    the R lanes advance in lockstep through one ``lax.scan``.  The axis is
    hand-vectorized rather than ``jax.vmap``-ed, and the step is written to
    MINIMIZE THE NUMBER OF GATHER/SCATTER OPS, not FLOPs: beyond a small
    body size XLA:CPU stops fusing the while body and pays fixed per-op
    dispatch every event, so job attributes are packed into one [J, 4]
    record (arrival, service, class, need — one gather instead of four),
    the per-class free/head/tail counters live in one [3C] vector updated
    by a single 3-entry scatter-add, and related single-element writes are
    merged into multi-entry scatters with disjoint (or dropped
    out-of-bounds) indices.

    Exactly 2J events exist per lane: each job contributes its arrival
    plus either its A-system completion (it ran in an A_i — routed on
    arrival or pulled back by rule 3) or its helper start ("commit", it
    ran in H), so a fixed-length scan of 2*J steps processes every event
    with none to spare.  Per step and lane the three candidate next events
    are

    * the next arrival,                       time  Ta = arrival[ai]
    * the earliest outstanding A completion,  time  Tc = min(comp)
    * the helper-queue head's FCFS start,     time  Th = max(A_head, t_prev,
                                                             t_hol, W[n-1])

    and the earliest wins (commit on ties: at equal times the engine's
    helper start belongs to an event that already happened; arrivals
    precede A completions, matching the engine's heap order).  Rule 3 runs
    inside the A-completion event: the freed class's ring-buffer head (its
    oldest waiting job) starts in A_i at Tc — reusing the freed comp slot —
    and if it was the *global* queue head, t_hol := Tc: the job promoted
    to the head cannot start in H before the pull that promoted it (the
    fixed Python engine re-runs the helper scheduler at exactly that
    instant).  Helper starts use the same sorted Kiefer-Wolfowitz
    free-time vector W as the FCFS core, so helper completions never need
    events of their own.

    Returns the raw per-event streams ``(tagged, rec_t)`` (each [R, 2J];
    tagged encodes j = A start, j + J = routed to H, j + 2J = helper
    commit, j + 3J = helper commit at the job's own arrival, -1 = no
    record) and each lane's peak helper-wait ring occupancy (the most
    jobs of one class queued at once; the ring overflowed where it
    exceeds ``q_cap``); the host wrappers (`_bs_scatter_events`) scatter
    the events to per-job arrays.
    """
    R, J = arrival.shape
    C = slots.shape[0]
    dt = arrival.dtype
    # packed per-job record: one gather fetches all four attributes
    # (class/need are exact in f64 for any realistic J, k)
    jobrec = jnp.stack([arrival, service, cls.astype(dt), need.astype(dt)],
                       axis=2)                            # [R, J, 4]
    step = _bs_make_step(jobrec, C, s_max, h, q_cap)
    carry0 = _bs_init(R, J, C, s_max, h, q_cap, slots, dt)
    (_, _, _, _, _, _, _, _, rpk), (tagged, rec_t) \
        = jax.lax.scan(step, carry0, None, length=2 * J)

    # ys are stacked [2J, R]; hand back [R, 2J] event streams.  The host
    # wrappers scatter them to per-job arrays with numpy — an in-graph
    # .at[job].set scatter looks natural here but XLA:CPU lowers the
    # unsorted scatter to a serial per-element loop that dwarfs the scan.
    return tagged.T, rec_t.T, rpk




def _bs_stream_make_step(jobrec, horizon, C: int, s_max: int, h: int,
                         q_cap: int, j_live=None):
    """Chunk-resumable variant of ``_bs_make_step`` (streaming execution).

    ``j_live`` (optional, [R] int32) caps the per-lane admitted arrivals:
    jobs at index >= ``j_live[r]`` are padding that the lane never sees —
    the J-padding guard of the grid driver, where heterogeneous-J cells
    are stacked to a shared [L, J_pad] shape.  ``None`` (the streaming
    path) admits every job, i.e. ``j_live = J``.

    Identical event semantics with two additions that make a *bounded*
    scan over one chunk of the job stream exact:

    * ``horizon`` [R] is the first arrival time of the *next* chunk (inf
      on the last chunk).  Helper commits are only processed while
      ``Th <= horizon`` and A-completions while ``Tc < horizon`` — every
      later event is deferred, and because deferral leaves the carry
      untouched, the next chunk's scan recomputes the identical candidate
      times and processes the deferred events first, in the exact order
      the monolithic scan would have (the tie asymmetry matches the
      monolithic selectors: at ``t == horizon`` a commit still belongs to
      this chunk while a completion yields to the next chunk's equal-time
      arrival, which the monolithic ``Tc < Ta`` tie-break also orders
      first).
    * trailing steps past a chunk's true event count are no-ops, so the
      selectors carry the guards of the failure scan (``Tc`` below the
      ``_BIG`` sentinel, ``ai < J``), and the carry grows a per-lane
      processed-event counter ``ne`` — each fed job contributes exactly
      two events over the whole stream (arrival + A-completion-or-commit),
      so the host driver knows precisely how many events remain at drain
      time.
    """
    R, J, _ = jobrec.shape
    dt = jobrec.dtype
    INF = jnp.asarray(jnp.inf, dt)
    GUARD = jnp.asarray(0.5 * _BIG, dt)
    jl = J if j_live is None else j_live
    lanes = jnp.arange(R)
    lanes1 = lanes[:, None]
    ar = jnp.arange(h)[None, :]

    def taa(a, idx):
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def rec(idx):
        return jnp.take_along_axis(jobrec, idx[:, None, None], axis=1)[:, 0]

    def step(carry, _):
        (ai, st, comp, ring, heads, W, t_prev, t_hol, rpk, ne) = carry

        j_arr = jnp.minimum(ai, J - 1)
        rec_a = rec(j_arr)
        Ta = jnp.where(ai < jl, rec_a[:, 0], INF)
        cm = jnp.argmin(comp, axis=1).astype(jnp.int32)
        Tc = taa(comp, cm)
        gh_job = jnp.min(heads, axis=1)
        has_head = gh_job < J
        jh = jnp.minimum(gh_job, J - 1)
        rec_h = rec(jh)
        nh = rec_h[:, 3].astype(jnp.int32)
        Wn = taa(W, nh - 1)
        Th = jnp.where(has_head,
                       jnp.maximum(jnp.maximum(rec_h[:, 0], t_hol),
                                   jnp.maximum(t_prev, Wn)),
                       INF)

        is_commit = (Th <= Tc) & (Th <= Ta) & (Th <= horizon)
        is_comp = ((~is_commit) & (Tc < Ta) & (Tc < horizon)
                   & (Tc < GUARD))
        is_arr = (~is_commit) & (~is_comp) & (ai < jl)
        ne = ne + jnp.where(is_commit | is_comp | is_arr, 1, 0)

        # --- arrival (rule 1), as in _bs_make_step
        c_arr = rec_a[:, 2].astype(jnp.int32)
        g = jnp.take_along_axis(
            st, jnp.stack([c_arr, C + c_arr, 2 * C + c_arr], 1), axis=1)
        free_c, head_c, tail_c = g[:, 0], g[:, 1], g[:, 2]
        has_slot = is_arr & (free_c > 0)
        enq = is_arr & ~has_slot
        ring = ring.at[lanes,
                       jnp.where(enq, c_arr * q_cap + tail_c % q_cap,
                                 C * q_cap)].set(j_arr, mode="drop")
        rpk = jnp.maximum(rpk, jnp.where(enq, tail_c + 1 - head_c, 0))
        ai = ai + jnp.where(is_arr, 1, 0)

        # --- A-completion: rule-3 pull
        c_comp = cm // s_max
        pull = taa(heads, c_comp)
        can_pull = is_comp & (pull < J)
        jp = jnp.minimum(pull, J - 1)
        t_hol = jnp.where(can_pull & (pull == gh_job),
                          jnp.maximum(t_hol, Tc), t_hol)

        # --- comp update, as in _bs_make_step
        ins = has_slot | can_pull
        j_ins = jnp.where(is_arr, j_arr, jp)
        t_ins = jnp.where(is_arr, Ta, Tc)
        svc_ins = rec(j_ins)[:, 1]
        row = jnp.take_along_axis(
            comp, c_arr[:, None] * s_max + jnp.arange(s_max)[None, :],
            axis=1)
        pos = jnp.argmax(row, axis=1).astype(jnp.int32)
        OOBC = C * s_max
        idx2 = jnp.stack(
            [jnp.where(is_comp & ~can_pull, cm, OOBC),
             jnp.where(has_slot, c_arr * s_max + pos,
                       jnp.where(can_pull, cm, OOBC))], 1)
        val2 = jnp.stack([jnp.full(R, _BIG, dt), t_ins + svc_ins], 1)
        comp = comp.at[lanes1, idx2].set(val2, mode="drop")

        # --- helper commit (batched KW step), as in _bs_make_step
        comp_h = Th + rec_h[:, 1]
        p = (jnp.sum(W <= comp_h[:, None], axis=1).astype(jnp.int32)
             - nh)[:, None]
        nh_ = nh[:, None]
        W_roll = jnp.take_along_axis(
            W, jnp.minimum(jnp.where(ar < p, ar + nh_, ar), h - 1), axis=1)
        W2 = jnp.where((ar >= p) & (ar < p + nh_), comp_h[:, None], W_roll)
        W = jnp.where(is_commit[:, None], W2, W)
        t_prev = jnp.where(is_commit, Th, t_prev)

        # --- counter updates, as in _bs_make_step
        did_pop = can_pull | is_commit
        pop_c = jnp.where(can_pull, c_comp, rec_h[:, 2].astype(jnp.int32))
        OOBS = 3 * C
        idx3 = jnp.stack(
            [jnp.where(is_arr, c_arr, jnp.where(is_comp, c_comp, OOBS)),
             jnp.where(enq, 2 * C + c_arr, OOBS),
             jnp.where(did_pop, C + pop_c, OOBS)], 1)
        val3 = jnp.stack(
            [jnp.where(has_slot, -1, 0) +
             jnp.where(is_comp & ~can_pull, 1, 0),
             jnp.ones(R, jnp.int32), jnp.ones(R, jnp.int32)], 1)
        st = st.at[lanes1, idx3].add(val3, mode="drop")

        # --- per-class head refresh, as in _bs_make_step
        gp = jnp.take_along_axis(
            st, jnp.stack([C + pop_c, 2 * C + pop_c], 1), axis=1)
        nxt = jnp.where(gp[:, 0] < gp[:, 1],
                        taa(ring, pop_c * q_cap + gp[:, 0] % q_cap), J)
        hidx = jnp.stack([jnp.where(enq & (head_c == tail_c), c_arr, C),
                          jnp.where(did_pop, pop_c, C)], 1)
        hval = jnp.stack([j_arr, nxt], 1)
        heads = heads.at[lanes1, hidx].set(hval, mode="drop")

        tagged = jnp.where(is_commit,
                           jh + jnp.where(Th == rec_h[:, 0], 3 * J, 2 * J),
                           jnp.where(ins, j_ins,
                                     jnp.where(enq, j_arr + J, -1)))
        rec_t = jnp.where(is_commit, Th, t_ins)
        out = (tagged, rec_t)
        return (ai, st, comp, ring, heads, W, t_prev, t_hol, rpk, ne), out

    return step


def _bs_stream_core(arrival, cls, need, service, horizon, carry,
                    C: int, s_max: int, h: int, q_cap: int, length: int,
                    j_live=None):
    """One BS-FCFS chunk scan resumed from ``carry``, batched over lanes.

    ``arrival``/``cls``/``need``/``service`` are the chunk's job records
    [R, J] — the host driver prepends the still-queued jobs of earlier
    chunks (re-based to local indices 0..B-1 in global-FIFO order, see
    ``sim_batch._bs_rebase``) so every ring-buffer reference stays in
    bounds.  ``horizon`` [R] is the first arrival of the next chunk (inf
    when draining).  ``carry`` is the full event-scan state
    ``(ai, st, comp, ring, heads, W, t_prev, t_hol, rpk, ne)``; the scan
    runs ``length`` steps (enough for every event dated before the
    horizon — trailing steps no-op) and returns the updated carry plus
    the tagged per-event record streams of ``_bs_core``.
    """
    dt = arrival.dtype
    jobrec = jnp.stack([arrival, service, cls.astype(dt), need.astype(dt)],
                       axis=2)
    step = _bs_stream_make_step(jobrec, horizon, C, s_max, h, q_cap,
                                j_live=j_live)
    carry, (tagged, rec_t) = jax.lax.scan(step, carry, None, length=length)
    return carry, tagged.T, rec_t.T


def _bs_fail_make_step(jobrec, failrec, C: int, s_max: int, h: int,
                       q_cap: int, j_live=None):
    """Failure-aware variant of ``_bs_make_step``.

    ``j_live`` (optional, [R] int32) is the per-lane J-padding guard of
    ``_bs_stream_make_step`` — lanes never admit arrivals at index
    >= ``j_live[r]``; ``None`` admits every job.

    ``failrec`` is the packed [R, F, 3] (t_down, target, t_up) event
    array from :func:`repro.core.failures.partition_targets`, sorted
    chronologically; the carry grows a per-lane failure cursor ``fi``.  A
    failure event wins ties against every other candidate (it happened
    first in the merged chronology) and claims the earliest-free capacity
    unit of its target block:

    * target == C — drain the helper W vector (``W[0] := max(W[0], t_up)``);
    * target < C with a free A slot — occupy it until ``t_up``: decrement
      the free counter and insert ``t_up`` at an empty ``_BIG`` entry,
      which later fires as an ordinary A-completion (the *repair* event,
      rule-3 pull included for free);
    * target < C fully busy — extend the argmin completion entry to
      ``t_up`` (non-preemption: the running gang finishes, the slot then
      stays down until repair).

    Because trailing steps past the per-lane event count are no-ops, the
    event selectors carry guards the exact-length 2J scan never needed:
    completions require ``Tc`` below the ``_BIG`` sentinel and arrivals
    require ``ai < J``.
    """
    R, J, _ = jobrec.shape
    F = failrec.shape[1]
    dt = jobrec.dtype
    INF = jnp.asarray(jnp.inf, dt)
    GUARD = jnp.asarray(0.5 * _BIG, dt)
    jl = J if j_live is None else j_live
    lanes = jnp.arange(R)
    lanes1 = lanes[:, None]
    ar = jnp.arange(h)[None, :]

    def taa(a, idx):
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def rec(idx):
        return jnp.take_along_axis(jobrec, idx[:, None, None], axis=1)[:, 0]

    def frec(idx):
        return jnp.take_along_axis(failrec, idx[:, None, None], axis=1)[:, 0]

    def step(carry, _):
        (ai, fi, st, comp, ring, heads, W, t_prev, t_hol, rpk) = carry

        j_arr = jnp.minimum(ai, J - 1)
        rec_a = rec(j_arr)
        Ta = jnp.where(ai < jl, rec_a[:, 0], INF)
        cm = jnp.argmin(comp, axis=1).astype(jnp.int32)
        Tc = taa(comp, cm)
        gh_job = jnp.min(heads, axis=1)
        has_head = gh_job < J
        jh = jnp.minimum(gh_job, J - 1)
        rec_h = rec(jh)
        nh = rec_h[:, 3].astype(jnp.int32)
        Wn = taa(W, nh - 1)
        Th = jnp.where(has_head,
                       jnp.maximum(jnp.maximum(rec_h[:, 0], t_hol),
                                   jnp.maximum(t_prev, Wn)),
                       INF)
        rec_f = frec(jnp.minimum(fi, F - 1))
        Tf = jnp.where(fi < F, rec_f[:, 0], INF)
        fc = rec_f[:, 1].astype(jnp.int32)
        fu = rec_f[:, 2]

        is_fail = (Tf <= Ta) & (Tf <= Tc) & (Tf <= Th) & (Tf < INF)
        is_commit = (~is_fail) & (Th <= Tc) & (Th <= Ta)
        is_comp = (~is_fail) & (~is_commit) & (Tc < Ta) & (Tc < GUARD)
        is_arr = (~is_fail) & (~is_commit) & (~is_comp) & (ai < jl)
        fi = fi + jnp.where(is_fail, 1, 0)

        # --- arrival (rule 1), as in _bs_make_step
        c_arr = rec_a[:, 2].astype(jnp.int32)
        g = jnp.take_along_axis(
            st, jnp.stack([c_arr, C + c_arr, 2 * C + c_arr], 1), axis=1)
        free_c, head_c, tail_c = g[:, 0], g[:, 1], g[:, 2]
        has_slot = is_arr & (free_c > 0)
        enq = is_arr & ~has_slot
        ring = ring.at[lanes,
                       jnp.where(enq, c_arr * q_cap + tail_c % q_cap,
                                 C * q_cap)].set(j_arr, mode="drop")
        rpk = jnp.maximum(rpk, jnp.where(enq, tail_c + 1 - head_c, 0))
        ai = ai + jnp.where(is_arr, 1, 0)

        # --- A-completion: rule-3 pull
        c_comp = cm // s_max
        pull = taa(heads, c_comp)
        can_pull = is_comp & (pull < J)
        jp = jnp.minimum(pull, J - 1)
        t_hol = jnp.where(can_pull & (pull == gh_job),
                          jnp.maximum(t_hol, Tc), t_hol)

        # --- failure target bookkeeping
        fcc = jnp.minimum(fc, C - 1)
        helper_fail = is_fail & (fc == C)
        class_fail = is_fail & ~helper_fail
        free_f = taa(st, fcc)
        row_f = jnp.take_along_axis(
            comp, fcc[:, None] * s_max + jnp.arange(s_max)[None, :], axis=1)
        pos_free = jnp.argmax(row_f, axis=1).astype(jnp.int32)
        cmf = jnp.argmin(row_f, axis=1).astype(jnp.int32)
        vmin = taa(row_f, cmf)
        fail_free = class_fail & (free_f > 0)
        fail_busy = class_fail & ~(free_f > 0)

        # --- comp update: the 2-entry scatter of _bs_make_step plus the
        # failure entry (disjoint: under is_fail the first two drop OOB)
        ins = has_slot | can_pull
        j_ins = jnp.where(is_arr, j_arr, jp)
        t_ins = jnp.where(is_arr, Ta, Tc)
        svc_ins = rec(j_ins)[:, 1]
        row = jnp.take_along_axis(
            comp, c_arr[:, None] * s_max + jnp.arange(s_max)[None, :],
            axis=1)
        pos = jnp.argmax(row, axis=1).astype(jnp.int32)
        OOBC = C * s_max
        idx3 = jnp.stack(
            [jnp.where(is_comp & ~can_pull, cm, OOBC),
             jnp.where(has_slot, c_arr * s_max + pos,
                       jnp.where(can_pull, cm, OOBC)),
             jnp.where(fail_free, fcc * s_max + pos_free,
                       jnp.where(fail_busy, fcc * s_max + cmf, OOBC))], 1)
        val3 = jnp.stack([jnp.full(R, _BIG, dt), t_ins + svc_ins,
                          jnp.where(fail_free, fu,
                                    jnp.maximum(vmin, fu))], 1)
        comp = comp.at[lanes1, idx3].set(val3, mode="drop")

        # --- helper commit + helper drain (disjoint lane masks)
        comp_h = Th + rec_h[:, 1]
        p = (jnp.sum(W <= comp_h[:, None], axis=1).astype(jnp.int32)
             - nh)[:, None]
        nh_ = nh[:, None]
        W_roll = jnp.take_along_axis(
            W, jnp.minimum(jnp.where(ar < p, ar + nh_, ar), h - 1), axis=1)
        W2 = jnp.where((ar >= p) & (ar < p + nh_), comp_h[:, None], W_roll)
        comp_f = jnp.maximum(W[:, 0], fu)
        pf = (jnp.sum(W <= comp_f[:, None], axis=1).astype(jnp.int32)
              - 1)[:, None]
        W_roll_f = jnp.take_along_axis(
            W, jnp.minimum(jnp.where(ar < pf, ar + 1, ar), h - 1), axis=1)
        Wf = jnp.where(ar == pf, comp_f[:, None], W_roll_f)
        W = jnp.where(is_commit[:, None], W2,
                      jnp.where(helper_fail[:, None], Wf, W))
        t_prev = jnp.where(is_commit, Th, t_prev)

        # --- counter updates: the 3-entry scatter-add of _bs_make_step
        # plus the free-slot claim of a class drain
        did_pop = can_pull | is_commit
        pop_c = jnp.where(can_pull, c_comp, rec_h[:, 2].astype(jnp.int32))
        OOBS = 3 * C
        idx4 = jnp.stack(
            [jnp.where(is_arr, c_arr, jnp.where(is_comp, c_comp, OOBS)),
             jnp.where(enq, 2 * C + c_arr, OOBS),
             jnp.where(did_pop, C + pop_c, OOBS),
             jnp.where(fail_free, fcc, OOBS)], 1)
        val4 = jnp.stack(
            [jnp.where(has_slot, -1, 0) +
             jnp.where(is_comp & ~can_pull, 1, 0),
             jnp.ones(R, jnp.int32), jnp.ones(R, jnp.int32),
             jnp.full(R, -1, jnp.int32)], 1)
        st = st.at[lanes1, idx4].add(val4, mode="drop")

        # --- per-class head refresh, as in _bs_make_step
        gp = jnp.take_along_axis(
            st, jnp.stack([C + pop_c, 2 * C + pop_c], 1), axis=1)
        nxt = jnp.where(gp[:, 0] < gp[:, 1],
                        taa(ring, pop_c * q_cap + gp[:, 0] % q_cap), J)
        hidx = jnp.stack([jnp.where(enq & (head_c == tail_c), c_arr, C),
                          jnp.where(did_pop, pop_c, C)], 1)
        hval = jnp.stack([j_arr, nxt], 1)
        heads = heads.at[lanes1, hidx].set(hval, mode="drop")

        tagged = jnp.where(is_commit,
                           jh + jnp.where(Th == rec_h[:, 0], 3 * J, 2 * J),
                           jnp.where(ins, j_ins,
                                     jnp.where(enq, j_arr + J, -1)))
        rec_t = jnp.where(is_commit, Th, t_ins)
        out = (tagged, rec_t)
        return (ai, fi, st, comp, ring, heads, W, t_prev, t_hol, rpk), out

    return step


def _bs_fail_stream_core(arrival, cls, need, service, ft, ftgt, fup,
                         carry, C: int, s_max: int, h: int, q_cap: int,
                         length: int, j_live=None):
    """BS-FCFS drained-capacity event scan resumed from ``carry``.

    The carry-accepting form of :func:`_bs_fail_core` — per-lane grid
    carries (padded free-slot counters, dead ``_BIG`` helper entries) and
    the ``j_live`` J-padding guard plug in directly; padding failure rows
    (``t_down = inf``) never fire thanks to the ``Tf < INF`` selector.
    """
    dt = arrival.dtype
    jobrec = jnp.stack([arrival, service, cls.astype(dt), need.astype(dt)],
                       axis=2)
    failrec = jnp.stack([ft, ftgt.astype(dt), fup], axis=2)  # [R, F, 3]
    step = _bs_fail_make_step(jobrec, failrec, C, s_max, h, q_cap,
                              j_live=j_live)
    carry, (tagged, rec_t) = jax.lax.scan(step, carry, None, length=length)
    return carry, tagged.T, rec_t.T


def _bs_fail_core(arrival, cls, need, service, ft, ftgt, fup, slots,
                  s_max: int, h: int, q_cap: int, length: int):
    """BS-FCFS sample paths with drained-capacity failure events.

    Same event semantics as ``_bs_core`` plus a fourth candidate event —
    the next breakdown, which wins ties.  The scan runs ``length`` =
    2J + F + F_A steps (F_A bounds the extra repair-completions created
    by free-slot drains); lanes that exhaust their events no-op to the
    end, guarded by the ``Tc < GUARD`` / ``ai < J`` selector terms.
    """
    R, J = arrival.shape
    C = slots.shape[0]
    dt = arrival.dtype
    c0 = _bs_init(R, J, C, s_max, h, q_cap, slots, dt)
    carry0 = (c0[0], jnp.zeros(R, jnp.int32)) + c0[1:]
    carry, tagged, rec_t = _bs_fail_stream_core(
        arrival, cls, need, service, ft, ftgt, fup, carry0,
        C, s_max, h, q_cap, length)
    return tagged, rec_t, carry[9]


def _bs_scatter_events(J: int, tagged, rec_t, arrival):
    """Scatter [R, 2J] event records to per-job [R, J] arrays, all reps at
    once.

    ``tagged`` encodes the event: j = job j started in its A_i (the record
    time is its start), j + J = job j was routed to H on arrival, j + 2J =
    job j started on a helper server (the record time is its start), j +
    3J = job j started on a helper server at its own arrival.  Each job
    yields exactly one start record and at most one routing record per
    replication, so every target cell is written at most once and one flat
    advanced-indexing assignment per record kind handles the whole batch —
    host post-processing stays O(R·J) vectorized numpy instead of an
    R-iteration Python loop.

    A job that was never routed took a free A_i slot on arrival (rule 1),
    so it and a j + 3J job start at their own ``arrival`` ([R, J], the
    host's float64), not at the record time: a chip that emulates float64
    holds times to fewer bits, and a zero wait must stay exactly zero.
    """
    tagged = np.asarray(tagged)
    rec_t = np.asarray(rec_t)
    R = tagged.shape[0]
    rows = np.broadcast_to(np.arange(R)[:, None], tagged.shape)
    start = np.array(arrival, np.float64)
    served = np.zeros((R, J), bool)
    routed = np.zeros((R, J), bool)
    m_r = (tagged >= J) & (tagged < 2 * J)
    routed[rows[m_r], tagged[m_r] - J] = True
    m_a = (tagged >= 0) & (tagged < J)
    m_a[m_a] = routed[rows[m_a], tagged[m_a]]     # rule-3 pull-backs
    start[rows[m_a], tagged[m_a]] = rec_t[m_a]
    m_h = (tagged >= 2 * J) & (tagged < 3 * J)
    start[rows[m_h], tagged[m_h] - 2 * J] = rec_t[m_h]
    served[rows[m_h], tagged[m_h] - 2 * J] = True
    m_h = tagged >= 3 * J
    served[rows[m_h], tagged[m_h] - 3 * J] = True
    return start, served, routed


def _bs_args(trace_or_batch, partition, wl, queue_cap):
    """Shared argument validation for ``bs_sim`` / ``bs_sim_batch``."""
    with engines.call_span("repro.prep"):
        if partition is None:
            if wl is None:
                raise ValueError("need a partition or a workload")
            partition = balanced_partition(wl)
        slots = np.asarray(partition.slots, dtype=np.int32)
        h = int(partition.helpers)
        if h < int(trace_or_batch.need.max()):
            raise ValueError("helper set smaller than the largest server need")
    s_max = max(1, int(slots.max()))
    if queue_cap is None:
        queue_cap = max(1, min(trace_or_batch.num_jobs, 8192))
    elif queue_cap < 1:
        raise ValueError(f"queue_cap must be >= 1, got {queue_cap}")
    return slots, s_max, h, queue_cap


def bs_sim(trace: Trace, partition: BalancedPartition | None = None,
           wl: Workload | None = None, queue_cap: int | None = None,
           engine: str = "jax") -> JaxSimResult:
    """BS-FCFS (Definition 1, rule-3 pull-backs) — exact sample path.

    ``queue_cap`` bounds the per-class helper-wait ring buffers (default
    ``min(J, 8192)``); a stable workload never comes close, and an overflow
    raises rather than returning a silently wrong path.  ``engine`` selects
    any registered substrate — bit-identical across engines.
    """
    return engines.simulate("bs-fcfs", _as_batch(trace), engine=engine,
                            partition=partition, wl=wl,
                            queue_cap=queue_cap).rep(0)


def estimate_p_helper(wl: Workload, num_jobs: int = 200_000,
                      seed: int = 0, reps: int = 1) -> float:
    """Fast Monte-Carlo P_H^{ModifiedBS-π} (the Cor.-1 upper bound).

    Runs on the batched vmap substrate: ``reps`` independent Philox
    replications of ``num_jobs`` arrivals each, averaged.
    """
    from .sim_batch import modified_bs_sim_batch  # local: avoid import cycle
    batch = wl.sample_traces(num_jobs, reps, seed=seed)
    res = modified_bs_sim_batch(batch, wl=wl)
    return float(res.p_helper.mean())


# --------------------------------------------------------------------------
# Preemptive SRPT-family event scans (ServerFilling-SRPT / FirstFit-SRPT).
#
# Unlike the nonpreemptive cores above, a preemptive size-aware policy
# re-evaluates the whole running set at every event: an arrival with a
# short remaining size may preempt a running job, and a departure may
# admit several waiting jobs at once.  The scan therefore carries the full
# in-system job set — a static table of ``Q`` slots per lane holding
# (job id, arrival, need, remaining work, burst start, running/started
# flags, first-start time) — and each event step re-sorts and re-packs it
# exactly the way the python oracle's ``Policy.select`` does:
#
# * current remaining work ``max(0, rem - (t - run_start))`` for running
#   jobs (the identical float ops as ``Simulation.remaining_now``, so
#   event times and ranks are bit-equal to the oracle),
# * a stable rank sort — rank = remaining (FirstFit-SRPT) or
#   remaining x need (ServerFilling-SRPT), ties by arrival time,
# * ServerFilling's candidate prefix M (smallest m with cumulative need
#   >= k; all jobs when total need < k) re-sorted stably by
#   (-need, rank) — matching the oracle's stable ``sorted`` calls,
# * a first-fit packing walk over the candidate order.
#
# The walk ("take each job in order iff its need fits the free servers")
# is inherently sequential, but over a *static* set of distinct need
# values NU it vectorizes: in each round let u be the largest need value
# <= F (the free servers).  Any job with need > u has need > F — free
# servers only shrink as the walk advances, so it can never be taken and
# the walk may pass it forever.  Jobs with need <= u are taken while the
# running prefix sum of their needs fits (the condition fails
# monotonically along the round's eligibles, so the taken set is a prefix
# and the prefix sum counts exactly the jobs taken before).  A round that
# stops early leaves F < u, so u strictly decreases and len(NU) unrolled
# rounds complete any walk.
#
# Exactly 2J events exist per lane (each job arrives once and departs
# once; preemptions happen inside an event, adding none), and whenever
# jobs are in the system at least one is running — every packing order
# starts with a job of need <= k — so a fixed 2J-step scan processes
# every event.  Per-job completion/first-start records are emitted at
# departure events and scattered to [R, J] arrays on the host
# (`_srpt_scatter_events`), like the BS event core.
# --------------------------------------------------------------------------


def _srpt_first_fit(kk, need_w, cand, NU: tuple):
    """Vectorized first-fit packing walk over pre-ordered candidates.

    ``need_w`` [R, Q] holds the candidate needs *in packing order* (0 for
    empty slots), ``cand`` [R, Q] the candidate mask, ``kk`` [R] the free
    servers, and ``NU`` the static ascending tuple of distinct need
    values.  Returns the taken mask, bit-equal to the sequential walk
    ``for j in order: if need[j] <= free: take; free -= need[j]``.
    """
    R, Q = need_w.shape
    pos = jnp.arange(Q, dtype=jnp.int32)[None, :]
    F = kk
    take = jnp.zeros((R, Q), bool)
    ptr = jnp.zeros(R, jnp.int32)
    for _ in range(len(NU)):
        u = jnp.zeros_like(F)
        for v in NU:  # ascending: ends at the largest need value <= F
            u = jnp.where(v <= F, float(v), u)
        elig = (cand & ~take & (need_w >= 1.0) & (need_w <= u[:, None])
                & (pos >= ptr[:, None]))
        csum = jnp.cumsum(jnp.where(elig, need_w, 0.0), axis=1)
        newt = elig & (F[:, None] - (csum - need_w) >= u[:, None])
        take = take | newt
        F = F - jnp.sum(jnp.where(newt, need_w, 0.0), axis=1)
        missed = elig & ~newt
        ptr = jnp.where(missed.any(axis=1),
                        jnp.argmax(missed, axis=1).astype(jnp.int32),
                        jnp.asarray(Q, jnp.int32))
    return take


#: slot-table columns of the SRPT scan state (one packed [R, Q, 8] array:
#: one gather fetches a departing job's record, one scatter admits or
#: clears a slot — the op-count discipline of ``_bs_make_step``)
_SRPT_COLS = 8  # job, arrival, need, rem, run_start, running, started, fstart


def _srpt_make_step(jobrec, kk, Q: int, NU: tuple, sf: bool, j_live=None,
                    sort=None):
    """Event step of the preemptive SRPT-family scan (see section above).

    ``jobrec`` [R, J, 3] packs (arrival, service, need); ``kk`` [R] is the
    per-lane server count — *data*, not shape, so heterogeneous-k grid
    cells need no dead-capacity masking.  ``sf`` statically selects
    ServerFilling-SRPT (rank = remaining x need, prefix-M completion)
    over FirstFit-SRPT (rank = remaining, first-fit over everything).
    ``j_live`` (optional [R]) caps admitted arrivals — the J-padding
    guard of the grid driver; trailing steps past a lane's 2*j_live true
    events are no-ops.

    ``sort`` swaps the stable sort implementation (signature and contract
    of ``jax.lax.sort``, the default): the fused Pallas kernels pass the
    in-kernel bitonic network of :mod:`repro.kernels.msj_scan.sort`, which
    is bit-equal to ``lax.sort`` — this reference step stays the oracle
    either way.  This is the *reference* step; the batched jax engines run
    the op-lean :func:`_srpt_fast_make_step` below, pinned bit-identical
    to this one in ``tests/test_sim_cross.py``.
    """
    if sort is None:
        sort = jax.lax.sort
    R, J, _ = jobrec.shape
    dt = jobrec.dtype
    INF = jnp.asarray(jnp.inf, dt)
    GUARD = jnp.asarray(0.5 * _BIG, dt)
    jl = J if j_live is None else j_live
    lanes = jnp.arange(R)
    pos = jnp.arange(Q, dtype=jnp.int32)[None, :]
    slot_i = jnp.broadcast_to(pos, (R, Q))

    def taa(a, idx):
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def unsort(slot_perm, take):
        # inverse-permute ``take`` back to slot order: ``slot_perm`` is an
        # exact per-lane permutation of 0..Q-1 (the slot-index payload
        # carried through the stable sorts), so a scatter is bit-equal to
        # re-sorting by slot index — at a fraction of the cost
        return jnp.zeros((R, Q), bool).at[
            lanes[:, None], slot_perm.astype(jnp.int32)].set(take)

    def rec(idx):
        return jnp.take_along_axis(jobrec, idx[:, None, None], axis=1)[:, 0]

    def step(carry, _):
        ai, S, ovf, npre, ne, peak = carry
        job, s_need, s_rem = S[..., 0], S[..., 2], S[..., 3]
        s_rs, s_run = S[..., 4], S[..., 5] > 0

        # -- candidate events: next arrival vs earliest departure.  A
        # running job's completion time is run_start + rem — the identical
        # addition the oracle's departure push uses, so ties break the
        # same way (arrivals first, matching the heap kind order).
        j_arr = jnp.minimum(ai, J - 1)
        rec_a = rec(j_arr)
        Ta = jnp.where(ai < jl, rec_a[:, 0], INF)
        comp = jnp.where(s_run, s_rs + s_rem, _BIG)
        qd = jnp.argmin(comp, axis=1).astype(jnp.int32)
        Tc = taa(comp, qd)
        is_arr = (ai < jl) & (Ta <= Tc)
        is_dep = (~is_arr) & (Tc < GUARD)
        active = is_arr | is_dep
        ne = ne + jnp.where(active, 1, 0)
        t = jnp.where(is_arr, Ta, Tc)

        # -- departure record, read before the slot is cleared
        dep = jnp.take_along_axis(S, qd[:, None, None], axis=1)[:, 0]
        job_out = jnp.where(is_dep, dep[:, 0], -1.0)
        t_out = jnp.where(is_dep, Tc, jnp.zeros(R, dt))
        fs_out = jnp.where(is_dep, dep[:, 7], jnp.zeros(R, dt))

        # -- admit the arrival into the first free slot / clear the
        # departed slot: mutually exclusive, one merged 1-entry scatter
        free = job < 0
        fs = jnp.argmax(free, axis=1).astype(jnp.int32)
        has_free = taa(free, fs)
        do_ins = is_arr & has_free
        ovf = ovf | (is_arr & ~has_free)
        idx = jnp.where(do_ins, fs, jnp.where(is_dep, qd, Q))
        zero = jnp.zeros(R, dt)
        vals = jnp.stack(
            [jnp.where(is_arr, j_arr.astype(dt), -1.0),
             jnp.where(is_arr, rec_a[:, 0], zero),
             jnp.where(is_arr, rec_a[:, 2], zero),
             jnp.where(is_arr, rec_a[:, 1], zero),
             zero, zero, zero, zero], axis=1)
        S = S.at[lanes, idx].set(vals, mode="drop")
        ai = ai + jnp.where(is_arr, 1, 0)
        job, s_arr, s_need, s_rem = S[..., 0], S[..., 1], S[..., 2], S[..., 3]
        s_rs, s_run = S[..., 4], S[..., 5] > 0
        s_started, s_fstart = S[..., 6] > 0, S[..., 7]
        occ = job >= 0
        # peak in-system count (a dropped arrival still counts: on overflow
        # the reported peak is the capacity the run *needed*, a lower bound)
        peak = jnp.maximum(peak, jnp.sum(occ, axis=1, dtype=jnp.int32)
                           + jnp.where(is_arr & ~has_free, 1, 0))

        # -- reconcile at t: rank-sort the in-system set (stable, ties by
        # arrival), pick the desired running set, preempt / start.
        # Identical float ops to Simulation.remaining_now for every job.
        cur_rem = jnp.where(
            s_run, jnp.maximum(0.0, s_rem - (t[:, None] - s_rs)), s_rem)
        rank = cur_rem * s_need if sf else cur_rem
        rk = jnp.where(occ, rank, INF)
        ak = jnp.where(occ, s_arr, INF)
        rk_s, _, need_s, slot_s = sort(
            (rk, ak, s_need, slot_i), dimension=1, num_keys=2,
            is_stable=True)
        occ_s = rk_s < GUARD
        if sf:
            # ServerFilling: candidate prefix M = smallest m whose
            # cumulative need reaches k, packed largest-need-first
            # (stable by rank below it — the oracle's sorted(M, key=
            # (-need, rank)) over a rank-ordered list); when the total
            # need is below k every job simply runs.
            cum = jnp.cumsum(jnp.where(occ_s, need_s, 0.0), axis=1)
            has_m = cum[:, -1] >= kk
            idx_m = jnp.argmax(cum >= kk[:, None], axis=1)
            in_M = occ_s & (pos <= idx_m[:, None])
            key1 = jnp.where(in_M, -need_s, _BIG)
            key1_s, _, need_w, slot_w = sort(
                (key1, rk_s, need_s, slot_s), dimension=1, num_keys=2,
                is_stable=True)
            take = _srpt_first_fit(kk, need_w, key1_s < GUARD, NU)
            desired = jnp.where(has_m[:, None], unsort(slot_w, take), occ)
        else:
            take = _srpt_first_fit(kk, need_s, occ_s, NU)
            desired = unsort(slot_s, take)

        to_pre = active[:, None] & s_run & ~desired
        to_start = active[:, None] & desired & ~s_run
        npre = npre + jnp.sum(to_pre, axis=1).astype(jnp.int32)
        new_run = jnp.where(active[:, None], desired, s_run)
        S = jnp.stack(
            [job, s_arr, s_need,
             jnp.where(to_pre, cur_rem, s_rem),
             jnp.where(to_start, t[:, None], s_rs),
             new_run.astype(dt),
             (s_started | to_start).astype(dt),
             jnp.where(to_start & ~s_started, t[:, None], s_fstart)],
            axis=2)
        return (ai, S, ovf, npre, ne, peak), (job_out, t_out, fs_out)

    return step


def _srpt_init(R: int, Q: int, dt):
    """Empty slot table + counters (the reference scan carry), ``R`` lanes.

    Carry = (arrival cursor, slot table [R, Q, 8], overflow flag,
    preemption count, processed-event count, peak in-system count).
    """
    S = jnp.zeros((R, Q, _SRPT_COLS), dt).at[..., 0].set(-1.0)
    return (jnp.zeros(R, jnp.int32), S, jnp.zeros(R, bool),
            jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32),
            jnp.zeros(R, jnp.int32))


# --------------------------------------------------------------------------
# Fast SRPT step: the engine="jax" / "jax-shard" substrate.
#
# Profiling the reference step on XLA:CPU shows the two 4-operand stable
# lax.sort calls dominating the per-event cost (the multi-operand
# comparator is an opaque library call per event), with the [R, Q]
# boolean unsort scatter second — ScatterExpander serializes it into a
# Q·R-trip while loop.  The step below is bit-identical to the reference
# (pinned in tests/test_sim_cross.py) but restructures every hot op into
# single-operand sorts, most of them u32/u64 packs of composite integer
# keys:
#
# * Rank keys are nonnegative f64 (or +inf empty sentinels): one single-
#   operand sort of the keys + a branchless bisection turns the (rank,
#   arrival) sort into collapsed integer ranks.  The keys are sorted as
#   floats, not as u64 bit patterns: the TPU's f64 emulation refuses a
#   bitcast to u64, and float order ties -0.0 with +0.0 exactly as the
#   reference sort and the python oracle do (bit order would put -0.0
#   after +inf; only a -0.0 service time could produce one).
# * Tie-break arrival times are replaced by dense per-lane arrival *ranks*
#   (a one-time cummax over the sorted trace), preserving every equality
#   class, so the composite (rank, arrival-rank, slot) key packs into one
#   machine word — the second sort becomes a single-operand integer sort.
# * The unsort scatter becomes another pack sort: sorting
#   (slot_index << bQ | position) recovers the inverse permutation as a
#   gather (an exact permutation, so "sort by destination" == scatter).
# * The first-fit walk runs in pure int32 (needs are integers, and
#   ``floor(k)`` is exact for the capacity test: integer LHS >= u - frac
#   iff LHS >= u for 0 <= frac < 1), with the per-round threshold u from
#   a count-leading-zeros when NU is the contiguous powers of two.  The
#   reference walk's blocking pointer is provably redundant — within a
#   round takes form a prefix of the eligibles, and u never increases —
#   and, as there, len(NU) rounds complete the walk.
# * ServerFilling with pow2-contiguous NU *and* k a multiple of max(NU)
#   (``k_mult``, a static flag the callers compute host-side) admits a
#   closed form: capacity stays a multiple of the class need while that
#   class is walked, so the threshold rounds converge to the per-class
#   greedy count min(cnt_c, F_c // c) — no while loop at all.
#
# The slot table is carried as per-column arrays in their natural dtypes
# (i32 ids/needs, bool flags) instead of one [R, Q, 8] f64 stack: the
# integer columns feed the pack sorts without per-event casts.
#
# Pairwise ordering (the static ``pairwise``, Q up to a per-backend
# _SRPT_PAIRWISE_MAX_Q).  On a TPU the sorts are cheap and the row
# gathers around them (bisection probes, the gathers into sorted order
# and back) are not: each is a serial element-by-element gather along
# the lane axis.  So the step can stay in slot order.  Each order above
# is computed as per-slot counts of the slots ordered before it, with
# dense [R, Q, Q] compares and int32 sums (``_srpt_count_before``): the
# collapsed rank r1 = #{rk_j < rk_i} is the bisection's result, the
# position #{pack_j < pack_i} of the unique (r1, arrival rank, slot)
# pack key is the stable sort's inverse permutation, and the walk's
# inclusive prefix sum in packing order is the sum of w_j over the slots
# at or before slot i in that order.  The walk's rounds, the closed form
# and every output are unchanged, so the two orderings are bit-identical;
# ``take`` comes out in slot order and needs no unsort.  The pairwise
# form costs O(Q^2) per lane and event.
# --------------------------------------------------------------------------


def _srpt_ff_walk(Fi0, need_w, cand, NU: tuple, NUi, prefix=None):
    """Integer first-fit walk: bit-equal to :func:`_srpt_first_fit` on
    integer needs/capacities (see section comment for the argument).

    ``Fi0`` [R] i32 is floor(k); ``need_w`` [R, Q] i32 the candidate
    needs (0 for empty); ``cand`` the candidate mask.  ``prefix`` maps an
    [R, Q] i32 row to its inclusive prefix sums in packing order; the
    default, a cumsum along the row, means the rows are already in
    packing order.
    """
    if prefix is None:
        prefix = partial(jnp.cumsum, axis=1, dtype=jnp.int32)
    R, Q = need_w.shape
    pow2 = tuple(NU) == tuple(2 ** i for i in range(len(NU)))
    maxnu = int(max(NU))
    # Over the rounds that take anything, u falls strictly through the
    # values of NU (a round either takes every eligible job or leaves
    # F < u), and a round that takes nothing changes nothing: len(NU)
    # rounds complete every lane, as in the reference walk.  A fixed
    # count keeps the step's cost independent of the data.
    take, F = jnp.zeros((R, Q), bool), Fi0
    for _ in range(len(NU)):
        if pow2:
            # largest NU <= F is min(2^msb(F), max NU) when NU is the
            # contiguous powers of two
            u = jnp.minimum(
                jnp.where(F > 0, 1 << (31 - jax.lax.clz(jnp.maximum(F, 1))),
                          0), maxnu)
        else:
            cnt = jnp.sum(NUi[None, :] <= F[:, None], axis=1,
                          dtype=jnp.int32)
            u = jnp.where(cnt > 0, jnp.take(NUi, jnp.clip(cnt - 1, 0)), 0)
        elig = cand & ~take & (need_w <= u[:, None])
        csum = prefix(jnp.where(elig, need_w, 0))
        # Within a round F - (csum - need) is nonincreasing along the row,
        # so takes are a prefix of the eligible set; with u nonincreasing
        # across rounds no skipped job regains eligibility, which makes
        # the reference walk's blocking pointer a no-op.
        newt = elig & (F[:, None] - (csum - need_w) >= u[:, None])
        take = take | newt
        F = F - jnp.sum(jnp.where(newt, need_w, 0), axis=1, dtype=jnp.int32)
    return take


#: Per backend, the largest slot table (the static ``Q``) that the fast
#: step orders by pairwise precedence counts; larger tables, and the
#: backends not listed, sort.  The pairwise form costs O(Q^2) per lane
#: and event, the sorts O(Q log^2 Q) plus row gathers.  Measured on one
#: ``_srpt_scan_batch`` call of each form (PERF.md, "Where the time
#: goes"): on XLA:CPU pairwise wins at Q <= 128 and loses from 256 on; on
#: a TPU v5e it wins at every Q measured, 256 to 16384.
_SRPT_PAIRWISE_MAX_Q = {"cpu": 128, "tpu": 16384}


def _srpt_pairwise(Q: int) -> bool:
    """Whether the fast SRPT step orders a ``Q``-slot table pairwise on
    the default backend: the callers' static ``pairwise`` argument."""
    return Q <= _SRPT_PAIRWISE_MAX_Q.get(jax.default_backend(), 0)


def _srpt_count_before(key, w=None, inclusive=False):
    """Per lane and slot ``i``: the sum of ``w`` (a count when None) over
    the slots ``j`` that ``key`` orders before ``i``, and ``i`` itself if
    ``inclusive``.

    One dense compare-and-reduce over an [R, Q, Q] block (``j`` on the
    reduced axis 1, ``i`` minor), in int32: no sort and no gather, slot
    order in and out.  Over a key unique per slot, the exclusive count is
    the slot's position in the key's ascending order and the inclusive
    sum the prefix sum of ``w`` in that order.
    """
    kj, ki = key[:, :, None], key[:, None, :]
    before = (kj <= ki) if inclusive else (kj < ki)
    if w is None:
        return jnp.sum(before, axis=1, dtype=jnp.int32)
    return jnp.sum(jnp.where(before, w[:, :, None], 0), axis=1,
                   dtype=jnp.int32)


def _srpt_fast_init(R: int, Q: int, dt):
    """Empty per-column slot table + counters (the fast scan carry).

    Same logical state as :func:`_srpt_init`, carried as one array per
    column in its natural dtype.
    """
    cols = (jnp.full((R, Q), -1, jnp.int32),   # job id
            jnp.zeros((R, Q), jnp.int32),      # arrival rank
            jnp.zeros((R, Q), jnp.int32),      # need
            jnp.zeros((R, Q), dt),             # remaining work
            jnp.zeros((R, Q), dt),             # run start
            jnp.zeros((R, Q), bool),           # running
            jnp.zeros((R, Q), bool),           # started
            jnp.zeros((R, Q), dt))             # first start
    return (jnp.zeros(R, jnp.int32), cols, jnp.zeros(R, bool),
            jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32),
            jnp.zeros(R, jnp.int32))


def _srpt_fast_make_step(jobrec, kk, Q: int, NU: tuple, sf: bool,
                         j_live=None, k_mult: bool = False,
                         pairwise: bool = False):
    """Op-lean SRPT event step, bit-identical to :func:`_srpt_make_step`.

    Same inputs as the reference factory plus ``k_mult``, the static
    "every lane's k is an integer multiple of max(NU)" flag enabling the
    closed-form ServerFilling walk, and ``pairwise``, the static choice
    of the pairwise ordering over the sorts (see the section comment;
    :func:`_srpt_pairwise` makes it).  The carry is the
    :func:`_srpt_fast_init` per-column layout.
    """
    R, J, _ = jobrec.shape
    dt = jobrec.dtype
    INF = jnp.asarray(jnp.inf, dt)
    GUARD = jnp.asarray(0.5 * _BIG, dt)
    jl = J if j_live is None else j_live
    pos = jnp.arange(Q, dtype=jnp.int32)[None, :]
    iota_u = jnp.broadcast_to(jnp.arange(Q, dtype=jnp.uint32), (R, Q))

    # --- one-time precomputation: dense arrival ranks + integer needs.
    # Arrival times enter the sorts only as tie-break keys; the dense rank
    # (strictly increasing across distinct times, equal within a tie
    # group) preserves every equality class, so tie-breaking is identical.
    arrival = jobrec[:, :, 0]
    ii = jnp.arange(1, J, dtype=jnp.int32)
    neq = arrival[:, 1:] != arrival[:, :-1]
    abt = jnp.concatenate(
        [jnp.zeros((R, 1), jnp.int32),
         jax.lax.cummax(jnp.where(neq, ii[None, :], 0), axis=1)], axis=1)
    need_t = jobrec[:, :, 2].astype(jnp.int32)

    assert all(float(v).is_integer() for v in NU), \
        "integer walk requires integer server needs"
    NUi = jnp.asarray([int(v) for v in NU], jnp.int32)
    Fi0 = jnp.floor(kk).astype(jnp.int32)
    kceil = (-jnp.floor(-kk)).astype(jnp.int32)

    bQ = int(np.log2(Q))
    assert 1 << bQ == Q, "Q must be a power of two (see _srpt_args)"
    bJ = max(1, int(np.ceil(np.log2(max(J, 2)))))
    packdt = jnp.uint32 if (bQ + 1) + bJ + bQ <= 32 else jnp.uint64

    NCLS = len(NU)
    maxneed = int(max(NU))
    pow2nu = tuple(NU) == tuple(2 ** i for i in range(len(NU)))
    closed_sf = sf and pow2nu and k_mult
    bN = max(1, int(np.ceil(np.log2(maxneed + 2))))
    pay2 = 2 * bN + 1 + bQ <= 32
    lut = np.full(maxneed + 1, NCLS, np.int32)
    for i, v in enumerate(sorted(NU, reverse=True)):
        lut[int(v)] = i
    lut = jnp.asarray(lut)
    assert max(1, int(np.ceil(np.log2(NCLS + 1)))) + bQ <= 32
    assert not (pairwise and sf) or (maxneed + 1) * Q < 2 ** 31, \
        "the ServerFilling walk key (maxneed - need) * Q + pos is int32"

    def taa(a, idx):
        return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def unsort(slot_perm, take):
        # Inverse-permute via one u32 pack sort + gather (a [R, Q] scatter
        # expands to a sequential R*Q-trip while loop on XLA:CPU).
        packi = (slot_perm.astype(jnp.uint32) << bQ) | iota_u
        inv = (jax.lax.sort((packi,), dimension=1, num_keys=1)[0]
               & (Q - 1)).astype(jnp.int32)
        return jnp.take_along_axis(take, inv, axis=1)

    def bsearch(srt, v):
        # branchless searchsorted-left of every v in its own sorted row
        lo = jnp.zeros(v.shape, jnp.int32)
        step = Q >> 1
        while step >= 1:
            probe = lo + step - 1
            sv = jnp.take_along_axis(srt, probe, axis=1)
            lo = lo + jnp.where(sv < v, step, 0)
            step >>= 1
        sv = jnp.take_along_axis(srt, jnp.minimum(lo, Q - 1), axis=1)
        return lo + jnp.where((lo < Q) & (sv < v), 1, 0)

    def pack_key(r1, abr):
        # (collapsed rank, arrival rank, slot) in one word: unique per
        # slot, and its order is the reference's stable (rank, arrival)
        # sort
        return ((r1.astype(packdt) << (bJ + bQ))
                | (abr.astype(packdt) << bQ) | iota_u.astype(packdt))

    def class_ends(cand, nd):
        # Closed-form ServerFilling (NU contiguous powers of two and k a
        # multiple of max(NU)): capacity stays a multiple of the class
        # need while that class is walked, so the threshold rounds
        # converge to the per-class greedy count min(cnt_c, F_c // c).
        # Returns [R, NCLS], classes by descending need: where each
        # class's takes end in the packing order.
        onec = cand[:, :, None] & (nd[:, :, None] == NUi[None, None, ::-1])
        cnt_c = jnp.sum(onec, axis=1, dtype=jnp.int32)
        lims = []
        F = Fi0
        for c in range(NCLS):
            nu_c = int(NU[NCLS - 1 - c])
            lim = jnp.minimum(cnt_c[:, c], F // nu_c)
            F = F - lim * nu_c
            lims.append(lim)
        start_t = jnp.cumsum(cnt_c, axis=1, dtype=jnp.int32) - cnt_c
        return start_t + jnp.stack(lims, axis=1)

    def desired_sorted(rk, abr, need, occ):
        # single-operand sort of the rank keys + bisection collapses them
        # to integers, then one pack sort on (rank', arrival rank, slot)
        # yields the stable permutation
        srt = jax.lax.sort((rk,), dimension=1, num_keys=1)[0]
        ps = jax.lax.sort((pack_key(bsearch(srt, rk), abr),), dimension=1,
                          num_keys=1)[0]
        perm = (ps & (Q - 1)).astype(jnp.int32)
        need_s = jnp.take_along_axis(need, perm, axis=1)
        occ_s = need_s >= 1
        if not sf:
            return unsort(perm, _srpt_ff_walk(Fi0, need_s, occ_s, NU, NUi))
        cum = jnp.cumsum(jnp.where(occ_s, need_s, 0), axis=1,
                         dtype=jnp.int32)
        has_m = cum[:, -1] >= kceil
        idx_m = jnp.argmax(cum >= kceil[:, None], axis=1)
        in_M = occ_s & (pos <= idx_m[:, None])
        if pay2:
            # key = descending-need class (maxneed - need; non-M last);
            # payload need/in_M/rank ride along so no post-sort gathers.
            # Non-M entries reorder by need, which is sound: they are
            # never eligible, so take and missed are identically zero
            # there.
            key2 = jnp.where(in_M, maxneed - need_s,
                             maxneed + 1).astype(jnp.uint32)
            pack2 = ((key2 << (bN + 1 + bQ))
                     | (need_s.astype(jnp.uint32) << (1 + bQ))
                     | (in_M << bQ) | iota_u)
            ps2 = jax.lax.sort((pack2,), dimension=1, num_keys=1)[0]
            need_w = ((ps2 >> (1 + bQ)) & ((1 << bN) - 1)).astype(jnp.int32)
            cand_w = ((ps2 >> bQ) & 1) == 1
            perm2 = (ps2 & (Q - 1)).astype(jnp.int32)
            slot_w = jnp.take_along_axis(perm, perm2, axis=1)
        else:
            cls = jnp.where(in_M, jnp.take(lut, need_s),
                            NCLS).astype(jnp.uint32)
            ps2 = jax.lax.sort(((cls << bQ) | iota_u,), dimension=1,
                               num_keys=1)[0]
            perm2 = (ps2 & (Q - 1)).astype(jnp.int32)
            need_w = jnp.take_along_axis(need_s, perm2, axis=1)
            slot_w = jnp.take_along_axis(perm, perm2, axis=1)
            cand_w = jnp.take_along_axis(in_M, perm2, axis=1)
        if closed_sf:
            clsw = NCLS - 1 - (31 - jax.lax.clz(jnp.maximum(need_w, 1)))
            endp = jnp.take_along_axis(class_ends(cand_w, need_w),
                                       jnp.clip(clsw, 0, NCLS - 1), axis=1)
            take = cand_w & (pos < endp)
        else:
            take = _srpt_ff_walk(Fi0, need_w, cand_w, NU, NUi)
        return jnp.where(has_m[:, None], unsort(slot_w, take), occ)

    def desired_pairwise(rk, abr, need, occ):
        # The same orders as counts of the slots ordered before each
        # slot, all in slot order: nothing is permuted, so nothing is
        # gathered back.  r1 is bsearch(sort(rk), rk) (the same float
        # compare: -0.0 ties +0.0, INF marks an empty slot); the pack
        # key is unique per slot, so spos is the stable sort's inverse
        # permutation.
        spos = _srpt_count_before(pack_key(_srpt_count_before(rk), abr))
        cand = need >= 1
        if not sf:
            return _srpt_ff_walk(
                Fi0, need, cand, NU, NUi,
                prefix=partial(_srpt_count_before, spos, inclusive=True))
        w = jnp.where(cand, need, 0)
        # in M: the need ranked strictly before a slot is below k (the
        # sorted cumsum has not reached k before it)
        in_M = cand & (_srpt_count_before(spos, w) < kceil[:, None])
        has_m = jnp.sum(w, axis=1) >= kceil
        # the walk order of M: descending need, then rank order
        key2 = (maxneed - need) * Q + spos
        if closed_sf:
            ends = class_ends(in_M, need)
            endp = sum(jnp.where(need == int(v), ends[:, c:c + 1], 0)
                       for c, v in enumerate(sorted(NU, reverse=True)))
            at = _srpt_count_before(key2, in_M.astype(jnp.int32))
            take = in_M & (at < endp)
        else:
            take = _srpt_ff_walk(
                Fi0, need, in_M, NU, NUi,
                prefix=partial(_srpt_count_before, key2, inclusive=True))
        return jnp.where(has_m[:, None], take, occ)

    def step(carry, _):
        ai, cols, ovf, npre, ne, peak = carry
        job, abr, need, rem, rs, run, started, fstart = cols

        j_arr = jnp.minimum(ai, J - 1)
        rec_a = jnp.take_along_axis(jobrec, j_arr[:, None, None],
                                    axis=1)[:, 0]
        Ta = jnp.where(ai < jl, rec_a[:, 0], INF)
        comp = jnp.where(run, rs + rem, _BIG)
        qd = jnp.argmin(comp, axis=1).astype(jnp.int32)
        Tc = taa(comp, qd)
        is_arr = (ai < jl) & (Ta <= Tc)
        is_dep = (~is_arr) & (Tc < GUARD)
        active = is_arr | is_dep
        ne = ne + jnp.where(active, 1, 0)
        t = jnp.where(is_arr, Ta, Tc)

        job_out = jnp.where(is_dep, taa(job, qd), -1).astype(dt)
        t_out = jnp.where(is_dep, Tc, 0.0)
        fs_out = jnp.where(is_dep, taa(fstart, qd), 0.0)

        free = job < 0
        fs_i = jnp.argmax(free, axis=1).astype(jnp.int32)
        has_free = taa(free, fs_i)
        do_ins = is_arr & has_free
        ovf = ovf | (is_arr & ~has_free)
        idx = jnp.where(do_ins, fs_i, jnp.where(is_dep, qd, Q))
        mask = pos == idx[:, None]
        job = jnp.where(mask, jnp.where(is_arr, j_arr, -1)[:, None], job)
        abr = jnp.where(
            mask, jnp.where(is_arr, taa(abt, j_arr), 0)[:, None], abr)
        need = jnp.where(
            mask, jnp.where(is_arr, taa(need_t, j_arr), 0)[:, None], need)
        rem = jnp.where(
            mask, jnp.where(is_arr, rec_a[:, 1], 0.0)[:, None], rem)
        rs = jnp.where(mask, 0.0, rs)
        run = run & ~mask
        started_pi = started & ~mask
        fstart_pi = jnp.where(mask, 0.0, fstart)
        ai = ai + jnp.where(is_arr, 1, 0)
        occ = job >= 0
        # peak in-system count (a dropped arrival still counts: on
        # overflow the reported peak is a lower bound on the needed Q)
        peak = jnp.maximum(peak, jnp.sum(occ, axis=1, dtype=jnp.int32)
                           + jnp.where(is_arr & ~has_free, 1, 0))

        cur_rem = jnp.where(
            run, jnp.maximum(0.0, rem - (t[:, None] - rs)), rem)
        rank = cur_rem * need.astype(dt) if sf else cur_rem
        rk = jnp.where(occ, rank, INF)
        desired = (desired_pairwise if pairwise else desired_sorted)(
            rk, abr, need, occ)

        to_pre = active[:, None] & run & ~desired
        to_start = active[:, None] & desired & ~run
        npre = npre + jnp.sum(to_pre, axis=1).astype(jnp.int32)
        cols = (job, abr, need,
                jnp.where(to_pre, cur_rem, rem),
                jnp.where(to_start, t[:, None], rs),
                jnp.where(active[:, None], desired, run),
                started_pi | to_start,
                jnp.where(to_start & ~started_pi, t[:, None], fstart_pi))
        return (ai, cols, ovf, npre, ne, peak), (job_out, t_out, fs_out)

    return step


def _srpt_stream_core(arrival, need, service, kk, carry, Q: int, NU: tuple,
                      sf: bool, length: int, j_live=None,
                      k_mult: bool = False, pairwise: bool = False):
    """``length`` SRPT event steps resumed from ``carry``, batched.

    Runs the fast step (``carry`` is the :func:`_srpt_fast_init` layout).
    Returns the updated carry plus the per-event (job id, completion,
    first start) record streams, each [R, length]; -1 job ids mark
    non-departure steps.
    """
    jobrec = jnp.stack([arrival, service, need], axis=2)
    step = _srpt_fast_make_step(jobrec, kk, Q, NU, sf, j_live=j_live,
                                k_mult=k_mult, pairwise=pairwise)
    carry, (job_ev, t_ev, fs_ev) = jax.lax.scan(step, carry, None,
                                                length=length)
    return carry, job_ev.T, t_ev.T, fs_ev.T


def _srpt_core(arrival, need, service, kk, Q: int, NU: tuple, sf: bool,
               k_mult: bool = False, pairwise: bool = False):
    """Full-trace SRPT event scan: 2J steps from an empty system.

    Returns the event streams plus the per-lane (ovf, npre, ne, peak)
    counters: slot-table overflow (the sys_cap analogue of the BS ring
    overflow), preemption count, processed-event count (== 2J on
    success), and peak in-system job count (the overflow diagnostic).
    """
    R, J = arrival.shape
    carry0 = _srpt_fast_init(R, Q, arrival.dtype)
    carry, job_ev, t_ev, fs_ev = _srpt_stream_core(
        arrival, need, service, kk, carry0, Q, NU, sf, 2 * J,
        k_mult=k_mult, pairwise=pairwise)
    return job_ev, t_ev, fs_ev, carry[2], carry[3], carry[4], carry[5]


def _srpt_scatter_events(J: int, job_ev, t_ev, fs_ev):
    """Scatter [R, 2J] departure records to per-job [R, J] arrays.

    Each job departs exactly once per replication, so every target cell
    is written exactly once — one flat advanced-indexing assignment for
    the whole batch, like ``_bs_scatter_events``.
    """
    job_ev = np.asarray(job_ev)
    jobs = job_ev.astype(np.int64)
    valid = jobs >= 0
    rows = np.broadcast_to(np.arange(job_ev.shape[0])[:, None],
                           job_ev.shape)[valid]
    cols = jobs[valid]
    comp = np.zeros((job_ev.shape[0], J))
    fstart = np.zeros((job_ev.shape[0], J))
    comp[rows, cols] = np.asarray(t_ev)[valid]
    fstart[rows, cols] = np.asarray(fs_ev)[valid]
    return comp, fstart


def _srpt_args(trace_or_batch, queue_cap) -> int:
    """The slot-table capacity ``Q`` (system size bound) of an SRPT scan.

    Results are independent of ``Q`` unless the in-system job count ever
    exceeds it, which raises loudly (``_srpt_check_ovf``) instead of
    returning a silently wrong path.  The default ``min(J, max(4k, 256))``
    comfortably bounds any stable workload; per-step cost grows with
    ``Q`` (as ``Q^2`` in the pairwise ordering of the fast step), so it
    is deliberately not ``J``.  The
    result is rounded up to a power of two: the slot-index pack keys of
    the fast step and the bitonic network of the Pallas kernels both
    need it, and results are Q-independent below the overflow bound.
    """
    J = int(trace_or_batch.num_jobs)
    if queue_cap is None:
        queue_cap = max(4 * int(trace_or_batch.k), 256)
    elif queue_cap < 1:
        raise ValueError(f"queue_cap must be >= 1, got {queue_cap}")
    q = max(1, min(J, int(queue_cap)))
    return 1 << (q - 1).bit_length()
