"""Event-for-event cross-validation of the lax.scan simulators.

The contract promised by the ``sim_jax`` module docstring: every scan
simulator (and its batched vmap variant) reproduces the Python
event-driven engine's sample path exactly — same start times, same
responses, same blocking decisions — on the traces both can run.  Also
pins the O(k) sorted-invariant FCFS step bit-for-bit to the retained
full-sort reference step, and the fused Pallas kernels
(``repro.kernels.msj_scan``, interpret mode on CPU) bit-for-bit (rtol=0)
to the jax-batch scan cores at k ∈ {32, 256} — including the preemptive
``sf-srpt``/``ff-srpt`` kernels, whose in-kernel stable bitonic
rank/permute network is additionally property-tested against
``jax.lax.sort(..., is_stable=True)`` on adversarial key sets.
"""

import heapq

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp
from jax import enable_x64

from repro.core import sim_jax
from repro.core.policies import make_policy
from repro.core.sim_batch import (bs_sim_batch, fcfs_sim_batch,
                                  loss_queue_sim_batch, modified_bs_sim_batch)
from repro.core.sim_jax import bs_sim, fcfs_sim, loss_queue_sim, \
    modified_bs_sim
from repro.core.simulator import Simulation
from repro.core.workload import Exp, JobClass, Workload, figure1_workload


def small_workload(k=24, load=0.85):
    classes = (
        JobClass("s", 1, Exp(1.0), 0.7),
        JobClass("m", 4, Exp(4.0), 0.2),
        JobClass("l", 8, Exp(8.0), 0.1),
    )
    return Workload(k=k, lam=1.0, classes=classes).with_load(load)


# -- loss queue ---------------------------------------------------------------


def loss_queue_reference(arrival, service, s):
    """Tiny event-driven M/GI/s/s oracle: heap of completion times."""
    comp: list[float] = []
    blocked = np.zeros(len(arrival), dtype=bool)
    for j, (t, svc) in enumerate(zip(arrival, service)):
        while comp and comp[0] <= t:
            heapq.heappop(comp)
        if len(comp) >= s:
            blocked[j] = True
        else:
            heapq.heappush(comp, t + svc)
    return blocked


def test_loss_queue_event_for_event(rng):
    n, s, lam = 5000, 6, 5.0
    arrival = np.cumsum(rng.exponential(1 / lam, n))
    service = rng.exponential(1.0, n)
    res = loss_queue_sim(arrival, service, s)
    ref = loss_queue_reference(arrival, service, s)
    assert np.array_equal(res.blocked, ref)


def test_loss_queue_batched_matches_single(rng):
    R, n, s = 3, 2000, 5
    arrival = np.cumsum(rng.exponential(0.25, (R, n)), axis=1)
    service = rng.exponential(1.0, (R, n))
    batch = loss_queue_sim_batch(arrival, service, s)
    for r in range(R):
        single = loss_queue_sim(arrival[r], service[r], s)
        assert np.array_equal(batch.blocked[r], single.blocked)
        assert np.array_equal(batch.response[r], single.response)


# -- FCFS ---------------------------------------------------------------------


def test_fcfs_event_for_event_vs_python_engine():
    wl = small_workload()
    trace = wl.sample_trace(4000, seed=3)
    sim = Simulation(trace, make_policy("fcfs"))
    sim.run()
    jx = fcfs_sim(trace)
    starts = jx.response + trace.arrival - trace.service
    np.testing.assert_allclose(starts, sim.start_time, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(jx.response, sim.completion - trace.arrival,
                               rtol=1e-12, atol=1e-9)


def test_fcfs_batched_matches_single():
    wl = small_workload()
    batch = wl.sample_traces(2000, 3, seed=11)
    b = fcfs_sim_batch(batch)
    for r in range(batch.reps):
        single = fcfs_sim(batch.rep(r))
        assert np.array_equal(b.response[r], single.response)


def test_fcfs_sorted_step_bitexact_vs_sort_reference():
    """The O(k) roll-and-insert must equal the O(k log k) sort step
    bit-for-bit, including tied arrivals and zero service times."""
    rng = np.random.default_rng(12)
    for k, n_jobs in ((8, 500), (64, 2000), (256, 2000)):
        arrival = np.cumsum(rng.exponential(0.05, n_jobs))
        arrival[1::7] = arrival[0::7][: len(arrival[1::7])]  # inject ties
        arrival = np.sort(arrival)
        need = rng.integers(1, max(2, k // 4), size=n_jobs)
        service = np.where(rng.random(n_jobs) < 0.2, 0.0,
                           rng.exponential(1.0, n_jobs))
        with enable_x64():
            args = (jnp.asarray(arrival, jnp.float64),
                    jnp.asarray(need, jnp.int32),
                    jnp.asarray(service, jnp.float64), k)
            fast = np.asarray(sim_jax._fcfs_scan(*args))
            ref = np.asarray(sim_jax._fcfs_scan_reference(*args))
        assert np.array_equal(fast, ref), f"k={k}"


def test_fcfs_full_need_jobs():
    """Jobs needing all k servers exercise the p == 0 insertion edge."""
    k = 8
    arrival = np.arange(20, dtype=np.float64) * 0.1
    need = np.full(20, k, dtype=np.int64)
    service = np.full(20, 1.0)
    with enable_x64():
        args = (jnp.asarray(arrival), jnp.asarray(need, jnp.int32),
                jnp.asarray(service), k)
        fast = np.asarray(sim_jax._fcfs_scan(*args))
        ref = np.asarray(sim_jax._fcfs_scan_reference(*args))
    assert np.array_equal(fast, ref)
    # serial system: job j starts when job j-1 completes
    np.testing.assert_allclose(fast, np.arange(20) * 1.0 + arrival[0])


# -- ModifiedBS-FCFS ----------------------------------------------------------


def test_modbs_event_for_event_vs_python_engine():
    wl = figure1_workload(256, theta=0.7)
    trace = wl.sample_trace(4000, seed=4)
    sim = Simulation(trace, make_policy("modbs", wl=wl))
    py = sim.run()
    jx = modified_bs_sim(trace, wl=wl)
    np.testing.assert_allclose(jx.response, sim.completion - trace.arrival,
                               rtol=1e-12, atol=1e-9)
    assert py.p_helper == pytest.approx(jx.p_helper, abs=1e-12)


def test_modbs_batched_matches_single():
    wl = figure1_workload(256, theta=0.7)
    batch = wl.sample_traces(2000, 3, seed=13)
    b = modified_bs_sim_batch(batch, wl=wl)
    for r in range(batch.reps):
        single = modified_bs_sim(batch.rep(r), wl=wl)
        assert np.array_equal(b.response[r], single.response)
        assert float(b.p_helper[r]) == single.p_helper
        assert np.array_equal(b.blocked[r], single.blocked)


# -- BS-FCFS proper (Definition 1, rule-3 pull-backs) -------------------------


@pytest.mark.slow
@pytest.mark.parametrize("k", [32, 256])
def test_bs_event_for_event_vs_python_engine(k):
    """The event-indexed 2J-step scan must reproduce the (fixed) Python
    engine's BS-π sample path bit-for-bit — starts, responses, and both
    helper observables — on the Fig.-1 critical workload."""
    wl = figure1_workload(k, theta=0.7)
    trace = wl.sample_trace(4000, seed=3)
    pol = make_policy("bs", wl=wl)
    sim = Simulation(trace, pol)
    sim.run()
    jx = bs_sim(trace, wl=wl)
    # rtol=0: every scan start time is a max/selection over the same event
    # times the engine computes (never a new rounding), and both sides
    # derive response via the identical (start + service) - arrival float
    # ops — starts and responses match bit-for-bit
    assert np.array_equal(jx.start, sim.start_time)
    assert np.array_equal(jx.response, sim.completion - trace.arrival)
    assert jx.p_helper == pol.p_helper_estimate
    assert jx.p_routed == pol.p_routed_estimate


def test_bs_pullbacks_happen_and_differ_from_modbs():
    """Sanity that the cross-validation above exercises rule 3: pull-backs
    occur (served < routed) and the BS path differs from ModifiedBS."""
    wl = figure1_workload(64, theta=0.7)
    trace = wl.sample_trace(3000, seed=5)
    bs = bs_sim(trace, wl=wl)
    mod = modified_bs_sim(trace, wl=wl)
    assert bs.p_helper < bs.p_routed            # some jobs were pulled back
    assert not np.array_equal(bs.response, mod.response)
    assert bs.response.mean() <= mod.response.mean()


@pytest.mark.slow
def test_bs_batched_matches_single():
    wl = figure1_workload(256, theta=0.7)
    batch = wl.sample_traces(2000, 3, seed=13)
    b = bs_sim_batch(batch, wl=wl)
    for r in range(batch.reps):
        single = bs_sim(batch.rep(r), wl=wl)
        assert np.array_equal(b.response[r], single.response)
        assert float(b.p_helper[r]) == single.p_helper
        assert float(b.p_routed[r]) == single.p_routed


def test_bs_queue_cap_overflow_raises():
    """A too-small ring buffer must raise, never silently corrupt."""
    wl = figure1_workload(64, theta=0.7)
    trace = wl.sample_trace(3000, seed=7)
    with pytest.raises(RuntimeError, match="overflow"):
        bs_sim(trace, wl=wl, queue_cap=4)


# -- fused Pallas kernels (interpret mode on CPU) -----------------------------
#
# The rtol=0 contract of the msj_scan kernel family: grid cell r runs the
# *same* step functions as the jax-batch scan cores (see sim_jax's
# "Fused-kernel layer" docstring), so starts/waits/observables must be
# bit-identical, not merely close.  The test iterates the engine registry,
# so a newly registered (policy, engine) pair is cross-validated the
# moment it registers — no hand-written pair list to forget to extend.


@pytest.mark.parametrize("k", [32, 256])
def test_registry_fast_engines_bitexact_vs_jax(k):
    from repro.core import engines

    wl = figure1_workload(k, theta=0.7)
    batch = wl.sample_traces(1200, 2, seed=17)
    # The srpt pallas kernels run the reference step per event in the
    # interpreter, and the bitonic width Q dominates their cost — a
    # shorter batch and a bounded queue_cap keep those legs to seconds
    # while still covering both k values (a too-small cap raises
    # overflow, it never corrupts; the same cap goes to every engine so
    # the comparison stays apples-to-apples).
    srpt_batch = wl.sample_traces(400, 2, seed=17)
    checked = 0
    for policy in engines.policies_for("jax"):
        srpt = policy.endswith("srpt")
        b = srpt_batch if srpt else batch
        kw = {"queue_cap": 96} if srpt else {}
        ref = engines.simulate(policy, b, engine="jax", wl=wl, **kw)
        for eng in engines.engines_for(policy):
            if eng in ("jax", "python"):
                continue
            out = engines.simulate(policy, b, engine=eng, wl=wl, **kw)
            for f in ("response", "wait", "start", "blocked", "p_helper",
                      "p_routed", "preemptions"):
                a, b2 = getattr(out, f), getattr(ref, f)
                assert (a is None) == (b2 is None), (policy, eng, f)
                if a is not None:
                    assert np.array_equal(a, b2), (policy, eng, f)
            checked += 1
    assert checked >= 10   # 5 jax policies x {jax-shard, pallas}


def test_pallas_kernel_family_matches_refs_at_raw_stream_level():
    """Below the sim_batch wrappers: each msj_scan kernel against its ref
    (the scan core with the kernel call signature) on the raw outputs —
    including the BS event stream (tagged/rec_t/ovf) before the host
    scatter."""
    from repro.core.sim_jax import _bs_args
    from repro.kernels.msj_scan import (bs_scan, bs_scan_ref, fcfs_scan,
                                        fcfs_scan_ref, modbs_scan,
                                        modbs_scan_ref)

    wl = figure1_workload(32, theta=0.7)
    batch = wl.sample_traces(800, 2, seed=21)
    slots, s_max, h, q_cap = _bs_args(batch, None, wl, None)
    with enable_x64():
        a = jnp.asarray(batch.arrival, jnp.float64)
        c = jnp.asarray(batch.cls, jnp.int32)
        n = jnp.asarray(batch.need, jnp.int32)
        v = jnp.asarray(batch.service, jnp.float64)
        assert np.array_equal(np.asarray(fcfs_scan(a, n, v, k=batch.k)),
                              np.asarray(fcfs_scan_ref(a, n, v, k=batch.k)))
        out = modbs_scan(a, c, n, v, slots=slots, s_max=s_max, h=h)
        ref = modbs_scan_ref(a, c, n, v, slots=slots, s_max=s_max, h=h)
        for o, r in zip(out, ref):
            assert np.array_equal(np.asarray(o), np.asarray(r))
        out = bs_scan(a, c, n, v, slots=slots, s_max=s_max, h=h,
                      q_cap=q_cap)
        ref = bs_scan_ref(a, c, n, v, slots=slots, s_max=s_max, h=h,
                          q_cap=q_cap)
        for o, r in zip(out, ref):
            assert np.array_equal(np.asarray(o), np.asarray(r))


def test_pallas_single_trace_engines_match():
    """The engine knob on the single-trace wrappers routes to the kernels."""
    wl = figure1_workload(32, theta=0.7)
    trace = wl.sample_trace(600, seed=2)
    assert np.array_equal(fcfs_sim(trace, engine="pallas").response,
                          fcfs_sim(trace).response)
    assert np.array_equal(modified_bs_sim(trace, wl=wl,
                                          engine="pallas").response,
                          modified_bs_sim(trace, wl=wl).response)
    a = bs_sim(trace, wl=wl, engine="pallas")
    b = bs_sim(trace, wl=wl)
    assert np.array_equal(a.response, b.response)
    assert a.p_helper == b.p_helper


def test_unknown_engine_raises():
    wl = small_workload()
    batch = wl.sample_traces(10, 1, seed=0)
    with pytest.raises(ValueError, match="unknown engine"):
        fcfs_sim_batch(batch, engine="tpu")
    with pytest.raises(ValueError, match="unknown engine"):
        fcfs_sim(batch.rep(0), engine="")


# -- O(k) roll-and-insert under ties (property test) --------------------------
#
# Duplicated arrival/service values drive searchsorted(W, comp, "right")
# into tied boundaries (comp equal to one or more entries of W, tied
# arrivals, zero services).  The O(k) sorted-invariant step, the retained
# full-sort reference, and the fused Pallas kernel must agree bit-for-bit
# on every such trace.

_TIE_J = 64  # fixed length: one compile per k for all examples

tie_traces = st.tuples(
    st.sampled_from([8, 32]),                                  # k
    st.lists(st.tuples(st.sampled_from([0.0, 0.0, 0.25, 1.0]),  # gap
                       st.integers(1, 8),                       # need
                       st.sampled_from([0.0, 0.5, 0.5, 1.0, 2.0])),  # svc
             min_size=_TIE_J, max_size=_TIE_J),
)


@settings(max_examples=25, deadline=None)
@given(tie_traces)
def test_fcfs_roll_insert_ties_bitexact(args):
    k, jobs = args
    gaps = np.array([j[0] for j in jobs])
    need = np.minimum(np.array([j[1] for j in jobs]), k)
    svc = np.array([j[2] for j in jobs])
    arrival = np.cumsum(gaps)
    with enable_x64():
        a = jnp.asarray(arrival, jnp.float64)
        n = jnp.asarray(need, jnp.int32)
        v = jnp.asarray(svc, jnp.float64)
        fast = np.asarray(sim_jax._fcfs_scan(a, n, v, k))
        ref = np.asarray(sim_jax._fcfs_scan_reference(a, n, v, k))
        from repro.kernels.msj_scan import fcfs_scan
        fused = np.asarray(fcfs_scan(a[None], n[None], v[None], k=k)[0])
    assert np.array_equal(fast, ref), f"roll-and-insert != sort ref (k={k})"
    assert np.array_equal(fused, ref), f"pallas != sort ref (k={k})"


# -- stable bitonic rank/permute vs lax.sort (property test) ------------------
#
# The srpt pallas kernels rank and permute their slot tables with the
# bitonic network in kernels/msj_scan/sort.py instead of jax.lax.sort.
# Bit-equality with the *stable* lax.sort on adversarial keys — heavy
# duplicates, ±inf empty-slot sentinels, all-equal columns — is exactly
# what makes the fused kernels' queue permutation identical to the scan
# cores' and hence the whole sample path rtol=0.  The int payload column
# is a distinct per-element tag, so equality checks the full permutation,
# not just the sorted keys.

_SORT_R, _SORT_Q = 2, 24   # fixed non-pow2 width: exercises +inf padding

sort_cases = st.tuples(
    st.integers(1, 2),                                         # num_keys
    st.lists(st.tuples(
        st.sampled_from([-np.inf, np.inf, 0.0, 0.0, 1.0, 1.5, 2.5, 2.5]),
        st.sampled_from([0.0, 1.0, 1.0, 4.0])),                # tie-breaker
        min_size=_SORT_R * _SORT_Q, max_size=_SORT_R * _SORT_Q),
)


@settings(max_examples=25, deadline=None)
@given(sort_cases)
def test_bitonic_sort_bitexact_vs_stable_lax_sort(args):
    import jax

    from repro.kernels.msj_scan.sort import bitonic_sort

    num_keys, rows = args
    key = np.array([r[0] for r in rows]).reshape(_SORT_R, _SORT_Q)
    key2 = np.array([r[1] for r in rows]).reshape(_SORT_R, _SORT_Q)
    payload = np.arange(key.size, dtype=np.int32).reshape(key.shape)
    with enable_x64():
        ops = (jnp.asarray(key, jnp.float64),
               jnp.asarray(key2, jnp.float64),
               jnp.asarray(payload, jnp.int32))
        got = bitonic_sort(ops, num_keys=num_keys)
        want = jax.lax.sort(ops, dimension=-1, num_keys=num_keys,
                            is_stable=True)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), num_keys


def test_bitonic_sort_corner_cases():
    """Deterministic corners the sampler may miss: all-equal keys (pure
    stability — payload must come back verbatim), all-``+inf`` columns
    (indistinguishable from the pow2 padding), and widths on both sides
    of a power of two including the degenerate Q=1."""
    import jax

    from repro.kernels.msj_scan.sort import bitonic_sort

    with enable_x64():
        for Q in (1, 2, 7, 8, 9, 64):
            pay = jnp.arange(Q, dtype=jnp.int32)[None]
            for key in (np.zeros(Q),
                        np.full(Q, np.inf),
                        np.resize([np.inf, -np.inf, 0.0], Q)):
                ops = (jnp.asarray(key, jnp.float64)[None], pay)
                got = bitonic_sort(ops, num_keys=1)
                want = jax.lax.sort(ops, dimension=-1, num_keys=1,
                                    is_stable=True)
                for g, w in zip(got, want):
                    assert np.array_equal(np.asarray(g),
                                          np.asarray(w)), (Q, key[:3])
