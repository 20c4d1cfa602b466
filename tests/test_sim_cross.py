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

import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import sim_jax
from repro.core.policies import make_policy
from repro.core.sim_batch import (bs_sim_batch, fcfs_sim_batch,
                                  loss_queue_sim_batch, modified_bs_sim_batch)
from repro.core.sim_jax import bs_sim, fcfs_sim, loss_queue_sim, \
    modified_bs_sim
from repro.core.simulator import Simulation
from repro.core.workload import (BatchTrace, Exp, JobClass, Workload,
                                 figure1_workload)


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


def test_bs_ring_overflow_names_the_peak():
    """The overflow error states the peak against ``queue_cap`` and
    blames the ring's size, not the load."""
    wl = figure1_workload(64, theta=0.7)
    trace = wl.sample_trace(3000, seed=7)
    with pytest.raises(RuntimeError) as err:
        bs_sim(trace, wl=wl, queue_cap=4)
    msg = str(err.value)
    peak = int(msg.split("up to ")[1].split()[0])
    assert peak > 4
    assert "queue_cap=4" in msg and f"queue_cap >= {peak}" in msg
    assert "unstable" not in msg


def _oracle_ring_peaks(batch, wl) -> np.ndarray:
    """Each replication's largest helper-wait backlog of one class, counted
    from the python oracle's routing and start times: a routed job joins
    its class's queue at its arrival and leaves it when it starts (helper
    commit or rule-3 pull-back), so at job j's enqueue the queue holds j
    and every earlier routed job of its class that starts after a_j."""
    from repro.core.simulator import _make_python_policy
    peaks = []
    for r in range(batch.reps):
        trace = batch.rep(r)
        pol = _make_python_policy("bs-fcfs", None, wl)
        sim = Simulation(trace, pol)
        sim.run()
        routed = np.zeros(trace.num_jobs, bool)
        routed[sorted(pol.routed_jobs)] = True
        peak = 0
        for c in range(batch.C):
            idx = np.flatnonzero(routed & (trace.cls == c))
            a, s = trace.arrival[idx], sim.start_time[idx]
            earlier = np.tri(idx.size, k=-1, dtype=bool)   # [j, i]: i < j
            occ = 1 + (earlier & (s[None, :] > a[:, None])).sum(axis=1)
            peak = max(peak, int(occ.max(initial=0)))
        peaks.append(peak)
    return np.array(peaks)


def test_bs_sdsc_sp2_deployment_matches_oracle():
    """The SDSC-SP2 deployment (Table 2, k=512, load 0.85) on its own
    shape: block-bootstrapped replications of one Poisson base trace.
    Here the helpers carry a quarter of the jobs and class n2 has no
    block slot, so every n2 job waits in the helper ring.  ``jax`` and
    ``jax-shard`` equal the oracle at rtol=0, and the ring peak the scan
    reads back is the oracle's largest per-class backlog."""
    from repro.core import engines, spans
    from repro.core.sim_batch import (_bs_scan_batch, _bs_scatter_events,
                                      _class_inputs)
    from repro.core.sim_jax import _bs_args
    from repro.core.workload import sdsc_sp2_workload

    wl = sdsc_sp2_workload(k=512, load=0.85)
    batch = BatchTrace.from_trace(wl.sample_trace(3000, seed=5), reps=4,
                                  seed=9, method="block")
    oracle = engines.simulate("bs-fcfs", batch, engine="python", wl=wl)
    peaks = _oracle_ring_peaks(batch, wl)
    for engine in ("jax", "jax-shard"):
        spans.reset()
        out = engines.simulate("bs-fcfs", batch, engine=engine, wl=wl)
        for f in ("start", "wait", "p_helper", "p_routed"):
            assert np.array_equal(getattr(out, f), getattr(oracle, f)), \
                (engine, f)
        c = spans.counters()
        assert c["bs_ring_peak"] == peaks.max(), engine
        assert c["bs_ring_cap"] == 3000
        assert c["bs_jobs"] == 3000 * 4
        assert c["bs_helper_served"] == round(oracle.p_helper.sum() * 3000)
    assert (oracle.p_helper > 0.10).all()

    # per job and per lane, from the raw event streams of the scan
    slots, s_max, h, q_cap = _bs_args(batch, None, wl, None)
    assert slots[1] == 0 and h == 69
    with enable_x64():
        tagged, rec_t, rpk = _bs_scan_batch(
            *_class_inputs(batch), jnp.asarray(slots), s_max, h, q_cap)
    _, served, _ = _bs_scatter_events(batch.num_jobs, tagged, rec_t,
                                      batch.arrival)
    n2 = batch.cls == 1
    assert n2.sum() > 0 and served[n2].all()
    assert np.array_equal(np.asarray(rpk), peaks)


def _rounded_times(x, bits=44):
    """``x`` held to ``bits`` bits of mantissa, as a chip that emulates
    float64 with two float32 words holds it."""
    m, e = np.frexp(np.asarray(x, np.float64))
    return np.ldexp(np.round(m * 2.0**bits) / 2.0**bits, e)


@pytest.mark.parametrize("path", ["batch", "stream"])
def test_bs_zero_waits_stay_exact_under_rounded_times(path, monkeypatch):
    """At SDSC-SP2 times (1e5-1e7 s) a record time rounded on the device
    is off from the host's arrival by more than the 1e-9 s that counts as
    a wait: a job that started at its own arrival takes that arrival, so
    P(wait > 0) stays the oracle's exactly."""
    from repro.core import engines, sim_batch
    from repro.core.workload import sdsc_sp2_workload

    wl = sdsc_sp2_workload(k=512, load=0.85)
    batch = BatchTrace.from_trace(wl.sample_trace(3000, seed=5), reps=2,
                                  seed=9, method="block")
    oracle = engines.simulate("bs-fcfs", batch, engine="python", wl=wl)
    moved = []

    def rounded(rec_t):
        out = _rounded_times(rec_t)
        moved.append(int((out != rec_t).sum()))
        return out

    if path == "batch":
        fetch = sim_batch._fetch
        monkeypatch.setattr(sim_batch, "_fetch", lambda out, reps=None: (
            lambda t, r, p: (t, rounded(r), p))(*fetch(out, reps)))
        out = engines.simulate("bs-fcfs", batch, engine="jax", wl=wl)
        p_wait = (out.wait > sim_batch.WAIT_EPS).mean(axis=1)
    else:
        chunk_scan = sim_batch._bs_chunk_scan_jax

        def rounded_scan(*a):
            scan = chunk_scan(*a)

            def run(*b):
                carry, tagged, rec_t = scan(*b)
                return carry, tagged, rounded(rec_t)
            return run
        monkeypatch.setattr(sim_batch, "_bs_chunk_scan_jax", rounded_scan)
        p_wait = engines.simulate_stream("bs-fcfs", batch, chunk_jobs=1000,
                                         wl=wl).p_wait
    assert sum(moved) > 1000
    assert np.array_equal(p_wait,
                          (oracle.wait > sim_batch.WAIT_EPS).mean(axis=1))


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


# -- SRPT fast step: both slot orderings, rtol=0 ---------------------------
#
# The fast step orders the slot table by pairwise precedence counts when
# Q <= the backend's _SRPT_PAIRWISE_MAX_Q and by sorts above it.  Each
# case moves that crossover so that its Q lies on the side it names, and
# pins the raw event streams and counters of the fast scan to the
# reference step's, and the engine's results to the python oracle's.  On
# the tie-heavy trace the oracle is left out: it breaks exact (rank,
# arrival) ties and equal completion times by its own set and heap
# order, which no scan engine follows; its hand-built tie cases are in
# ``test_policies.py``.


def _srpt_classes(k, needs=(1, 2, 4, 8)):
    classes = tuple(JobClass(f"n{n}", n, Exp(float(n)), 1.0 / len(needs))
                    for n in needs)
    return Workload(k=k, lam=1.0, classes=classes).with_load(0.85)


def _srpt_tie_batch(J, k=13, needs=(1, 3, 5), R=2, seed=2):
    """Jobs arrive in pairs at one instant, and services come from a pool
    of 16 values: ranks tie within and across arrival times."""
    rng = np.random.default_rng(seed)
    nd = rng.choice(needs, (R, J))
    pool = rng.exponential(2.0, 16)
    lam = 0.8 * k / (np.mean(needs) * pool.mean())
    t = np.cumsum(rng.exponential(2.0 / lam, (R, J // 2)), axis=1)
    return BatchTrace(arrival=np.repeat(t, 2, axis=1),
                      cls=np.zeros((R, J), np.int64),
                      service=rng.choice(pool, (R, J)),
                      need=nd.astype(np.int64), k=k, C=1)


def _srpt_batch(trace, J, R):
    if trace == "ties":
        return _srpt_tie_batch(J, R=R)
    k = 32 if trace == "k-mult" else 30     # max need 8 divides k or not
    return _srpt_classes(k).sample_traces(J, R, seed=J)


def _srpt_reference_streams(batch, Q, sf):
    """Raw streams of the reference step (``_srpt_make_step``)."""
    from repro.core.sim_batch import _srpt_nu
    with enable_x64():
        a, n, v = (jnp.asarray(x, jnp.float64)
                   for x in (batch.arrival, batch.need, batch.service))
        kk = jnp.full(batch.reps, float(batch.k), jnp.float64)
        step = sim_jax._srpt_make_step(jnp.stack([a, v, n], axis=2), kk, Q,
                                       _srpt_nu(batch), sf)
        carry, ev = jax.jit(lambda c: jax.lax.scan(
            step, c, None, length=2 * batch.num_jobs))(
                sim_jax._srpt_init(batch.reps, Q, jnp.float64))
        return [np.asarray(x).T for x in ev] + [np.asarray(x)
                                                for x in carry[2:]]


def _srpt_fast_streams(batch, Q, sf, pairwise):
    from repro.core.sim_batch import _srpt_k_mult, _srpt_nu
    NU = _srpt_nu(batch)
    with enable_x64():
        a, n, v = (jnp.asarray(x, jnp.float64)
                   for x in (batch.arrival, batch.need, batch.service))
        kk = jnp.full(batch.reps, float(batch.k), jnp.float64)
        out = jax.jit(sim_jax._srpt_core, static_argnums=(4, 5, 6, 7, 8))(
            a, n, v, kk, Q, NU, sf, _srpt_k_mult(NU, batch), pairwise)
        return [np.asarray(x) for x in out]


@pytest.mark.parametrize("side", ("pairwise", "sort"))
@pytest.mark.parametrize("trace", ("k-mult", "not-k-mult", "ties", "grid"))
@pytest.mark.parametrize("policy", ("ff-srpt", "sf-srpt"))
def test_srpt_fast_step_bitexact_both_sides_of_crossover(policy, trace,
                                                         side, monkeypatch):
    from repro.core import engines
    from repro.core.sim_jax import _srpt_args, _srpt_pairwise

    J, R, cap = 240, 2, 256
    pairwise = side == "pairwise"
    monkeypatch.setattr(sim_jax, "_SRPT_PAIRWISE_MAX_Q",
                        {jax.default_backend(): cap if pairwise else cap // 2})
    sf = policy == "sf-srpt"
    if trace == "grid":
        # two cells of unequal J: the grid pads J, and j_live stops the
        # shorter cell's lane at its own 2J events
        wl = _srpt_classes(32)
        cells = [engines.GridCell(wl.sample_traces(J, R, seed=3),
                                  queue_cap=cap),
                 engines.GridCell(wl.sample_traces(J - 40, R, seed=4),
                                  queue_cap=cap)]
        assert _srpt_pairwise(_srpt_args(cells[0].batch, cap)) == pairwise
        for c, out in zip(cells, engines.simulate_grid(policy, cells,
                                                       engine="jax")):
            ref = engines.simulate(policy, c.batch, engine="python")
            for f in ("response", "wait", "start", "preemptions"):
                assert np.array_equal(getattr(out, f), getattr(ref, f)), f
        return
    batch = _srpt_batch(trace, J, R)
    Q = _srpt_args(batch, cap)
    assert _srpt_pairwise(Q) == pairwise
    fast = _srpt_fast_streams(batch, Q, sf, pairwise)
    ref = _srpt_reference_streams(batch, Q, sf)
    for i, (x, y) in enumerate(zip(fast, ref)):
        assert np.array_equal(x, y), (i, x, y)
    assert fast[4].sum() > 0                  # the scans preempted
    if trace == "ties":
        return
    oracle = engines.simulate(policy, batch, engine="python")
    out = engines.simulate(policy, batch, engine="jax", queue_cap=cap)
    for f in ("response", "wait", "start", "preemptions"):
        assert np.array_equal(getattr(out, f), getattr(oracle, f)), f


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
