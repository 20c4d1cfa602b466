"""Host spans and counters of a simulator call.

A span names a stretch of host time on the profiler's clock: it is a
``jax.profiler.TraceAnnotation``, which records only while a profiler
session is open (``jax.profiler.trace(dir)``) and then lands in the same
trace as the device lanes, keyword metadata attached to the event.  With
no session open a span costs about a microsecond.  Spans belong in host
Python only: inside ``jit``, ``scan`` or ``shard_map`` one would fire
once, at trace time.

Counters stay in this process's memory: :func:`add` keeps a sum,
:func:`high` a high-water mark, :func:`counters` returns a copy and
:func:`reset` clears them.  Nothing is exported or written to a file.

The batch path (``engines.simulate`` and the ``sim_batch`` helpers)
opens ``repro.simulate`` around each call and, inside it, ``repro.prep``
(input checks, partition, padding, copies and puts, until the inputs are
on the device), ``repro.run`` (dispatch until the program is done),
``repro.fetch`` (outputs to the host) and ``repro.assemble`` (overflow
checks, event-to-job scatters, the result), all with the call's
``call=<n>``.  Its counters: ``fetch_bytes`` (sum of the bytes fetched),
``srpt_peak`` (the largest in-system job count an SRPT scan saw),
``srpt_q`` (the largest slot-table size Q it ran with) and
``srpt_pairwise_events`` (sum of the event steps, 2J per replication,
that the ``jax`` and ``jax-shard`` SRPT scans ran with the slot table
ordered by pairwise precedence counts, at Q up to the backend's
``sim_jax._SRPT_PAIRWISE_MAX_Q``; absent when every scan sorted),
``bs_ring_peak`` (the most jobs of one class that a BS-FCFS scan held
in its helper-wait ring at once, over every replication: batch, grid,
jax-shard, drain-mode and each chunk of a stream), ``bs_ring_cap`` (the
largest ring size, ``queue_cap`` slots per class, such a scan ran with),
``bs_helper_served`` (sum of the jobs that a BS-FCFS batch or grid
result saw start on the helpers) and ``bs_jobs`` (sum of the jobs in
those results).
"""

from __future__ import annotations

import jax

_COUNTERS: dict[str, int] = {}


def span(name: str, **meta):
    """A host span ``name`` carrying ``meta`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name, **meta)


def add(name: str, n: int) -> None:
    """Add ``n`` to the sum counter ``name``."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def high(name: str, v: int) -> None:
    """Raise the high-water counter ``name`` to ``v`` if it is higher."""
    v = int(v)
    _COUNTERS[name] = max(_COUNTERS.get(name, v), v)


def counters() -> dict[str, int]:
    """A copy of every counter."""
    return dict(_COUNTERS)


def reset() -> None:
    """Clear every counter."""
    _COUNTERS.clear()
