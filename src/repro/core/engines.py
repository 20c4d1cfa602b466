"""Unified simulation-engine registry — the single dispatch point.

Before this module, every ``*_sim_batch`` wrapper hand-routed between the
vmapped ``lax.scan`` cores and the fused Pallas kernels (a per-policy
``if engine == "pallas"`` plus a lazy import), and the Python event engine
lived behind an entirely different interface — so new engines and new
policies both meant touching N call sites.  Now every simulation core
registers itself under a ``(policy, engine)`` key and *all* callers —
batched wrappers, single-trace wrappers, ``sweep_many_server``, the
benchmark drivers, and the cross-validation tests — go through one entry
point:

    from repro.core import engines
    res = engines.simulate("bs-fcfs", batch, engine="jax", wl=wl)

Registry contract
-----------------
* **Key**: ``(policy, engine)``.  ``policy`` is the canonical policy name —
  identical to the Python engine's ``Policy.name`` (``"fcfs"``,
  ``"modbs-fcfs"``, ``"bs-fcfs"``, ``"sf-srpt"``, ...) so CSV rows line up
  across engines; :func:`canonical` resolves the short CLI aliases
  (``"bs"`` → ``"bs-fcfs"``).  ``engine`` names a substrate: ``"python"``
  (the exact event-driven oracle, :mod:`repro.core.simulator`), ``"jax"``
  (vmapped ``lax.scan`` cores, :mod:`repro.core.sim_batch`), ``"pallas"``
  (fused step kernels, :mod:`repro.kernels.msj_scan`), ``"jax-shard"``
  (the same scan cores with the replications axis sharded over the local
  device mesh, :mod:`repro.core.shard`).
* **Core**: a callable ``core(batch, *, partition=None, wl=None, **kw) ->
  BatchSimResult``.  ``batch`` is a :class:`~repro.core.workload.BatchTrace`
  ([R, J] replications — synthetic Poisson via ``Workload.sample_traces``
  or empirical bootstrap via ``BatchTrace.from_trace``); ``partition``/
  ``wl`` feed the eq.-2 balanced partition where the policy needs one;
  extra keywords (e.g. ``queue_cap``) pass through untouched.  Cores must
  not mutate the batch.
* **Determinism**: on a fixed batch, every engine registered under one
  policy must produce the *bit-identical* ``BatchSimResult`` (rtol=0) —
  the registry is iterated by the parity tests in
  ``tests/test_engines.py`` / ``tests/test_sim_cross.py``, so a new
  engine is cross-validated the moment it registers.
* **Registration**: cores self-register at import time via the
  :func:`register` decorator; double registration of a key is an error.
  Providers are imported lazily on first dispatch (``_PROVIDERS``), so
  importing this module costs nothing and there are no import cycles —
  this module never imports the core modules at top level.
* **Coverage** (batch registry; ``+g`` marks a grid-native core)::

      policy          python   jax      jax-shard   pallas
      fcfs            yes      yes +g   yes +g      yes
      modbs-fcfs      yes      yes +g   yes +g      yes
      bs-fcfs         yes      yes +g   yes +g      yes
      sf-srpt         yes      yes +g   yes +g      yes
      ff-srpt         yes      yes +g   yes +g      yes
      serverfilling,  yes      --       --          --
      sf-gittins, msf, lsf, backfill, maxweight (oracle only)

  The sf-srpt/ff-srpt scan cores are the preemptive event scans of
  :mod:`repro.core.sim_jax` (per-job remaining work as carry state, a
  bounded re-sort/re-pack per event); their pallas cores run the
  reference step with the in-kernel stable bitonic rank/permute of
  :mod:`repro.kernels.msj_scan.sort`.  They cover the clean and grid
  paths but not fault injection — ``failures=`` raises
  ``NotImplementedError`` there (use ``engine="python"``).  The
  FCFS/ModBS/BS-π pallas kernels *do* take ``failures=`` (drain
  semantics, same merged-stream flow as ``jax``).
* **Fallback visibility**: :func:`simulate`/:func:`simulate_grid` accept
  ``fallback=True`` to downgrade an unregistered pair to the python
  oracle — announced by a once-per-process ``RuntimeWarning``
  (:func:`warn_fallback`), never silently.  Benchmark drivers that
  hand-route (``benchmarks.common.run_policies_batch``) call
  :func:`warn_fallback` at their own substitution sites.

Streaming registry
------------------
A parallel registry serves the constant-memory chunked path:
:func:`simulate_stream` dispatches ``(policy, engine)`` to cores that
consume a :class:`~repro.core.workload.ChunkSource` (chunk generator)
instead of a materialized batch and return a
:class:`~repro.core.sim_batch.StreamResult` of online-folded
observables — peak memory O(R · chunk_jobs), independent of the stream
length.  Streaming cores register via :func:`register_stream` under
``"jax"`` and ``"jax-shard"``; on the replay path the result is
bit-identical (rtol=0) to ``stream_fold(simulate(...))`` for every
chunk schedule, and engines without a chunked carry (``pallas``,
``python``) reject loudly naming the engines that stream
(:func:`get_stream`).  Streams checkpoint mid-flight through
``ckpt_dir=``/``resume=`` — see :mod:`repro.core.sim_batch`.

Grid registry
-------------
A third registry serves whole-figure grids: :func:`simulate_grid` takes a
sequence of :class:`GridCell`\\ s — each a ``BatchTrace`` plus its own
partition/workload/failures context, with *heterogeneous* k, J, and class
counts — and returns one ``BatchSimResult`` per cell.  Grid-native cores
(``register_grid``; ``"jax"`` and ``"jax-shard"``) stack every cell onto
one flattened (cells × reps) lane axis and run **one jit-compiled
program per policy**:

* *Padding rules*: per-cell batches are J-padded to the grid max via
  ``BatchTrace.pad_jobs`` (sentinel no-op jobs at the horizon; the BS
  event cores additionally guard arrivals with a per-lane ``j_live``
  count so padding never enters the rings); heterogeneous k/C/s_max/h
  share one static shape via *dead capacity* in the per-lane initial
  carries — ``_BIG`` entries in the FCFS/helper free-time vectors and
  permanently-busy A-slots, the same masking the drain-mode failure
  machinery uses, so every per-cell state is scan *data*, not a static.
* *Mesh layout* (``jax-shard``): cells × reps shard over a 2-D
  ``("c", "r")`` device mesh (:func:`repro.core.shard.grid_mesh`); both
  axes pad up to the mesh shape by repeating their last entry, so grids
  never need to divide the device count.
* *Determinism*: every grid cell is bit-identical (rtol=0) to the
  per-cell :func:`simulate` path on every engine — pinned by
  ``tests/test_grid.py``.
* Engines without a grid-native core (``python``, ``pallas``) fall back
  to a per-cell :func:`simulate` loop behind the same call, so
  ``sweep_many_server`` runs on :func:`simulate_grid` for all engines.

Checkpoint granularity: grid callers (``sweep_many_server``, the fig
drivers) launch one grid per policy and write the extracted per-cell
results as individual atomic checkpoints — old per-cell checkpoints
resume forward, new runs pay one compile per policy.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import importlib
import itertools
from typing import TYPE_CHECKING, Callable, Sequence

from . import spans

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids import cycles
    from .sim_batch import BatchSimResult
    from .workload import BatchTrace

#: modules whose import registers engine cores (order is irrelevant;
#: registration is idempotent because modules import once)
_PROVIDERS = (
    "repro.core.simulator",        # engine="python"
    "repro.core.sim_batch",        # engine="jax"
    "repro.kernels.msj_scan.ops",  # engine="pallas"
    "repro.core.shard",            # engine="jax-shard"
)

_REGISTRY: dict[tuple[str, str], Callable[..., "BatchSimResult"]] = {}

#: streaming cores live in their own registry: a stream core consumes a
#: ChunkSource (not a BatchTrace) and returns a StreamResult, so the two
#: call signatures must never be confused by a registry lookup
_STREAM_REGISTRY: dict[tuple[str, str], Callable] = {}

#: grid cores consume a sequence of GridCells and return one
#: BatchSimResult per cell — again a distinct signature, distinct registry
_GRID_REGISTRY: dict[tuple[str, str], Callable] = {}

#: engines whose FCFS/ModBS/BS-π cores support the failure axis
#: (``failures=``): 'python' kills in-flight jobs, the scan/kernel engines
#: drain capacity — iterated by ``tests/test_failures.py``
FAILURE_ENGINES = ("python", "jax", "jax-shard", "pallas")

#: ids of this process's :func:`simulate` calls, the ``call`` of their spans
_CALL_IDS = itertools.count(1)
#: the id of the :func:`simulate` call running now (None outside one)
_CALL: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_call", default=None)

#: short benchmark-CLI aliases -> canonical policy names (Policy.name)
ALIASES = {
    "bs": "bs-fcfs", "balanced-splitting": "bs-fcfs",
    "modbs": "modbs-fcfs", "modified-bs": "modbs-fcfs",
}


def canonical(policy: str) -> str:
    """Resolve a short policy alias to its canonical ``Policy.name``."""
    return ALIASES.get(policy, policy)


def register(policy: str, engine: str):
    """Decorator: register a simulation core under ``(policy, engine)``."""
    def deco(fn: Callable[..., "BatchSimResult"]):
        key = (policy, engine)
        if key in _REGISTRY:
            raise ValueError(f"engine core {key} registered twice")
        _REGISTRY[key] = fn
        return fn
    return deco


def register_stream(policy: str, engine: str):
    """Decorator: register a *streaming* core under ``(policy, engine)``.

    A stream core has the signature ``core(source, *, chunk_jobs,
    total_jobs=None, partition=None, wl=None, **kw) -> StreamResult`` —
    it pulls per-chunk :class:`~repro.core.workload.BatchTrace`\\ s from a
    :class:`~repro.core.workload.ChunkSource` and folds observables
    online, never materializing the full [R, J] batch.
    """
    def deco(fn: Callable):
        key = (policy, engine)
        if key in _STREAM_REGISTRY:
            raise ValueError(f"stream core {key} registered twice")
        _STREAM_REGISTRY[key] = fn
        return fn
    return deco


def register_grid(policy: str, engine: str):
    """Decorator: register a *grid* core under ``(policy, engine)``.

    A grid core has the signature ``core(cells, **kw) ->
    list[BatchSimResult]`` — ``cells`` is a tuple of :class:`GridCell`\\ s
    (already validated, uniform ``reps``, homogeneous failure axis) and
    the returned list is index-aligned with it.  The contract: cell ``g``
    of the list is bit-identical (rtol=0) to
    ``simulate(policy, cells[g].batch, engine=engine, ...)``.
    """
    def deco(fn: Callable):
        key = (policy, engine)
        if key in _GRID_REGISTRY:
            raise ValueError(f"grid core {key} registered twice")
        _GRID_REGISTRY[key] = fn
        return fn
    return deco


def _ensure_registered() -> None:
    """Import every provider module so self-registration has happened."""
    for mod in _PROVIDERS:
        importlib.import_module(mod)


def registered() -> tuple[tuple[str, str], ...]:
    """All registered ``(policy, engine)`` keys, sorted."""
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def available_engines() -> tuple[str, ...]:
    """All engine names with at least one registered core, sorted."""
    return tuple(sorted({e for _, e in registered()}))


def engines_for(policy: str) -> tuple[str, ...]:
    """Engines registered for a policy (canonicalized), sorted."""
    pol = canonical(policy)
    return tuple(sorted(e for p, e in registered() if p == pol))


def policies_for(engine: str) -> tuple[str, ...]:
    """Policies registered for an engine, sorted."""
    return tuple(sorted(p for p, e in registered() if e == engine))


def get(policy: str, engine: str) -> Callable[..., "BatchSimResult"]:
    """The registered core for ``(policy, engine)``; loud errors otherwise.

    Unknown policy -> ``KeyError`` (mirrors the old ``BATCHED_SIMS`` dict
    lookup); known policy under an unknown engine -> ``ValueError``.
    """
    _ensure_registered()
    pol = canonical(policy)
    core = _REGISTRY.get((pol, engine))
    if core is not None:
        return core
    if not engines_for(pol):
        raise KeyError(f"no simulation core for policy {policy!r}; "
                       f"registered policies: {sorted({p for p, _ in _REGISTRY})}")
    raise ValueError(f"unknown engine {engine!r} for policy {pol!r}; "
                     f"registered engines: {list(engines_for(pol))}")


#: (policy, engine) pairs that already emitted their fallback warning —
#: one RuntimeWarning per process per pair, not one per replication batch
_WARNED_FALLBACKS: set[tuple[str, str]] = set()


def warn_fallback(policy: str, engine: str) -> None:
    """Once-per-process ``RuntimeWarning`` for a python-oracle fallback.

    The oracle is orders of magnitude slower than the scan engines, so a
    sweep that quietly downgrades a (policy, engine) pair can burn hours
    without anyone noticing *why*.  Every dispatch site that substitutes
    ``engine="python"`` for an unregistered pair must announce it here.
    """
    import warnings
    key = (canonical(policy), engine)
    if key in _WARNED_FALLBACKS:
        return
    _WARNED_FALLBACKS.add(key)
    warnings.warn(
        f"policy {key[0]!r} has no engine {engine!r} core — falling back "
        f"to the python event oracle (orders of magnitude slower); "
        f"registered engines for this policy: {list(engines_for(key[0]))}",
        RuntimeWarning, stacklevel=3)


def _resolve_fallback(policy: str, engine: str, fallback: bool) -> str:
    """The engine to dispatch, downgrading to ``"python"`` when allowed."""
    pol = canonical(policy)
    if (not fallback or engine == "python"
            or (pol, engine) in registered()):
        return engine
    get(pol, "python")  # unknown policy stays a loud KeyError
    warn_fallback(pol, engine)
    return "python"


def validate_batch(batch: "BatchTrace", *, partition=None,
                   failures=None) -> None:
    """Loud input validation shared by every engine.

    The scan cores happily fold NaNs or time-travelling arrivals into
    garbage outputs (and the Python oracle would diverge from them in
    undefined ways), so malformed batches are rejected *before* dispatch
    with a ``ValueError`` naming the first offending replication.
    """
    import numpy as np

    def _first_bad(mask) -> int:
        return int(np.argmax(mask.any(axis=1)))

    if np.isnan(batch.arrival).any():
        raise ValueError("batch.arrival contains NaN (first bad replication "
                         f"{_first_bad(np.isnan(batch.arrival))})")
    if np.isnan(batch.service).any():
        raise ValueError("batch.service contains NaN (first bad replication "
                         f"{_first_bad(np.isnan(batch.service))})")
    gaps = np.diff(batch.arrival, axis=1)
    if batch.arrival.size and (batch.arrival[:, 0] < 0).any():
        raise ValueError("negative arrival times (first bad replication "
                         f"{int(np.argmax(batch.arrival[:, 0] < 0))})")
    if (gaps < 0).any():
        raise ValueError("arrival times are not nondecreasing along the job "
                         f"axis (first bad replication {_first_bad(gaps < 0)})")
    if (batch.service < 0).any():
        raise ValueError("negative service times (first bad replication "
                         f"{_first_bad(batch.service < 0)})")
    if (batch.need < 1).any():
        raise ValueError("server needs must be >= 1 (first bad replication "
                         f"{_first_bad(batch.need < 1)})")
    if partition is not None:
        C = partition.C
        bad = (batch.cls < 0) | (batch.cls >= C)
        if bad.any():
            raise ValueError(
                f"class ids outside the partition's [0, {C}) range (first "
                f"bad replication {_first_bad(bad)})")
    if failures is not None:
        if getattr(failures, "k", batch.k) != batch.k:
            raise ValueError(f"failures.k={failures.k} != batch.k={batch.k}")
        if getattr(failures, "reps", batch.reps) != batch.reps:
            raise ValueError(f"failures.reps={failures.reps} != "
                             f"batch.reps={batch.reps}")


def simulate(policy: str, batch: "BatchTrace", *, engine: str = "jax",
             partition=None, wl=None, fallback: bool = False,
             **kw) -> "BatchSimResult":
    """Run ``batch`` through the registered ``(policy, engine)`` core.

    The single dispatch point of the simulation stack: no caller branches
    on the engine name.  ``partition``/``wl`` are forwarded to the core
    (BSF policies need one of them for the eq.-2 partition); extra
    keywords (e.g. ``queue_cap`` for ``bs-fcfs``) pass through.  Inputs
    are validated (:func:`validate_batch`) before dispatch — malformed
    batches fail loudly instead of folding NaNs through the scans.

    ``fallback=True`` downgrades an unregistered ``(policy, engine)``
    pair to the python event oracle instead of raising, announcing the
    substitution with a once-per-process ``RuntimeWarning``
    (:func:`warn_fallback`) — never silently.

    Each call is one ``repro.simulate`` host span (:mod:`repro.core.spans`)
    with a per-process ``call`` id; the batch helpers the cores share open
    its ``repro.prep``/``run``/``fetch``/``assemble`` spans.
    """
    engine = _resolve_fallback(policy, engine, fallback)
    core = get(policy, engine)
    fb = kw.get("failures")
    n = next(_CALL_IDS)
    token = _CALL.set(n)
    try:
        with spans.span("repro.simulate", call=n, policy=canonical(policy),
                        engine=engine):
            with call_span("repro.prep"):
                validate_batch(batch, partition=partition,
                               failures=fb if hasattr(fb, "k") else None)
            return core(batch, partition=partition, wl=wl, **kw)
    finally:
        _CALL.reset(token)


def call_span(name: str):
    """Span ``name`` of the :func:`simulate` call running now, carrying its
    ``call`` id; no span outside a call (the grid and stream paths)."""
    n = _CALL.get()
    return contextlib.nullcontext() if n is None else spans.span(name, call=n)


def stream_registered() -> tuple[tuple[str, str], ...]:
    """All registered streaming ``(policy, engine)`` keys, sorted."""
    _ensure_registered()
    return tuple(sorted(_STREAM_REGISTRY))


def stream_engines_for(policy: str) -> tuple[str, ...]:
    """Engines with a streaming core for a policy (canonicalized), sorted."""
    pol = canonical(policy)
    return tuple(sorted(e for p, e in stream_registered() if p == pol))


def get_stream(policy: str, engine: str) -> Callable:
    """The registered streaming core for ``(policy, engine)``.

    Engines without a chunked carry path (``pallas`` fuses the whole scan
    into one kernel launch; ``python`` replays discrete events over the
    full trace) reject loudly, naming the engines that *do* stream.
    """
    _ensure_registered()
    pol = canonical(policy)
    core = _STREAM_REGISTRY.get((pol, engine))
    if core is not None:
        return core
    streaming = stream_engines_for(pol)
    if streaming:
        raise ValueError(
            f"engine {engine!r} has no streaming core for policy {pol!r}; "
            f"streaming engines: {list(streaming)}")
    raise KeyError(
        f"no streaming core for policy {policy!r}; registered streaming "
        f"policies: {sorted({p for p, _ in _STREAM_REGISTRY})}")


def simulate_stream(policy: str, source, *, engine: str = "jax",
                    chunk_jobs: int, total_jobs: int | None = None,
                    partition=None, wl=None, **kw):
    """Stream ``source`` through the ``(policy, engine)`` chunked core.

    The constant-memory counterpart of :func:`simulate`: instead of one
    monolithic [R, J] batch, the simulation is a sequence of
    ``chunk_jobs``-sized chunk scans, each resumed from the previous
    chunk's carry, with observables (online Welford mean/M2 of response
    and wait, queueing/helper/routing probabilities) folded into a
    running accumulator — peak memory is O(R · chunk_jobs), independent
    of the stream length.

    ``source`` is a :class:`~repro.core.workload.ChunkSource` — replayed
    (:class:`~repro.core.workload.TraceReplaySource`, or a ``BatchTrace``
    which is wrapped automatically), bootstrap
    (``BatchTrace.from_trace(..., stream=True)``), or generated
    (:class:`~repro.core.workload.PoissonSource` and the non-stationary
    :class:`~repro.core.workload.DiurnalSource` /
    :class:`~repro.core.workload.FlashCrowdSource` /
    :class:`~repro.core.workload.MMPPSource`).  ``total_jobs`` bounds an
    unbounded source (required there; defaults to ``source.total_jobs``
    for finite ones).

    Determinism contract: on the replay path, the result equals
    ``stream_fold(simulate(policy, batch, engine=...), ...)``
    *bit-identically* (rtol=0) for every chunk size — the chunk
    boundaries are purely an execution-shape choice.  Streaming cores
    register via :func:`register_stream` under ``"jax"`` and
    ``"jax-shard"``; ``pallas``/``python`` reject loudly
    (:func:`get_stream`).

    Checkpointing: pass ``ckpt_dir=`` to save the carry + accumulator +
    source state after every chunk through :mod:`repro.checkpoint`;
    ``resume=True`` restores the latest chunk and continues, failing
    loudly (``checkpoint.require_layout``) if the stream layout
    (``chunk_jobs``, ``reps``, ``k``, policy, ...) changed since the
    checkpoint was written.  A 10^8-job stream is SIGKILL-resumable
    mid-stream.  Extra keywords (``queue_cap``, ``backlog_cap``,
    ``block``, ``seed`` ...) pass through to the core.
    """
    from .workload import BatchTrace, TraceReplaySource

    if isinstance(source, BatchTrace):
        source = TraceReplaySource(source)
    core = get_stream(policy, engine)
    if chunk_jobs < 1:
        raise ValueError(f"chunk_jobs must be >= 1, got {chunk_jobs}")
    if total_jobs is None:
        total_jobs = source.total_jobs
    if total_jobs is None:
        raise ValueError(
            "total_jobs is required for an unbounded source "
            f"({type(source).__name__} has source.total_jobs=None)")
    if total_jobs < 1:
        raise ValueError(f"total_jobs must be >= 1, got {total_jobs}")
    return core(source, chunk_jobs=chunk_jobs, total_jobs=total_jobs,
                partition=partition, wl=wl, policy=policy, **kw)


@dataclasses.dataclass(frozen=True)
class GridCell:
    """One cell of a simulation grid: a batch plus its per-cell context.

    ``partition``/``wl`` feed the eq.-2 balanced partition exactly as the
    matching :func:`simulate` keywords would; ``failures`` injects the
    cell's drain-mode :class:`~repro.core.failures.FailureBatch`;
    ``queue_cap`` bounds the BS-FCFS helper-wait rings (``None`` = the
    per-cell default ``min(J, 8192)``) and the SRPT in-system slot
    tables (``None`` = ``min(J, max(4k, 256))``).  Cells of one grid may
    differ in k, J, class count, partition, and load — the grid cores
    pad them to a shared shape without changing any cell's result.
    """

    batch: "BatchTrace"
    partition: object = None
    wl: object = None
    failures: object = None
    queue_cap: int | None = None


def grid_registered() -> tuple[tuple[str, str], ...]:
    """All registered grid-native ``(policy, engine)`` keys, sorted."""
    _ensure_registered()
    return tuple(sorted(_GRID_REGISTRY))


def grid_engines_for(policy: str) -> tuple[str, ...]:
    """Engines with a grid-native core for a policy, sorted."""
    pol = canonical(policy)
    return tuple(sorted(e for p, e in grid_registered() if p == pol))


def simulate_grid(policy: str, cells: Sequence[GridCell], *,
                  engine: str = "jax", fallback: bool = False,
                  **kw) -> list:
    """Run every grid cell under one policy; one ``BatchSimResult`` each.

    Grid-native engines (:func:`grid_engines_for`; ``"jax"`` and
    ``"jax-shard"``) stack the cells onto one flattened (cells × reps)
    lane axis and execute a *single* jit-compiled program — one compile
    and one dispatch for the whole grid, however many (k, load) cells it
    has.  Engines without a grid core fall back to a per-cell
    :func:`simulate` loop, so every registered engine accepts the same
    call.  Either way, cell ``g`` of the returned list is bit-identical
    (rtol=0) to ``simulate(policy, cells[g].batch, engine=engine, ...)``.

    Constraints: at least one cell; every cell the same ``reps`` (the
    lane axis is cells × reps); failures all-or-none across cells (split
    mixed grids into two calls).  Extra keywords (e.g. ``devices`` for
    ``jax-shard``) pass through to the core.  ``fallback=True``
    downgrades an unregistered ``(policy, engine)`` pair to the python
    oracle with a once-per-process ``RuntimeWarning``, exactly like
    :func:`simulate`.
    """
    cells = tuple(cells)
    if not cells:
        raise ValueError("simulate_grid needs at least one cell")
    engine = _resolve_fallback(policy, engine, fallback)
    core = get(policy, engine)  # loud unknown-policy/engine errors first
    R = cells[0].batch.reps
    for g, cell in enumerate(cells):
        if cell.batch.reps != R:
            raise ValueError(
                f"grid cells must share one replication count; cell {g} "
                f"has reps={cell.batch.reps}, cell 0 has reps={R}")
        fb = cell.failures
        try:
            validate_batch(cell.batch, partition=cell.partition,
                           failures=fb if hasattr(fb, "k") else None)
        except ValueError as e:
            raise ValueError(f"grid cell {g}: {e}") from None
    n_fail = sum(1 for c in cells if c.failures is not None)
    if n_fail not in (0, len(cells)):
        raise ValueError(
            "mixed failure/no-failure cells in one grid — split into one "
            "simulate_grid call per failure axis")
    pol = canonical(policy)
    grid_core = _GRID_REGISTRY.get((pol, engine))
    if grid_core is not None:
        return grid_core(cells, **kw)
    out = []           # fallback: per-cell dispatch, same results
    for cell in cells:
        ckw = dict(kw)
        if cell.queue_cap is not None:
            ckw["queue_cap"] = cell.queue_cap
        if cell.failures is not None:
            ckw["failures"] = cell.failures
        out.append(core(cell.batch, partition=cell.partition, wl=cell.wl,
                        **ckw))
    return out
