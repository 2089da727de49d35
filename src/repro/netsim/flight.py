"""Flight-recorder bookkeeping of the simulator's executors: launches,
compiles, host spans and counters, attributed to the sweep that caused
them.

A sweep (one `execute_points` call) opens a `collect_dispatch` scope.
Its `DispatchCounter` gathers, from every thread that adopted it:

  dispatches, compiles  device-program launches, and launches of a
                        (program, shapes, devices) fingerprint not seen
                        before in this process (`record_launch`)
  phases                host seconds per `span` name
  counts                named counters (`count`), among them the XLA
                        compiles and persistent-cache loads that JAX
                        reports (`watch_compiles`)

`span` also enters a `jax.profiler.TraceAnnotation` tagged with the
sweep's id, so the same spans sit on the profiler's clock beside the
device's ops, and spans of a helper thread join their sweep.  With no
profiler running an annotation costs about a microsecond, so spans stay
on.  Module-level totals (`dispatch_stats`, `dispatch_counts`) count
every thread.

This module imports no engine code: `scenarios.compile` spans its flow
build in NumPy pool workers too, which never load the JAX engine.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from typing import Dict, Hashable, Tuple

import jax
from jax.profiler import TraceAnnotation

_LOCK = threading.RLock()
_STATS = {"dispatches": 0, "compiles": 0}
_COUNTS: Dict[str, float] = {}
_SEEN_PROGRAMS: set = set()
_COLLECTORS = threading.local()
_SWEEP_IDS = itertools.count(1)


class DispatchCounter:
    """One scope's launches, compiles, span seconds (`phases`) and
    counters (`counts`), see `collect_dispatch`.  Updated only under the
    module lock; `snapshot()` returns a plain dict in the
    `dispatch_stats` shape.  `sweep` is the scope's id, the metadata of
    every span taken inside it."""

    __slots__ = ("dispatches", "compiles", "phases", "counts", "sweep")

    def __init__(self) -> None:
        self.dispatches = 0
        self.compiles = 0
        self.phases: Dict[str, float] = {}
        self.counts: Dict[str, float] = {}
        self.sweep = next(_SWEEP_IDS)

    def snapshot(self) -> Dict[str, int]:
        with _LOCK:
            return {"dispatches": self.dispatches,
                    "compiles": self.compiles}

    def record(self) -> Tuple[Dict[str, float], Dict[str, float]]:
        """Copies of `(phases, counts)`."""
        with _LOCK:
            return dict(self.phases), dict(self.counts)


def _stack():
    if not hasattr(_COLLECTORS, "stack"):
        _COLLECTORS.stack = []
    return _COLLECTORS.stack


@contextmanager
def collect_dispatch():
    """Attribute launches made by *this thread* inside the block to a
    fresh `DispatchCounter`.  Unlike sampling the module-global
    `dispatch_stats` before/after (which misattributes launches from
    concurrent executors), a collector only sees its own thread's
    dispatches.  Collectors nest: every active one on the thread counts
    each launch."""
    stack = _stack()
    counter = DispatchCounter()
    stack.append(counter)
    try:
        yield counter
    finally:
        stack.remove(counter)


def current_collectors() -> Tuple[DispatchCounter, ...]:
    """Snapshot of the collectors active on *this* thread — capture it
    before handing work to a helper thread, then `adopt_dispatch` the
    snapshot there so `collect_dispatch` scopes survive the hop."""
    return tuple(getattr(_COLLECTORS, "stack", None) or ())


@contextmanager
def adopt_dispatch(collectors: Tuple[DispatchCounter, ...]):
    """Attribute this thread's launches to collectors captured on
    another thread (via `current_collectors`).  The pipelined megabatch
    executor dispatches from a worker thread while the caller's
    `collect_dispatch` scope lives on the main thread — without
    adoption those launches would vanish from the sweep's own counter.
    Collectors already active on this thread are not double-counted."""
    stack = _stack()
    adopted = [c for c in collectors if c not in stack]
    stack.extend(adopted)
    try:
        yield
    finally:
        for c in adopted:
            stack.remove(c)


def record_launch(fingerprint: Hashable) -> None:
    """Count one launch of the program identified by `fingerprint`, and
    a compile if this process has not launched it before."""
    with _LOCK:
        _STATS["dispatches"] += 1
        fresh = fingerprint not in _SEEN_PROGRAMS
        if fresh:
            _SEEN_PROGRAMS.add(fingerprint)
            _STATS["compiles"] += 1
        for counter in current_collectors():
            counter.dispatches += 1
            if fresh:
                counter.compiles += 1


def count(name: str, n: float = 1) -> None:
    """Add `n` to counter `name` of the process and of every collector
    active on this thread."""
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n
        for counter in current_collectors():
            counter.counts[name] = counter.counts.get(name, 0) + n


@contextmanager
def span(name: str, **meta):
    """A host span: a `TraceAnnotation` on the profiler's clock, tagged
    with the innermost collector's sweep id, whose elapsed seconds are
    added to `phases[name]` of every collector active on this thread."""
    stack = current_collectors()
    if stack:
        meta.setdefault("sweep", stack[-1].sweep)
    with TraceAnnotation(name, **meta):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with _LOCK:
                for c in current_collectors():
                    c.phases[name] = c.phases.get(name, 0.0) + dt


def dispatch_stats() -> Dict[str, int]:
    """Process-wide counters since the last reset: `dispatches` =
    device-program launches, `compiles` = launches whose (program,
    shapes, devices) fingerprint had not been seen before in this
    process.  For attributing launches to one executor, prefer
    `collect_dispatch` — these globals count every thread."""
    with _LOCK:
        return dict(_STATS)


def dispatch_counts() -> Dict[str, float]:
    """Process-wide `count` totals since the last reset, every thread."""
    with _LOCK:
        return dict(_COUNTS)


def reset_dispatch_stats() -> None:
    """Zero the counters.  The seen-program set is *not* cleared — it
    mirrors the lifetime of jax's own executable caches, so a warm
    re-run correctly reports 0 compiles."""
    with _LOCK:
        _STATS["dispatches"] = 0
        _STATS["compiles"] = 0
        _COUNTS.clear()


# JAX's compile events: the backend compile (a persistent-cache load
# included, when the cache has the program) and the cache hit, which is
# reported inside it on the same thread
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_HITS = threading.local()
_watching = False


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _HITS.pending = True


def _on_duration(event: str, secs: float, **_) -> None:
    if event != _BACKEND_COMPILE:
        return
    if getattr(_HITS, "pending", False):
        _HITS.pending = False
        count("cache_loads")
        count("cache_load_s", secs)
    else:
        count("xla_compiles")
        count("xla_compile_s", secs)


def watch_compiles() -> None:
    """Register, once per process, the `jax.monitoring` listener that
    counts `xla_compiles`/`xla_compile_s` (backend compiles) and
    `cache_loads`/`cache_load_s` (programs the persistent cache served),
    credited to the collectors on the thread that compiles."""
    global _watching
    with _LOCK:
        if _watching:
            return
        _watching = True
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
