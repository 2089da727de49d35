"""Grid-point executor shared by `Experiment.run` and the deprecated
`sweep`/`sweep_many` shims.

'numpy' fans points out over a process pool; 'jax' dispatches the whole
grid through the megabatch path: every structurally compatible point
(any mix of routing / nic / fault / seed axes) stacks into ONE fused
`jit(vmap)` launch (mesh-sharded over multiple devices) that compiles
once (`repro.netsim.jx.megabatch`), with host prep of bucket k+1
pipelined against device execution of bucket k.  Either way completed
rows stream back through `on_result(index, metrics)` as they finish —
per future on the pool path, per finalized bucket on the JAX path —
which is what lets `run_experiment` write the cache and fill the
`ResultSet` incrementally instead of all-or-nothing at the end.

Every JAX sweep keeps JAX's persistent compilation cache on
(`enable_compile_cache`), so the megabatch program (one compile per
grid *structure*) survives process restarts: in `JAX_COMPILATION_CACHE_DIR`
when that is set, else in an explicit `compile_cache_dir`, else in
`.jax_cache/` at the root of the checkout.

NumPy pool workers pin JAX to the CPU before any backend starts, so a
worker never reaches for an accelerator the parent process holds.
"""
from __future__ import annotations

import multiprocessing
import os
import sys
import time
from concurrent.futures import (FIRST_COMPLETED, ProcessPoolExecutor,
                                ThreadPoolExecutor, wait)
from dataclasses import replace
from functools import partial
from typing import Callable, Dict, List, Optional

from repro.netsim.flight import collect_dispatch, span
from repro.scenarios.compile import compile_scenario
from repro.scenarios.runner import ScenarioMetrics, distill_metrics, run_point
from repro.scenarios.spec import ScenarioSpec

OnResult = Callable[[int, ScenarioMetrics], None]

# the compile cache's home when neither JAX_COMPILATION_CACHE_DIR nor an
# explicit directory names one: fixed (the path is part of every cache
# key, so a moving directory never hits) and git-ignored
DEFAULT_COMPILE_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Enable JAX's persistent compilation cache with thresholds dropped
    to zero, so every simulator program is cached — a re-run of a sweep
    in a fresh process then pays deserialization instead of XLA
    compilation.  `JAX_COMPILATION_CACHE_DIR`, when set, is the cache
    and wins over `cache_dir`; otherwise `cache_dir`, else
    `DEFAULT_COMPILE_CACHE_DIR`.  Returns the directory (created if
    missing)."""
    import jax

    cache_dir = os.path.abspath(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
        or DEFAULT_COMPILE_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def compile_cache_entries(cache_dir: str) -> int:
    """Number of compiled-program entries currently in a persistent
    compilation cache directory."""
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if n.endswith("-cache"))
    except OSError:
        return 0


def _pin_cpu() -> None:
    """NumPy pool initializer: keep this worker's JAX (schedule compiles
    and `derive` callbacks may touch it) on the CPU platform, set before
    any backend starts.  An accelerator belongs to one process, and the
    parent may hold it."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _timed_point(p: ScenarioSpec, derive: Optional[Callable] = None):
    """`run_point` plus its wall clock — module-level so process pools
    can pickle it (workers time themselves; the parent only sees
    completion order)."""
    t0 = time.perf_counter()
    m = run_point(p, derive=derive)
    return m, time.perf_counter() - t0


def execute_points(points: List[ScenarioSpec],
                   processes: Optional[int] = None,
                   backend: Optional[str] = None,
                   derive: Optional[Callable] = None,
                   on_result: Optional[OnResult] = None,
                   compile_cache_dir: Optional[str] = None,
                   flight: Optional[Dict] = None
                   ) -> List[ScenarioMetrics]:
    """Run every point; returns metrics in point order.  `backend=None`
    inherits the specs' `sim.backend` (which must agree — mixed grids
    are partitioned by the caller).  `on_result` fires once per point as
    it completes, *before* the call returns.  `compile_cache_dir` places
    the persistent compilation cache unless `JAX_COMPILATION_CACHE_DIR`
    is set (see `enable_compile_cache`).

    The call is one sweep of the flight recorder
    (`repro.netsim.flight`): its host spans (`repro.execute` around the
    whole call, and inside it `repro.scenario`, `repro.prep.*`,
    `repro.plan`, `repro.dispatch`, `repro.launch`, `repro.finalize.*`
    and `repro.distill`) carry the sweep's id, and sit on the profiler's
    clock whenever a JAX profiler runs.

    `flight`, when a dict, is filled with the sweep's summary:
    backend/mode, total wall clock, the sweep's id (`sweep`, the `sweep`
    metadata of its spans), `phases` (host seconds per span name, the
    prep worker thread's included) and `counters` (among them
    `flow_slots_real` and `flow_slots_launched`, `launch_bytes`,
    `xla_compiles`/`xla_compile_s` and `cache_loads`/`cache_load_s`).  The NumPy paths add per-point wall
    times (`points`), each taken where the point ran; the JAX paths
    add this sweep's dispatch/compile counts (`dispatch_stats`) plus
    any float32 bytes_total overflow conditions hit while preparing
    it."""
    t_start = time.perf_counter()
    with collect_dispatch() as rec:
        with span("repro.execute"):
            backend, out, summary = _execute(
                points, processes, backend, derive,
                on_result or (lambda i, m: None), compile_cache_dir)
    if flight is not None:
        if backend == "jax":
            summary["dispatch_stats"] = rec.snapshot()
        phases, counters = rec.record()
        flight.update(
            {"backend": backend, "mode": summary.pop("mode"),
             "n_points": len(points),
             "wall_s": round(time.perf_counter() - t_start, 6),
             **summary, "sweep": rec.sweep, "phases": phases,
             "counters": counters})
    return out


def _execute(points: List[ScenarioSpec], processes: Optional[int],
             backend: Optional[str], derive: Optional[Callable],
             emit: OnResult, compile_cache_dir: Optional[str]):
    """`execute_points` without its flight record: returns the resolved
    backend, the metrics in point order and the path's summary."""
    if backend is None:
        inherited = {p.sim.backend for p in points}
        if len(inherited) > 1:
            raise ValueError(
                f"sweep mixes spec backends {sorted(inherited)}; pass "
                "backend= explicitly")
        backend = inherited.pop() if inherited else "numpy"
    if backend == "jax":
        enable_compile_cache(compile_cache_dir)
        out, overflows, pipeline = _execute_jax(points, derive, emit)
        return backend, out, {"mode": "megabatch",
                              "f32_overflows": overflows,
                              "pipeline": pipeline}
    if backend != "numpy":
        raise ValueError(
            f"unknown backend {backend!r}; expected 'numpy' or 'jax'")
    # make the override symmetric: run_point honors each spec's own
    # sim.backend, so pin it to numpy or a backend="numpy" sweep of
    # jax-backend specs would silently still run on JAX
    points = [replace(p, sim=replace(p.sim, backend="numpy"))
              if p.sim.backend != "numpy" else p for p in points]
    if processes is None:
        processes = min(len(points), os.cpu_count() or 1)
    runner = partial(_timed_point, derive=derive)
    point_walls: List[Dict] = []

    def _serial():
        results = []
        for i, p in enumerate(points):
            m, w = runner(p)
            point_walls.append({"index": i, "wall_s": round(w, 6)})
            emit(i, m)
            results.append(m)
        return backend, results, {"mode": "serial", "points": point_walls}

    if processes <= 1 or len(points) <= 1:
        return _serial()
    # forking a parent whose XLA backend is live (multithreaded) can
    # deadlock the workers, so after a backend="jax" sweep ran in this
    # process switch to the spawn family.  Merely having jax *imported*
    # is fine — repro.core pulls it in transitively, and penalizing
    # every NumPy sweep with spawn start-up costs would be wrong.
    # Spawn/forkserver re-import __main__, which is impossible for
    # stdin/heredoc programs — fall back to serial there rather than
    # crash or risk the fork.
    if _xla_backend_live():
        main_file = getattr(sys.modules.get("__main__"), "__file__", None)
        if main_file is not None and not os.path.exists(main_file):
            return _serial()
        methods = multiprocessing.get_all_start_methods()
        ctx = multiprocessing.get_context(
            "forkserver" if "forkserver" in methods else "spawn")
    else:
        ctx = multiprocessing.get_context()
    out: List[Optional[ScenarioMetrics]] = [None] * len(points)
    with ProcessPoolExecutor(max_workers=processes, mp_context=ctx,
                             initializer=_pin_cpu) as ex:
        futures = {ex.submit(runner, p): i for i, p in enumerate(points)}
        pending = set(futures)
        while pending:
            done, pending = wait(pending, return_when=FIRST_COMPLETED)
            for fut in done:
                i = futures[fut]
                m, w = fut.result()   # re-raises worker exceptions
                point_walls.append({"index": i, "wall_s": round(w, 6)})
                out[i] = m
                emit(i, m)
    return backend, out, {"mode": "pool", "points": point_walls,
                          "processes": processes}


def _xla_backend_live() -> bool:
    """True iff an XLA backend (and its thread pools) was plausibly
    created in this process — not merely `import jax`.  First line: our
    own jax engine's dispatch flag (set on actual use, not import).
    Second line: jax's private backend cache,
    `jax._src.xla_bridge._backends`, filled once a backend starts
    (pinned by `tests/test_placement.py`, so a JAX upgrade that
    renames it fails a test instead of silently forking a live
    backend)."""
    if getattr(sys.modules.get("repro.netsim.jx.engine"),
               "_BACKEND_USED", False):
        return True
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and bool(xb._backends)


def _execute_jax(points: List[ScenarioSpec], derive: Optional[Callable],
                 emit: OnResult):
    """Batched single-process sweep through the megabatch path.

    Every structurally compatible point — any mix of routing, nic,
    fault, and seed axes — stacks into ONE fused `jit(vmap)` launch
    that compiles once; heterogeneous flow counts and fault timelines
    share programs via shape buckets (`repro.netsim.jx.megabatch`).
    Dispatch is pipelined: a single prep worker runs the memoized host
    prep + launch of shape bucket k+1 while the device executes bucket
    k, and the main thread finalizes each bucket's rows as it retires.
    Launches, spans and counters go to the caller's `collect_dispatch`
    scope, which the prep worker adopts."""
    import jax

    from repro.netsim.flight import adopt_dispatch, current_collectors
    from repro.netsim.jx.engine import f32_overflow_log
    from repro.netsim.jx.megabatch import (dispatch_planned, finalize_group,
                                           plan_megabatch)

    results: List[Optional[ScenarioMetrics]] = [None] * len(points)
    n_overflows0 = len(f32_overflow_log())

    def deliver(i, c, r):
        with span("repro.distill"):
            m = distill_metrics(points[i], c, r)
        if derive is not None:
            m.extra.update(derive(points[i], c, r))
        results[i] = m
        emit(i, m)

    def compile_point(p):
        with span("repro.scenario"):
            return compile_scenario(p)

    compiled = [compile_point(p) for p in points]
    caches, planned = plan_megabatch(compiled)
    collectors = current_collectors()
    x64 = bool(jax.config.jax_enable_x64)

    def prep(group):
        # the worker thread runs outside the main thread's
        # collect_dispatch scope AND its thread-local jax config
        # overrides (`jax.enable_x64(...)` contexts): adopt the counters
        # and re-assert the caller's x64 state so the launch traces with
        # the caller's dtypes
        with adopt_dispatch(collectors), jax.enable_x64(x64):
            return dispatch_planned(group, caches)

    launches = 0
    # single prep worker: host prep (memoized flow arrays, fault
    # timelines, ECMP replays) of bucket k+1 overlaps device execution
    # of bucket k (JAX dispatch is async); the main thread finalizes
    # rows as buckets retire
    with ThreadPoolExecutor(max_workers=1) as pool:
        futs = [pool.submit(prep, g) for g in planned]
        for fut in futs:
            for idxs, handle in fut.result():
                launches += 1
                for i, r in zip(idxs, finalize_group(handle)):
                    deliver(i, compiled[i], r)
    overflows = list(f32_overflow_log()[n_overflows0:])
    # >1 launch means prep/execute/finalize actually overlapped (launch
    # k+1's host prep runs while the device executes k)
    return results, overflows, {"groups": len(planned),
                                "launches": launches,
                                "pipelined": launches > 1}
