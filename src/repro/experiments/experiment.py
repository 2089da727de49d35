"""The `Experiment` spec: a named grid over arbitrary `ScenarioSpec`
override paths, executed into a columnar `ResultSet` through an on-disk
run cache.

    exp = Experiment(
        name="fault_fraction_x_planes",
        base="allreduce_under_random_failures",
        axes=product(Axis("faults[0].frac", (0.05, 0.1, 0.2)),
                     Axis("topo.n_planes", (1, 2, 4))),
    )
    rs = run_experiment(exp, cache=".expcache")
    rs.pivot("axis.faults[0].frac", "axis.topo.n_planes",
             "mean_goodput")

Each grid point is the base spec with that point's coordinate values
applied in axis order ("scenario" replaces the base, "seed" perturbs
both `sim.seed` and `workload_seed`, everything else is an override
path), then validated.  Re-running with the same cache directory skips
every point whose fully-resolved spec hashes to a cached entry, so an
interrupted sweep resumes where it died.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from repro.scenarios.registry import get_scenario
from repro.scenarios.spec import ScenarioSpec

from .axes import Axis, Chain, Product, Zip, product
from .cache import RunCache, spec_key
from .execute import execute_points
from .overrides import apply_override
from .resultset import ResultSet

GridExpr = Union[Axis, Product, Zip, Chain]


@dataclass(frozen=True)
class ExperimentPoint:
    """One fully-resolved grid point: its ordinal, its coordinate labels
    (axis path -> label), and the spec to run."""
    index: int
    coords: Dict[str, Any]
    spec: ScenarioSpec


@dataclass(frozen=True)
class Experiment:
    """A named parameter study.  `base` is a registry scenario name or an
    inline `ScenarioSpec` (optional when a "scenario" axis supplies it).
    `axes` is a grid expression — a single `Axis`, a combinator
    (`product`/`zip_axes`/`chain`), or a plain sequence of those, which
    is treated as an implicit product.  `derive(spec, compiled, result)
    -> dict` adds per-run `extra` metrics; it must be a module-level
    function (process pools pickle it) and is folded into the cache key
    by qualified name."""
    name: str
    axes: Union[GridExpr, Sequence[GridExpr]]
    base: Union[str, ScenarioSpec, None] = None
    derive: Optional[Callable] = None
    description: str = ""

    def grid(self) -> GridExpr:
        if isinstance(self.axes, (Axis, Product, Zip, Chain)):
            return self.axes
        return product(*self.axes)

    def coord_names(self) -> List[str]:
        return list(self.grid().paths())

    def _base_spec(self) -> Optional[ScenarioSpec]:
        if self.base is None:
            return None
        if isinstance(self.base, str):
            return get_scenario(self.base)
        return self.base

    def points(self) -> List[ExperimentPoint]:
        base = self._base_spec()
        out: List[ExperimentPoint] = []
        for i, pt in enumerate(self.grid().points()):
            spec = base
            coords: Dict[str, Any] = {}
            overridden = False
            for path, value, label in pt:
                coords[path] = label
                if path == "scenario":
                    if overridden:
                        # replacing the spec now would silently discard
                        # the overrides already applied (while their
                        # coordinates still label the row) — refuse
                        raise ValueError(
                            f"experiment {self.name!r}: 'scenario' axis "
                            "must come before override axes — it "
                            "replaces the spec and would drop "
                            f"{[p for p, _, _ in pt if p != 'scenario']}")
                    spec = (get_scenario(value) if isinstance(value, str)
                            else value)
                    continue
                overridden = True
                if spec is None:
                    raise ValueError(
                        f"experiment {self.name!r}: no base scenario — "
                        "pass base= or lead with a 'scenario' axis")
                if path == "seed":
                    spec = spec.with_sim(
                        seed=spec.sim.seed + value).with_workload_seed(
                        spec.workload_seed + value)
                else:
                    spec = apply_override(spec, path, value)
            if spec is None:
                raise ValueError(
                    f"experiment {self.name!r}: no base scenario — "
                    "pass base= or lead with a 'scenario' axis")
            spec.validate()
            out.append(ExperimentPoint(index=i, coords=coords, spec=spec))
        return out

    def cache_salt(self) -> str:
        """Folds the derive hook's identity into cache keys: different
        extra-metric logic must not alias plain runs.  `functools.partial`
        of a module-level function is accepted (its bound arguments join
        the salt — e.g. a trace export directory)."""
        if self.derive is None:
            return ""
        d = self.derive
        if isinstance(d, functools.partial):
            inner = f"{d.func.__module__}.{d.func.__qualname__}"
            return f"{inner}{d.args!r}{sorted(d.keywords.items())!r}"
        return f"{d.__module__}.{d.__qualname__}"


def run_experiment(exp: Experiment,
                   processes: Optional[int] = None,
                   backend: Optional[str] = None,
                   cache: Union[RunCache, str, None] = None,
                   compile_cache_dir: Optional[str] = None
                   ) -> ResultSet:
    """Execute the experiment grid into a `ResultSet`.

    `cache` is a `RunCache` or a directory path; cached points are
    served without running, fresh points stream into both the cache and
    the `ResultSet` as they complete (so an interrupt loses at most the
    in-flight points, and the next call resumes from the survivors).
    `backend` pins every point ('numpy' | 'jax'); None runs each point
    on its spec's own `sim.backend`, so a `sim.backend` axis sweeps
    both.  On the JAX backend the whole grid fuses into one launch per
    structure (megabatch), and `compile_cache_dir` places JAX's
    persistent compilation cache, so the fused program survives process
    restarts.  Rows come back in grid order; `rs.cache_hits` /
    `rs.cache_misses` report how the run was served."""
    if isinstance(cache, str):
        cache = RunCache(cache)
    if backend is not None and backend not in ("numpy", "jax"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'numpy' or 'jax'")
    pts = exp.points()
    if backend is not None:
        pts = [replace(p, spec=p.spec.with_sim(backend=backend))
               for p in pts]
    salt = exp.cache_salt()
    rs = ResultSet(exp.coord_names())
    pending: List[ExperimentPoint] = []
    for p in pts:
        hit = cache.get(spec_key(p.spec, salt)) if cache else None
        if hit is not None:
            rs.cache_hits += 1
            rs.append(hit, p.coords, order=p.index)
        else:
            pending.append(p)
    rs.cache_misses = len(pending)

    def on_result(group: List[ExperimentPoint], j: int,
                  m) -> None:
        p = group[j]
        if cache is not None:
            cache.put(spec_key(p.spec, salt), p.spec, m)
        rs.append(m, p.coords, order=p.index)

    # mixed-backend grids (e.g. a sim.backend axis) partition into one
    # executor call per backend, each batched as usual
    executions: List[Dict] = []
    for bk in ("numpy", "jax"):
        group = [p for p in pending if p.spec.sim.backend == bk]
        if group:
            fl: Dict = {}
            execute_points(
                [p.spec for p in group], processes=processes, backend=bk,
                derive=exp.derive, compile_cache_dir=compile_cache_dir,
                on_result=lambda j, m, g=group: on_result(g, j, m),
                flight=fl)
            # executor point indices are group-local; lift to grid order
            for pw in fl.get("points", ()):
                pw["index"] = group[pw["index"]].index
            executions.append(fl)
    rs.flight = {"experiment": exp.name,
                 "cache_hits": rs.cache_hits,
                 "cache_misses": rs.cache_misses,
                 "executions": executions}
    rs.sort_to_grid_order()
    return rs


# ---------------------------------------------------------------------------
# experiment registry (mirrors the scenario registry)
# ---------------------------------------------------------------------------

EXPERIMENTS: Dict[str, Callable[[], Experiment]] = {}


def register_experiment(fn: Callable[[], Experiment]
                        ) -> Callable[[], Experiment]:
    exp = fn()
    exp.points()                      # fail at import, not first run
    EXPERIMENTS[exp.name] = fn
    return fn


def get_experiment(name: str) -> Experiment:
    try:
        return EXPERIMENTS[name]()
    except KeyError:
        raise KeyError(
            f"unknown experiment {name!r}; known: {sorted(EXPERIMENTS)}"
        ) from None


def list_experiments() -> List[str]:
    return sorted(EXPERIMENTS)
