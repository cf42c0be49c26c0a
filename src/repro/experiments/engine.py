"""The single experiment entry point: spec in, reports out.

``run_experiment`` compiles the spec (``repro.experiments.plan``),
consults the content-addressed store, and -- on a miss or ``force`` --
executes every scheme task through ``Scheme.mc_grid`` on the resolved
sampler backend.  Multi-device specs (``devices > 1`` on the jax /
pallas backends) run under ``repro.core.samplers.grid_sharding``: the
scenario x trials batch rows are split across a 1-D device mesh with
``shard_map``, one independent round pipeline per device.  The numpy
backend always runs single-device: it is the bit-exact oracle every
other configuration is validated against.

Seed discipline: each task draws from its own fresh
``default_rng(task.seed)``, so per-task numbers are independent of task
order and of which other tasks the spec carries -- exactly the figure
drivers' historical behaviour, which is what makes the fig5/6/7 rewrite
seed-for-seed bit-identical on numpy.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import platform
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core.samplers import grid_sharding
from repro.core.schemes import MCReport, get_scheme, mc_grid_panel
from repro.tracing import span

from .plan import Plan, compile_plan
from .spec import ExperimentSpec
from .store import ResultsStore

RESULT_VERSION = 1

# jax's persistent compilation cache: the directory named by
# $JAX_COMPILATION_CACHE_DIR when that is set (jax reads it itself),
# otherwise this fixed, git-ignored directory of the checkout -- the
# path is part of the cache key, so it must never move between runs
DEFAULT_JAX_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point jax's persistent compilation cache at its one directory and
    return that directory.  Every path that compiles jax code calls
    this before its first compile (idempotent; sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set)."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = str(DEFAULT_JAX_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


@dataclasses.dataclass
class ExperimentResult:
    """Everything one experiment run produced, serializable as stored."""

    spec: ExperimentSpec              # resolved: backend/devices concrete
    spec_hash: str
    reports: Dict[str, List[MCReport]]    # task key -> one row per point
    env: Dict[str, Any]
    wall_s: float
    cache_hit: bool = False           # set by run_experiment on a store hit

    def report(self, key: str) -> List[MCReport]:
        return self.reports[key]

    def keys(self) -> List[str]:
        return list(self.reports)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": RESULT_VERSION,
            "spec": self.spec.to_dict(),
            "spec_hash": self.spec_hash,
            "reports": {k: [r.to_dict() for r in rows]
                        for k, rows in self.reports.items()},
            "env": dict(self.env),
            "wall_s": round(float(self.wall_s), 4),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ExperimentResult":
        return cls(spec=ExperimentSpec.from_dict(d["spec"]),
                   spec_hash=d["spec_hash"],
                   reports={k: [MCReport.from_dict(r) for r in rows]
                            for k, rows in d["reports"].items()},
                   env=dict(d.get("env", {})),
                   wall_s=float(d.get("wall_s", 0.0)))


def _uses_jax(plan: Plan) -> bool:
    """Whether executing ``plan`` compiles jax code (the numpy sampler
    and the numpy serving loop never import jax)."""
    spec = plan.spec
    if spec.execution == "live" or spec.training is not None:
        return True
    if spec.serving is not None:
        return spec.serving.backend == "jax"
    return plan.backend in ("jax", "pallas")


def _environment(cache_dir: Optional[str]) -> Dict[str, Any]:
    env: Dict[str, Any] = {
        "numpy": np.__version__,
        "python": platform.python_version(),
    }
    if cache_dir is not None:
        import jax
        env["jax"] = jax.__version__
        env["jax_devices"] = len(jax.devices())
        env["jax_platform"] = jax.default_backend()
        env["jax_compilation_cache"] = cache_dir
    return env


def _execute_serving(plan: Plan) -> Dict[str, List[MCReport]]:
    """Serving specs: every scheme task becomes a dispatch policy run
    through the slotted queueing engine -- one report row per (grid
    point x offered load) instead of per grid point.  The engine is the
    plan's resolved serving backend (``SERVING_BACKENDS``): the numpy
    oracle loop runs single-device; the jax scan engine stacks the
    (load x trial) rows and, at ``devices > 1``, splits them over the
    1-D grid mesh exactly like the batch MC executor does."""
    from repro.serving import run_serving_grid
    shard = (grid_sharding(plan.devices) if plan.devices > 1
             else contextlib.nullcontext())
    reports: Dict[str, List[MCReport]] = {}
    with shard:
        for task in plan.tasks:
            reports[task.key] = run_serving_grid(
                task.scheme, task.params_dict, plan.het_specs,
                plan.spec.serving, plan.spec.N, plan.spec.trials,
                task.seed, rate_schedules=plan.rate_schedules)
    return reports


def _execute_live(plan: Plan) -> Dict[str, List[MCReport]]:
    """Live specs: every scheme task executes through the asyncio
    control plane (``repro.control``) -- real transport round-trips,
    real matmul shards, ``trials`` episodes per grid point, measured
    ``T_comp`` plus the telemetry timeline in each report's
    ``extra["control_plane"]``."""
    from repro.control import run_live_grid
    reports: Dict[str, List[MCReport]] = {}
    for task in plan.tasks:
        reports[task.key] = run_live_grid(
            task.scheme, task.params_dict, plan.het_specs,
            plan.spec.N, plan.spec.live, plan.spec.trials, task.seed,
            rate_schedules=plan.rate_schedules)
    return reports


def _execute_training(plan: Plan) -> Dict[str, List[MCReport]]:
    """Training specs: every scheme task becomes an epoch-assignment
    policy over real gradients (``repro.hettrain``) -- the batched scan
    engine computes one shared optimizer trajectory, each policy's
    scheduler moves virtual wall-clock, one report row per grid point
    with the loss curve in ``extra["training"]``."""
    from repro.hettrain.runner import run_training_grid
    reports: Dict[str, List[MCReport]] = {}
    for task in plan.tasks:
        reports[task.key] = run_training_grid(
            task.scheme, task.params_dict, plan.het_specs,
            plan.spec.training, plan.spec.N, plan.spec.trials, task.seed,
            rate_schedules=plan.rate_schedules)
    return reports


def execute_plan(plan: Plan) -> ExperimentResult:
    """Run a compiled plan (no store interaction)."""
    spec = plan.spec
    t0 = time.perf_counter()
    cache_dir = enable_compilation_cache() if _uses_jax(plan) else None
    if spec.execution == "live":
        reports = _execute_live(plan)
        return ExperimentResult(spec=spec, spec_hash=plan.spec_hash,
                                reports=reports, env=_environment(cache_dir),
                                wall_s=time.perf_counter() - t0)
    if spec.serving is not None:
        reports = _execute_serving(plan)
        return ExperimentResult(spec=spec, spec_hash=plan.spec_hash,
                                reports=reports, env=_environment(cache_dir),
                                wall_s=time.perf_counter() - t0)
    if spec.training is not None:
        reports = _execute_training(plan)
        return ExperimentResult(spec=spec, spec_hash=plan.spec_hash,
                                reports=reports, env=_environment(cache_dir),
                                wall_s=time.perf_counter() - t0)
    reports: Dict[str, List[MCReport]] = {}
    if spec.panel == "fused":
        # fused whole-panel dispatch: the WE known/unknown pair becomes
        # ONE engine call; every other task keeps its own per-task
        # stream (the rng mapping), bit-identical to per_scheme
        schemes = {t.key: get_scheme(t.scheme, **t.params_dict)
                   for t in plan.tasks}
        rngs = {t.key: np.random.default_rng(t.seed) for t in plan.tasks}
        shard = (grid_sharding(plan.devices) if plan.devices > 1
                 else contextlib.nullcontext())
        with shard:
            reports = mc_grid_panel(schemes, plan.het_specs, spec.N,
                                    spec.trials, rngs,
                                    backend=plan.backend,
                                    rate_schedule=plan.rate_schedules)
        if plan.rate_schedules is not None:
            for key, sch in schemes.items():
                if not sch.supports_rate_schedule:
                    for rep in reports[key]:
                        rep.extra["nominal_rates_only"] = 1
        return ExperimentResult(spec=spec, spec_hash=plan.spec_hash,
                                reports=reports, env=_environment(cache_dir),
                                wall_s=time.perf_counter() - t0)
    shard = (grid_sharding(plan.devices) if plan.devices > 1
             else contextlib.nullcontext())
    with shard:
        for task in plan.tasks:
            scheme = get_scheme(task.scheme, **task.params_dict)
            kwargs = {}
            if (plan.rate_schedules is not None
                    and scheme.supports_rate_schedule):
                # drifting / trace-corpus scenarios: the exchange-round
                # engines follow the schedule; single-shot schemes run
                # at the nominal (round-0 / window-mean) rates
                kwargs["rate_schedule"] = plan.rate_schedules
            with span(f"repro.scheme.{scheme.name}"):
                reports[task.key] = scheme.mc_grid(
                    plan.het_specs, spec.N, trials=spec.trials,
                    rng=np.random.default_rng(task.seed),
                    backend=plan.backend, **kwargs)
            if plan.rate_schedules is not None and not kwargs:
                # the grid drifts but this scheme cannot follow it:
                # stamp the rows so stored results (and the CLI table)
                # never read as if the scheme ran under the drift
                for rep in reports[task.key]:
                    rep.extra["nominal_rates_only"] = 1
    return ExperimentResult(spec=spec, spec_hash=plan.spec_hash,
                            reports=reports, env=_environment(cache_dir),
                            wall_s=time.perf_counter() - t0)


def run_experiment(spec: ExperimentSpec,
                   store: Optional[ResultsStore] = None,
                   force: bool = False) -> ExperimentResult:
    """Compile, consult the store, execute on miss, persist.

    ``force=True`` recomputes even on a hit and refreshes the stored
    entry -- what the benchmark harness uses so claim validation always
    reflects fresh numbers while still writing through the store.
    """
    with span("repro.study"):
        with span("repro.plan"):
            plan = compile_plan(spec)
        if store is not None and not force:
            cached = store.get(plan.spec)
            if cached is not None:
                cached.cache_hit = True
                return cached
        result = execute_plan(plan)
        if store is not None:
            store.put(result)
        return result


__all__ = ["RESULT_VERSION", "ExperimentResult", "enable_compilation_cache",
           "execute_plan", "run_experiment"]
