"""Declarative experiment API: spec -> plan -> engine -> store.

The one-stop surface for the paper's (and related work's) study shape --
schemes x scenario grid x N x trials -- with multi-device sharded
execution and a content-addressed results store:

    from repro.experiments import (ExperimentSpec, ScenarioGrid,
                                   scheme_spec, run_experiment,
                                   default_store)

    spec = ExperimentSpec(
        name="demo",
        grid=ScenarioGrid(K=50, points=[(mu, mu * mu / 6, int(mu))
                                        for mu in (10.0, 50.0)]),
        schemes=(scheme_spec("work_exchange"), scheme_spec("hedged")),
        N=1_000_000, trials=100, seed=1234,
        backend="jax", devices="auto")

    result = run_experiment(spec, store=default_store())
    result.report("work_exchange")[0].t_comp

Module map:
    spec.py    -- ExperimentSpec / ScenarioGrid / SchemeSpec (JSON + hash)
    plan.py    -- compile_plan: resolve backend/devices, validate tasks
    engine.py  -- run_experiment / execute_plan (sharded mc_grid dispatch)
    store.py   -- ResultsStore: results/store/<spec-hash>.json
    __main__   -- CLI: python -m repro.experiments [spec.json | --demo |
                  ls | compare <hash-a> <hash-b>]

The scenario axis (``grid=``) is pluggable: any family registered in
``repro.scenarios.SCENARIO_REGISTRY`` (uniform_random / explicit /
trace_corpus / drifting / hcmm_sweep) -- ``ScenarioGrid`` remains the
PR-4 constructor facade for the first two.

The arrival axis is pluggable too: ``ExperimentSpec(serving=
ServingConfig(loads=(0.5, 0.8, 0.95)))`` sweeps offered load through the
streaming-arrival engine (``repro.serving``), one report row per
(grid point x load) with latency percentiles in ``extra``.
"""
from repro.scenarios import (SCENARIO_REGISTRY, ScenarioFamily, get_family,
                             list_families)
from repro.serving import ServingConfig

from .engine import (ExperimentResult, enable_compilation_cache,
                     execute_plan, run_experiment)
from .plan import Plan, SHARDED_BACKENDS, Task, compile_plan
from .spec import (SPEC_VERSION, ExperimentSpec, ScenarioGrid, SchemeSpec,
                   scheme_spec)
from .store import DEFAULT_STORE_ROOT, ResultsStore, default_store

__all__ = [
    "SPEC_VERSION", "ExperimentSpec", "ScenarioGrid", "SchemeSpec",
    "scheme_spec", "ServingConfig",
    "SCENARIO_REGISTRY", "ScenarioFamily", "get_family", "list_families",
    "Plan", "Task", "SHARDED_BACKENDS", "compile_plan",
    "ExperimentResult", "execute_plan", "run_experiment",
    "enable_compilation_cache",
    "DEFAULT_STORE_ROOT", "ResultsStore", "default_store",
]
