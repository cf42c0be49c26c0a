"""Spec -> Plan compilation: resolve every execution knob up front.

``compile_plan`` turns a declarative ``ExperimentSpec`` into an
execution ``Plan``:

* the sampler backend is resolved (explicit field, else
  ``REPRO_SAMPLER_BACKEND``, else numpy) and validated against the
  registry;
* the device count is normalized to a concrete int -- ``"auto"`` and
  over-asks clamp to what the host offers, and backends without a
  sharded executor (numpy: the bit-exact single-device oracle) pin to 1;
* every scheme task is validated by instantiating it (unknown names and
  bad params fail at compile time, not mid-run) and gets its concrete
  rng seed;
* the scenario grid is materialized into ``HetSpec`` rows.

The plan's ``spec`` field is the *resolved* spec -- the value the store
hashes, so a cache hit promises the stored numbers are what this exact
execution would produce.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.schemes import get_scheme
from repro.core.samplers import grid_bucket_shape, resolve_backend
from repro.core.types import HetSpec

from .spec import ExperimentSpec

# backends with a sharded multi-device executor (repro.core.samplers
# ``grid_sharding``); everything else runs single-device
SHARDED_BACKENDS = ("jax", "pallas")


@dataclasses.dataclass(frozen=True)
class Task:
    """One resolved scheme run over the whole scenario grid."""

    key: str
    scheme: str
    params: Tuple[Tuple[str, Any], ...]
    seed: int

    @property
    def params_dict(self) -> Dict[str, Any]:
        return dict(self.params)


@dataclasses.dataclass
class Plan:
    """Compiled execution plan: resolved spec + materialized work.

    ``rate_schedules`` is the scenario family's optional ``(G, R, K)``
    per-exchange-round service-rate schedule (drifting / trace-corpus
    grids), handed to every scheme task whose scheme declares
    ``supports_rate_schedule``.
    """

    spec: ExperimentSpec          # backend/devices concrete
    het_specs: List[HetSpec]
    tasks: List[Task]
    rate_schedules: Optional[np.ndarray] = None

    @property
    def spec_hash(self) -> str:
        return self.spec.spec_hash()

    @property
    def backend(self) -> str:
        return self.spec.backend

    @property
    def devices(self) -> int:
        return int(self.spec.devices)

    @property
    def bucket_shape(self) -> Optional[Dict[str, int]]:
        """The padded ``(rows, K[, R])`` shape bucket this plan's panel
        dispatches at on a transform backend (None on the exact numpy
        oracle, which never pads).  Plans with equal buckets share one
        compilation -- and one persistent-cache entry -- regardless of
        their raw ``(G, trials, K, R)``."""
        if self.backend not in SHARDED_BACKENDS or not self.het_specs:
            return None
        R = (None if self.rate_schedules is None
             else int(self.rate_schedules.shape[1]))
        return grid_bucket_shape(len(self.het_specs), self.spec.trials,
                                 self.het_specs[0].K, R,
                                 backend=self.backend)


def _resolve_devices(requested, backend: str) -> int:
    if backend not in SHARDED_BACKENDS:
        return 1
    if requested == "auto" or requested is None:
        want = None
    else:
        want = int(requested)
        if want <= 1:
            return 1
    import jax
    have = len(jax.devices())
    return have if want is None else max(1, min(want, have))


def compile_plan(spec: ExperimentSpec) -> Plan:
    """Resolve backend/devices, validate tasks, materialize the grid."""
    backend = resolve_backend(spec.backend)
    devices = _resolve_devices(spec.devices, backend)
    if spec.serving is not None:
        # the queueing engine resolves like the sampler backend does
        # (explicit field > $REPRO_SERVING_BACKEND > numpy) and the
        # concrete name lands in the stored spec: the cache address
        # promises which engine produced the numbers
        from repro.serving.backends import get_serving_backend
        sname = spec.serving.resolve_backend()
        if sname != spec.serving.backend:
            spec = spec.replace(
                serving=dataclasses.replace(spec.serving, backend=sname))
        if get_serving_backend(sname).shards:
            # the scan engine stacks (load x trial) rows -- a batch axis
            # the 1-D grid mesh splits like any other
            devices = _resolve_devices(spec.devices, "jax")
        else:
            # the numpy oracle loop is sequential in time and runs
            # single-device regardless of sampler backend
            devices = 1
    if spec.execution == "live":
        # live episodes are one asyncio event loop; the sharded executor
        # does not apply, and the transport must exist at compile time
        devices = 1
        spec.live.build_transport()
    if spec.panel == "fused" and backend != "pallas":
        # the jax coupled-CRN fused-panel engine runs single-device;
        # only the pallas kernel path shards the stacked mixed-mode
        # rows (see we_rounds_grid)
        devices = 1
    if spec.training is not None:
        # the training engine is one jit stream (scan over unit groups);
        # the sharded MC executor does not apply
        devices = 1
    tasks = []
    for s in spec.schemes:
        scheme = get_scheme(s.scheme, **s.params_dict)  # fail fast
        if spec.execution == "live":
            from repro.control.coordinator import live_supported
            live_supported(scheme)      # unsupported schemes fail here
        tasks.append(Task(key=s.report_key, scheme=s.scheme,
                          params=s.params,
                          seed=int(s.seed if s.seed is not None
                                   else spec.seed)))
    resolved = spec.replace(backend=backend, devices=devices)
    return Plan(spec=resolved, het_specs=spec.grid.specs(), tasks=tasks,
                rate_schedules=spec.grid.rate_schedules())


__all__ = ["SHARDED_BACKENDS", "Task", "Plan", "compile_plan"]
