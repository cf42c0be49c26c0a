"""The one general generator: a configuration and a traffic mix in, the
calls of a cell out.

A configuration file states a deployment (``kind`` ``batch_mc``: the
paper's Section 7 cluster).  A traffic file states the study a caller
runs against it over and over: which schemes with which parameters, how
many trials, which engine.  Both are plain data; this module turns them
into ``ExperimentSpec`` values for ``repro.experiments.run_experiment``,
the entry the window drives, and reads each call's answers back out of
the result.  Per-call seeds come from ``--seed`` (``run.call_seed``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

KINDS = ("batch_mc",)


def het_points(config: Dict[str, Any]) -> List[tuple]:
    """``(mu, sigma2, draw_seed)`` per grid point, in the config's order
    (mu outer, sigma^2 level inner); the draw seed is ``int(mu)`` for a
    ``het_seed`` of ``"int(mu)"``, else the stated integer."""
    rule = config["het_seed"]
    return [(float(mu), float(frac) * mu * mu,
             int(mu) if rule == "int(mu)" else int(rule))
            for mu in config["mus"] for frac in config["sigma2_fracs"]]


def het_rates(config: Dict[str, Any]) -> np.ndarray:
    """The ``(G, K)`` rate grid of the deployment, drawn as the paper's
    Section 7 states: ``lambda_k ~ U(mu - sqrt(3 sigma^2), mu + sqrt(3
    sigma^2))`` from ``default_rng(draw_seed)``."""
    K = int(config["K"])
    out = []
    for mu, sigma2, draw in het_points(config):
        half = np.sqrt(3.0 * sigma2)
        lam = np.random.default_rng(draw).uniform(mu - half, mu + half, K)
        out.append(np.maximum(lam, 1e-12))
    return np.stack(out)


@dataclasses.dataclass
class Cell:
    """One configuration under one traffic mix."""

    config: Dict[str, Any]
    traffic: Dict[str, Any]

    def __post_init__(self):
        kind = self.config["kind"]
        if kind not in KINDS:
            raise KeyError(f"unknown configuration kind {kind!r}; have "
                           f"{KINDS}")
        if self.traffic["kind"] != kind:
            raise ValueError(f"traffic of kind {self.traffic['kind']!r} "
                             f"cannot drive a {kind!r} configuration")

    @property
    def schemes(self) -> List[str]:
        return list(self.traffic["schemes"])

    @property
    def trials(self) -> int:
        return int(self.traffic["trials"])

    @property
    def points(self) -> int:
        return len(het_points(self.config))

    @property
    def work_per_call(self) -> float:
        """Scheme-trials one call returns."""
        return float(self.points * self.trials * len(self.schemes))

    def scheme_params(self, scheme: str) -> Dict[str, Any]:
        """The configuration's parameters of ``scheme`` (the exchange
        thresholds, a code's redundancy), then the traffic's (how the
        study runs it, such as the MDS sweep's trials)."""
        c = self.config
        params = {"work_exchange": c["exchange"],
                  "work_exchange_unknown": c["exchange"],
                  "het_mds": c.get("het_mds", {})}.get(scheme, {})
        return {**params, **self.traffic.get("scheme_params", {})
                .get(scheme, {})}

    def spec(self, call_seed: int):
        from repro.experiments import (ExperimentSpec, ScenarioGrid,
                                       scheme_spec)
        c, t = self.config, self.traffic
        return ExperimentSpec(
            name=f"chipbench-{c['name']}",
            grid=ScenarioGrid(K=int(c["K"]), points=het_points(c)),
            schemes=tuple(scheme_spec(s, **self.scheme_params(s))
                          for s in self.schemes),
            N=int(c["N"]), trials=self.trials, seed=int(call_seed),
            backend=t["backend"], devices=int(t.get("devices", 1)),
            panel=t.get("panel", "per_scheme"))

    def answers(self, result) -> Dict[str, np.ndarray]:
        """What one call returned, per scheme, as ``(G, 4)`` rows of
        (mean T_comp, std, trials, L): ``L`` is the code length an MDS
        scheme chose for the point, 0 for every other scheme."""
        return {key: np.asarray([(rep.t_comp, rep.t_comp_std, rep.trials,
                                  rep.extra.get("L", 0))
                                 for rep in result.report(key)],
                                dtype=np.float64)
                for key in self.schemes}
