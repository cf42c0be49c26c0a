"""Discovery by name and the call-log arithmetic.

Everything here is pure Python: no jax, no program imports.  The CPU
tests drive it directly.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(kind: str, name: str, base: Path) -> Path:
    path = Path(base) / kind / f"{name}.json"
    if not path.is_file():
        have = sorted(p.stem for p in (Path(base) / kind).glob("*.json"))
        raise KeyError(f"no {kind} file {name!r}; have {have}")
    return path


def load_config(name: str, base: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads(_named("configs", name, base).read_text())


def load_traffic(name: str, base: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads(_named("traffic", name, base).read_text())


def load_limits(workload: str, base: Path = BENCH_DIR) -> Dict[str, float]:
    """``limits/<workload>.json``: number name -> limit (a number passes
    when it is at or below its limit)."""
    data = json.loads(_named("limits", workload, base).read_text())
    return {k: float(v) for k, v in data["limits"].items()}


def find_workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: Dict[str, Any], workload: str,
                trace: bool) -> List[Dict[str, Any]]:
    """The metric entries a run of ``workload`` reports: the end-to-end
    ones with ``trace`` off, the per-layer ones with it on.  An entry
    with a ``workloads`` key applies to the cells it lists; one without
    applies to every cell that reports the end-to-end metric it moves
    (per-layer) or to every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def metric_reader(name: str, base: Path = BENCH_DIR) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``, loaded by
    path (metric names carry dots, so they are not module names)."""
    path = Path(base) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no metric reader {path.name}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{len(name)}_{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the call log
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    """One closed-loop call of the entry: host clock at start and end
    (seconds, ``time.perf_counter``), the work it returned (scheme-trials)
    and whether it raised."""

    start: float
    end: float
    work: float
    ok: bool = True

    @property
    def wall(self) -> float:
        return self.end - self.start


def window_bounds(calls: Sequence[Call]) -> tuple:
    """The window runs from the first call's start to the last call's
    end: calls are started until ``--seconds`` have passed, and the one
    running then is waited for, so every call in the log completed
    inside the window."""
    return calls[0].start, calls[-1].end


def work_rate(calls: Sequence[Call]) -> float:
    """All work the window's calls returned over all of its seconds."""
    if not calls:
        raise ValueError("no calls in the window")
    w0, w1 = window_bounds(calls)
    return sum(c.work for c in calls if c.ok) / (w1 - w0)


def fmt_checks(checks: Dict[str, Dict[str, float]]) -> str:
    """One line per compared number: name, reading, limit, verdict."""
    return "\n".join(
        f"check {name}: {c['value']!r} limit {c['limit']!r} "
        f"{'ok' if c['value'] is not None and c['value'] <= c['limit'] else 'FAIL'}"
        for name, c in checks.items())


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                checks: Dict[str, Dict[str, float]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks           # last key: each number beside its limit
    return json.dumps(out)
