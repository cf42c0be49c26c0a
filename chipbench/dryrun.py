"""Rehearse a cell end to end on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python3 -m chipbench.dryrun k50_pair

Runs ``chipbench.run`` with the look for a chip skipped and the traffic
cut down (fewer trials), so that the harness, the window,
the reference and the comparison all run here.  It checks paths and
control flow only: the result it returns has ``correct`` and the
checks, and no metric, because no number from a CPU is a measurement
of the chip.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
from typing import Any, Dict, Optional
from unittest import mock

from chipbench import bench, run

TINY = {"trials": 64, "ref_trials": 256}


def dry_run(workload: str, seed: int = 12345, seconds: float = 2.0,
            sizes: Optional[Dict[str, int]] = None,
            trace: int = 0) -> Dict[str, Any]:
    """The result line of one CPU run of ``workload`` at ``sizes``
    (defaults ``TINY``), without its metrics."""
    import jax
    sizes = dict(TINY, **(sizes or {}))
    real = bench.load_traffic

    def tiny(name, base=bench.BENCH_DIR):
        t = dict(real(name, base))
        t.update({k: v for k, v in sizes.items() if k in t})
        return t

    out = io.StringIO()
    with mock.patch.object(run, "attach",
                           lambda chips: jax.devices()[:chips]), \
            mock.patch.object(bench, "load_traffic", tiny), \
            mock.patch.dict(os.environ), \
            contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    if rc != 0:
        raise RuntimeError(f"dry run of {workload} exited {rc}")
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    result.pop("metrics")
    return result


if __name__ == "__main__":
    for name in sys.argv[1:] or [w["name"] for w in
                                 bench.load_benchmark()["workloads"]]:
        res = dry_run(name)
        print(name, json.dumps({k: res[k] for k in ("correct", "attempted",
                                                     "failed", "checks")}))
