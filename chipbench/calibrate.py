"""Read the numbers the correctness limits are set from, on the chip.

    python3 -m chipbench.calibrate --workload k50_pair --seed 100 --seeds 12 \\
        --seconds 30 --control 3 --fault half_batch --fault altered_answer

One process: the cell's set-up once, then one window of ``--seconds``
per seed (``--seed`` .. ``--seed + --seeds - 1``), each compared with
the plain reference exactly as a run compares it (the lower readings);
then ``--control`` windows with the control in the program's place,
and three windows per ``--fault`` with that fault planted (the upper
readings).  One JSON line per window on standard output and in
``chipbench_out/calibrate-<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, faults, run, workloads  # noqa: E402


def window(cell, run_experiment, seed, seconds, annotate, calls=None):
    """One window and its numbers."""
    if calls is None:
        win = run.run_window(cell, run_experiment, seed, seconds, annotate)
    else:                      # a fixed number of calls (the control)
        win = run.Window([], [], [])
        for i in range(calls):
            run.one_call(cell, run_experiment, run.call_seed(seed, i), win,
                         annotate)
    t0 = time.perf_counter()
    numbers = run.reference_numbers(cell, win, seed) if win.answers else {}
    return {"seed": seed, "calls": len(win.calls),
            "failed": sum(not c.ok for c in win.calls),
            "errors": win.errors[:2],
            "rate": bench.work_rate(win.calls),
            "reference_s": time.perf_counter() - t0, "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="control windows (each of --control-calls calls)")
    ap.add_argument("--control-calls", type=int, default=3)
    ap.add_argument("--fault", action="append", default=[],
                    choices=sorted(faults.FAULTS))
    ap.add_argument("--fault-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    spec = bench.load_benchmark()
    wl = bench.find_workload(spec, args.workload)
    cell = workloads.Cell(bench.load_config(wl["config"]),
                          bench.load_traffic(wl["traffic"]))
    run.set_cache_env()
    try:
        run.attach(int(wl["chips"]))
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.experiments import run_experiment
    annotate = jax.profiler.TraceAnnotation
    warm = run.Window([], [], [])
    run.one_call(cell, run_experiment, run.call_seed(args.seed, 0), warm,
                 annotate)
    out_path = run.OUT_DIR / f"calibrate-{wl['name']}.jsonl"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    plan = [("program", s, contextlib.nullcontext, args.seconds, None)
            for s in range(args.seed, args.seed + args.seeds)]
    plan += [("control", args.seed + 1000 + i, faults.control, 0.0,
              args.control_calls) for i in range(args.control)]
    for name in args.fault:
        plan += [(name, args.seed + 2000 + i,
                  lambda n=name: faults.planted(n), args.fault_seconds, None)
                 for i in range(3)]
    with out_path.open("a") as log:
        for kind, seed, ctx, seconds, calls in plan:
            with ctx():
                row = window(cell, run_experiment, seed, seconds, annotate,
                             calls)
            row = {"workload": wl["name"], "kind": kind, **row}
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
