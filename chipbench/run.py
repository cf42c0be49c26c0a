"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Closed loop: one caller runs the cell's study through
``repro.experiments.run_experiment(spec, store=None, force=True)`` back
to back, each call with its own seed drawn from ``--seed``, for
``--seconds``; the store is left out (it only writes a file).  Set-up
(process start, jax and TPU init, the compile cache, one warm-up call
of the cell's own shapes) ends where the window starts.  With
``--trace 1`` the last seconds of the window run under the profiler,
the per-layer metrics are read from that trace, and its reduction is
kept as ``chipbench_out/trace/<cell>-<seed>.json.gz`` (the form of the
recorded traces under ``testdata/``); with ``--trace 0``
the end-to-end metrics are read from the host clock.  Either way the
answers of every call are compared with the plain reference once the
window has closed, and the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced), then ``checks``, each compared number
beside its limit.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for: no number of this benchmark comes from a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, correct, workloads  # noqa: E402

TRACE_SECONDS = 4.0          # the traced part of a --trace 1 window
OUT_DIR = ROOT / "chipbench_out"


def process_start() -> float:
    """Epoch seconds at which this process started (kernel's record,
    10 ms ticks), or now when /proc cannot say."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
        start_ticks = float(fields.split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - (uptime - start_ticks
                              / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


class NoChip(RuntimeError):
    """The run was asked for chips this machine does not have."""


def set_cache_env() -> str:
    """The compile cache lives in the checkout at a fixed path, and the
    program takes it from ``JAX_COMPILATION_CACHE_DIR``; every program
    is cached, however quickly it compiled, and nothing is evicted (an
    evicting cache drops entries whose access-time file is missing)."""
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    return cache


def attach(chips: int):
    """The devices the cell runs on; raises ``NoChip`` unless JAX sees
    at least ``chips`` TPU devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts executables built (compiled or read from the persistent
    cache) through ``jax.monitoring``, from ``mark()`` on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0
        self.base = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.total += 1

    def mark(self) -> None:
        self.base = self.total

    @property
    def since(self) -> int:
        return self.total - self.base


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    calls: List[bench.Call]
    setup_s: float
    compiles_in_window: int
    reduced: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class Window:
    calls: List[bench.Call]
    answers: List[Dict[str, Any]]
    errors: List[str]


def call_seed(seed: int, i: int) -> int:
    """Spec seed of call ``i``: distinct per call, drawn from ``--seed``."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed), int(i)])
    return int(ss.generate_state(1, dtype=np.uint32)[0] & 0x7FFFFFFF)


def one_call(cell: workloads.Cell, run_experiment, seed: int, win: Window,
             annotate) -> None:
    t0 = time.perf_counter()
    try:
        with annotate("chipbench.call"):
            res = run_experiment(cell.spec(seed), store=None, force=True)
            ans = cell.answers(res)
        ok = True
    except Exception as e:           # a failed call is counted, not fatal
        win.errors.append(f"{type(e).__name__}: {e}")
        ans, ok = None, False
    t1 = time.perf_counter()
    win.calls.append(bench.Call(t0, t1, cell.work_per_call, ok))
    if ok:
        win.answers.append(ans)


def run_window(cell, run_experiment, seed: int, seconds: float, annotate,
               trace_dir: Optional[Path] = None) -> Window:
    """Calls back to back until ``seconds`` have passed; the call running
    then is waited for.  Call 0 of ``seed`` is the warm-up's, so the
    window's calls are 1, 2, ...  With ``trace_dir`` the last
    ``TRACE_SECONDS`` run under the profiler."""
    win = Window([], [], [])
    t_end = time.perf_counter() + seconds
    t_trace = t_end - min(TRACE_SECONDS, seconds) if trace_dir else None
    tracing = False
    i = 1
    while True:
        if t_trace is not None and not tracing \
                and time.perf_counter() >= t_trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing = True
        one_call(cell, run_experiment, call_seed(seed, i), win, annotate)
        i += 1
        if win.calls[-1].end >= t_end:
            break
    if tracing:
        import jax
        jax.profiler.stop_trace()
    return win


def reference_numbers(cell: workloads.Cell, win: Window,
                      seed: int) -> Dict[str, float]:
    import numpy as np
    from chipbench import reference_mc
    rng = np.random.default_rng(np.random.SeedSequence([int(seed),
                                                        2 ** 32 + 1]))
    lam = workloads.het_rates(cell.config)
    ref = {key: reference_mc.point_stats(
        key, lam, cell.config, int(cell.traffic["ref_trials"]), rng)
        for key in cell.schemes}
    return correct.mc_numbers(win.answers, ref)


def device_info(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    import jax
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    t_proc = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    spec = bench.load_benchmark()
    wl = bench.find_workload(spec, args.workload)
    cell = workloads.Cell(bench.load_config(wl["config"]),
                          bench.load_traffic(wl["traffic"]))
    limits = bench.load_limits(wl["name"])
    wanted = bench.metrics_for(spec, wl["name"], bool(args.trace))

    set_cache_env()
    try:
        devs = attach(int(wl["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.experiments import run_experiment
    compiles = CompileCounter()

    # set-up: one call of the cell's shapes
    warm = Window([], [], [])
    one_call(cell, run_experiment, call_seed(args.seed, 0), warm,
             jax.profiler.TraceAnnotation)
    if warm.errors:
        print(f"chipbench: warm-up call failed: {warm.errors[0]}",
              file=sys.stderr)
        return 1
    compiles.mark()
    setup_s = time.time() - t_proc

    trace_dir = None
    if args.trace:
        trace_dir = OUT_DIR / "trace" / f"{wl['name']}-{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_window(cell, run_experiment, args.seed, args.seconds,
                     jax.profiler.TraceAnnotation, trace_dir)
    in_window = compiles.since
    device = device_info(devs)

    ctx = Context(win.calls, setup_s, in_window)
    breakdown = None
    if args.trace:
        from chipbench import trace as tr
        ctx.reduced = tr.load_xplane(tr.find_xplane(trace_dir))
        lo, hi = tr.window(ctx.reduced)
        busy = tr.busy_ns(ctx.reduced)
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": tr.top_ops(ctx.reduced),
                     "idle_gaps": tr.idle_gaps(ctx.reduced)}
        tr.save(ctx.reduced, trace_dir.parent / f"{trace_dir.name}.json.gz")
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in wanted:
        value = bench.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    numbers = reference_numbers(cell, win, args.seed) if win.answers else {}
    checks = correct.judge(numbers, limits)
    failed = sum(not c.ok for c in win.calls)
    ok = failed == 0 and bool(win.answers) and correct.passed(checks)
    print(f"window {bench.window_bounds(win.calls)[1] - win.calls[0].start:.3f}"
          f" s, {len(win.calls)} calls, {in_window} compiles in window, "
          f"setup {setup_s:.3f} s, reference {time.perf_counter() - t_ref:.1f}"
          f" s", file=sys.stderr)
    for err in win.errors[:3]:
        print(f"call failed: {err}", file=sys.stderr)
    print(bench.fmt_checks(checks), file=sys.stderr)
    print(bench.result_line(ok, len(win.calls), failed, metrics, device,
                            checks, breakdown))
    return 0


if __name__ == "__main__":
    sys.exit(main())
