"""CI benchmark-regression gate over ``results/BENCH_schemes.json``.

Compares a freshly generated benchmark json against the committed
baseline and fails (exit 1) on

* **wall-clock regression > 25%** after machine-speed normalization: raw
  wall-clocks are not comparable across runner generations, so every
  wall ratio is divided by the median ratio over all timed entries (the
  machine calibration factor); what remains is per-entry drift.  Entries
  faster than ``--min-wall`` seconds in the baseline are reported but
  not gated (timer noise); wall gating is skipped entirely when the two
  runs used different global configs (quick vs full).  Residual risk:
  a runner whose numpy-vs-jax relative speed differs sharply from the
  baseline machine shows up as per-entry drift -- the walls in the json
  are min-of-reps to keep jitter out, and ``--wall-tol`` widens the
  band when a runner generation change lands.
* **mean T_comp drift beyond Monte-Carlo tolerance**: both runs use
  fixed seeds, so per-scheme means should agree to ~5 combined standard
  errors (numpy backends are bit-reproducible; the tolerance absorbs
  numpy-version and platform differences).

A before/after markdown table goes to ``$GITHUB_STEP_SUMMARY`` when set
(always to stdout), so the regression picture is one click away in CI.

Usage:
    python -m benchmarks.bench_gate --baseline results/BENCH_schemes.json \
        --current /tmp/fresh.json [--wall-tol 0.25] [--min-wall 0.02]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

WALL_KEYS_GRID = ("pr1_numpy_loop_s", "numpy_grid_s", "jax_grid_s",
                  "pallas_grid_s")
WALL_KEYS_MDS = ("pr2_loop_s", "numpy_grid_s", "jax_grid_s",
                 "pallas_grid_s")
WALL_KEYS_SHARDED = ("single_jax_s", "sharded_jax_s")
WALL_KEYS_DRIFTING = ("numpy_grid_s", "jax_grid_s", "pallas_grid_s")
WALL_KEYS_PANEL = ("per_scheme_jax_s", "fused_jax_s",
                   "per_scheme_pallas_s", "fused_pallas_s")
WALL_KEYS_SERVE = ("engine_wall_s",)
WALL_KEYS_SERVE_SCAN = ("numpy_sweep_s", "jax_sweep_s",
                        "jax_first_call_s")
# episode wall is pinned by LiveConfig.target_wall_s (time-scale solved),
# so drift here means the coordinator itself got slower; the pure
# coordination wall is tiny and usually falls under --min-wall (reported,
# not gated)
WALL_KEYS_CONTROL = ("episode_wall_s", "coordination_wall_s")
WALL_KEYS_TRAIN = ("per_unit_loop_s", "scan_engine_s")


def load(path: str) -> dict:
    return json.loads(Path(path).read_text())


def collect_walls(report: dict) -> dict:
    """name -> wall seconds, over schemes + engine + grid sections."""
    walls = {}
    for name, entry in report.get("schemes", {}).items():
        walls[f"schemes.{name}"] = float(entry["wall_s"])
    eng = report.get("mc_engine", {})
    if "vectorized_s" in eng:
        walls["mc_engine.vectorized_s"] = float(eng["vectorized_s"])
    grid = report.get("fig5_grid", {})
    for key in WALL_KEYS_GRID:
        if key in grid:
            walls[f"fig5_grid.{key}"] = float(grid[key])
    mds = report.get("mds_grid", {})
    for key in WALL_KEYS_MDS:
        if key in mds:
            walls[f"mds_grid.{key}"] = float(mds[key])
    sharded = report.get("fig5_sharded", {})
    # only comparable when both runs saw the same device count
    for key in WALL_KEYS_SHARDED:
        if key in sharded:
            walls[f"fig5_sharded.{key}@{sharded.get('devices')}dev"] = \
                float(sharded[key])
    drifting = report.get("fig5_drifting", {})
    for key in WALL_KEYS_DRIFTING:
        if key in drifting:
            walls[f"fig5_drifting.{key}"] = float(drifting[key])
    panel = report.get("panel", {})
    for key in WALL_KEYS_PANEL:
        if key in panel:
            walls[f"panel.{key}"] = float(panel[key])
    serve = report.get("serve_load", {})
    for key in WALL_KEYS_SERVE:
        if key in serve:
            walls[f"serve_load.{key}"] = float(serve[key])
    serve_scan = report.get("serve_scan", {})
    for key in WALL_KEYS_SERVE_SCAN:
        if key in serve_scan:
            walls[f"serve_scan.{key}"] = float(serve_scan[key])
    # the sharded sweep wall is only comparable at equal device counts
    if "sharded_jax_sweep_s" in serve_scan:
        walls[(f"serve_scan.sharded_jax_sweep_s"
               f"@{serve_scan.get('sharded_devices')}dev")] = \
            float(serve_scan["sharded_jax_sweep_s"])
    control = report.get("control_plane", {})
    for key in WALL_KEYS_CONTROL:
        if key in control:
            walls[f"control_plane.{key}"] = float(control[key])
    train = report.get("train", {})
    for key in WALL_KEYS_TRAIN:
        if key in train:
            walls[f"train.{key}"] = float(train[key])
    return walls


def gate(baseline: dict, current: dict, wall_tol: float, min_wall: float,
         se_tol: float = 5.0):
    failures, rows = [], []

    # --- wall-clock, machine-speed normalized ---------------------------
    # quick-mode and full-mode runs do different amounts of work: wall
    # gating only makes sense between runs of the same global config
    same_config = (baseline.get("config") == current.get("config"))
    if not same_config:
        rows.append(("(wall gating)", str(baseline.get("config")),
                     str(current.get("config")), "config mismatch", "skip"))
    base_w = collect_walls(baseline) if same_config else {}
    cur_w = collect_walls(current) if same_config else {}
    shared = [k for k in base_w if k in cur_w and base_w[k] > 0]
    ratios = {k: cur_w[k] / base_w[k] for k in shared}
    sizable = [r for k, r in ratios.items() if base_w[k] >= min_wall]
    calib = statistics.median(sizable) if sizable else 1.0
    for k in sorted(shared):
        drift = ratios[k] / calib
        gated = base_w[k] >= min_wall
        ok = (not gated) or drift <= 1.0 + wall_tol
        if not ok:
            failures.append(f"wall regression {k}: {base_w[k]:.3f}s -> "
                            f"{cur_w[k]:.3f}s ({drift:.2f}x normalized, "
                            f"tol {1 + wall_tol:.2f}x)")
        rows.append((k, f"{base_w[k]:.4f}s", f"{cur_w[k]:.4f}s",
                     f"{drift:.2f}x" + ("" if gated else " (ungated)"),
                     "FAIL" if not ok else "ok"))

    # --- mean T_comp drift vs MC tolerance ------------------------------
    for name, base in sorted(baseline.get("schemes", {}).items()):
        cur = current.get("schemes", {}).get(name)
        if cur is None:
            failures.append(f"scheme {name!r} present in baseline but "
                            f"missing from the current run")
            rows.append((f"schemes.{name}.t_comp",
                         f"{base['t_comp_mean']:.4f}", "MISSING", "-",
                         "FAIL"))
            continue
        if (base.get("N") != cur.get("N")
                or base.get("trials") != cur.get("trials")):
            rows.append((f"schemes.{name}.t_comp",
                         f"{base['t_comp_mean']:.4f}",
                         f"{cur['t_comp_mean']:.4f}",
                         "config changed", "skip"))
            continue
        se = ((base["t_comp_std"] ** 2 / max(base["trials"], 1)
               + cur["t_comp_std"] ** 2 / max(cur["trials"], 1)) ** 0.5)
        tol = max(se_tol * se, 1e-9 + 1e-6 * abs(base["t_comp_mean"]))
        drift = abs(cur["t_comp_mean"] - base["t_comp_mean"])
        ok = drift <= tol
        if not ok:
            failures.append(f"T_comp drift {name}: "
                            f"{base['t_comp_mean']:.4f} -> "
                            f"{cur['t_comp_mean']:.4f} "
                            f"(|drift| {drift:.4g} > tol {tol:.4g})")
        rows.append((f"schemes.{name}.t_comp", f"{base['t_comp_mean']:.4f}",
                     f"{cur['t_comp_mean']:.4f}",
                     f"{drift / se:.1f} se" if se > 0 else "exact",
                     "FAIL" if not ok else "ok"))

    return failures, rows, calib


def markdown_table(rows, calib: float, failures) -> str:
    lines = ["# Benchmark gate",
             "",
             f"Machine calibration (median wall ratio): `{calib:.2f}x`",
             "",
             "| metric | baseline | current | drift | status |",
             "|---|---|---|---|---|"]
    lines += [f"| {m} | {b} | {c} | {d} | {s} |" for m, b, c, d, s in rows]
    lines.append("")
    lines.append(f"**{'FAIL' if failures else 'PASS'}** -- "
                 f"{len(failures)} regression(s)")
    lines += [f"- {f}" for f in failures]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--current", required=True)
    ap.add_argument("--wall-tol", type=float, default=0.25,
                    help="allowed normalized wall-clock regression (0.25 "
                         "= 25%%)")
    ap.add_argument("--min-wall", type=float, default=0.02,
                    help="baseline walls below this many seconds are "
                         "reported but not gated (timer noise)")
    args = ap.parse_args(argv)

    failures, rows, calib = gate(load(args.baseline), load(args.current),
                                 args.wall_tol, args.min_wall)
    table = markdown_table(rows, calib, failures)
    print(table)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write(table + "\n")
    if failures:
        print(f"\nbench-gate: FAIL ({len(failures)} regression(s))",
              file=sys.stderr)
        return 1
    print("\nbench-gate: PASS", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
