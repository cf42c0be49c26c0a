"""Smoke run of the experiment engines on a TPU.

    python chip_smoke.py                # one chip: every engine once
    python chip_smoke.py --four-chips   # the sharded executor on four

Drives the normal path -- ``ExperimentSpec`` -> ``compile_plan`` ->
engine -> store (``run_experiment``, always ``force=True`` so a store hit
never skips the device work) -- at the paper's size, checks each result
against the repo's own oracles, and prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

One chip: the paper's fig5 grid on ``pallas`` (the compiled ``we_rounds``
kernel, fused known/unknown panel) and on ``jax`` against ``numpy``; the
kernel against its jnp reference on the same rows; the AR(1)-drifting
grid on ``pallas``; the full ``fig_load`` serving sweep on the scan
against the numpy slot loop; the training and live demo specs.

``--four-chips``: only the sharded executor -- the fig5 grid on
``pallas`` and ``jax`` at ``devices=4`` against ``devices=1`` (within 6
SE: per-device key streams differ by design), and the ``fig_load`` scan
at ``devices=4`` against ``devices=1`` (bitwise: fixed-unit sweeps draw
no random numbers inside the scan).

It prints no timings: the benchmark (``chipbench``) measures.
Everything runs in this one process: a chip belongs to one process at a
time.  Exits non-zero, printing no result line, when no TPU is attached
or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import fig5, fig_load  # noqa: E402
from benchmarks.common import K_PAPER, N_PAPER, we_cfg  # noqa: E402
from repro.core.samplers import bucket_cols  # noqa: E402
from repro.experiments import (ResultsStore, compile_plan,  # noqa: E402
                               enable_compilation_cache, run_experiment)
from repro.experiments.__main__ import demo_spec  # noqa: E402
from repro.kernels.we_rounds import resolve_mode, we_rounds_grid  # noqa: E402

STORE = ResultsStore(ROOT / ".chip_smoke_store")
TRIALS = 4096            # per grid point: 8 points x 4096 = 32768 rows
KERNEL_ROWS = 8192       # kernel vs reference: 8 points x 1024 rows
K_SE = 6.0               # agreement band, in combined standard errors
REL_FLOOR = 2e-3         # float32 fluid floor of the conformance contract
SERVE_SEEDS = 8          # fig_load replicates per serving backend

FAILED: list = []


def check(ok: bool, what: str) -> None:
    print(f"    [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        FAILED.append(what)


def run(spec, label: str):
    res = run_experiment(spec, store=STORE, force=True)
    backend = (res.spec.serving.backend if res.spec.serving is not None
               else res.spec.backend)
    print(f"  {label}: backend={backend} devices={res.spec.devices}")
    return res


def se_of(a, b) -> float:
    return float(np.hypot(a.t_comp_std / np.sqrt(a.trials),
                          b.t_comp_std / np.sqrt(b.trials)))


def agree_grid(res, ref, label: str, floor: float = REL_FLOOR) -> None:
    """Every scheme at every point within ``K_SE`` combined SE of ``ref``
    (with the conformance suite's relative float32 floor).  A scheme that
    picks its own code length (MDS: the optimal ``L`` from a short
    sweep) is compared where both runs chose the same ``L``; elsewhere
    the choices must be neighbours, as the conformance suite asks."""
    worst, failed = 0.0, len(FAILED)
    for key in ref.keys():
        for g, (a, b) in enumerate(zip(res.report(key), ref.report(key))):
            La, Lb = a.extra.get("L"), b.extra.get("L")
            if La != Lb:
                check(abs(La - Lb) <= 2, f"{label} {key}[{g}]: optimal L "
                      f"{La:g} vs {Lb:g} (|dL| <= 2; T_comp {a.t_comp:.6g}"
                      f" vs {b.t_comp:.6g} belong to different codes)")
                continue
            se = max(se_of(a, b), 1e-12)
            d = abs(a.t_comp - b.t_comp)
            worst = max(worst, d / se)
            if d >= max(K_SE * se, floor * b.t_comp):
                check(False, f"{label} {key}[{g}] {a.t_comp:.6g} vs "
                             f"{b.t_comp:.6g} ({d / se:.2f} SE)")
    check(len(FAILED) == failed, f"{label}: every scheme x point within "
                                 f"{K_SE:g} SE (worst {worst:.2f} SE)")


def above_bound(res, label: str) -> None:
    """No mean T_comp below the merged-process bound N / lambda_sum."""
    hets = res.spec.grid.specs()
    low = min(rep.t_comp / (res.spec.N / het.lambda_sum)
              for key in res.keys()
              for rep, het in zip(res.report(key), hets))
    check(low >= 0.999, f"{label}: min T_comp / (N/lambda_sum) = "
                        f"{low:.6f} >= 0.999")


def phase_grid(backend: str, panel: str, ref) -> None:
    spec = fig5.experiment(trials=TRIALS, backend=backend, panel=panel)
    res = run(spec, f"fig5 {backend}")
    agree_grid(res, ref, f"fig5 {backend} vs numpy")
    above_bound(res, f"fig5 {backend}")


def phase_kernel_vs_reference() -> None:
    """The compiled kernel and the jnp reference on the same rows."""
    hets = fig5.grid_specs()
    lam = np.stack([h.lambdas for h in hets]).astype(np.float32)
    lam = np.pad(lam, ((0, 0), (0, bucket_cols(K_PAPER) - K_PAPER)))
    per = KERNEL_ROWS // len(hets)
    rows = np.repeat(lam, per, axis=0)
    for known in (True, False):
        cfg = we_cfg(known)
        cap = (np.inf if known
               else float(np.ceil(cfg.storage_cap_frac * N_PAPER / K_PAPER)))
        kw = dict(n0=float(N_PAPER), known=known, cap=cap,
                  threshold=cfg.threshold_frac * N_PAPER / K_PAPER,
                  max_iter=cfg.max_iterations)
        out_k = we_rounds_grid(rows, (1234, 5678), mode="kernel", **kw)
        out_r = we_rounds_grid(rows, (1234, 5678), mode="reference", **kw)
        same = np.all([a == b for a, b in zip(out_k, out_r)], axis=0)
        diff = max(float(np.max(np.abs(a - b)))
                   for a, b in zip(out_k, out_r))
        tag = "known" if known else "unknown"
        print(f"  kernel vs reference ({tag}, {rows.shape[0]} rows): "
              f"max |diff| {diff:.6g}, bitwise-equal rows "
              f"{same.mean():.6f}")
        check(all(np.isfinite(a).all() for a in out_k),
              f"kernel ({tag}) outputs finite")
        worst = 0.0
        for g in range(len(hets)):
            tk = out_k[0][g * per:(g + 1) * per]
            tr = out_r[0][g * per:(g + 1) * per]
            se = float(np.hypot(tk.std(), tr.std()) / np.sqrt(per))
            worst = max(worst, abs(tk.mean() - tr.mean()) / max(se, 1e-12))
        check(worst < K_SE, f"kernel vs reference ({tag}): point means "
                            f"within {K_SE:g} SE (worst {worst:.2f})")


def serving_runs(backend: str, devices: int = 1, seeds: int = 1):
    """The stationary ``fig_load`` sweep (one spec per seed)."""
    base = fig_load.experiment(scenario="stationary")
    serving = dataclasses.replace(base.serving, backend=backend)
    return [run(base.replace(seed=base.seed + i, devices=devices,
                             serving=serving),
                f"fig_load {backend} x{devices} seed {base.seed + i}")
            for i in range(seeds)]


SERVE_METRICS = (("sojourn", lambda r: r.t_comp),
                 ("p50", lambda r: r.extra["p50"]),
                 ("p99", lambda r: r.extra["p99"]),
                 ("goodput", lambda r: r.extra["goodput_units"]))


def agree_serving(runs, refs, label: str) -> None:
    """Scan against the numpy loop over the same seeds: identical offered
    demand seed by seed, the conservation identity, then every metric's
    mean over seeds within ``K_SE`` SE.  The SE comes from the spread
    over seeds: a pooled percentile has no per-trial spread, and its
    seed-to-seed spread is several times the mean sojourn's SE."""
    worst, failed = {name: 0.0 for name, _ in SERVE_METRICS}, len(FAILED)
    for key in refs[0].keys():
        for li, rep in enumerate(refs[0].report(key)):
            tag = f"{label} {key}@{rep.extra['offered_load']:g}"
            a = [res.report(key)[li] for res in runs]
            b = [res.report(key)[li] for res in refs]
            if any(x.extra["units_admitted"] != y.extra["units_admitted"]
                   for x, y in zip(a, b)):
                check(False, f"{tag}: offered demand differs")
            if any(x.extra["serving_backend"] != "jax" for x in a):
                check(False, f"{tag}: did not run on the scan")
            for x in a:
                e = x.extra
                gap = (e["units_admitted"] - e["units_served"]
                       - e["units_cancelled"] - e["units_backlog"])
                if abs(gap) > 1e-9 * max(e["units_admitted"], 1.0):
                    check(False, f"{tag}: admitted != served + cancelled "
                                 f"+ backlog ({gap:g})")
            for name, get in SERVE_METRICS:
                va = np.array([get(x) for x in a])
                vb = np.array([get(y) for y in b])
                se = float(np.sqrt((va.var(ddof=1) + vb.var(ddof=1))
                                   / len(va)))
                d = abs(va.mean() - vb.mean())
                worst[name] = max(worst[name], d / max(se, 1e-12))
                if d > K_SE * se + 1e-12:
                    check(False, f"{tag} {name}: {va.mean():.5g} vs "
                                 f"{vb.mean():.5g} (se {se:.3g})")
    check(len(FAILED) == failed,
          f"{label}: scan ran, same offered demand, conservation holds; "
          f"every scheme x load within {K_SE:g} SE (worst "
          + ", ".join(f"{k} {v:.2f}" for k, v in worst.items()) + ")")


def phase_training() -> None:
    res = run(demo_spec("train"), "train demo")
    curves = [rep.extra["training"]["loss_curve"]
              for rows in res.reports.values() for rep in rows]
    first, last = curves[0][0], curves[0][-1]
    print(f"    loss first {first:.6f} last {last:.6f} over "
          f"{len(curves[0])} steps, {len(curves)} policy curves")
    check(all(np.isfinite(c).all() for c in curves), "losses finite")
    check(all(c == curves[0] for c in curves),
          "every policy sees the same loss curve")


def phase_live() -> None:
    res = run(demo_spec("live"), "live demo")
    reps = [rep for rows in res.reports.values() for rep in rows]
    episodes = sum(rep.trials for rep in reps)
    print(f"    episodes completed {episodes}; mean T_comp "
          + ", ".join(f"{k}={rows[0].t_comp:.4g}"
                      for k, rows in res.reports.items()))
    check(all(np.isfinite(r.t_comp) and r.t_comp > 0 for r in reps),
          "live T_comp finite and positive")
    check(all(not r.extra["control_plane"]["workers_lost"] for r in reps),
          "no worker lost")


def one_chip() -> None:
    check(resolve_mode() == "kernel", "pallas resolves we_rounds to the "
                                      "compiled kernel")
    print("phase: fig5 grid (K=50, N=1e6, 8 points x "
          f"{TRIALS} trials)")
    ref = run(fig5.experiment(trials=TRIALS, backend="numpy"),
                 "fig5 numpy oracle")
    above_bound(ref, "fig5 numpy")
    phase_grid("pallas", "fused", ref)
    phase_grid("jax", "per_scheme", ref)
    print("phase: kernel vs reference")
    phase_kernel_vs_reference()
    print("phase: AR(1) drift grid")
    drift_ref = run(fig5.drifting_experiment(trials=TRIALS,
                                                backend="numpy"),
                       "drift numpy oracle")
    drift = run(fig5.drifting_experiment(trials=TRIALS,
                                            backend="pallas"),
                   "drift pallas")
    agree_grid(drift, drift_ref, "drift pallas vs numpy")
    print("phase: fig_load serving sweep (K=16, 4 loads, 2000 slots, "
          f"{SERVE_SEEDS} seeds)")
    scan = serving_runs("jax", seeds=SERVE_SEEDS)
    agree_serving(scan, serving_runs("numpy", seeds=SERVE_SEEDS),
                  "scan vs numpy")
    print("phase: training demo")
    phase_training()
    print("phase: live demo")
    phase_live()


def four_chips() -> None:
    check(jax.device_count() == 4, f"jax.device_count() == 4 "
                                   f"(got {jax.device_count()})")
    for backend, panel in (("pallas", "fused"), ("jax", "per_scheme")):
        print(f"phase: fig5 grid on {backend}, devices=4 vs 1")
        one = fig5.experiment(trials=TRIALS, backend=backend, panel=panel)
        four = fig5.experiment(trials=TRIALS, backend=backend, panel=panel,
                               devices=4)
        check(compile_plan(four).devices == 4, "plan.devices == 4")
        r1 = run(one, f"fig5 {backend} x1")
        r4 = run(four, f"fig5 {backend} x4")
        check(r4.spec.devices == 4, "run on 4 devices")
        agree_grid(r4, r1, f"fig5 {backend} 4 vs 1 devices")
    print("phase: fig_load scan, devices=4 vs 1")
    (s1,), (s4,) = serving_runs("jax", 1), serving_runs("jax", 4)
    check(s4.spec.devices == 4, "plan.devices == 4")
    same = all([r.to_dict() for r in s1.report(k)]
               == [r.to_dict() for r in s4.report(k)] for k in s1.keys())
    check(same, "scan reports bitwise equal across 1 and 4 devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded executor on four chips")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU attached (jax platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache = enable_compilation_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()} jax={jax.__version__} "
          f"cache={cache}")
    four_chips() if args.four_chips else one_chip()
    print(f"{len(FAILED)} failed checks")
    if FAILED:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
