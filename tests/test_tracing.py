"""Spans and counters on the Monte Carlo path (``repro.tracing``): the
numpy engines stay free of jax, the spans land in a profiler trace under
their ``repro.*`` names with the stated nesting, and the ``we_rounds``
row-round counters count what the tiles execute."""
import functools
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.experiments import (ExperimentSpec, ScenarioGrid, run_experiment,
                               scheme_spec)
from repro.kernels.we_rounds import ref, we_rounds_grid
from repro.kernels.we_rounds.ops import count_row_rounds

ROOT = Path(__file__).resolve().parents[1]
USEFUL = "we_rounds.row_rounds_useful"
EXECUTED = "we_rounds.row_rounds_executed"
PANEL = ("mds", "fixed", "work_exchange", "work_exchange_unknown",
         "het_mds", "hedged")


def delta(before):
    now = tracing.counters()
    return {k: now.get(k, 0) - before.get(k, 0) for k in (USEFUL, EXECUTED)}


def test_numpy_study_imports_no_jax():
    """A fused six-scheme panel on the numpy backend, spans and all,
    never imports jax."""
    code = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro.core.schemes import get_scheme, mc_grid_panel
        from repro.core.types import HetSpec
        specs = [HetSpec.uniform_random(8, 10.0, 100 / 6,
                                        rng=np.random.default_rng(1))]
        out = mc_grid_panel({{n: get_scheme(n) for n in {PANEL!r}}}, specs,
                            2000, 16, np.random.default_rng(0),
                            backend="numpy")
        assert sorted(out) == sorted({PANEL!r})
        print("jax" in sys.modules)
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "False"


def test_count_adds_and_counters_copies():
    before = tracing.counters()
    tracing.count("test.tracing", 3)
    tracing.count("test.tracing", 4)
    got = tracing.counters()
    assert got["test.tracing"] - before.get("test.tracing", 0) == 7
    got["test.tracing"] = -1                 # a copy: the counter keeps
    assert tracing.counters()["test.tracing"] != -1


def test_span_without_jax_is_a_shared_no_op(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing.span("repro.a") is tracing.span("repro.b")
    with tracing.span("repro.a"):
        pass


def _study(panel, schemes, seed):
    return ExperimentSpec(
        name="test-tracing",
        grid=ScenarioGrid(K=8, points=[(10.0, 100 / 6, 1), (20.0, 0.0, 2)]),
        schemes=tuple(scheme_spec(s) for s in schemes), N=2000, trials=32,
        seed=seed, backend="pallas", devices=1, panel=panel)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_spans_land_in_the_profiler_trace(tmp_path, monkeypatch):
    """A CPU profiler trace of a per-scheme pallas study and a fused
    six-scheme panel (reference mode), read back with the benchmark's
    own reduction, holds every span with the stated nesting."""
    from chipbench import trace
    monkeypatch.setenv("REPRO_WE_ROUNDS_MODE", "reference")
    studies = [_study("per_scheme", ("work_exchange",), 1),
               _study("fused", PANEL, 2)]
    for spec in studies:                      # compile outside the trace
        run_experiment(spec, store=None, force=True)
    with jax.profiler.trace(str(tmp_path)):
        for spec in studies:
            run_experiment(spec, store=None, force=True)
    red = trace.load_xplane(trace.find_xplane(tmp_path))
    ev = {}
    for evs in red["host"].values():
        for name, s, d in evs:
            if name.startswith("repro."):
                ev.setdefault(name, []).append((s, s + d))
    want = {"repro.study", "repro.plan", "repro.scheme.we_pair",
            "repro.mds.select", "repro.mds.topup", "repro.we_rounds.rows",
            "repro.we_rounds", "repro.we_rounds.h2d",
            "repro.we_rounds.launch", "repro.we_rounds.wait",
            "repro.report"} | {f"repro.scheme.{s}" for s in PANEL
                               if s != "work_exchange_unknown"}
    assert want <= set(ev), want - set(ev)
    # the pair runs fused under one span; the unknown half has none
    assert "repro.scheme.work_exchange_unknown" not in ev
    assert len(ev["repro.study"]) == 2
    assert len(ev["repro.we_rounds"]) == 2      # the single and the pair
    for name, spans in ev.items():
        for sp in spans:
            if name != "repro.study":
                assert any(_inside(sp, st) for st in ev["repro.study"]), name
            if name.startswith("repro.we_rounds."):
                parent = ("repro.scheme.we_pair",
                          "repro.scheme.work_exchange")
                if name == "repro.we_rounds.rows":
                    assert any(_inside(sp, p) for q in parent
                               for p in ev[q]), name
                else:
                    assert any(_inside(sp, p)
                               for p in ev["repro.we_rounds"]), name
            if name.startswith("repro.mds."):
                assert any(_inside(sp, p) for p in ev["repro.scheme.mds"])
    for sp in ev["repro.we_rounds"]:
        assert any(_inside(sp, p) for q in ("repro.scheme.we_pair",
                                            "repro.scheme.work_exchange")
                   for p in ev[q])


@pytest.mark.parametrize("it,real,tile,useful,executed", [
    # two tiles of 8: maxima 5 and 7
    ([1, 2, 3, 4, 5, 1, 1, 1] + [7, 1, 1, 1, 1, 1, 1, 2], 16, 8, 33,
     8 * (5 + 7)),
    # the last 3 rows are padding: executed, never useful
    ([2] * 8 + [3, 3, 3, 3, 3, 9, 9, 9], 13, 8, 16 + 15, 8 * (2 + 9)),
    # reference mode: one loop over the whole batch
    ([1, 2, 3, 4, 6], 5, 5, 16, 5 * 6),
], ids=["tiles", "padding", "reference"])
def test_row_round_arithmetic(it, real, tile, useful, executed):
    before = tracing.counters()
    count_row_rounds(np.asarray(it, np.float64), real, tile)
    assert delta(before) == {USEFUL: useful, EXECUTED: executed}


def test_executed_row_rounds_match_the_tile_trip_counts():
    """Interpret mode, 20 real rows padded to three tiles of 8: each
    tile executes its ``while_loop``'s trips (a Python loop of
    ``ref.round_body`` over the tile counts them) plus the final phase's
    one round, on all 8 rows.  That is exact when the tile's slowest row
    has work left for the final phase, which is checked too."""
    K, N, B, block = 12, 30_000, 20, 8
    threshold, cap = 0.01 * N / K, float(np.ceil(N / K))
    lam = np.random.default_rng(3).uniform(10.0, 30.0, size=(B, K))
    lam = lam.astype(np.float32)
    seed = (11, 22)
    before = tracing.counters()
    _, it, _ = we_rounds_grid(lam, seed, n0=N, threshold=threshold,
                              cap=cap, known=False, max_iter=10_000,
                              mode="interpret", block_b=block)
    got = delta(before)
    assert got[USEFUL] == int(it.sum())

    padded = np.concatenate([lam, np.repeat(lam[:1], (-B) % block, 0)])
    body = jax.jit(functools.partial(
        ref.round_body, K=K, cap=cap, threshold=threshold, known=False,
        max_iter=10_000))
    k0, k1 = jnp.uint32(seed[0]), jnp.uint32(seed[1])
    executed = 0
    for base in range(0, padded.shape[0], block):
        tile = jnp.asarray(padded[base:base + block])
        rows = jnp.arange(base, base + block, dtype=jnp.int32)[:, None]
        st = ref.init_state(block, K, float(N), threshold, False, lam=tile)
        trips = 0
        while bool((st["active"] > 0).any()):
            st = body(st, tile, 1.0 / tile, rows, k0, k1)
            trips += 1
        last = np.asarray(st["iters"][:, 0] + (st["n_rem"][:, 0] > 1e-6))
        assert last.max() == trips + 1       # the slowest row had work left
        executed += block * (trips + 1)
    assert got[EXECUTED] == executed
    assert got[EXECUTED] >= got[USEFUL]
