"""The readers of the one-shot program's device time and the kernel's
rounds per trial: on a hand-made reduced trace and counter set, on the
recorded trace of a program without the one-shot program, and with a
program that keeps no such counters (each gives nothing)."""
import sys
import types

import pytest

from chipbench import bench, trace

NO_ONE_SHOT_TRACE = (bench.BENCH_DIR / "testdata"
                     / "k50_panel_spans_v5e.json.gz")


def ctx(reduced=None):
    return types.SimpleNamespace(reduced=reduced)


def synthetic():
    """Two calls of 1000 ns.  ``jit_one_shot`` runs three times on one
    device (100, 150 and 50 ns) and once on a second (40 ns); other
    programs, one whose name only starts alike, do not count."""
    return {"devices": {
        "/device:TPU:0": [["jit_one_shot(123)", 100, 100],
                          ["jit_we_rounds_panel(9)", 300, 200],
                          ["jit_one_shot(123)", 1100, 150],
                          ["jit_one_shot(124)", 1300, 50],
                          ["jit_one_shot_other(5)", 1500, 70]],
        "/device:TPU:1": [["jit_one_shot(123)", 1600, 40]]},
        "ops": {}, "calls": [[0, 1000], [1000, 2000]], "host": {}}


def test_one_shot_ms_sums_every_device_over_the_calls():
    read = bench.metric_reader("one_shot_ms_per_study")
    assert read(ctx(synthetic())) == pytest.approx((100 + 150 + 50 + 40)
                                                   / 2 / 1e6)


def test_one_shot_reads_nothing_without_the_program():
    """The recorded k50_panel trace predates the device one-shot path;
    a trace without calls reads nothing either."""
    read = bench.metric_reader("one_shot_ms_per_study")
    assert read(ctx(trace.load(NO_ONE_SHOT_TRACE))) is None
    assert read(ctx(dict(synthetic(), calls=[]))) is None


def test_rounds_per_row_reads_the_counters(monkeypatch):
    from repro import tracing
    read = bench.metric_reader("we_rounds_rounds_per_row")
    monkeypatch.setattr(tracing, "_COUNTERS", {})
    assert read(ctx()) is None                       # no launch yet
    tracing.count("we_rounds.rows", 400)
    tracing.count("we_rounds.row_rounds_useful", 4_800)
    tracing.count("we_rounds.row_rounds_executed", 6_000)
    assert read(ctx()) == pytest.approx(12.0)
    monkeypatch.setattr(tracing, "_COUNTERS",
                        {"we_rounds.row_rounds_useful": 10})
    assert read(ctx()) is None                       # an older program
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert read(ctx()) is None                       # no counters at all
