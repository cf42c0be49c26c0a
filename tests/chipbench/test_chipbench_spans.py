"""The readers of the program's own spans and counters: on hand-made
reduced traces, on the recorded trace of a program without spans (every
reader gives nothing), and on a ``k50_panel`` trace recorded on one v5e
chip with them."""
import sys
import types

import pytest

from chipbench import bench, spans, trace

TESTDATA = bench.BENCH_DIR / "testdata"
SPANS_TRACE = TESTDATA / "k50_panel_spans_v5e.json.gz"
NO_SPANS_TRACE = TESTDATA / "k50_panel_v5e.json.gz"
SPAN_READERS = ["we_rows_ms_per_study", "we_h2d_ms_per_study",
                "we_wait_ms_per_study", "host_schemes_ms_per_study",
                "mds_ms_per_study", "host_untraced_ms_per_study"]
LEAVES = ["repro.plan", "repro.we_rounds.rows", "repro.we_rounds.h2d",
          "repro.we_rounds.launch", "repro.we_rounds.wait", "repro.report",
          "repro.mds.select", "repro.mds.topup", "repro.scheme.fixed",
          "repro.scheme.het_mds", "repro.scheme.hedged"]


def read(name, reduced):
    return bench.metric_reader(name)(types.SimpleNamespace(reduced=reduced))


def synthetic():
    """Two calls of 1000 ns.  Call 1: plan, rows, the we_rounds dispatch
    (h2d, launch, wait), a report and ``fixed``; the device busy
    300..500.  Call 2: rows, a dispatch, ``hedged``, and an ``mds`` span
    that runs 100 ns past the call's end; device busy 1400..1600.  A
    rows span after the last call; a second device busy only 0..100.
    ``XlaLinearize`` on two runtime threads: one event starts before
    the dispatch, one nests in another of the same thread."""
    py = [["repro.study", 10, 980], ["repro.plan", 20, 40],
          ["repro.we_rounds.rows", 100, 100], ["repro.we_rounds", 200, 500],
          ["repro.we_rounds.h2d", 200, 60],
          ["repro.we_rounds.launch", 260, 40],
          ["repro.we_rounds.wait", 300, 350], ["repro.report", 700, 100],
          ["repro.scheme.fixed", 800, 150],
          ["repro.study", 1010, 980], ["repro.we_rounds.rows", 1100, 150],
          ["repro.we_rounds", 1250, 450], ["repro.we_rounds.wait", 1400, 300],
          ["repro.scheme.hedged", 1700, 290], ["repro.scheme.mds", 1990, 110],
          ["repro.we_rounds.rows", 2100, 100], ["np.asarray", 310, 300]]
    tasks = [["XlaLinearize", 150, 40], ["XlaLinearize", 210, 80],
             ["XlaLinearize", 215, 65], ["XlaLinearize", 1260, 40]]
    return {"devices": {"/device:TPU:0": [["jit_we_rounds_panel", 300, 200],
                                          ["jit_we_rounds_panel", 1400, 200]],
                        "/device:TPU:1": [["jit_other", 0, 100]]},
            "ops": {},
            "calls": [[0, 1000], [1000, 2000]],
            "host": {"python3": py, "pjrt-tpu-tasks/1": tasks,
                     "main/2": [["XlaLinearize", 220, 20]]}}


def test_span_totals_are_clipped_to_the_calls():
    red = synthetic()
    ns = 1e-6                                  # readers give milliseconds
    # rows: 100 + 150 inside calls; the one after the last call is out
    assert read("we_rows_ms_per_study", red) == pytest.approx(125 * ns)
    assert read("we_wait_ms_per_study", red) == pytest.approx(325 * ns)
    # fixed 150 + hedged 290
    assert read("host_schemes_ms_per_study", red) == pytest.approx(220 * ns)
    # mds runs 1990..2100: 10 ns of it inside the second call
    assert read("mds_ms_per_study", red) == pytest.approx(5 * ns)


def test_linearize_attributed_to_the_dispatch():
    """Counted where an event starts inside a ``repro.we_rounds`` span,
    on any thread; nested events of one thread count once; threads
    add."""
    red = synthetic()
    # pjrt: 210..290 (the nested 215..280 inside it), 1260..1300;
    # main: 220..240; 150..190 starts before the dispatch
    assert read("we_h2d_ms_per_study", red) == pytest.approx(
        (80 + 40 + 20) / 2 * 1e-6)


def test_untraced_time_is_idle_and_outside_every_layer():
    """Call 1: spans and busy time cover 20..60 and 100..950, leaving
    110 ns; call 2: 1100..2000, leaving 100 ns.  ``repro.study`` names
    no layer, and only the busiest device counts as busy (the other
    one's 0..100 does not cover call 1's start)."""
    red = synthetic()
    assert read("host_untraced_ms_per_study", red) == pytest.approx(
        105 * 1e-6)
    # the device time alone covers only the busy intervals
    red["host"]["python3"] = [["repro.study", 10, 980],
                              ["repro.plan", 0, 1]]
    assert read("host_untraced_ms_per_study", red) == pytest.approx(
        (799 + 800) / 2 * 1e-6)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_no_span_reads_nothing(name):
    """The recorded trace of a program without spans: every reader
    returns None rather than raising or reading a zero."""
    assert read(name, trace.load(NO_SPANS_TRACE)) is None


def test_row_occupancy_reads_the_counters(monkeypatch):
    from repro import tracing
    reader = bench.metric_reader("we_rounds_row_occupancy")
    ctx = types.SimpleNamespace(reduced=None)
    monkeypatch.setattr(tracing, "_COUNTERS", {})
    assert reader(ctx) is None                  # no launch yet
    tracing.count("we_rounds.row_rounds_useful", 300)
    tracing.count("we_rounds.row_rounds_executed", 400)
    assert reader(ctx) == pytest.approx(75.0)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader(ctx) is None                  # a program without them


def test_recorded_spans_trace_reads_every_metric():
    """A reduced trace of a traced ``k50_panel`` run on one v5e chip:
    every new reader gives a number, the kernel is found under its
    stable name by ``tpu_custom_call``, and the leaf spans plus the
    untraced idle time fit inside the call spans."""
    red = trace.load(SPANS_TRACE)
    for name in SPAN_READERS:
        value = read(name, red)
        assert value is not None and value >= 0.0, name
    assert read("we_rounds_ms_per_study", red) > 0.0
    kernel = [n for ops in red["ops"].values() for n in ops
              if "tpu_custom_call" in n]
    assert kernel and all(trace.short(n).startswith("%we_rounds")
                          for n in kernel)
    calls = red["calls"]
    leaves = sum(spans.in_calls_ns(trace.union(
        spans.intervals(red, [name])), calls) for name in LEAVES)
    untraced = spans.untraced_ms_per_call(red) * 1e6 * len(calls)
    assert 0 < leaves + untraced <= sum(e - s for s, e in calls)
