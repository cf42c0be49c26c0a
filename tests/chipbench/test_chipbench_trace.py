"""The reduction from a profiler trace to the per-layer metrics, on a
small trace recorded on the chip (``chipbench/testdata``) and on
hand-made ones, and the compile counter."""
from pathlib import Path

import pytest

from chipbench import bench, trace

RECORDED = sorted((bench.BENCH_DIR / "testdata").glob("*.json.gz"))


def synthetic():
    """Window 0..1000 ns, two calls; device busy 100..300, 250..400 and
    600..700 (union 400 ns), a kernel op named we_rounds among them."""
    return {"devices": {"/device:TPU:0": [
                ["jit_a", 100, 200], ["jit_b", 250, 150],
                ["jit_a", 600, 100]]},
            "ops": {"/device:TPU:0": {
                "fusion.1 = f32[8] fusion(...)": [200, 1],
                "%fn.1 = f32[8,3] custom-call(...), custom_call_target="
                "\"tpu_custom_call\"": [150, 1],
                "fusion.2 = f32[8] fusion(...)": [100, 1]}},
            "calls": [[0, 500], [500, 1000]],
            "host": {"python": [["np.repeat", 420, 150],
                                ["report", 720, 250]]}}


def test_union_and_idle_share():
    red = synthetic()
    assert trace.union([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
    assert trace.busy_ns(red) == 400
    assert trace.idle_share(red) == pytest.approx(0.6)


def test_kernel_and_host_time_per_call():
    red = synthetic()
    assert trace.kernel_ns(red, "tpu_custom_call") == 150
    assert trace.kernel_ns(red, "no_such_kernel") is None
    # call 1: 500 ns, busy 300 inside; call 2: 500 ns, busy 100 inside
    assert trace.host_ns_per_call(red) == pytest.approx((200 + 400) / 2)


def test_breakdown_names_ops_and_gaps():
    red = synthetic()
    ops = trace.top_ops(red)
    assert ops[0][0] == "fusion.1" and ops[0][1] == pytest.approx(2e-7)
    gaps = trace.idle_gaps(red)
    assert gaps[0] == ["report", pytest.approx(3e-7)]
    # a gap that no host event covers by half is named untraced
    assert [g[0] for g in gaps] == ["report", "np.repeat", "untraced"]
    red["host"]["python"][0][2] = 60          # np.repeat: 420..480 only
    assert [g[0] for g in trace.idle_gaps(red)] == ["report", "untraced",
                                                     "untraced"]


def test_no_device_event_reads_nothing():
    red = dict(synthetic(), devices={})
    assert trace.busy_ns(red) is None and trace.idle_share(red) is None


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_reduces(path: Path):
    """A reduced trace recorded on one v5e chip: the readers give a
    share in (0, 100), a positive kernel time inside the busy time, and
    a host time per call shorter than the mean call."""
    red = trace.load(path)
    share = trace.idle_share(red)
    assert 0.0 < share < 1.0
    lo, hi = trace.window(red)
    calls = red["calls"]
    mean_call = sum(e - s for s, e in calls) / len(calls)
    assert 0 < trace.host_ns_per_call(red) < mean_call
    assert trace.busy_ns(red) <= hi - lo
    assert 0 < trace.kernel_ns(red, "tpu_custom_call") <= trace.busy_ns(red)


def test_compile_counter_counts_new_programs():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import run
    x7, x9 = np.ones(7, np.float32), np.ones(9, np.float32)
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 17.0)
    counter = run.CompileCounter()
    counter.mark()
    np.asarray(f(x7))
    assert counter.since == 1
    np.asarray(f(x7))                        # cached: no new program
    assert counter.since == 1
    np.asarray(f(x9))                        # a new shape compiles
    assert counter.since == 2
