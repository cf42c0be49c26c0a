"""The comparison that decides ``correct`` fails what it must fail.

Each test drives a whole run of a cell on the CPU at a tiny size, with
the look for a chip skipped (``chipbench.dryrun``), and breaks the timed
path underneath (``chipbench.faults``):

* the control: the plain reference itself put in the program's place,
  computed one precision down (bfloat16);
* an answer altered where it is produced;
* half of the batch left out and the mean taken over the rest;
* the MDS code length moved off the one the sweep chose.

A sound run of the same size passes (``test_chipbench_dryrun``).
"""
import numpy as np
import pytest

from chipbench import correct, dryrun, faults


def _failed(result, name):
    assert result["correct"] is False
    check = result["checks"][name]
    assert check["value"] is None or check["value"] > check["limit"], \
        result["checks"]


@pytest.mark.parametrize("workload", ["k50_pair", "k50_panel"])
def test_control_bfloat16_reference_fails(workload):
    """At half the cell's trials: the control's bias is a fixed share of
    T_comp, so its reading in standard errors grows with the trials."""
    with faults.control():
        res = dryrun.dry_run(workload, seconds=1.0,
                             sizes={"trials": 2048, "ref_trials": 2048})
    _failed(res, "pooled_gap_se")


@pytest.mark.parametrize("fault,workload,number,sizes", [
    ("altered_answer", "k50_pair", "call_gap_se", {}),
    ("half_batch", "k50_pair", "dispersion", {"trials": 32}),
    ("altered_answer", "k50_panel", "call_gap_se", {}),
    ("half_batch", "k50_panel", "dispersion", {"trials": 32}),
    ("mds_L_shifted", "k50_panel", "mds_L_excess_pct", {}),
])
def test_fault_fails(fault, workload, number, sizes):
    with faults.planted(fault):
        res = dryrun.dry_run(workload, seconds=2.0, sizes=sizes)
    _failed(res, number)


def _rows(mean, std=1.0, trials=100.0, L=0.0):
    return np.array([[mean, std, trials, L]])


def test_mds_is_compared_at_the_length_each_call_chose():
    """Reference curve over L = 1..3 at one point: least mean 10 at
    L = 2, 11 at L = 3.  A call at L = 3 is compared with 11, and reads
    10% of excess; calls split between two lengths are grouped by
    length for the dispersion."""
    curve = np.array([[[12.0, 1.0, 100.0], [10.0, 1.0, 100.0],
                       [11.0, 1.0, 100.0]]])
    answers = [{"mds": _rows(10.0, L=2)}, {"mds": _rows(11.0, L=3)},
               {"mds": _rows(10.05, L=2)}, {"mds": _rows(10.95, L=3)}]
    out = correct.mc_numbers(answers, {"mds": curve})
    assert out["mds_L_excess_pct"] == pytest.approx(10.0)
    assert out["call_gap_se"] == pytest.approx(0.05 / np.sqrt(2 / 99))
    # within each length the two calls differ by 0.05, pooled variance
    # 0.00125 against se^2 = 1/99: the 1-unit length effect is left out
    assert out["dispersion"] == pytest.approx(abs(0.00125 * 99 - 1.0))


def test_copied_trials_read_about_one():
    """Calls whose means spread as each call's own standard error says
    read near 0; calls that copy half their trials spread twice as
    widely and read near 1."""
    rng = np.random.default_rng(0)
    se = 1.0 / np.sqrt(99)
    ref = {"fixed": np.array([[10.0, 1.0, 100.0]] * 8)}
    for spread, lo, hi in ((se, 0.0, 0.3), (se * np.sqrt(2), 0.6, 1.5)):
        answers = [{"fixed": np.column_stack([
            10.0 + spread * rng.standard_normal(8), np.ones(8),
            np.full(8, 100.0), np.zeros(8)])} for _ in range(60)]
        d = correct.mc_numbers(answers, ref)["dispersion"]
        assert lo <= d <= hi, (spread, d)
