"""Every traffic mix runs through ``run_experiment`` on the CPU at a tiny
size (the pallas backend takes its jnp reference path here), the plain
reference agrees with the program's exact numpy engines, and the whole
harness runs end to end with the look for a chip skipped."""
import numpy as np
import pytest

from chipbench import bench, dryrun, reference_mc, workloads

SPEC = bench.load_benchmark()


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_traffic_runs_through_the_entry(wl):
    from repro.experiments import run_experiment
    traffic = dict(bench.load_traffic(wl["traffic"]), trials=8)
    cell = workloads.Cell(bench.load_config(wl["config"]), traffic)
    res = run_experiment(cell.spec(7), store=None, force=True)
    ans = cell.answers(res)
    assert set(ans) == set(cell.schemes)
    for key, a in ans.items():
        assert a.shape == (cell.points, 4) and np.isfinite(a[:, 0]).all()
        assert (a[:, 3] >= 1).all() == (key == "mds")


def test_rates_match_the_program_draw():
    """The reference draws the deployment's rates itself; they are the
    rates the program's grid holds."""
    for c in SPEC["configs"]:
        config = bench.load_config(c["name"])
        traffic = next(bench.load_traffic(w["traffic"])
                       for w in SPEC["workloads"] if w["config"] == c["name"])
        cell = workloads.Cell(config, traffic)
        ours = workloads.het_rates(config)
        theirs = np.stack([h.lambdas for h in cell.spec(1).grid.specs()])
        assert np.array_equal(ours, theirs)


@pytest.mark.parametrize("scheme", ["fixed", "het_mds", "hedged", "mds"])
def test_reference_agrees_with_the_exact_engine(scheme):
    """Each one-shot scheme of the plain reference against the program's
    exact numpy engine, at two points of the deployment (equal and
    spread rates), within 5 combined standard errors; MDS at the L the
    program chose."""
    from repro.core import HetSpec
    from repro.core.schemes import get_scheme
    config = bench.load_config("paper_k50")
    cell = workloads.Cell(config, bench.load_traffic("fig5_panel"))
    lam = workloads.het_rates(config)[[2, 3]]
    trials = 2048
    got = get_scheme(scheme, **cell.scheme_params(scheme)).mc_grid(
        [HetSpec(x) for x in lam], config["N"], trials,
        np.random.default_rng(3), backend="numpy")
    ref = reference_mc.point_stats(scheme, lam, config, trials,
                                   np.random.default_rng(4))
    for g, rep in enumerate(got):
        r = ref[g, rep.extra["L"] - 1] if scheme == "mds" else ref[g]
        se = np.sqrt((rep.t_comp_std ** 2 + r[1] ** 2) / (trials - 1))
        assert abs(rep.t_comp - r[0]) < 5 * se, (g, rep.t_comp, r)
        if scheme == "mds":
            assert r[0] <= 1.01 * ref[g, :, 0].min()


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_harness_end_to_end(wl):
    res = dryrun.dry_run(wl["name"], seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["attempted"] >= 3
    assert set(res["checks"]) == set(bench.load_limits(wl["name"]))
    assert res["device"]["count"] >= 1
