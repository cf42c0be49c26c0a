"""A 2,048-worker cluster through the normal path: the exact sort-based
cover, the kernel's tile height from K, the wide-lane kernel in interpret
mode, and the fused panel at K = 2,048 against the plain reference."""
import numpy as np
import pytest

from repro import tracing
from repro.kernels.we_rounds import cover_time, tile_rows
from repro.kernels.we_rounds import kernel as we_kernel
from repro.kernels.we_rounds import ref as we_ref
from repro.kernels.we_rounds.ops import we_rounds_grid

RNG = np.random.default_rng


def pairwise_cover(t, w, need, dtype):
    """The K^2 form the cover replaced: ``sum_j w_j [t_j <= t_k]`` per
    column, summed in ``dtype``; the least weighted ``t_k`` whose sum
    reaches ``need``.  Weight-0 columns count nowhere."""
    t, w = np.asarray(t, np.float64), np.asarray(w, dtype)
    need = np.asarray(need).astype(dtype)
    le = t[:, None, :] <= t[:, :, None]
    covered = np.where(le, w[:, None, :], dtype(0)).sum(axis=2, dtype=dtype)
    first = np.where((w > 0) & (covered >= need[:, None]), t, np.inf)
    return np.where(need > 0, first.min(axis=1), 0.0)


def _rows(case, rng):
    """Seeded random rows of finish times, weights and needs."""
    R, K = 512, 24
    t = rng.integers(1, 40, size=(R, K)).astype(np.float32)   # ties
    w = rng.integers(0, 6, size=(R, K))
    need = rng.integers(1, 2 * K, size=R)
    if case == "zero_weight_nan":
        w[:, ::5] = 0
        t[:, ::5] = np.nan
    elif case == "need_zero":
        need[::3] = 0
    elif case == "unreachable":
        need[::4] = w.sum(axis=1)[::4] + 1
    elif case == "over_2_24":
        w = w * 2_000_000 + rng.integers(0, 2, size=(R, K))
        need = (w.sum(axis=1) * rng.uniform(0.2, 0.9, size=R)).astype(
            np.int64)
        assert (w.sum(axis=1) > 2 ** 24).all()
    return t, w, need


@pytest.fixture(params=["sort", "pairwise"])
def cover_form(request, monkeypatch):
    """Each of ``cover_time``'s two forms, whatever the rows' width."""
    monkeypatch.setattr(we_ref, "PAIRWISE_MAX_K",
                        0 if request.param == "sort" else 10 ** 9)
    return request.param


@pytest.mark.parametrize("case", ["ties", "zero_weight_nan", "need_zero",
                                  "unreachable", "over_2_24"])
def test_sort_cover_matches_the_exact_pairwise_form(case, cover_form):
    """On seeded rows with tied times, either form of the cover picks
    exactly the time the pairwise rule picks with exact (int64) sums."""
    t, w, need = _rows(case, RNG(sum(map(ord, case))))
    got = np.asarray(cover_time(t, w, need))
    np.testing.assert_array_equal(got, pairwise_cover(t, w, need, np.int64))
    if case == "need_zero":
        assert (got[need == 0] == 0).all()
    if case == "unreachable":
        assert np.isinf(got[::4]).all()


def test_int32_cover_is_exact_where_float32_sums_misjudge(cover_form):
    """Weights totalling more than 2^24: float32 sums (and a float32
    need) misjudge the cover; the int32 sums do not."""
    big = 2 ** 24
    t = np.array([[1.0, 2.0, 3.0], [3.0, 1.0, 2.0]], np.float32)
    w = np.array([[big, 1, 1], [1, big, 3]])
    need = np.array([big + 1, big + 4])
    exact = pairwise_cover(t, w, need, np.int64)
    np.testing.assert_array_equal(exact, [2.0, 3.0])
    assert (pairwise_cover(t, w, need, np.float32) != exact).all()
    np.testing.assert_array_equal(np.asarray(cover_time(t, w, need)), exact)


@pytest.mark.parametrize("K,want", [(1, 128), (50, 128), (56, 128),
                                    (128, 128), (2048, None), (8192, 8)])
def test_tile_rows_rule(K, want):
    """128 rows up to one vreg of lanes (the paper's K = 50 programs keep
    their tile); wider rows take the tallest power of two whose live
    round state fits the stated budget, and never fewer than 8."""
    rows = tile_rows(K)
    assert rows % 8 == 0 and rows & (rows - 1) == 0 and 8 <= rows <= 128
    if want is not None:
        assert rows == want
    else:
        lanes = -(-K // 128) * 128
        assert (we_kernel.LIVE_TILES * 4 * lanes * rows
                <= we_kernel.TILE_STATE_BUDGET
                < we_kernel.LIVE_TILES * 4 * lanes * rows * 2)


def test_wide_kernel_interpret_is_bit_identical_to_the_reference():
    """The fused panel kernel at K = 2,048 and the rule's tile height,
    in interpret mode, on three tiles of mixed known / unknown rows:
    bit-identical to the jnp reference; the launch counts its rows and
    tiles."""
    K, N = 2048, 2048 * 20_000
    B = 3 * tile_rows(K) - 5
    lam = RNG(5).uniform(5.0, 15.0, size=(1, K)).astype(np.float32)
    lam = np.repeat(lam, B, axis=0)
    flags = np.arange(B) % 2
    kw = dict(n0=float(N), threshold=0.4096 * N / K,
              cap=float(np.ceil(N / K)), known=flags, max_iter=10_000)
    before = tracing.counters()
    got = we_rounds_grid(lam, (7, 8), mode="interpret", **kw)
    after = tracing.counters()
    want = we_rounds_grid(lam, (7, 8), mode="reference", **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert after["we_rounds.rows"] - before.get("we_rounds.rows", 0) == B
    assert after["we_rounds.tiles"] - before.get("we_rounds.tiles", 0) == 3
    assert np.isfinite(got[0]).all() and (got[1] >= 2).all()


def test_fused_panel_at_k2048_agrees_with_the_plain_reference():
    """``cluster_k2048`` on two of its points through ``run_experiment``
    (the pallas fused panel, which takes its jnp reference path here):
    every scheme within 6 standard errors of ``chipbench.reference_mc``;
    the one-shot covers run on the device path and count their rows."""
    from chipbench import bench, reference_mc, workloads
    from repro.experiments import run_experiment
    config = dict(bench.load_config("cluster_k2048"), mus=[20.0, 100.0],
                  sigma2_fracs=[1.0 / 6.0])
    assert config["N"] == config["K"] * 20_000
    trials = 32
    cell = workloads.Cell(config, dict(bench.load_traffic("wide_panel"),
                                       trials=trials))
    before = tracing.counters()
    res = run_experiment(cell.spec(11), store=None, force=True)
    after = tracing.counters()
    assert (after["schemes.cover_rows"]
            - before.get("schemes.cover_rows", 0)) == 3 * 2 * trials
    ans = cell.answers(res)
    lam = workloads.het_rates(config)
    ref_trials = 48
    rng = RNG(12)
    for key in cell.schemes:
        r = reference_mc.point_stats(key, lam, config, ref_trials, rng)
        a = ans[key]
        se = np.sqrt(a[:, 1] ** 2 / (trials - 1)
                     + r[:, 1] ** 2 / (ref_trials - 1))
        assert (np.abs(a[:, 0] - r[:, 0]) < 6 * se).all(), (key, a, r)


def test_one_shot_span_sits_in_its_scheme_span(tmp_path):
    """``repro.one_shot`` wraps the device dispatch and readback of each
    one-shot scheme, inside its ``repro.scheme.*`` span."""
    import jax
    from chipbench import trace
    from repro.experiments import (ExperimentSpec, ScenarioGrid,
                                   run_experiment, scheme_spec)
    spec = ExperimentSpec(
        name="one-shot-span", grid=ScenarioGrid(K=12, points=[(10.0, 0.0, 1)]),
        schemes=tuple(scheme_spec(s) for s in ("fixed", "het_mds",
                                               "hedged")),
        N=12_000, trials=16, seed=3, backend="pallas", panel="fused")
    run_experiment(spec, store=None, force=True)
    with jax.profiler.trace(str(tmp_path)):
        run_experiment(spec, store=None, force=True)
    red = trace.load_xplane(trace.find_xplane(tmp_path))
    ev = {}
    for evs in red["host"].values():
        for name, s, d in evs:
            ev.setdefault(name, []).append((s, s + d))
    schemes = [sp for s in ("fixed", "het_mds", "hedged")
               for sp in ev[f"repro.scheme.{s}"]]
    assert len(ev["repro.one_shot"]) == 3
    for a, b in ev["repro.one_shot"]:
        assert any(s <= a and b <= e for s, e in schemes)


def test_wide_config_and_traffic_build_their_specs():
    """The new deployment and traffic mixes load and build the spec the
    window drives: no lane cut, the paper's per-worker load, no MDS in
    the wide panel, and the pair on four devices."""
    from chipbench import bench, workloads
    config = bench.load_config("cluster_k2048")
    wide = workloads.Cell(config, bench.load_traffic("wide_panel"))
    spec = wide.spec(3)
    assert spec.grid.K == 2048 and spec.N == 40_960_000
    assert spec.backend == "pallas" and spec.panel == "fused"
    assert "mds" not in wide.schemes and len(wide.schemes) == 5
    assert wide.work_per_call == 8 * 4096 * 5
    assert config["exchange"]["threshold_frac"] * config["N"] \
        / config["K"] ** 2 == pytest.approx(4.0)
    x4 = workloads.Cell(bench.load_config("paper_k50"),
                        bench.load_traffic("we_pair_x4"))
    assert x4.spec(3).devices == 4 and x4.trials == 16_384
    assert x4.work_per_call == 8 * 16_384 * 2


@pytest.mark.parametrize("shares,total", [([5e-324], 1),
                                          ([5e-324, 1e-323, 0.0], 7),
                                          ([1e-320, 5e-324], 3)])
def test_largest_remainder_survives_subnormal_shares(shares, total):
    """Shares so small that ``total / sum`` overflows still round to
    whole units summing to the total, the same in both forms."""
    from repro.core.assignment import (largest_remainder_round,
                                       largest_remainder_round_batch)
    with np.errstate(all="raise"):
        one = largest_remainder_round(np.array(shares), total)
        batch = largest_remainder_round_batch(np.array([shares]),
                                              np.array([total]))
    assert one.sum() == total and (one >= 0).all()
    np.testing.assert_array_equal(batch[0], one)
