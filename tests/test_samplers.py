"""Sampler backends: registry/selection semantics, numpy bit-identity,
numpy-vs-jax statistical equivalence, and ``mc_grid`` agreement.

The jax tests deliberately share one padded batch-shape bucket (B=512) so
the whole file pays a single jit compilation.
"""
import numpy as np
import pytest

from repro.core.samplers import (ENV_VAR, SAMPLER_BACKENDS, SamplerBackend,
                                 get_backend, list_backends,
                                 register_backend, resolve_backend,
                                 work_exchange_grid_numpy)
from repro.core.schemes import get_scheme, work_exchange_mc_batched
from repro.core.types import ExchangeConfig, HetSpec

RNG = lambda s=0: np.random.default_rng(s)  # noqa: E731

K, N, TRIALS = 15, 50_000, 512      # B = 512: one jit bucket for the file


def make_het(K=K, mu=20.0, sigma2=20.0 ** 2 / 6, seed=3):
    return HetSpec.uniform_random(K, mu, sigma2, RNG(seed))


# ---------------------------------------------------------------------------
# registry + selection
# ---------------------------------------------------------------------------

class TestBackendRegistry:
    def test_builtin_backends_registered(self):
        assert {"numpy", "jax", "pallas"} <= set(list_backends())
        for name in ("numpy", "jax", "pallas"):
            assert get_backend(name).name == name
            assert get_backend(name).description

    def test_default_is_numpy(self, monkeypatch):
        monkeypatch.delenv(ENV_VAR, raising=False)
        assert resolve_backend() == "numpy"

    def test_env_var_selects_backend(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "jax")
        assert resolve_backend() == "jax"
        monkeypatch.setenv(ENV_VAR, "numpy")
        assert resolve_backend() == "numpy"

    def test_kwarg_overrides_env(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "jax")
        assert resolve_backend("numpy") == "numpy"

    def test_unknown_backend_raises(self, monkeypatch):
        with pytest.raises(KeyError, match="no_such"):
            resolve_backend("no_such")
        monkeypatch.setenv(ENV_VAR, "bogus")
        with pytest.raises(KeyError, match="bogus"):
            resolve_backend()

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_backend(SamplerBackend(
                name="numpy", work_exchange_grid=work_exchange_grid_numpy))

    def test_unavailable_backend_rejected_with_hint(self):
        register_backend(SamplerBackend(name="tmp_unavailable",
                                        work_exchange_grid=None),
                         available=lambda: False)
        try:
            with pytest.raises(RuntimeError, match="unavailable"):
                resolve_backend("tmp_unavailable")
        finally:
            del SAMPLER_BACKENDS["tmp_unavailable"]

    def test_env_var_reaches_scheme_mc(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "jax")
        rep = get_scheme("work_exchange").mc(make_het(), N, TRIALS, RNG(0))
        assert rep.extra["backend"] == "jax"
        rep = get_scheme("work_exchange").mc(make_het(), N, TRIALS, RNG(0),
                                             backend="numpy")
        assert rep.extra["backend"] == "numpy"


class TestBackendValidationFix:
    """Regression: an unknown backend -- kwarg OR env var -- must raise a
    KeyError naming the registered backends from EVERY scheme's mc/mc_grid
    entry point, including schemes that never draw through a backend
    (previously the name was silently ignored there, and the env-var path
    could only fail far downstream)."""

    # one loop-based, one static-batched, one redundant-batched, one
    # engine-backed, plus the sweep scheme: the full mc override surface
    SCHEMES = ("oracle", "fixed", "mds", "het_mds", "work_exchange",
               "trace_replay")

    @pytest.mark.parametrize("name", SCHEMES)
    def test_kwarg_nosuch_raises_keyerror(self, name):
        with pytest.raises(KeyError, match="nosuch.*numpy"):
            get_scheme(name).mc(make_het(), 1_000, 2, RNG(0),
                                backend="nosuch")

    @pytest.mark.parametrize("name", SCHEMES)
    def test_env_nosuch_raises_keyerror(self, name, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "nosuch")
        with pytest.raises(KeyError, match="nosuch.*numpy"):
            get_scheme(name).mc(make_het(), 1_000, 2, RNG(0))

    @pytest.mark.parametrize("name", ("fixed", "mds", "het_mds",
                                      "work_exchange"))
    def test_mc_grid_nosuch_raises_keyerror(self, name, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "nosuch")
        with pytest.raises(KeyError, match="nosuch.*numpy"):
            get_scheme(name).mc_grid([make_het()], 1_000, 2, RNG(0))

    def test_error_lists_registered_backends(self):
        with pytest.raises(KeyError) as ei:
            get_scheme("oracle").mc(make_het(), 1_000, 2, RNG(0),
                                    backend="nosuch")
        for registered in list_backends():
            assert registered in str(ei.value)

    def test_loop_engine_still_validates_backend(self):
        # regression: engine="loop" used to drop the kwarg entirely
        with pytest.raises(KeyError, match="nosuch"):
            get_scheme("work_exchange", engine="loop").mc(
                make_het(), 1_000, 2, RNG(0), backend="nosuch")


class TestGammaRows:
    """The per-backend batched Gamma primitive the MDS sweep draws on."""

    @pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
    def test_broadcast_shapes_including_R_equals_K(self, backend):
        # regression: a 1-D (K,) scale with R == K used to be padded as
        # if it carried the batch rows, crashing the jitted kernel
        from repro.core.samplers import get_gamma_rows
        draw = get_gamma_rows(backend)
        K = 8
        for shape_rows, scale in (
                (np.full((K, 1), 50.0), np.full(K, 0.1)),      # R == K
                (np.full((3, 1), 20.0), np.full((1, K), 0.2)),
                (np.full((5, K), 10.0), np.full((5, K), 0.5))):
            out = draw(shape_rows, scale, RNG(1))
            R = np.broadcast_shapes(shape_rows.shape,
                                    np.asarray(scale).shape)[0]
            assert out.shape == (R, K)
            assert np.isfinite(out).all() and (out > 0).all()

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_mean_matches_exact_numpy(self, backend):
        from repro.core.samplers import get_gamma_rows
        shape_rows = np.full((4096, 4), 12.0)
        scale = np.full(4, 0.25)
        g = get_gamma_rows(backend)(shape_rows, scale, RNG(2))
        n = g.size
        se = np.sqrt(12.0 + 1 / 9) * 0.25 / np.sqrt(n)
        assert abs(g.mean() - 3.0) < 6 * se


# ---------------------------------------------------------------------------
# numpy backend: exact semantics
# ---------------------------------------------------------------------------

class TestNumpyBackend:
    def test_mc_backend_numpy_is_the_batched_engine(self):
        het = make_het()
        cfg = ExchangeConfig(known_heterogeneity=True)
        a = get_scheme("work_exchange").mc(het, 5_000, 32, RNG(1),
                                           keep_trials=True,
                                           backend="numpy")
        b = work_exchange_mc_batched(het, 5_000, cfg, 32, RNG(1),
                                     keep_trials=True)
        np.testing.assert_array_equal(a.t_comp_trials, b.t_comp_trials)
        np.testing.assert_array_equal(a.n_comm_trials, b.n_comm_trials)

    def test_single_spec_grid_is_bitwise_mc(self):
        het = make_het(seed=9)
        for known in (True, False):
            scheme = get_scheme("work_exchange" if known
                                else "work_exchange_unknown")
            rep = scheme.mc(het, 4_000, 24, RNG(2), keep_trials=True,
                            backend="numpy")
            [grid] = scheme.mc_grid([het], 4_000, 24, RNG(2),
                                    keep_trials=True, backend="numpy")
            np.testing.assert_array_equal(rep.t_comp_trials,
                                          grid.t_comp_trials)
            np.testing.assert_array_equal(rep.iterations_trials,
                                          grid.iterations_trials)
            np.testing.assert_array_equal(rep.n_comm_trials,
                                          grid.n_comm_trials)

    def test_grid_engine_conserves_work_per_row(self):
        lam = np.stack([make_het(seed=s).lambdas for s in (1, 2, 3)])
        cfg = ExchangeConfig(known_heterogeneity=False)
        t, it, cm = work_exchange_grid_numpy(lam, 3_000, cfg, 8, RNG(3))
        assert t.shape == it.shape == cm.shape == (24,)
        assert (t > 0).all() and (it >= 1).all() and (cm >= 0).all()

    def test_bad_lam_shape_raises(self):
        with pytest.raises(ValueError, match="G, K"):
            work_exchange_grid_numpy(np.ones(5), 100,
                                     ExchangeConfig(), 2, RNG(0))


# ---------------------------------------------------------------------------
# jax backend: statistical equivalence with the exact engine
# ---------------------------------------------------------------------------

def _stat_close(rep_np, rep_jax, trials):
    """Mean agreement within MC tolerance: 6 combined standard errors with
    a small relative floor for the fluid relaxation's float32 pipeline."""
    se = np.hypot(rep_np.t_comp_std, rep_jax.t_comp_std) / np.sqrt(trials)
    tol = max(6.0 * se, 1e-3 * rep_np.t_comp)
    assert abs(rep_np.t_comp - rep_jax.t_comp) < tol, \
        (rep_np.t_comp, rep_jax.t_comp, tol)


class TestJaxEquivalence:
    @pytest.mark.parametrize("name", ["work_exchange",
                                      "work_exchange_unknown"])
    def test_mean_time_matches(self, name):
        het = make_het(seed=11)
        scheme = get_scheme(name)
        rn = scheme.mc(het, N, TRIALS, RNG(5), backend="numpy")
        rj = scheme.mc(het, N, TRIALS, RNG(5), backend="jax")
        assert rj.extra["backend"] == "jax"
        _stat_close(rn, rj, TRIALS)
        # both sit just above the work-conservation lower bound
        oracle = N / het.lambda_sum
        assert oracle <= rj.t_comp < 1.05 * oracle

    @pytest.mark.parametrize("name", ["work_exchange",
                                      "work_exchange_unknown"])
    def test_iterations_and_comm_match(self, name):
        het = make_het(seed=12)
        scheme = get_scheme(name)
        rn = scheme.mc(het, N, TRIALS, RNG(6), backend="numpy")
        rj = scheme.mc(het, N, TRIALS, RNG(6), backend="jax")
        # the fluid relaxation may end the exchange loop a couple of
        # rounds away from the integer engine (sub-half-unit shares are
        # carried, not rounded up)
        assert abs(rn.iterations - rj.iterations) <= max(
            4.0, 0.2 * rn.iterations)
        # communication: identical at the fraction-of-N scale
        assert abs(rn.n_comm - rj.n_comm) / N < 0.01

    def test_keep_trials_shapes(self):
        rep = get_scheme("work_exchange").mc(make_het(), N, TRIALS, RNG(7),
                                             keep_trials=True, backend="jax")
        for arr in (rep.t_comp_trials, rep.iterations_trials,
                    rep.n_comm_trials):
            assert arr is not None and arr.shape == (TRIALS,)
        assert rep.t_comp == pytest.approx(rep.t_comp_trials.mean())

    def test_waterfill_mode_not_supported(self):
        scheme = get_scheme("work_exchange_unknown", capped_mode="waterfill")
        with pytest.raises(ValueError, match="waterfill"):
            scheme.mc(make_het(), 2_000, 4, RNG(8), backend="jax")

    def test_loop_engine_ignores_backend(self):
        # engine="loop" is the scalar validation reference: it stays numpy
        rep = get_scheme("work_exchange", engine="loop").mc(
            make_het(), 2_000, 3, RNG(9), backend="jax")
        assert rep.trials == 3 and rep.t_comp > 0


# ---------------------------------------------------------------------------
# mc_grid semantics
# ---------------------------------------------------------------------------

class TestMcGrid:
    def test_default_loop_equals_manual_loop(self):
        # base-class mc_grid draws from the shared rng in spec order
        specs = [make_het(seed=s) for s in (1, 2)]
        scheme = get_scheme("oracle")
        grid = scheme.mc_grid(specs, 10_000, 16, RNG(10))
        rng = RNG(10)
        manual = [scheme.mc(h, 10_000, 16, rng) for h in specs]
        for g, m in zip(grid, manual):
            assert g.t_comp == m.t_comp and g.t_comp_std == m.t_comp_std

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_grid_matches_looped_mc_statistically(self, backend):
        specs = [make_het(seed=s, mu=10.0 * (s + 1),
                          sigma2=(10.0 * (s + 1)) ** 2 / 6) for s in (0, 1)]
        trials = TRIALS // len(specs)       # same B bucket as the rest
        scheme = get_scheme("work_exchange_unknown")
        grid = scheme.mc_grid(specs, N, trials, RNG(11), backend=backend)
        for het, g in zip(specs, grid):
            m = scheme.mc(het, N, trials, RNG(12), backend="numpy")
            se = np.hypot(g.t_comp_std, m.t_comp_std) / np.sqrt(trials)
            assert abs(g.t_comp - m.t_comp) < max(6 * se, 2e-3 * m.t_comp)
        # reports align with the spec axis: faster cluster finishes sooner
        assert grid[1].t_comp < grid[0].t_comp

    def test_mixed_k_grid_falls_back_to_loop(self):
        specs = [make_het(K=5, seed=1), make_het(K=8, seed=2)]
        scheme = get_scheme("work_exchange")
        grid = scheme.mc_grid(specs, 3_000, 6, RNG(13), backend="numpy")
        rng = RNG(13)
        manual = [scheme.mc(h, 3_000, 6, rng, backend="numpy")
                  for h in specs]
        for g, m in zip(grid, manual):
            assert g.t_comp == m.t_comp

    def test_grid_report_metadata(self):
        specs = [make_het(seed=s) for s in (4, 5)]
        grid = get_scheme("work_exchange").mc_grid(
            specs, 5_000, 8, RNG(14), keep_trials=True, backend="numpy")
        assert len(grid) == 2
        for rep in grid:
            assert rep.scheme == "work_exchange"
            assert rep.trials == 8
            assert rep.extra["backend"] == "numpy"
            assert rep.t_comp_trials.shape == (8,)

    @pytest.mark.parametrize("name", ["fixed", "uniform", "het_mds"])
    def test_static_scheme_grid_matches_looped_mc(self, name):
        # the one-draw batched grid is the same distribution as looped mc
        specs = [make_het(seed=s) for s in (6, 7)]
        trials = 400
        scheme = get_scheme(name)
        grid = scheme.mc_grid(specs, 20_000, trials, RNG(16))
        for het, g in zip(specs, grid):
            m = scheme.mc(het, 20_000, trials, RNG(17))
            se = np.hypot(g.t_comp_std, m.t_comp_std) / np.sqrt(trials)
            assert abs(g.t_comp - m.t_comp) < 6 * se
            assert g.n_comm == m.n_comm and g.iterations == 1.0

    def test_empty_grid(self):
        assert get_scheme("work_exchange").mc_grid([], 1_000, 4,
                                                   RNG(15)) == []


# ---------------------------------------------------------------------------
# K / R shape bucketing
# ---------------------------------------------------------------------------

class TestShapeBucketing:
    """Panel shape bucketing: non-pow2 ``(K, R)`` pad into pow2 buckets
    with fully-masked columns / repeated last schedule rows, so one
    compilation (and one persistent-cache entry) serves the shape
    family.  On the counter-keyed pallas pipeline the padding must be
    bitwise invisible; on the stream-keyed jax engine, statistically."""

    def test_bucket_targets(self):
        from repro.core.samplers import bucket_cols, bucket_rounds
        assert [bucket_cols(k) for k in (3, 12, 13, 16, 17, 50)] == \
            [4, 16, 16, 16, 24, 56]
        assert [bucket_rounds(r) for r in (6, 7, 16, 19, 48)] == \
            [8, 8, 16, 32, 48]

    def test_disable_env(self, monkeypatch):
        from repro.core.samplers import bucket_cols, bucket_rounds
        monkeypatch.setenv("REPRO_SHAPE_BUCKETS", "0")
        assert bucket_cols(13) == 13 and bucket_rounds(19) == 19

    def test_grid_bucket_shape_families(self):
        # two different raw panel shapes landing in ONE bucket is the
        # whole point: one compile, one shared cache entry
        from repro.core.samplers import grid_bucket_shape
        a = grid_bucket_shape(2, 16, 12, None, backend="jax")
        b = grid_bucket_shape(3, 8, 14, None, backend="jax")
        assert a == b == {"rows": 64, "K": 16}

    @pytest.mark.parametrize("known", [True, False])
    def test_non_pow2_K_mode_identity_under_bucketing(self, known,
                                                      monkeypatch):
        """K=13 pads to the 16 bucket with masked zero-rate columns; at
        the padded shape the interpreted kernel and the jnp reference
        stay BIT-identical (the pin the bucketing must not break).
        Bucketed vs exact shapes are NOT bit-equal -- float32 reduction
        order over the K axis changes with the padded width -- so the
        cross-setting check is statistical, below."""
        from repro.core.samplers import work_exchange_grid_pallas
        lam = RNG(2).uniform(5.0, 15.0, size=(2, 13))
        cfg = ExchangeConfig(known_heterogeneity=known)
        for buckets in ("1", "0"):
            monkeypatch.setenv("REPRO_SHAPE_BUCKETS", buckets)
            outs = []
            for mode in ("interpret", "reference"):
                monkeypatch.setenv("REPRO_WE_ROUNDS_MODE", mode)
                outs.append(work_exchange_grid_pallas(lam, 6_000, cfg, 32,
                                                      RNG(9)))
            for a, b in zip(*outs):
                np.testing.assert_array_equal(a, b, err_msg=buckets)

    @pytest.mark.parametrize("known", [True, False])
    def test_non_pow2_R_drift_mode_identity_under_bucketing(self, known,
                                                            monkeypatch):
        """A 19-round drift schedule pads to the 32 bucket by repeating
        the last row -- exactly the engines' ``round >= R`` clamp -- and
        the padded shape keeps the interpret/reference bit-identity."""
        from repro.core.samplers import work_exchange_grid_pallas
        rng = RNG(4)
        lam = rng.uniform(5.0, 15.0, size=(2, 13))
        sched = lam[:, None, :] * np.exp(
            0.2 * rng.standard_normal((2, 19, 13)))
        cfg = ExchangeConfig(known_heterogeneity=known)
        for buckets in ("1", "0"):
            monkeypatch.setenv("REPRO_SHAPE_BUCKETS", buckets)
            outs = []
            for mode in ("interpret", "reference"):
                monkeypatch.setenv("REPRO_WE_ROUNDS_MODE", mode)
                outs.append(work_exchange_grid_pallas(
                    lam, 6_000, cfg, 32, RNG(9), rate_schedule=sched))
            for a, b in zip(*outs):
                np.testing.assert_array_equal(a, b, err_msg=buckets)

    def test_bucketed_vs_exact_statistical_on_pallas(self, monkeypatch):
        """Bucketing on vs off at non-pow2 K: means agree at 6 SE (the
        padding is statistically, not bitwise, invisible)."""
        from repro.core.samplers import work_exchange_grid_pallas
        lam = RNG(5).uniform(15.0, 25.0, size=(1, 13))
        cfg = ExchangeConfig(known_heterogeneity=False)
        trials = 512
        res = {}
        for buckets in ("1", "0"):
            monkeypatch.setenv("REPRO_SHAPE_BUCKETS", buckets)
            res[buckets] = work_exchange_grid_pallas(lam, N, cfg, trials,
                                                     RNG(9))
        t1, t0 = res["1"][0], res["0"][0]
        se = np.hypot(t1.std(), t0.std()) / np.sqrt(trials)
        assert abs(t1.mean() - t0.mean()) < max(6 * se, 2e-3 * t0.mean())

    def test_non_pow2_K_statistical_on_jax(self, monkeypatch):
        """The jax engine keys draws by stream, not counters, so K
        padding moves individual samples; means must still agree with
        the exact numpy engine at 6 SE at a non-pow2 K."""
        from repro.core.samplers import work_exchange_grid_jax
        lam = RNG(6).uniform(15.0, 25.0, size=(1, 13))
        cfg = ExchangeConfig(known_heterogeneity=False)
        trials = 512
        t_j, _, _ = work_exchange_grid_jax(lam, N, cfg, trials, RNG(7))
        t_n, _, _ = work_exchange_grid_numpy(lam, N, cfg, trials, RNG(8))
        se = np.hypot(t_j.std(), t_n.std()) / np.sqrt(trials)
        assert abs(t_j.mean() - t_n.mean()) < max(6 * se,
                                                  2e-3 * t_n.mean())


# ---------------------------------------------------------------------------
# fused whole-panel dispatch
# ---------------------------------------------------------------------------

class TestFusedPanelDispatch:
    """``mc_grid_panel``: the WE known/unknown pair as ONE engine call."""

    def _schemes(self):
        return {"we": get_scheme("work_exchange"),
                "weu": get_scheme("work_exchange_unknown"),
                "fixed": get_scheme("fixed")}

    def test_pair_detection(self):
        from repro.core.schemes import _panel_pair
        assert _panel_pair(self._schemes()) == ("we", "weu")
        # mismatched thresholds cannot share one round loop
        s = self._schemes()
        s["weu"] = get_scheme("work_exchange_unknown", threshold_frac=0.05)
        assert _panel_pair(s) is None
        # loop-engine references never fuse
        s = self._schemes()
        s["we"] = get_scheme("work_exchange", engine="loop")
        assert _panel_pair(s) is None

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_panel_matches_numpy_at_6se(self, backend):
        from repro.core.schemes import mc_grid_panel
        specs = [make_het(seed=s) for s in (1, 2)]
        trials = 256
        out = mc_grid_panel(self._schemes(), specs, N, trials, RNG(21),
                            backend=backend)
        for key in ("we", "weu"):
            assert all(r.extra.get("fused_panel") == 1 for r in out[key])
            name = ("work_exchange" if key == "we"
                    else "work_exchange_unknown")
            ref = get_scheme(name).mc_grid(specs, N, trials, RNG(22),
                                           backend="numpy")
            for g, (a, b) in enumerate(zip(out[key], ref)):
                se = np.hypot(a.t_comp_std, b.t_comp_std) / np.sqrt(trials)
                assert abs(a.t_comp - b.t_comp) < max(6 * se,
                                                      2e-3 * b.t_comp), \
                    (backend, key, g)
                assert abs(a.n_comm - b.n_comm) / N < 0.01

    def test_rng_mapping_keeps_non_pair_bitwise(self):
        """With the executor's per-task rng mapping, non-fused schemes
        draw from exactly the per-scheme stream: panel mode only moves
        the fused pair's numbers."""
        from repro.core.schemes import mc_grid_panel
        specs = [make_het(seed=4)]
        rngs = {"we": RNG(31), "weu": RNG(32), "fixed": RNG(33)}
        out = mc_grid_panel(self._schemes(), specs, 20_000, 64, rngs,
                            backend="jax")
        ref = get_scheme("fixed").mc_grid(specs, 20_000, 64, RNG(33),
                                          backend="jax")
        assert out["fixed"][0].t_comp == ref[0].t_comp
        assert out["fixed"][0].extra.get("fused_panel") is None

    def test_numpy_falls_back_per_scheme_bitwise(self):
        """No panel executor on the exact backend: every scheme runs its
        own mc_grid from its own stream -- bit-identical to per-scheme
        dispatch, no fused_panel flag."""
        from repro.core.schemes import mc_grid_panel
        specs = [make_het(seed=5)]
        rngs = {"we": RNG(41), "weu": RNG(42), "fixed": RNG(43)}
        out = mc_grid_panel(self._schemes(), specs, 20_000, 16, rngs,
                            backend="numpy")
        for key, name, seed in (("we", "work_exchange", 41),
                                ("weu", "work_exchange_unknown", 42),
                                ("fixed", "fixed", 43)):
            ref = get_scheme(name).mc_grid(specs, 20_000, 16, RNG(seed),
                                           backend="numpy")
            assert out[key][0].t_comp == ref[0].t_comp
            assert out[key][0].extra.get("fused_panel") is None

    def test_pallas_panel_mode_identity(self, monkeypatch):
        """The stacked pallas panel launch is bitwise mode-identical:
        interpret-mode kernel == jitted reference, known and unknown
        halves both."""
        from repro.core.samplers import work_exchange_panel_pallas
        lam = RNG(51).uniform(10.0, 30.0, size=(2, 12))
        cfg_k = ExchangeConfig(known_heterogeneity=True)
        cfg_u = ExchangeConfig(known_heterogeneity=False)
        outs = []
        for mode in ("interpret", "reference"):
            monkeypatch.setenv("REPRO_WE_ROUNDS_MODE", mode)
            outs.append(work_exchange_panel_pallas(lam, 10_000, cfg_k,
                                                   cfg_u, 32, RNG(52)))
        for slot in ("known", "unknown"):
            for a, b in zip(outs[0][slot], outs[1][slot]):
                np.testing.assert_array_equal(a, b, err_msg=slot)

    def test_drift_panel_matches_numpy_at_6se(self):
        from repro.core.schemes import mc_grid_panel
        rng = RNG(61)
        specs = [make_het(seed=6)]
        lam = specs[0].lambdas
        sched = (lam[None, None, :]
                 * np.exp(0.15 * rng.standard_normal((1, 9, K))))
        trials = 256
        out = mc_grid_panel(self._schemes(), specs, N, trials, RNG(62),
                            backend="jax", rate_schedule=sched)
        for key, name in (("we", "work_exchange"),
                          ("weu", "work_exchange_unknown")):
            ref = get_scheme(name).mc_grid(specs, N, trials, RNG(63),
                                           backend="numpy",
                                           rate_schedule=sched)
            a, b = out[key][0], ref[0]
            se = np.hypot(a.t_comp_std, b.t_comp_std) / np.sqrt(trials)
            assert abs(a.t_comp - b.t_comp) < max(6 * se, 2e-3 * b.t_comp)


# ---------------------------------------------------------------------------
# one-shot schemes on the device
# ---------------------------------------------------------------------------

ONE_SHOT = ("fixed", "uniform", "het_mds", "hedged")
# case -> (N, specs): the paper's panel point; loads below 3 (the boost
# chain); a worker too slow for any share (a load-0 column, no boost)
ONE_SHOT_CASES = {
    "panel": (1_000_000, lambda: [HetSpec.uniform_random(
        50, 50.0, 50.0 ** 2 / 6, RNG(50))]),
    "boost": (12, lambda: [make_het(K=8, mu=1.0, sigma2=1 / 6, seed=s)
                           for s in (1, 2)]),
    "zero_load": (3_000, lambda: [HetSpec(np.array([30.0] * 5 + [1e-3]))]),
}


class TestOneShotDevice:
    """``fixed``, ``uniform``, ``het_mds`` and ``hedged`` grids through the
    backends' ``one_shot_times``: draws and per-row reduction on the
    device, against the exact numpy draw."""

    @pytest.mark.parametrize("case", sorted(ONE_SHOT_CASES))
    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    @pytest.mark.parametrize("name", ONE_SHOT)
    def test_grid_matches_numpy_at_6se(self, name, backend, case):
        n, specs = ONE_SHOT_CASES[case]
        specs, trials = specs(), 2048
        scheme = get_scheme(name)
        dev = scheme.mc_grid(specs, n, trials, RNG(71), backend=backend)
        ref = scheme.mc_grid(specs, n, trials, RNG(72), backend="numpy")
        for a, b in zip(dev, ref):
            se = np.hypot(a.t_comp_std, b.t_comp_std) / np.sqrt(trials)
            assert abs(a.t_comp - b.t_comp) < 6 * se, (a.t_comp, b.t_comp)
            assert (a.n_comm, a.iterations) == (b.n_comm, b.iterations)
            assert a.extra == {**b.extra, "backend": backend}

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_hedged_k1_reduces_like_fixed(self, backend):
        het = HetSpec(np.array([3.0]))
        [dev] = get_scheme("hedged").mc_grid([het], 1_000, 512, RNG(73),
                                             backend=backend)
        ref = get_scheme("fixed").mc(het, 1_000, 512, RNG(74))
        assert dev.n_comm == 0 and dev.extra == {"backend": backend}
        se = np.hypot(dev.t_comp_std, ref.t_comp_std) / np.sqrt(512)
        assert abs(dev.t_comp - ref.t_comp) < 6 * se

    def test_cover_reduction_equals_sort_rule(self):
        """On one finish-time matrix with ties (integer times) and load-0
        columns, the sort-free cover picks exactly the time the argsort /
        cumsum rule picks."""
        from repro.core.schemes import HetMDSScheme
        from repro.kernels.we_rounds import cover_time
        rng = RNG(75)
        loads = rng.integers(0, 40, size=(4096, 12))
        loads[:, 3] = 0
        t = rng.integers(1, 30, size=loads.shape).astype(np.float32)
        n = int(0.8 * loads.sum(axis=1).min())
        want = HetMDSScheme._cover_rule(np.where(loads > 0, t, np.inf),
                                        loads, n)
        got = cover_time(np.where(loads > 0, t, np.nan), loads,
                         np.full(len(t), n))
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_race_weights_make_the_cover_the_hedged_race(self):
        """On one matrix with ties: the cover under ``_race_weights`` is
        the latest of the other loaded workers and the earlier replica,
        exactly; with no spare it is the plain max over loaded workers."""
        from repro.core.schemes import HedgedScheme
        from repro.kernels.we_rounds import cover_time
        rng = RNG(76)
        R, K = 512, 9
        t = rng.integers(1, 12, size=(R, K)).astype(np.float32)
        rows, want = [], []
        for r in range(R):
            loads = rng.integers(0, 4, size=K)
            spare = None if r % 4 == 0 else int(rng.integers(K))
            strag = None if spare is None else (spare + 1) % K
            if spare is not None:
                loads[spare], loads[strag] = 0, 1
            rows.append(HedgedScheme._race_weights(loads, spare, strag))
            eff = np.where(loads > 0, t[r], 0.0)
            if spare is not None:
                eff[strag] = min(t[r, strag], t[r, spare])
            want.append(eff.max())
        weights, need = (np.array(x) for x in zip(*rows))
        got = cover_time(t, weights, need)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.array(want, dtype=np.float32))

    @pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
    def test_counters_advance_on_the_path_taken(self, backend):
        from repro import tracing
        specs = [make_het(seed=s) for s in (1, 2, 3)]
        taken = ("schemes.one_shot_rows_host" if backend == "numpy"
                 else "schemes.one_shot_rows_device")
        other = ({"schemes.one_shot_rows_host",
                  "schemes.one_shot_rows_device"} - {taken}).pop()
        for name in ONE_SHOT:
            before = tracing.counters()
            get_scheme(name).mc_grid(specs, 2_000, 64, RNG(77),
                                     backend=backend)
            after = tracing.counters()
            assert after[taken] - before.get(taken, 0) == 3 * 64, name
            assert after.get(other, 0) == before.get(other, 0), name
