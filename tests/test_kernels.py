"""Per-kernel allclose vs the pure-jnp oracle, interpret mode, with
shape/dtype sweeps (and a backward check through the custom VJPs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.linear_scan.kernel import linear_scan as ls_kernel
from repro.kernels.linear_scan.ops import linear_scan as ls_op
from repro.kernels.linear_scan.ref import linear_scan_ref
from repro.kernels.moe_gmm.kernel import expert_matmul
from repro.kernels.moe_gmm.ref import expert_matmul_ref
from repro.kernels.we_rounds import (gamma_rows_grid, resolve_mode,
                                     we_rounds_grid)

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Sq,Sk,Hq,Hkv,d,causal,window",
        [
            (1, 64, 64, 2, 2, 32, True, 0),
            (2, 128, 128, 4, 2, 16, True, 0),      # GQA
            (1, 64, 64, 4, 1, 32, True, 0),        # MQA
            (1, 128, 128, 2, 2, 32, True, 32),     # sliding window
            (2, 64, 64, 2, 2, 64, False, 0),       # non-causal (encoder)
            (1, 32, 128, 2, 1, 32, True, 0),       # Sq < Sk (right-aligned)
        ])
    def test_fwd_matches_ref(self, dtype, B, Sq, Sk, Hq, Hkv, d, causal,
                             window):
        q = _rand((B, Sq, Hq, d), dtype)
        k = _rand((B, Sk, Hkv, d), dtype)
        v = _rand((B, Sk, Hkv, d), dtype)
        out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  block_q=32, block_k=32, interpret=True)
        want = fa_ref.attention_ref(q, k, v, causal=causal, window=window)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_block_shape_sweep(self):
        q = _rand((1, 128, 2, 32), jnp.float32)
        k = _rand((1, 128, 2, 32), jnp.float32)
        v = _rand((1, 128, 2, 32), jnp.float32)
        want = fa_ref.attention_ref(q, k, v)
        for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]:
            out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                                      interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"block {bq}x{bk}")

    def test_vjp_matches_ref_grad(self):
        q = _rand((1, 64, 2, 16), jnp.float32)
        k = _rand((1, 64, 1, 16), jnp.float32)
        v = _rand((1, 64, 1, 16), jnp.float32)

        def f_kernel(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 0, None, True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(fa_ref.attention_ref(q, k, v) ** 2)

        g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestExpertMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("E,C,D,F", [
        (2, 32, 32, 32), (4, 64, 32, 64), (1, 128, 64, 32), (8, 32, 64, 64),
    ])
    def test_matches_ref(self, dtype, E, C, D, F):
        buf = _rand((E, C, D), dtype)
        w = _rand((E, D, F), dtype)
        out = expert_matmul(buf, w, block_c=32, block_f=32, block_d=32,
                            interpret=True)
        want = expert_matmul_ref(buf, w)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


class TestLinearScan:
    @pytest.mark.parametrize("B,S,D,chunk", [
        (1, 64, 16, 16), (2, 128, 32, 32), (3, 96, 8, 32), (1, 256, 64, 64),
    ])
    def test_matches_ref(self, B, S, D, chunk):
        a = jnp.asarray(RNG.uniform(0.5, 1.0, (B, S, D)), jnp.float32)
        b = _rand((B, S, D), jnp.float32)
        out = ls_kernel(a, b, chunk=chunk, interpret=True)
        want = linear_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_vjp_matches_ref_grad(self):
        a = jnp.asarray(RNG.uniform(0.5, 0.99, (1, 64, 8)), jnp.float32)
        b = _rand((1, 64, 8), jnp.float32)

        def f_kernel(a, b):
            return jnp.sum(ls_op(a, b, True) ** 2)

        def f_ref(a, b):
            return jnp.sum(linear_scan_ref(a, b) ** 2)

        g1 = jax.grad(f_kernel, argnums=(0, 1))(a, b)
        g2 = jax.grad(f_ref, argnums=(0, 1))(a, b)
        for x, y in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4)

    def test_matches_model_recurrence(self):
        """The kernel is the oracle-equivalent of models.recurrent."""
        from repro.models.recurrent import linear_recurrence
        a = jnp.asarray(RNG.uniform(0.2, 1.0, (2, 64, 16)), jnp.float32)
        b = _rand((2, 64, 16), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ls_kernel(a, b, chunk=32, interpret=True)),
            np.asarray(linear_recurrence(a, b)), rtol=1e-5, atol=1e-5)


class TestWeRounds:
    """The fused work-exchange round-pipeline kernel (pallas backend)."""

    K, N = 12, 30_000
    THRESHOLD = 0.01 * N / K

    def _lam_rows(self, B, seed=3):
        rng = np.random.default_rng(seed)
        return np.repeat(rng.uniform(10.0, 30.0, size=(1, self.K)), B,
                         axis=0)

    def _run(self, B, mode, known=True, seed=(11, 22)):
        cap = np.inf if known else float(np.ceil(self.N / self.K))
        return we_rounds_grid(self._lam_rows(B), seed, n0=self.N,
                              threshold=self.THRESHOLD, cap=cap,
                              known=known, max_iter=10_000, mode=mode)

    @pytest.mark.parametrize("known", [True, False])
    def test_interpret_kernel_bitwise_matches_reference(self, known):
        """Counter-based draws make kernel tiling invisible: the
        interpreted kernel and the jnp oracle are BIT-identical."""
        for a, b in zip(self._run(256, "interpret", known),
                        self._run(256, "reference", known)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("known", [True, False])
    def test_drift_schedule_bitwise_across_modes(self, known):
        """The per-round rate schedule (drifting scenarios) keeps the
        kernel/reference bit-identity: counters are untouched, the
        schedule only re-scales the Gamma draws -- including on odd
        batches where the schedule rows are padded alongside."""
        for B in (256, 100):
            lam = self._lam_rows(B)
            rng = np.random.default_rng(17)
            sched = (lam[:, None, :]
                     * np.exp(0.15 * rng.standard_normal((B, 6, self.K))))
            cap = np.inf if known else float(np.ceil(self.N / self.K))
            out = [we_rounds_grid(lam, (11, 22), n0=self.N,
                                  threshold=self.THRESHOLD, cap=cap,
                                  known=known, max_iter=10_000, mode=mode,
                                  rate_schedule=sched)
                   for mode in ("interpret", "reference")]
            for a, b in zip(*out):
                np.testing.assert_array_equal(a, b)
            # and the schedule actually changed the outcome
            plain = we_rounds_grid(lam, (11, 22), n0=self.N,
                                   threshold=self.THRESHOLD, cap=cap,
                                   known=known, max_iter=10_000,
                                   mode="reference")
            assert not np.array_equal(out[1][0], plain[0])

    @pytest.mark.parametrize("B", [1, 77, 130, 200])
    def test_padding_path_odd_batches(self, B):
        """Odd / non-power-of-two trial counts pad to the tile multiple;
        padding rows must never perturb real rows (vs the unpadded
        reference) and outputs keep the requested length."""
        t, it, cm = self._run(B, "interpret")
        t_ref, it_ref, cm_ref = self._run(B, "reference")
        assert t.shape == it.shape == cm.shape == (B,)
        np.testing.assert_array_equal(t, t_ref)
        np.testing.assert_array_equal(it, it_ref)
        np.testing.assert_array_equal(cm, cm_ref)
        assert np.isfinite(t).all() and (it >= 1).all() and (cm >= 0).all()

    @pytest.mark.parametrize("known", [True, False])
    def test_statistically_equivalent_to_jax_backend(self, known):
        """Interpret-mode kernel vs the fused jax backend at 6 combined
        standard errors on a shared scenario (both sample the same fluid
        relaxation from independent bit streams)."""
        from repro.core.samplers import work_exchange_grid_jax
        from repro.core.types import ExchangeConfig, HetSpec

        trials = 512
        lam = self._lam_rows(1)[0]
        t_k, _, cm_k = self._run(trials, "interpret", known)
        cfg = ExchangeConfig(known_heterogeneity=known)
        t_j, _, cm_j = work_exchange_grid_jax(
            lam[None, :], self.N, cfg, trials, np.random.default_rng(5))
        se = np.hypot(t_k.std(), t_j.std()) / np.sqrt(trials)
        assert abs(t_k.mean() - t_j.mean()) < max(6.0 * se,
                                                  1e-3 * t_j.mean())
        assert abs(cm_k.mean() - cm_j.mean()) / self.N < 0.01
        oracle = self.N / HetSpec(lam).lambda_sum
        assert oracle <= t_k.mean() < 1.05 * oracle

    def test_gamma_rows_moments(self):
        """Counter-based MT gamma rows: mean exact, variance alpha + 1/9
        (large-shape transform) at 6 SE."""
        R, K, alpha, scale = 4096, 8, 7.5, 0.5
        g = gamma_rows_grid(np.full((R, K), alpha), np.full((R, K), scale),
                            (1, 2))
        n = R * K
        se_mean = np.sqrt(alpha + 1 / 9) * scale / np.sqrt(n)
        assert abs(g.mean() - alpha * scale) < 6 * se_mean
        var_want = (alpha + 1 / 9) * scale ** 2
        assert abs(g.var() - var_want) < 0.05 * var_want

    def test_mode_resolution_and_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_WE_ROUNDS_MODE", raising=False)
        assert resolve_mode() in ("kernel", "reference")
        assert resolve_mode("interpret") == "interpret"
        monkeypatch.setenv("REPRO_WE_ROUNDS_MODE", "reference")
        assert resolve_mode() == "reference"
        with pytest.raises(KeyError, match="bogus"):
            resolve_mode("bogus")


class TestChunkedAttentionSkip:
    def test_unrolled_causal_skip_matches_map_and_direct(self):
        """The static causal-block-skip path (UNROLL_CHUNKS) is exact."""
        from repro.models import attention as attn
        from repro.models.common import causal_mask
        rng = np.random.default_rng(3)
        B, S, Hq, Hkv, d = 2, 256, 4, 2, 16
        q = jnp.asarray(rng.normal(size=(B, S, Hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)), jnp.float32)
        for window in (0, 64):
            ref = attn.grouped_attention(q, k, v,
                                         causal_mask(S, S, 0, window),
                                         d ** -0.5)
            old = attn.UNROLL_CHUNKS
            try:
                attn.UNROLL_CHUNKS = True
                out = attn.chunked_attention(q, k, v, d ** -0.5,
                                             window=window, chunk=64)
            finally:
                attn.UNROLL_CHUNKS = old
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"window={window}")
