"""Pluggable sampler backends for the work-exchange Monte-Carlo engine.

The engine's hot loop is a round pipeline -- batched Gamma service draws,
argmin over workers, Binomial done-counts -- repeated for ~60 exchange
rounds.  Two backends implement it behind one grid-shaped contract:

``numpy``
    The exact integer-unit engine (largest-remainder assignments, exact
    ``Generator.gamma`` / ``Generator.binomial`` draws).  Bit-identical to
    the PR-1 trial-vectorized engine: with a single heterogeneity spec it
    consumes randomness in exactly the order of
    ``schemes.work_exchange_mc_batched``, which itself reduces to the
    scalar reference at ``trials=1``.

``jax``
    One jitted function fusing the whole pipeline -- assignment, Gamma,
    argmin, Binomial, estimator update -- with a ``lax.while_loop`` over
    exchange rounds and the ``(grid x trials)`` batch as the leading axis.
    It samples the paper's *fluid relaxation*: assignments are the exact
    real-valued proportional shares (the paper's eqs. 16/18/22 before
    unit rounding), Gamma draws use a mean-exact Marsaglia-Tsang transform
    (with the small-shape boost ``Gamma(a) = Gamma(a+1) * U^{1/a}``), and
    Binomial done-counts use their mean/variance-exact normal limit.
    Statistically equivalent to ``numpy`` at Monte-Carlo tolerance (unit
    rounding perturbs real shares by <1 unit in thousands); NOT
    bit-identical, and float32.  ``jax.random.gamma``'s per-element
    rejection loop is ~100x slower than NumPy on CPU, so the transform
    sampler is what makes the fused engine a win rather than a loss.

``pallas``
    The same fluid relaxation as ``jax``, but the whole round pipeline --
    counter-based Threefry-2x32 bit generation keyed per ``(trial,
    worker, round)``, the MT Gamma transform, the per-trial argmin, the
    normal-limit Binomial -- fused into ONE tiled Pallas kernel
    (``repro.kernels.we_rounds``): each program owns a ``(block_b, K)``
    tile of trials and runs the exchange-round loop to completion in
    VMEM.  On hosts without Pallas lowering (CPU CI) it executes a
    jitted ``jnp`` reference, bit-identical to the kernel under the
    Pallas interpreter (``REPRO_WE_ROUNDS_MODE=interpret``), so the
    backend is always selectable; the kernel wins on TPU where the jax
    backend is bit-generation-bound.

Backends are registered in ``SAMPLER_BACKENDS`` and selected per call
(``mc(..., backend="jax")``) or globally (``REPRO_SAMPLER_BACKEND=jax``);
the default is ``numpy``.  The grid contract returns flat per-run arrays
``(t_comp, iterations, n_comm)`` of length ``G * trials`` in
grid-major order; ``repro.core.schemes`` reshapes them into per-spec
``MCReport`` rows.  Backends also expose ``gamma_rows`` -- batched
``Gamma(shape) * scale`` over an ``(R, K)`` matrix in one call -- which
is what the batched MDS L-sweep draws through (``numpy`` is bit-identical
to the per-L loop; ``jax``/``pallas`` use their transform samplers), and
may expose ``one_shot_times`` -- a one-shot scheme's draws and per-trial
completion times in one device dispatch (``jax``/``pallas``; ``numpy``
leaves it unset and the schemes keep their exact host draw).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable, Dict, List, Literal, Optional, Tuple

import numpy as np

from repro.tracing import span

from .registry import Registry
from .assignment import (capped_proportional_assignment_batch,
                         largest_remainder_round_batch)
from .types import ExchangeConfig

ENV_VAR = "REPRO_SAMPLER_BACKEND"
DEFAULT_BACKEND = "numpy"

# (t_comp, iterations, n_comm), each shape (G * trials,), grid-major
GridArrays = Tuple[np.ndarray, np.ndarray, np.ndarray]
# backend contract: (lam (G, K), N, cfg, trials, rng,
#                    capped_mode: "carry"|"waterfill",
#                    rate_schedule: Optional[(G, R, K)]) -> GridArrays.
# rate_schedule is the optional per-exchange-round service-rate schedule
# (scenario drift): round r >= R holds the last row, assignment rates
# stay nominal (known) / estimated (unknown) -- only the realized
# service draws follow the schedule.  (Callable[...] because the last
# two parameters are keyword-or-defaulted; the registered backends are
# the normative signatures.)
WEGridFn = Callable[..., GridArrays]
# (shape_rows, scale_rows, rng) -> (R, K) Gamma(shape) * scale draws
GammaRowsFn = Callable[[np.ndarray, np.ndarray, np.random.Generator],
                       np.ndarray]
# (loads (G, K), scales (G, K), weights (G, K), need (G,), trials, rng)
#  -> (G * trials,) completion times of a one-shot scheme: per trial, the
#  earliest finish at which the finished workers' weights reach the need
OneShotFn = Callable[..., np.ndarray]


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SamplerBackend:
    """One RNG/compute backend behind the work-exchange MC pipeline.

    ``gamma_rows`` (optional) is the batched order-statistic primitive
    the MDS L-sweep draws through; backends that leave it ``None`` fall
    back to the exact numpy draw (``get_gamma_rows``), so any future
    backend gets the full scheme surface for free.  ``one_shot_times``
    (optional, same rule) computes the one-shot schemes' per-trial
    completion times (``fixed``, ``uniform``, ``het_mds``, ``hedged``)
    in one dispatch; left ``None``, those schemes draw on the host.

    ``coupled_mds_sweep`` opts the backend into the common-random-numbers
    L-sweep: candidate Erlangs built as cumulative Gamma *increments*
    over one shared trial axis, which stabilizes exactly the mean
    differences the argmin needs, so half the sweep trials match the
    independent sweep's selection accuracy (the winner's reported samples
    always come from an independent exact-marginal top-up draw).  Exact
    backends leave it False to stay bit-identical to the per-L loop.
    """

    name: str
    work_exchange_grid: WEGridFn
    description: str = ""
    gamma_rows: Optional[GammaRowsFn] = None
    one_shot_times: Optional[OneShotFn] = None
    coupled_mds_sweep: bool = False
    # fused whole-panel dispatch for the work-exchange known/unknown pair:
    # (lam (G, K), N, cfg_known, cfg_unknown, trials, rng,
    #  rate_schedule=None) -> {"known": GridArrays, "unknown": GridArrays}.
    # Backends that leave it None run the pair as two grid dispatches.
    work_exchange_panel: Optional[Callable] = None

    def available(self) -> bool:
        return _BACKEND_AVAILABLE.get(self.name, lambda: True)()


SAMPLER_BACKENDS: Registry[SamplerBackend] = Registry("sampler backend")
_BACKEND_AVAILABLE: Dict[str, Callable[[], bool]] = {}


def register_backend(backend: SamplerBackend,
                     available: Callable[[], bool] = lambda: True) -> None:
    SAMPLER_BACKENDS.register(backend.name, backend)
    _BACKEND_AVAILABLE[backend.name] = available


def list_backends() -> List[str]:
    return SAMPLER_BACKENDS.names()


def get_backend(name: str) -> SamplerBackend:
    return SAMPLER_BACKENDS.get(name)


def resolve_backend(backend: str | None = None) -> str:
    """Explicit kwarg > ``REPRO_SAMPLER_BACKEND`` > ``numpy`` default."""
    name = backend or os.environ.get(ENV_VAR) or DEFAULT_BACKEND
    b = get_backend(name)      # raises on unknown names, env or kwarg
    if not b.available():
        raise RuntimeError(
            f"sampler backend {name!r} is registered but unavailable "
            f"(is its runtime installed?); set {ENV_VAR} or pass "
            f"backend= one of {[n for n in list_backends() if get_backend(n).available()]}")
    return name


def validate_backend(backend: str | None = None) -> str:
    """Fail fast on unknown backend names without requiring availability.

    Every ``Scheme.mc``/``mc_grid`` entry point calls this, including
    schemes that never draw through a backend, so a typo in ``backend=``
    or ``REPRO_SAMPLER_BACKEND`` raises a ``KeyError`` listing the
    registered backends instead of being silently ignored (or surfacing
    later as an opaque attribute error)."""
    name = backend or os.environ.get(ENV_VAR) or DEFAULT_BACKEND
    get_backend(name)          # KeyError with the registered list
    return name


def get_gamma_rows(name: str) -> GammaRowsFn:
    """The backend's batched Gamma-rows primitive (numpy fallback)."""
    fn = get_backend(name).gamma_rows
    return fn if fn is not None else gamma_rows_numpy


# ---------------------------------------------------------------------------
# multi-device grid sharding (the experiment engine's scale layer)
# ---------------------------------------------------------------------------

_GRID_MESH: List[Optional[object]] = [None]   # active jax Mesh, or None


@contextlib.contextmanager
def grid_sharding(devices: Optional[int] = None):
    """Shard backend grid dispatches across devices inside the context.

    Builds a 1-D ``'grid'`` mesh (``repro.distributed.sharding.grid_mesh``)
    over up to ``devices`` devices (None = all) and routes the ``jax`` and
    ``pallas`` ``work_exchange_grid`` calls through a ``shard_map``
    executor that splits the scenario x trials batch rows across it --
    each device runs an independent round pipeline on its own key stream
    (embarrassingly parallel, no collectives).  The ``numpy`` backend is
    untouched: it stays the bit-exact single-device oracle.  With one
    device the context is a no-op, so callers can wrap unconditionally.
    """
    from repro.distributed.sharding import grid_mesh
    mesh = grid_mesh(devices)
    prev = _GRID_MESH[0]
    _GRID_MESH[0] = mesh if mesh.size > 1 else None
    try:
        yield mesh
    finally:
        _GRID_MESH[0] = prev


def active_grid_mesh():
    """The Mesh installed by ``grid_sharding``, or None outside it."""
    return _GRID_MESH[0]


# ---------------------------------------------------------------------------
# numpy backend: exact integer-unit engine, generalized to per-row rates
# ---------------------------------------------------------------------------

def work_exchange_grid_numpy(lam: np.ndarray, N: int, cfg: ExchangeConfig,
                             trials: int, rng: np.random.Generator,
                             capped_mode: Literal["carry", "waterfill"]
                             = "carry",
                             rate_schedule: Optional[np.ndarray] = None
                             ) -> GridArrays:
    """Exact batched engine over a ``(G, K)`` heterogeneity grid.

    Every row of the ``(G * trials, K)`` state is one independent run of
    Algorithm 1/3; rows are grid-major (``g * trials + t``).  With
    ``G == 1`` the randomness is consumed in exactly the order of the
    PR-1 trial-batched engine (and hence, at ``trials == 1``, of the
    scalar reference) -- the bit-identity the tests pin down.

    ``rate_schedule`` (optional, ``(G, R, K)``) drives scenario drift:
    the service draws of exchange round ``r`` use row ``min(r, R - 1)``
    of the point's schedule while the *assignment* keeps using the
    nominal ``lam`` (known) or the online estimate (unknown), exactly
    the scheduler-sees-nominal / reality-drifts split of the drifting
    and trace-corpus scenario families.  With ``rate_schedule=None``
    this path is byte-for-byte the stationary engine.
    """
    lam = np.asarray(lam, dtype=np.float64)
    if lam.ndim != 2:
        raise ValueError(f"lam must be (G, K); got shape {lam.shape}")
    G, K = lam.shape
    T = int(trials)
    B = G * T
    known = cfg.known_heterogeneity
    threshold = cfg.threshold_frac * N / K
    cap = (np.inf if cfg.storage_cap_frac is None or known
           else int(np.ceil(cfg.storage_cap_frac * N / K)))
    lam_rows = np.repeat(lam, T, axis=0)          # (B, K), grid-major
    inv_lam = 1.0 / lam_rows
    inv_sched = None
    if rate_schedule is not None:
        sched = np.asarray(rate_schedule, dtype=np.float64)
        if sched.ndim != 3 or sched.shape[0] != G or sched.shape[2] != K:
            raise ValueError(f"rate_schedule must be (G={G}, R, K={K}); "
                             f"got shape {sched.shape}")
        inv_sched = 1.0 / np.repeat(sched, T, axis=0)   # (B, R, K)

    est_done = np.zeros((B, K))
    est_time = np.zeros(B)
    lam_hat = np.ones((B, K))
    n_rem = np.full(B, N, dtype=np.int64)
    n_left_prev = np.zeros((B, K), dtype=np.int64)
    n_done = np.zeros((B, K), dtype=np.int64)
    t_comp = np.zeros(B)
    n_comm = np.zeros(B)
    iters = np.zeros(B, dtype=np.int64)
    in_loop = np.ones(B, dtype=bool)

    while True:
        # compact every pass to the runs still above the threshold; row
        # order is ascending, so a lone run draws in exactly the scalar
        # order and the tail of long-running runs stays cheap
        in_loop &= (n_rem > threshold) & (iters < cfg.max_iterations)
        idx = np.flatnonzero(in_loop)
        if idx.size == 0:
            break
        n = idx.size
        rates = lam_rows[idx] if known else lam_hat[idx]
        rem = n_rem[idx]
        if np.isinf(cap):
            assign = largest_remainder_round_batch(rates, rem)
        elif capped_mode == "waterfill":
            assign = capped_proportional_assignment_batch(rates, rem, cap)
        else:
            assign = np.minimum(largest_remainder_round_batch(rates, rem),
                                cap)
        assigned = assign.sum(axis=1)
        carried = rem - assigned
        # degenerate rounding: that run leaves the loop without drawing
        live = assigned > 0
        if not live.all():
            in_loop[idx[~live]] = False
            idx, assign, carried = idx[live], assign[live], carried[live]
            n = idx.size
            if n == 0:
                break

        started = iters[idx] > 0
        comm_add = np.maximum(assign - n_left_prev[idx], 0).sum(axis=1)
        n_comm[idx] += np.where(started, comm_add, 0.0)

        # batched iteration outcome (same draw order as the scalar path)
        if inv_sched is None:
            scale = inv_lam[idx]
        else:        # service rates of THIS round (clamped to the last row)
            r_idx = np.minimum(iters[idx], inv_sched.shape[1] - 1)
            scale = inv_sched[idx, r_idx]
        busy = assign > 0
        if busy.all():      # the common case: draw the full matrix directly
            t_k = rng.gamma(shape=assign, scale=scale)
        else:
            t_k = np.full((n, K), np.inf)
            t_k[busy] = rng.gamma(shape=assign[busy], scale=scale[busy])
        finisher = np.argmin(t_k, axis=1)
        rows = np.arange(n)
        t_star = t_k[rows, finisher]
        done = np.zeros((n, K), dtype=np.int64)
        done[rows, finisher] = assign[rows, finisher]
        others = busy.copy()
        others[rows, finisher] = False
        o_rows, o_cols = np.nonzero(others)      # C order == scalar draw order
        if o_rows.size:
            n_oth = np.maximum(assign[o_rows, o_cols] - 1, 0)
            p_oth = np.clip(t_star[o_rows] / t_k[o_rows, o_cols], 0.0, 1.0)
            done[o_rows, o_cols] = rng.binomial(n_oth, p_oth)

        iters[idx] += 1
        t_comp[idx] += t_star
        n_done[idx] += done
        leftover = assign - done
        n_left_prev[idx] = leftover
        n_rem[idx] = carried + leftover.sum(axis=1)
        if not known:        # online estimate, eq. (23)
            ed = est_done[idx] + done
            et = est_time[idx] + t_star
            est_done[idx] = ed
            est_time[idx] = et
            lam_hat[idx] = np.where(ed > 0,
                                    ed / np.maximum(et, 1e-300)[:, None], 1.0)

    # final phase below the threshold: assign the remainder, wait for all
    idx = np.flatnonzero(n_rem > 0)
    if idx.size:
        n = idx.size
        rates = lam_rows[idx] if known else lam_hat[idx]
        assign = largest_remainder_round_batch(rates, n_rem[idx])
        comm_add = np.maximum(assign - n_left_prev[idx], 0).sum(axis=1)
        n_comm[idx] += np.where(iters[idx] > 0, comm_add, 0.0)
        if inv_sched is None:
            scale = inv_lam[idx]
        else:
            r_idx = np.minimum(iters[idx], inv_sched.shape[1] - 1)
            scale = inv_sched[idx, r_idx]
        busy = assign > 0
        if busy.all():
            t_k = rng.gamma(shape=assign, scale=scale)
        else:
            t_k = np.zeros((n, K))
            t_k[busy] = rng.gamma(shape=assign[busy], scale=scale[busy])
        t_comp[idx] += t_k.max(axis=1)
        n_done[idx] += assign
        iters[idx] += 1

    totals = n_done.sum(axis=1)
    if not (totals == N).all():
        bad = int(np.flatnonzero(totals != N)[0])
        raise AssertionError(f"work conservation violated in run {bad}: "
                             f"processed {int(totals[bad])} of {N}")
    return t_comp, iters.astype(np.float64), n_comm


def gamma_rows_numpy(shape_rows: np.ndarray, scale_rows: np.ndarray,
                     rng: np.random.Generator) -> np.ndarray:
    """Exact ``Generator.gamma`` over an ``(R, K)`` matrix in one call.

    ``shape_rows`` and ``scale_rows`` broadcast against each other (e.g.
    an ``(R, 1)`` shape column against ``(R, K)`` scales).  With rows
    laid out L-major this consumes randomness in exactly the order of
    the PR-2 per-L sweep loop (``Generator.gamma`` fills the broadcast
    output element by element in C order whether the shape argument is
    scalar or array), which is what makes the batched MDS sweep
    bit-identical to the loop.
    """
    shape_rows = np.asarray(shape_rows, dtype=np.float64)
    out_shape = np.broadcast_shapes(shape_rows.shape,
                                    np.asarray(scale_rows).shape)
    if len(out_shape) != 2:
        raise ValueError(f"shape/scale rows must broadcast to (R, K); "
                         f"got {out_shape}")
    return rng.gamma(shape=shape_rows, scale=scale_rows)


# ---------------------------------------------------------------------------
# jax backend: one jitted fluid-relaxation pipeline
# ---------------------------------------------------------------------------

def _jax_available() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:
        return False


_JAX_TX = None               # transform-sampler namespace, built once
_JAX_ENGINES: Dict[bool, Callable] = {}   # drift? -> jitted engine


def _jax_transforms():
    """The fluid-relaxation transform samplers, shared by the fused
    engine and the batched MDS ``gamma_rows`` path (lazy jax import)."""
    global _JAX_TX
    if _JAX_TX is not None:
        return _JAX_TX
    import types

    import jax
    import jax.numpy as jnp

    def gamma_mt_large(key, alpha, inv_rate):
        """Raw Marsaglia-Tsang transform d*(1 + Z/(3 sqrt(d)))^3 with
        d = alpha - 1/3: mean-exact, variance alpha + 1/9, for alpha >= 3
        (there the rejection step it omits accepts with prob > 99.8% and
        the cube-root argument goes negative with prob < 2e-7)."""
        d = alpha - 1.0 / 3.0
        z = jax.random.normal(key, alpha.shape)
        c = jnp.maximum(1.0 + z / (3.0 * jnp.sqrt(d)), 0.0)
        return d * c ** 3 * inv_rate

    def _boosted(key, alpha, inv_rate, levels):
        """Boost sub-3 shapes through the exact identity
        Gamma(a) = Gamma(a+1) * U^(1/a), chained ``levels`` times, so the
        MT transform always runs at shape alpha + levels (>= 3 whenever
        alpha >= 3 - levels).  The chained mean telescopes exactly:
        (alpha + levels) * alpha/(alpha + levels) = alpha."""
        kz, ku = jax.random.split(key)
        boost = alpha < 3.0
        a = jnp.where(boost, alpha + levels, alpha)
        u = jax.random.uniform(ku, (levels,) + alpha.shape, minval=1e-12)
        inv_shapes = jnp.stack([1.0 / jnp.maximum(alpha + i, 1e-12)
                                for i in range(levels)])
        pow_u = jnp.exp((jnp.log(u) * inv_shapes).sum(0))
        return gamma_mt_large(kz, a, inv_rate) * jnp.where(boost, pow_u, 1.0)

    def gamma_mt_boost2(key, alpha, inv_rate):
        """Mean-exact for alpha >= 1 (callers mask smaller elements)."""
        return _boosted(key, alpha, inv_rate, 2)

    def gamma_mt(key, alpha, inv_rate):
        """Mean-exact MT transform sampler for any alpha > 0."""
        return _boosted(key, alpha, inv_rate, 3)

    def binomial_normal(key, n, p):
        """Binomial(n, p) in its mean/variance-exact normal limit (fluid
        done-counts stay real-valued; clipping to [0, n] is the only
        deviation and is negligible for the unit counts in play)."""
        mean = n * p
        std = jnp.sqrt(jnp.maximum(n * p * (1.0 - p), 0.0))
        z = jax.random.normal(key, n.shape)
        return jnp.clip(mean + z * std, 0.0, n)

    _JAX_TX = types.SimpleNamespace(
        gamma_mt_large=gamma_mt_large, gamma_mt_boost2=gamma_mt_boost2,
        gamma_mt=gamma_mt, binomial_normal=binomial_normal,
        gamma_mt_large_jit=jax.jit(gamma_mt_large),
        gamma_mt_jit=jax.jit(gamma_mt))
    return _JAX_TX


def _build_jax_engine(drift: bool = False):
    """Construct the jitted grid engine (imports jax lazily).

    ``drift=True`` builds the drifting-rates variant: an extra traced
    ``(B, R, K)`` schedule argument supplies each round's true service
    rates (row ``min(round, R - 1)``); the assignment shares keep using
    the nominal ``lam`` / online estimate.  ``drift=False`` compiles to
    exactly the stationary PR-4 engine (no schedule argument, no
    gathers).
    """
    import jax
    import jax.numpy as jnp

    tx = _jax_transforms()
    gamma_mt_large = tx.gamma_mt_large
    gamma_mt_boost2 = tx.gamma_mt_boost2
    gamma_mt = tx.gamma_mt
    binomial_normal = tx.binomial_normal

    def engine(key, lam, sched, n0, threshold, cap, known, max_iter):
        # ``known`` is STATIC: the known-heterogeneity engine compiles
        # with the whole online-estimator block dead-code-eliminated
        B, K = lam.shape
        inv_lam0 = 1.0 / lam
        lam_sum = lam.sum(1)
        # zero-rate columns are masked padding from the K-axis shape
        # buckets: the estimator must hold a zero estimate for them so
        # they are never assigned work (identical to ones without padding)
        prior = jnp.where(lam > 0.0, 1.0, 0.0)
        R = sched.shape[1] if drift else 1

        def inv_lam_at(iters):
            """1/rate in effect at each row's current round (per-row
            gather -- final phase only; the loop uses the scalar trip
            counter and one dynamic slice per round)."""
            if not drift:
                return inv_lam0
            r_idx = jnp.minimum(iters, R - 1)
            cur = jnp.take_along_axis(sched, r_idx[:, None, None],
                                      axis=1)[:, 0, :]
            return 1.0 / cur

        def cond(st):
            return st["active"].any()

        def body(st):
            key, kg, kb = jax.random.split(st["key"], 3)
            if drift:
                # every active row has proceeded on every prior trip, so
                # its round == the scalar trip counter: one row load
                # replaces the per-row take_along_axis gather (frozen
                # rows' stale reads are fully masked)
                r = jnp.minimum(st["round"], R - 1)
                inv_lam = 1.0 / jax.lax.dynamic_slice_in_dim(
                    sched, r, 1, axis=1)[:, 0, :]
            else:
                inv_lam = inv_lam0
            if known:
                share = lam * (st["n_rem"] / lam_sum)[:, None]
            else:
                rates = st["lam_hat"]
                share = rates * (st["n_rem"] / rates.sum(1))[:, None]
            assign = jnp.minimum(share, cap)
            # integer engine's "assign > 0" becomes "at least half a unit";
            # sub-half slivers are carried as leftover, and a round where
            # nothing reaches half a unit exits like degenerate rounding
            busy = assign > 0.5
            # tiered per-round gamma path keyed on the smallest live share:
            # >= 3 needs no boost (one normal, no uniforms), >= 1 a 2-chain
            # boost, only sub-unit rounds pay the full 3-chain -- the bit
            # stream is the engine's bottleneck, so draw no more than the
            # round's smallest shape requires
            live_min = jnp.where(busy & st["active"][:, None], assign,
                                 jnp.inf).min()
            t_raw = jax.lax.cond(
                live_min >= 3.0, gamma_mt_large,
                lambda k, a, i: jax.lax.cond(live_min >= 1.0,
                                             gamma_mt_boost2, gamma_mt,
                                             k, a, i),
                kg, jnp.maximum(assign, 0.5), inv_lam)
            t_k = jnp.where(busy, t_raw, jnp.inf)
            t_star = t_k.min(1)
            proceed = st["active"] & jnp.isfinite(t_star)
            fin = t_k == t_star[:, None]          # finisher clears its queue
            p = jnp.clip(t_star[:, None] / t_k, 0.0, 1.0)
            done = binomial_normal(kb, jnp.maximum(assign - 1.0, 0.0), p)
            done = jnp.where(fin, assign, jnp.where(busy, done, 0.0))
            # carried + leftover-sum telescopes: units either finish or stay
            # remaining, so conservation is structural
            n_rem = st["n_rem"] - done.sum(1)

            started = st["iters"] > 0
            comm = jnp.maximum(assign - st["n_left"], 0.0).sum(1)
            upd = lambda new, old: jnp.where(  # noqa: E731
                proceed if new.ndim == 1 else proceed[:, None], new, old)
            iters = st["iters"] + proceed
            n_rem_m = upd(n_rem, st["n_rem"])
            out = {
                "key": key,
                "n_rem": n_rem_m,
                "n_left": upd(assign - done, st["n_left"]),
                "t_comp": upd(st["t_comp"] + t_star, st["t_comp"]),
                "n_comm": upd(st["n_comm"] + jnp.where(started, comm, 0.0),
                              st["n_comm"]),
                "iters": iters,
                "active": proceed & (n_rem_m > threshold)
                          & (iters < max_iter),
            }
            if drift:
                out["round"] = st["round"] + jnp.int32(1)
            if not known:
                # est accumulators go unmasked -- frozen lanes only read
                # them through lam_hat, which IS masked
                ed = st["est_done"] + done
                et = st["est_time"] + t_star
                out["est_done"] = ed
                out["est_time"] = et
                out["lam_hat"] = upd(
                    jnp.where(ed > 0.0,
                              ed / jnp.maximum(et, 1e-30)[:, None], prior),
                    st["lam_hat"])
            return out

        st = {
            "key": key,
            "n_rem": jnp.full(B, n0),
            "n_left": jnp.zeros((B, K)),
            "t_comp": jnp.zeros(B),
            "n_comm": jnp.zeros(B),
            "iters": jnp.zeros(B, dtype=jnp.int32),
            "active": jnp.full(B, n0) > threshold,
        }
        if drift:
            st["round"] = jnp.int32(0)
        if not known:
            st.update(est_done=jnp.zeros((B, K)), est_time=jnp.zeros(B),
                      lam_hat=prior)
        st = jax.lax.while_loop(cond, body, st)

        # final phase: assign the remainder proportionally, wait for all
        kf = jax.random.split(st["key"])[0]
        has_rem = st["n_rem"] > 1e-6
        rates = lam if known else st["lam_hat"]
        inv_lam = inv_lam_at(st["iters"])
        share = rates * (st["n_rem"] / rates.sum(1))[:, None]
        comm = jnp.maximum(share - st["n_left"], 0.0).sum(1)
        t_k = jnp.where(share > 1e-9, gamma_mt(kf, share, inv_lam), 0.0)
        t_comp = st["t_comp"] + jnp.where(has_rem, t_k.max(1), 0.0)
        n_comm = st["n_comm"] + jnp.where(has_rem & (st["iters"] > 0),
                                          comm, 0.0)
        iters = st["iters"] + has_rem
        return t_comp, iters, n_comm

    if drift:
        return jax.jit(engine, static_argnames=("known",))

    def stationary(key, lam, n0, threshold, cap, known, max_iter):
        return engine(key, lam, None, n0, threshold, cap, known, max_iter)

    return jax.jit(stationary, static_argnames=("known",))


def _get_jax_engine(drift: bool = False):
    if drift not in _JAX_ENGINES:
        _JAX_ENGINES[drift] = _build_jax_engine(drift)
    return _JAX_ENGINES[drift]


_JAX_SHARDED: Dict[Tuple[object, bool], Callable] = {}   # (Mesh, drift?)


def _sharded_jax_engine(mesh, drift: bool = False):
    """Jitted shard_map wrapper of the fused engine, cached per mesh.

    Each device runs the whole ``lax.while_loop`` pipeline on its own
    block of batch rows with its own rbg key -- no collectives, so the
    shards never synchronize until the final gather.  The drift
    variant also shards the ``(B, R, K)`` rate schedule along the batch
    rows, so each device carries only its own rows' schedules.
    """
    if (mesh, drift) in _JAX_SHARDED:
        return _JAX_SHARDED[(mesh, drift)]
    import jax
    from jax.sharding import PartitionSpec

    eng = _get_jax_engine(drift)
    spec = PartitionSpec(mesh.axis_names[0])

    if drift:
        def sharded(keys, lam, sched, n0, threshold, cap, known, max_iter):
            def block(keys_b, lam_b, sched_b):
                return eng(keys_b[0], lam_b, sched_b, n0, threshold, cap,
                           known, max_iter)
            return jax.shard_map(block, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False)(keys, lam,
                                                                  sched)
    else:
        def sharded(keys, lam, n0, threshold, cap, known, max_iter):
            def block(keys_b, lam_b):
                return eng(keys_b[0], lam_b, n0, threshold, cap, known,
                           max_iter)
            return jax.shard_map(block, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False)(keys, lam)

    fn = jax.jit(sharded, static_argnames=("n0", "threshold", "cap",
                                           "known", "max_iter"))
    _JAX_SHARDED[(mesh, drift)] = fn
    return fn


def work_exchange_grid_jax(lam: np.ndarray, N: int, cfg: ExchangeConfig,
                           trials: int, rng: np.random.Generator,
                           capped_mode: Literal["carry", "waterfill"]
                           = "carry",
                           rate_schedule: Optional[np.ndarray] = None
                           ) -> GridArrays:
    """Fused fluid-relaxation engine: one device dispatch per grid call.

    The jitted function is cached per ``(G * trials, K)`` shape and
    known/unknown flag -- ``known`` is static so the known-heterogeneity
    engine compiles with the online-estimator block dead-code-eliminated
    (two compilations per shape bucket, each reused by every later call);
    threshold, cap and N stay traced.  The numpy ``rng`` only seeds the
    JAX key stream (one draw), keeping call sites generator-driven like
    every other scheme.  ``rate_schedule`` (``(G, R, K)``) selects the
    drift engine variant: per-round service rates follow the schedule
    while assignments stay nominal/estimated (same contract as the numpy
    backend, statistically -- not bitwise -- equivalent to it).
    """
    if capped_mode != "carry":
        raise ValueError(
            "the jax sampler backend implements the paper-faithful 'carry' "
            "storage mode only; use backend='numpy' for 'waterfill'")
    import jax

    lam = np.asarray(lam, dtype=np.float32)
    if lam.ndim != 2:
        raise ValueError(f"lam must be (G, K); got shape {lam.shape}")
    G, K = lam.shape
    known = cfg.known_heterogeneity
    # threshold / cap come from the REAL worker count; the K bucket below
    # only adds masked zero-rate columns
    threshold = cfg.threshold_frac * N / K
    cap = (np.inf if cfg.storage_cap_frac is None or known
           else float(np.ceil(cfg.storage_cap_frac * N / K)))
    lam_rows = np.repeat(_pad_cols(lam, bucket_cols(K)), int(trials),
                         axis=0)                         # (B, Kb), grid-major
    # pad the batch to a shape bucket (shared _pad_rows policy): jit
    # caches per shape, so fig5/fig6/fig7-sized grids land in a handful
    # of compilations per process instead of one per panel shape
    lam_rows, B = _pad_rows(lam_rows)
    drift = rate_schedule is not None
    sched_rows = None
    if drift:
        sched = np.asarray(rate_schedule, dtype=np.float32)
        if sched.ndim != 3 or sched.shape[0] != G or sched.shape[2] != K:
            raise ValueError(f"rate_schedule must be (G={G}, R, K={K}); "
                             f"got shape {sched.shape}")
        sched = _pad_sched(sched, bucket_rounds(sched.shape[1]),
                           bucket_cols(K))
        sched_rows = np.repeat(sched, int(trials), axis=0)
        sched_rows = _pad_rows_like(sched_rows, lam_rows.shape[0])
    mesh = active_grid_mesh()
    if mesh is not None:
        # sharded executor: one independent engine per device over its
        # block of rows, each on its own split of the key stream (NOT
        # bit-identical to the single-device jax path; statistically
        # equivalent -- the numpy oracle is the bit-exact reference)
        D = int(mesh.size)
        extra = (-lam_rows.shape[0]) % D
        if extra:
            lam_rows = np.concatenate(
                [lam_rows, np.repeat(lam_rows[:1], extra, axis=0)])
        keys = jax.random.split(
            jax.random.key(int(rng.integers(2 ** 63 - 1)), impl="rbg"), D)
        if drift:
            sched_rows = _pad_rows_like(sched_rows, lam_rows.shape[0])
            t, it, cm = _sharded_jax_engine(mesh, drift=True)(
                keys, lam_rows, sched_rows, float(N), float(threshold),
                cap, bool(known), int(cfg.max_iterations))
        else:
            t, it, cm = _sharded_jax_engine(mesh)(
                keys, lam_rows, float(N), float(threshold), cap,
                bool(known), int(cfg.max_iterations))
    else:
        # rbg keys: counter-based bit generation is ~3x faster than
        # threefry on CPU and ample for Monte Carlo
        key = jax.random.key(int(rng.integers(2 ** 63 - 1)), impl="rbg")
        if drift:
            t, it, cm = _get_jax_engine(drift=True)(
                key, lam_rows, sched_rows, float(N), float(threshold),
                cap, bool(known), int(cfg.max_iterations))
        else:
            t, it, cm = _get_jax_engine()(
                key, lam_rows, float(N), float(threshold), cap,
                bool(known), int(cfg.max_iterations))
    return (np.asarray(t, dtype=np.float64)[:B],
            np.asarray(it, dtype=np.float64)[:B],
            np.asarray(cm, dtype=np.float64)[:B])


def _rows_target(R: int, bucket: int = 64) -> int:
    """Batch-axis bucket: power-of-two (>= ``bucket``) up to 8192 rows,
    multiples of 8192 above (pow2 would waste up to 2x the draw work on
    panel-sized grids)."""
    if R > 8192:
        return -(-R // 8192) * 8192
    return max(bucket, 1 << (R - 1).bit_length())


def _shape_buckets_enabled() -> bool:
    return os.environ.get("REPRO_SHAPE_BUCKETS", "1").lower() not in (
        "0", "off", "false")


def bucket_cols(K: int) -> int:
    """Worker-axis (K) shape bucket: power-of-two up to 16 workers, then
    the next multiple of 8.  Padded columns carry ``lambda = 0`` and are
    fully masked (never busy, never assigned, estimator prior 0), so two
    panels whose K lands in the same bucket share one compilation -- and
    one persistent-cache entry -- instead of compiling per shape.
    ``REPRO_SHAPE_BUCKETS=0`` disables K/R bucketing (exact shapes, one
    compile per shape)."""
    if not _shape_buckets_enabled():
        return K
    if K <= 16:
        return 1 << max(K - 1, 0).bit_length()
    return -(-K // 8) * 8


def bucket_rounds(R: int) -> int:
    """Drift-schedule round-axis (R) bucket: power-of-two up to 16
    rounds, then the next multiple of 16.  Padding repeats the last
    schedule row, which is exactly the engines' round >= R clamp --
    value-preserving, not just masked."""
    if not _shape_buckets_enabled():
        return R
    if R <= 16:
        return 1 << max(R - 1, 0).bit_length()
    return -(-R // 16) * 16


def grid_bucket_shape(G: int, trials: int, K: int,
                      R: Optional[int] = None,
                      backend: Optional[str] = None) -> Dict[str, int]:
    """The padded ``(rows, K[, R])`` bucket a ``(G, trials, K[, R])``
    panel dispatches at -- the compile/persistent-cache key's shape part.
    Two panels with equal buckets (and equal static config) share one
    compilation and one persistent-cache entry."""
    bucket = 128 if resolve_backend(backend) == "pallas" else 64
    shape = {"rows": _rows_target(G * int(trials), bucket),
             "K": bucket_cols(K)}
    if R is not None:
        shape["R"] = bucket_rounds(R)
    return shape


def _pad_rows(rows: np.ndarray, bucket: int = 64) -> Tuple[np.ndarray, int]:
    """Pad the leading axis to its ``_rows_target`` bucket with copies of
    row 0, so jit caches land in a handful of compilations."""
    R = rows.shape[0]
    target = _rows_target(R, bucket)
    if target - R:
        rows = np.concatenate([rows, np.repeat(rows[:1], target - R,
                                               axis=0)])
    return rows, R


def _pad_cols(rows: np.ndarray, Kb: int) -> np.ndarray:
    """Zero-pad the trailing worker axis to the ``Kb`` bucket (masked
    columns: rate 0 means never busy, never assigned)."""
    K = rows.shape[-1]
    if Kb > K:
        rows = np.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, Kb - K)])
    return rows


def _pad_sched(sched: np.ndarray, Rb: int, Kb: int) -> np.ndarray:
    """Bucket-pad a ``(..., R, K)`` rate schedule: zero columns on the
    worker axis (masked), last-row repeats on the round axis (the
    round >= R clamp made explicit)."""
    R, K = sched.shape[-2], sched.shape[-1]
    if Kb > K:
        sched = _pad_cols(sched, Kb)
    if Rb > R:
        sched = np.concatenate(
            [sched, np.repeat(sched[..., -1:, :], Rb - R, axis=-2)],
            axis=-2)
    return sched


def _pad_rows_like(rows: np.ndarray, target: int) -> np.ndarray:
    """Pad the leading axis to an already-chosen target length with
    copies of row 0 (the schedule companion of ``_pad_rows``: schedule
    rows must stay aligned with the padded rate rows)."""
    extra = target - rows.shape[0]
    if extra > 0:
        rows = np.concatenate([rows, np.repeat(rows[:1], extra, axis=0)])
    return rows


def _pad_rows_to(rows: np.ndarray, R: int) -> np.ndarray:
    """Bucket-pad 2-D arrays whose leading axis carries the ``R``
    broadcast rows; leave size-1 leading axes and 1-D ``(K,)`` vectors
    (both pure-broadcast operands) untouched."""
    if rows.ndim == 2 and rows.shape[0] == R and R > 1:
        return _pad_rows(rows)[0]
    return rows


def _gamma_rows_prep(shape_rows: np.ndarray, scale_rows: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, int, bool]:
    """Shared gamma_rows prologue: float32 conversion, broadcast-shape
    validation, bucket padding of the row-carrying operands, and the
    static sub-3-shape (boost) flag.  Returns
    ``(padded_shape, padded_scale, R, boost)``."""
    shape_rows = np.asarray(shape_rows, dtype=np.float32)
    scale_rows = np.asarray(scale_rows, dtype=np.float32)
    out_shape = np.broadcast_shapes(shape_rows.shape, scale_rows.shape)
    if len(out_shape) != 2:
        raise ValueError(f"shape/scale rows must broadcast to (R, K); "
                         f"got {out_shape}")
    R = out_shape[0]
    return (_pad_rows_to(shape_rows, R),
            _pad_rows_to(np.ascontiguousarray(scale_rows), R),
            R, bool((shape_rows < 3.0).any()))


_JAX_GAMMA_ROWS = None


def gamma_rows_jax(shape_rows: np.ndarray, scale_rows: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """Batched MT-transform Gammas in one jitted dispatch (mean-exact;
    the boost chain compiles in only when some shape is below 3).

    ``shape_rows``/``scale_rows`` broadcast against each other -- compact
    ``(R, 1)`` shape columns stay compact until the kernel, where the
    normal draw materializes the full broadcast shape.  The numpy ``rng``
    only seeds the key stream; output is float32 (the fluid pipeline's
    dtype), which callers may sort/average as-is.
    """
    global _JAX_GAMMA_ROWS
    import jax

    padded_shape, padded_scale, R, boost = _gamma_rows_prep(shape_rows,
                                                            scale_rows)
    if _JAX_GAMMA_ROWS is None:
        import functools

        import jax.numpy as jnp
        tx = _jax_transforms()

        def kernel(key, alpha, scale, boost):
            out = jnp.broadcast_shapes(alpha.shape, scale.shape)
            alpha = jnp.broadcast_to(alpha, out)
            fn = tx.gamma_mt if boost else tx.gamma_mt_large
            return fn(key, alpha, scale)

        _JAX_GAMMA_ROWS = jax.jit(kernel, static_argnames=("boost",))
    key = jax.random.key(int(rng.integers(2 ** 63 - 1)), impl="rbg")
    out = np.asarray(_JAX_GAMMA_ROWS(key, padded_shape, padded_scale,
                                     boost))[:R]
    return np.array(out)      # own the memory: callers sort in place


# ---------------------------------------------------------------------------
# pallas backend: the fused we_rounds kernel (repro.kernels.we_rounds)
# ---------------------------------------------------------------------------

def work_exchange_grid_pallas(lam: np.ndarray, N: int, cfg: ExchangeConfig,
                              trials: int, rng: np.random.Generator,
                              capped_mode: Literal["carry", "waterfill"]
                              = "carry",
                              rate_schedule: Optional[np.ndarray] = None
                              ) -> GridArrays:
    """One fused Pallas pass over the ``(G * trials, K)`` grid.

    Same fluid relaxation as the ``jax`` backend but with counter-based
    Threefry bits generated *inside* the kernel, so the whole round
    pipeline -- bit generation included -- is one tiled device pass.  On
    CPU hosts the jnp reference (bit-identical to the interpreted kernel,
    ``REPRO_WE_ROUNDS_MODE=interpret``) runs instead; see
    ``repro.kernels.we_rounds.ops``.  The numpy ``rng`` only seeds the
    Threefry key (one draw), keeping call sites generator-driven.
    """
    if capped_mode != "carry":
        raise ValueError(
            "the pallas sampler backend implements the paper-faithful "
            "'carry' storage mode only; use backend='numpy' for "
            "'waterfill'")
    from repro.kernels.we_rounds import we_rounds_grid

    lam = np.asarray(lam, dtype=np.float32)
    if lam.ndim != 2:
        raise ValueError(f"lam must be (G, K); got shape {lam.shape}")
    K = lam.shape[1]
    known = cfg.known_heterogeneity
    # real-K scalars first; the K bucket only adds masked zero columns
    # (note the Threefry counter namespace is keyed by the padded K, so
    # bucketed and unbucketed runs are different -- equally valid --
    # bit streams; interpret/reference stay bit-identical at the padded
    # layout)
    threshold = cfg.threshold_frac * N / K
    cap = (np.inf if cfg.storage_cap_frac is None or known
           else float(np.ceil(cfg.storage_cap_frac * N / K)))
    G = lam.shape[0]
    with span("repro.we_rounds.rows"):
        lam_rows = np.repeat(_pad_cols(lam, bucket_cols(K)), int(trials),
                             axis=0)                     # (B, Kb), grid-major
        # power-of-two bucket >= 128 (the kernel's tallest tile; every
        # height ``tile_rows`` picks divides it): panel-sized
        # grids share a handful of compilations per process, and the bucket
        # is always a whole number of tiles
        lam_rows, B = _pad_rows(lam_rows, bucket=128)
        sched_rows = None
        if rate_schedule is not None:
            sched = np.asarray(rate_schedule, dtype=np.float32)
            if sched.ndim != 3 or sched.shape[0] != G or sched.shape[2] != K:
                raise ValueError(f"rate_schedule must be (G={G}, R, K={K}); "
                                 f"got shape {sched.shape}")
            sched = _pad_sched(sched, bucket_rounds(sched.shape[1]),
                               bucket_cols(K))
            sched_rows = _pad_rows_like(np.repeat(sched, int(trials), axis=0),
                                        lam_rows.shape[0])
    mesh = active_grid_mesh()
    if mesh is not None:
        # sharded executor: one independent seed pair per device (each
        # shard keys its Threefry counters from its own seed row)
        seed = rng.integers(0, 2 ** 32, size=(int(mesh.size), 2),
                            dtype=np.uint32)
    else:
        seed = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
    t, it, cm = we_rounds_grid(lam_rows, seed, n0=float(N),
                               threshold=float(threshold), cap=cap,
                               known=bool(known),
                               max_iter=int(cfg.max_iterations), mesh=mesh,
                               rate_schedule=sched_rows, real_rows=B)
    return t[:B], it[:B], cm[:B]


def gamma_rows_pallas(shape_rows: np.ndarray, scale_rows: np.ndarray,
                      rng: np.random.Generator) -> np.ndarray:
    """Counter-based Threefry + MT-transform Gamma rows (one dispatch;
    ``shape_rows``/``scale_rows`` broadcast like the other backends)."""
    from repro.kernels.we_rounds import gamma_rows_grid

    padded_shape, padded_scale, R, _ = _gamma_rows_prep(shape_rows,
                                                        scale_rows)
    seed = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
    out = gamma_rows_grid(padded_shape, padded_scale, seed)[:R]
    return np.array(out)      # own the memory: callers sort in place


def one_shot_times_device(loads: np.ndarray, scales: np.ndarray,
                          weights: np.ndarray, need: np.ndarray, trials: int,
                          rng: np.random.Generator) -> np.ndarray:
    """One-shot completion times, ``(G * trials,)`` float32, in one
    device dispatch (``repro.kernels.we_rounds.one_shot_grid``: the
    counter-based Threefry + MT-transform Gamma of ``gamma_rows_pallas``,
    then each trial's weighted cover time).  The numpy ``rng`` only
    seeds the Threefry key (one draw)."""
    from repro.kernels.we_rounds import one_shot_grid

    seed = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
    return one_shot_grid(loads, scales, weights, need, trials, seed)


# ---------------------------------------------------------------------------
# fused whole-panel dispatch: the work-exchange pair in one engine
# ---------------------------------------------------------------------------
#
# A figure's per-scheme loop dispatches the known and the unknown
# work-exchange engines separately, even though both simulate the same
# trials at the same rates.  The panel path fuses them:
#
# * **coupled common random numbers** -- both schemes' trajectories of one
#   trial live in ONE state row and share one bit stream per round (one
#   Gamma normal + tier uniforms + one Binomial normal, transformed at
#   each scheme's own shapes).  Each scheme's marginal distribution is
#   exactly the per-scheme engine's; the positive coupling only stabilizes
#   scheme *differences* (a variance reduction, like the MDS CRN sweep).
# * **straggler compaction** -- the engine runs in short chunks of rounds
#   (``REPRO_PANEL_CHUNK``, default 4); between chunks the host drops
#   finished rows to the next power-of-two bucket, running their final
#   phase immediately.  Late rounds then cost the few surviving stragglers
#   instead of the whole batch -- total work tracks the *mean* round
#   count, the numpy engine's own compaction trick, applied panel-wide.
#
# The numbers come from one stream, so panel results differ from (while
# being statistically equivalent to) the per-scheme dispatches; the
# cross-backend conformance battery pins both against the numpy oracle.

PANEL_CHUNK_ENV = "REPRO_PANEL_CHUNK"


def _panel_chunk() -> int:
    return max(1, int(os.environ.get(PANEL_CHUNK_ENV, "4")))


def _panel_pair_check(cfg_known: ExchangeConfig,
                      cfg_unknown: ExchangeConfig) -> None:
    if (not cfg_known.known_heterogeneity
            or cfg_unknown.known_heterogeneity):
        raise ValueError("panel fusion takes the (known, unknown) "
                         "work-exchange config pair, in that order")
    if (cfg_known.threshold_frac != cfg_unknown.threshold_frac
            or cfg_known.max_iterations != cfg_unknown.max_iterations):
        raise ValueError("panel fusion requires the pair to share "
                         "threshold_frac and max_iterations")


_JAX_PANEL: Dict[bool, Dict[str, Callable]] = {}   # drift? -> stage/final


def _build_jax_panel(drift: bool = False) -> Dict[str, Callable]:
    """The coupled pair engine: a resumable ``stage`` (runs rounds up to a
    traced stop counter, so the host can compact between chunks) and the
    shared-bits ``final`` phase."""
    import jax
    import jax.numpy as jnp

    def pair_gamma(key, a_k, a_u, inv_rate, live_min):
        """One raw bit draw (a normal + the tier's boost uniforms),
        transformed through the mean-exact MT formula at BOTH schemes'
        shapes -- the CRN coupling.  The tier comes from the *joint*
        smallest live share, which is never above either scheme's own, so
        each marginal stays exactly the per-scheme engine's relaxation."""
        kz, ku = jax.random.split(key)
        z = jax.random.normal(kz, a_k.shape)

        def mt_large_z(alpha):
            d = alpha - 1.0 / 3.0
            c = jnp.maximum(1.0 + z / (3.0 * jnp.sqrt(d)), 0.0)
            return d * c ** 3 * inv_rate

        def boosted_z(alpha, lu):
            levels = lu.shape[0]
            boost = alpha < 3.0
            a = jnp.where(boost, alpha + levels, alpha)
            inv_shapes = jnp.stack([1.0 / jnp.maximum(alpha + i, 1e-12)
                                    for i in range(levels)])
            pow_u = jnp.exp((lu * inv_shapes).sum(0))
            return mt_large_z(a) * jnp.where(boost, pow_u, 1.0)

        def tier_large():
            return mt_large_z(a_k), mt_large_z(a_u)

        def tier(levels):
            def draw():
                lu = jnp.log(jax.random.uniform(
                    ku, (levels,) + a_k.shape, minval=1e-12))
                return boosted_z(a_k, lu), boosted_z(a_u, lu)
            return draw

        return jax.lax.cond(
            live_min >= 3.0, tier_large,
            lambda: jax.lax.cond(live_min >= 1.0, tier(2), tier(3)))

    def _stage(st, lam, sched_chunk, round0, round_stop, threshold, cap_u,
               max_iter):
        B, K = lam.shape
        inv_lam0 = jnp.where(lam > 0.0, 1.0 / lam, 0.0)
        lam_sum = lam.sum(1)
        prior = jnp.where(lam > 0.0, 1.0, 0.0)
        CH = sched_chunk.shape[1] if drift else 1

        def cond(s):
            return ((s["round"] < round_stop)
                    & (s["active_k"] | s["active_u"]).any())

        def body(s):
            key, kg, kb = jax.random.split(s["key"], 3)
            if drift:
                # the chunk schedule is host-sliced so row j is global
                # round round0 + j; all active rows share the scalar trip
                # counter (iters == round), same argument as the
                # per-scheme drift engines
                j = jnp.clip(s["round"] - round0, 0, CH - 1)
                inv_lam = 1.0 / jax.lax.dynamic_slice_in_dim(
                    sched_chunk, j, 1, axis=1)[:, 0, :]
            else:
                inv_lam = inv_lam0
            share_k = lam * (s["n_rem_k"] / lam_sum)[:, None]
            rates_u = s["lam_hat"]
            share_u = rates_u * (s["n_rem_u"] / rates_u.sum(1))[:, None]
            assign_u = jnp.minimum(share_u, cap_u)
            busy_k = share_k > 0.5
            busy_u = assign_u > 0.5
            live = lambda a, b, act: jnp.where(       # noqa: E731
                b & act[:, None], a, jnp.inf)
            live_min = jnp.minimum(
                live(share_k, busy_k, s["active_k"]).min(),
                live(assign_u, busy_u, s["active_u"]).min())
            t_raw_k, t_raw_u = pair_gamma(
                kg, jnp.maximum(share_k, 0.5), jnp.maximum(assign_u, 0.5),
                inv_lam, live_min)
            z_b = jax.random.normal(kb, (B, K))
            out = {"key": key, "round": s["round"] + jnp.int32(1)}

            def branch(sfx, assign, busy, t_raw):
                """One scheme's round update off the shared bits -- the
                same arithmetic as the per-scheme engine body."""
                t_k = jnp.where(busy, t_raw, jnp.inf)
                t_star = t_k.min(1)
                proceed = s["active_" + sfx] & jnp.isfinite(t_star)
                fin = t_k == t_star[:, None]
                p = jnp.clip(t_star[:, None] / t_k, 0.0, 1.0)
                n = jnp.maximum(assign - 1.0, 0.0)
                done = jnp.clip(n * p + z_b * jnp.sqrt(
                    jnp.maximum(n * p * (1.0 - p), 0.0)), 0.0, n)
                done = jnp.where(fin, assign, jnp.where(busy, done, 0.0))
                n_rem = s["n_rem_" + sfx] - done.sum(1)
                started = s["iters_" + sfx] > 0
                comm = jnp.maximum(assign - s["n_left_" + sfx], 0.0).sum(1)
                upd = lambda new, old: jnp.where(     # noqa: E731
                    proceed if new.ndim == 1 else proceed[:, None],
                    new, old)
                iters = s["iters_" + sfx] + proceed
                n_rem_m = upd(n_rem, s["n_rem_" + sfx])
                out["n_rem_" + sfx] = n_rem_m
                out["n_left_" + sfx] = upd(assign - done,
                                           s["n_left_" + sfx])
                out["t_comp_" + sfx] = upd(s["t_comp_" + sfx] + t_star,
                                           s["t_comp_" + sfx])
                out["n_comm_" + sfx] = upd(
                    s["n_comm_" + sfx] + jnp.where(started, comm, 0.0),
                    s["n_comm_" + sfx])
                out["iters_" + sfx] = iters
                out["active_" + sfx] = (proceed & (n_rem_m > threshold)
                                        & (iters < max_iter))
                return done, t_star, upd

            branch("k", share_k, busy_k, t_raw_k)
            done_u, t_star_u, upd_u = branch("u", assign_u, busy_u,
                                             t_raw_u)
            ed = s["est_done"] + done_u
            et = s["est_time"] + t_star_u
            out["est_done"] = ed
            out["est_time"] = et
            out["lam_hat"] = upd_u(
                jnp.where(ed > 0.0, ed / jnp.maximum(et, 1e-30)[:, None],
                          prior),
                s["lam_hat"])
            return out

        return jax.lax.while_loop(cond, body, st)

    def _final(key, lam, inv_k, inv_u, st):
        """Both final phases off one shared raw draw (z + 3 boost
        uniforms, the full 3-chain as in the per-scheme final)."""
        kz, ku = jax.random.split(key)
        z = jax.random.normal(kz, lam.shape)
        lu = jnp.log(jax.random.uniform(ku, (3,) + lam.shape,
                                        minval=1e-12))

        def g(alpha, inv_rate):
            boost = alpha < 3.0
            a = jnp.where(boost, alpha + 3.0, alpha)
            d = a - 1.0 / 3.0
            c = jnp.maximum(1.0 + z / (3.0 * jnp.sqrt(d)), 0.0)
            inv_shapes = jnp.stack([1.0 / jnp.maximum(alpha + i, 1e-12)
                                    for i in range(3)])
            pow_u = jnp.exp((lu * inv_shapes).sum(0))
            return d * c ** 3 * inv_rate * jnp.where(boost, pow_u, 1.0)

        def fin(sfx, rates, inv_lam):
            has_rem = st["n_rem_" + sfx] > 1e-6
            share = rates * (st["n_rem_" + sfx]
                             / rates.sum(1))[:, None]
            comm = jnp.maximum(share - st["n_left_" + sfx], 0.0).sum(1)
            t_k = jnp.where(share > 1e-9,
                            g(jnp.maximum(share, 1e-9), inv_lam), 0.0)
            t_comp = st["t_comp_" + sfx] + jnp.where(has_rem, t_k.max(1),
                                                     0.0)
            n_comm = st["n_comm_" + sfx] + jnp.where(
                has_rem & (st["iters_" + sfx] > 0), comm, 0.0)
            iters = st["iters_" + sfx] + has_rem
            return t_comp, iters.astype(jnp.float32), n_comm

        return fin("k", lam, inv_k) + fin("u", st["lam_hat"], inv_u)

    if drift:
        stage = jax.jit(_stage)
    else:
        stage = jax.jit(
            lambda st, lam, round_stop, threshold, cap_u, max_iter:
            _stage(st, lam, None, 0, round_stop, threshold, cap_u,
                   max_iter))
    return {"stage": stage, "final": jax.jit(_final)}


def _get_jax_panel(drift: bool = False) -> Dict[str, Callable]:
    if drift not in _JAX_PANEL:
        _JAX_PANEL[drift] = _build_jax_panel(drift)
    return _JAX_PANEL[drift]


def work_exchange_panel_jax(lam: np.ndarray, N: int,
                            cfg_known: ExchangeConfig,
                            cfg_unknown: ExchangeConfig,
                            trials: int, rng: np.random.Generator,
                            rate_schedule: Optional[np.ndarray] = None
                            ) -> Dict[str, GridArrays]:
    """The work-exchange pair over a whole ``(G, K)`` panel in one fused
    engine (coupled CRN rounds + host-side straggler compaction; see the
    section comment).  Returns ``{"known": (t, it, cm), "unknown": ...}``
    in the usual grid-major layout."""
    import jax
    import jax.numpy as jnp

    _panel_pair_check(cfg_known, cfg_unknown)
    lam = np.asarray(lam, dtype=np.float32)
    if lam.ndim != 2:
        raise ValueError(f"lam must be (G, K); got shape {lam.shape}")
    G, K = lam.shape
    N = float(N)
    threshold = cfg_known.threshold_frac * N / K
    cap_u = (np.inf if cfg_unknown.storage_cap_frac is None
             else float(np.ceil(cfg_unknown.storage_cap_frac * N / K)))
    max_iter = int(cfg_known.max_iterations)
    lam_rows = np.repeat(_pad_cols(lam, bucket_cols(K)), int(trials),
                         axis=0)
    lam_rows, B = _pad_rows(lam_rows)
    Bp, Kb = lam_rows.shape
    drift = rate_schedule is not None
    sched_np = R = None
    if drift:
        sched = np.asarray(rate_schedule, dtype=np.float32)
        if sched.ndim != 3 or sched.shape[0] != G or sched.shape[2] != K:
            raise ValueError(f"rate_schedule must be (G={G}, R, K={K}); "
                             f"got shape {sched.shape}")
        sched = _pad_sched(sched, bucket_rounds(sched.shape[1]),
                           bucket_cols(K))
        sched_np = _pad_rows_like(np.repeat(sched, int(trials), axis=0),
                                  Bp)
        R = sched_np.shape[1]
    fns = _get_jax_panel(drift)
    stage, final = fns["stage"], fns["final"]
    key = jax.random.key(int(rng.integers(2 ** 63 - 1)), impl="rbg")
    key, kfin = jax.random.split(key)
    st = {"key": key, "round": jnp.int32(0),
          "est_done": jnp.zeros((Bp, Kb), jnp.float32),
          "est_time": jnp.zeros(Bp, jnp.float32),
          "lam_hat": jnp.asarray((lam_rows > 0).astype(np.float32))}
    for sfx in ("k", "u"):
        st["n_rem_" + sfx] = jnp.full(Bp, N, jnp.float32)
        st["n_left_" + sfx] = jnp.zeros((Bp, Kb), jnp.float32)
        st["t_comp_" + sfx] = jnp.zeros(Bp, jnp.float32)
        st["n_comm_" + sfx] = jnp.zeros(Bp, jnp.float32)
        st["iters_" + sfx] = jnp.zeros(Bp, jnp.int32)
        st["active_" + sfx] = jnp.full(Bp, N > threshold)
    # idx maps current state rows to original panel rows (-1: dead
    # compaction padding, never finalized); out collects scattered final
    # results as rows drop out
    idx = np.concatenate([np.arange(B), np.full(Bp - B, -1)])
    out = np.zeros((Bp, 6))
    lam_cur = lam_rows
    sched_cur = sched_np
    lam_dev = jnp.asarray(lam_rows)
    chunk = _panel_chunk()
    ncall = [0]
    skip = ("key", "round", "est_done", "est_time")

    def finalize(sub, cur_st, cur_idx, cur_lam):
        """Final-phase the given current-state rows; scatter to out."""
        sub = sub[cur_idx[sub] >= 0]
        if sub.size == 0:
            return
        n = sub.size
        tgt = _rows_target(n)
        gather = np.concatenate([sub, np.repeat(sub[:1], tgt - n)])
        gidx = jnp.asarray(gather)
        st_sub = {kk: vv[gidx] for kk, vv in cur_st.items()
                  if kk not in skip}
        orig = cur_idx[gather]
        lam_sub = cur_lam[gather]
        if drift:
            it_k = np.asarray(cur_st["iters_k"])[gather]
            it_u = np.asarray(cur_st["iters_u"])[gather]
            rk = sched_np[orig, np.minimum(it_k, R - 1)]
            ru = sched_np[orig, np.minimum(it_u, R - 1)]
        else:
            rk = ru = lam_sub
        inv_k = np.where(rk > 0, 1.0 / np.maximum(rk, 1e-30),
                         0.0).astype(np.float32)
        inv_u = np.where(ru > 0, 1.0 / np.maximum(ru, 1e-30),
                         0.0).astype(np.float32)
        res = final(jax.random.fold_in(kfin, ncall[0]),
                    jnp.asarray(lam_sub), jnp.asarray(inv_k),
                    jnp.asarray(inv_u), st_sub)
        ncall[0] += 1
        rows = cur_idx[sub]
        for j, arr in enumerate(res):
            out[rows, j] = np.asarray(arr)[:n]

    r0 = 0
    while True:
        r1 = min(r0 + chunk, max_iter)
        if drift:
            cols = np.minimum(np.arange(r0, r1), R - 1)
            st = stage(st, lam_dev,
                       jnp.asarray(sched_cur[:, cols, :]),
                       jnp.int32(r0), jnp.int32(r1), threshold, cap_u,
                       max_iter)
        else:
            st = stage(st, lam_dev, jnp.int32(r1), threshold, cap_u,
                       max_iter)
        r0 = r1
        act = np.asarray(st["active_k"] | st["active_u"])
        live = np.flatnonzero(act & (idx >= 0))
        if live.size == 0 or r0 >= max_iter:
            finalize(np.flatnonzero(idx >= 0), st, idx, lam_cur)
            break
        tgt = max(_rows_target(live.size), 256)
        if tgt < idx.size:
            # compact: final-phase the frozen rows now, gather the rest
            # into the next bucket (padding gets active forced off and
            # idx -1, so it is never finalized)
            finalize(np.flatnonzero(~act & (idx >= 0)), st, idx, lam_cur)
            gather = np.concatenate(
                [live, np.repeat(live[:1], tgt - live.size)])
            gidx = jnp.asarray(gather)
            valid = jnp.arange(tgt) < live.size
            st = {kk: (vv if kk in ("key", "round") else vv[gidx])
                  for kk, vv in st.items()}
            st["active_k"] = st["active_k"] & valid
            st["active_u"] = st["active_u"] & valid
            idx = np.where(np.asarray(valid), idx[gather], -1)
            lam_cur = lam_cur[gather]
            lam_dev = jnp.asarray(lam_cur)
            if drift:
                sched_cur = sched_cur[gather]
    known = tuple(out[:B, j].astype(np.float64) for j in range(3))
    unknown = tuple(out[:B, j].astype(np.float64) for j in range(3, 6))
    return {"known": known, "unknown": unknown}


def work_exchange_panel_pallas(lam: np.ndarray, N: int,
                               cfg_known: ExchangeConfig,
                               cfg_unknown: ExchangeConfig,
                               trials: int, rng: np.random.Generator,
                               rate_schedule: Optional[np.ndarray] = None
                               ) -> Dict[str, GridArrays]:
    """The pair as ONE ``we_rounds`` launch: known rows stacked on top of
    unknown rows with a per-row flag column, so the whole figure is a
    single tiled kernel pass.  With a grid mesh active the stacked rows
    shard over the devices (flags travel with their rows); each shard
    keys its Threefry counters from its own seed pair, so sharded runs
    are statistically equivalent -- not bit-identical -- to the
    single-device launch."""
    from repro.kernels.we_rounds import we_rounds_grid

    _panel_pair_check(cfg_known, cfg_unknown)
    lam = np.asarray(lam, dtype=np.float32)
    if lam.ndim != 2:
        raise ValueError(f"lam must be (G, K); got shape {lam.shape}")
    G, K = lam.shape
    threshold = cfg_known.threshold_frac * N / K
    cap_u = (np.inf if cfg_unknown.storage_cap_frac is None
             else float(np.ceil(cfg_unknown.storage_cap_frac * N / K)))
    with span("repro.we_rounds.rows"):
        half = np.repeat(_pad_cols(lam, bucket_cols(K)), int(trials), axis=0)
        B = half.shape[0]
        stacked = np.concatenate([half, half])
        flags = np.concatenate([np.ones(B, np.float32),
                                np.zeros(B, np.float32)])
        stacked, _ = _pad_rows(stacked, bucket=128)
        flags = np.concatenate(
            [flags, np.ones(stacked.shape[0] - 2 * B, np.float32)])
        sched_rows = None
        if rate_schedule is not None:
            sched = np.asarray(rate_schedule, dtype=np.float32)
            if sched.ndim != 3 or sched.shape[0] != G or sched.shape[2] != K:
                raise ValueError(f"rate_schedule must be (G={G}, R, K={K}); "
                                 f"got shape {sched.shape}")
            sched = _pad_sched(sched, bucket_rounds(sched.shape[1]),
                               bucket_cols(K))
            sched_half = np.repeat(sched, int(trials), axis=0)
            sched_rows = _pad_rows_like(
                np.concatenate([sched_half, sched_half]), stacked.shape[0])
    mesh = active_grid_mesh()
    if mesh is not None:
        # sharded launch: one independent seed pair per device (same
        # discipline as work_exchange_grid_pallas)
        seed = rng.integers(0, 2 ** 32, size=(int(mesh.size), 2),
                            dtype=np.uint32)
    else:
        seed = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
    t, it, cm = we_rounds_grid(stacked, seed, n0=float(N),
                               threshold=float(threshold), cap=cap_u,
                               known=flags,
                               max_iter=int(cfg_known.max_iterations),
                               mesh=mesh, rate_schedule=sched_rows,
                               real_rows=2 * B)
    return {"known": (t[:B], it[:B], cm[:B]),
            "unknown": (t[B:2 * B], it[B:2 * B], cm[B:2 * B])}


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

register_backend(SamplerBackend(
    name="numpy",
    work_exchange_grid=work_exchange_grid_numpy,
    description="exact integer-unit engine (Generator.gamma/binomial); "
                "bit-identical to the scalar reference at trials=1",
    gamma_rows=gamma_rows_numpy))

register_backend(SamplerBackend(
    name="jax",
    work_exchange_grid=work_exchange_grid_jax,
    description="one jitted fluid-relaxation pipeline (mean-exact MT gamma "
                "+ normal-limit binomial, float32); statistically "
                "equivalent, not bit-identical",
    gamma_rows=gamma_rows_jax,
    one_shot_times=one_shot_times_device,
    coupled_mds_sweep=True,
    work_exchange_panel=work_exchange_panel_jax),
    available=_jax_available)

register_backend(SamplerBackend(
    name="pallas",
    work_exchange_grid=work_exchange_grid_pallas,
    description="fused we_rounds Pallas kernel (counter-based Threefry "
                "bits + MT gamma + argmin + normal-limit binomial in one "
                "tiled pass); compiled on TPU, jnp reference / "
                "interpreted kernel (bit-identical) on CPU",
    gamma_rows=gamma_rows_pallas,
    one_shot_times=one_shot_times_device,
    coupled_mds_sweep=True,
    work_exchange_panel=work_exchange_panel_pallas),
    available=_jax_available)


__all__ = [
    "ENV_VAR", "DEFAULT_BACKEND", "SAMPLER_BACKENDS", "SamplerBackend",
    "register_backend", "get_backend", "list_backends", "resolve_backend",
    "validate_backend", "get_gamma_rows",
    "grid_sharding", "active_grid_mesh",
    "work_exchange_grid_numpy", "work_exchange_grid_jax",
    "work_exchange_grid_pallas", "gamma_rows_numpy", "gamma_rows_jax",
    "gamma_rows_pallas", "one_shot_times_device", "work_exchange_panel_jax",
    "work_exchange_panel_pallas",
]
