"""Counter-based work-exchange round pipeline: shared math + jnp oracle.

Everything the Pallas kernel computes lives here as pure ``jnp`` functions
on ``(rows, K)`` tiles, so the kernel (``kernel.py``) and the reference
engine (``we_rounds_reference``) share one implementation of

* **bit generation** -- Threefry-2x32 (20 rounds: add / xor / rotate on
  ``uint32`` only, the reason JAX itself uses Threefry on TPU), keyed per
  ``(trial, worker, round, slot)``.  Counter-based draws make the pipeline
  embarrassingly parallel AND tiling-invariant: a row's random stream
  depends only on its global row id, never on tile size, loop trip count,
  or padding rows, so the interpreted kernel and the reference are
  *bit-identical* and padded rows cannot perturb real ones.  The kernel
  compiled for a TPU draws the same bits, but Mosaic's float math
  (transcendentals in particular) rounds differently from XLA's, so
  there it matches the reference statistically, not bitwise.
* **Gamma service draws** -- the mean-exact Marsaglia-Tsang transform
  ``d * (1 + z / (3 sqrt(d)))^3`` with the exact boost
  ``Gamma(a) = Gamma(a+1) * U^(1/a)`` chained three times below shape 3
  (the same relaxation as the ``jax`` sampler backend).
* **straggler selection** -- per-trial argmin over the K workers.
* **Binomial done-counts** -- the mean/variance-exact normal limit.

``we_rounds_reference`` runs the full batch through one
``lax.while_loop``; it is both the CPU-CI execution path of the ``pallas``
sampler backend (jitted, no Pallas lowering required) and the oracle the
kernel is validated against.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# slot layout per (trial, worker, round): 4 Threefry calls x 2 words
#   pair 0 -> Box-Muller pair for the Gamma normal
#   pair 1 -> boost uniforms u0, u1
#   pair 2 -> boost uniform u2 (word 1 spare)
#   pair 3 -> Box-Muller pair for the Binomial normal
N_PAIRS = 4
_U32 = jnp.uint32


def _rotl(x: jnp.ndarray, d: int) -> jnp.ndarray:
    return (x << _U32(d)) | (x >> _U32(32 - d))


def threefry2x32(k0, k1, c0, c1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Threefry-2x32, 20 rounds (the full-strength variant)."""
    k0, k1 = _U32(k0) + _U32(0), _U32(k1) + _U32(0)
    ks2 = k0 ^ k1 ^ _U32(0x1BD11BDA)
    x0 = c0.astype(jnp.uint32) + k0
    x1 = c1.astype(jnp.uint32) + k1
    rot_a = (13, 15, 26, 6)
    rot_b = (17, 29, 16, 24)
    inject = ((k1, ks2), (ks2, k0), (k0, k1), (k1, ks2), (ks2, k0))
    for block in range(5):
        for d in (rot_a if block % 2 == 0 else rot_b):
            x0 = x0 + x1
            x1 = _rotl(x1, d) ^ x0
        x0 = x0 + inject[block][0]
        x1 = x1 + inject[block][1] + _U32(block + 1)
    return x0, x1


def uniform01(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> float32 uniform in (0, 1): top 24 bits, zero-excluded
    so ``log(u)`` stays finite.  The bits pass through ``int32`` (exact
    below 2^24) because Mosaic has no ``uint32 -> float32`` cast."""
    u = ((bits >> _U32(8)).astype(jnp.int32).astype(jnp.float32)
         * jnp.float32(1.0 / (1 << 24)))
    return jnp.maximum(u, jnp.float32(1e-12))


def _box_muller(u1: jnp.ndarray, u2: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        jnp.float32(2.0 * jnp.pi) * u2)


def round_uniforms(k0, k1, c0: jnp.ndarray, c1_base: jnp.ndarray):
    """The 7 variates one exchange round needs per ``(row, worker)`` cell.

    ``c0`` carries the global row (trial) id, ``c1_base`` the
    ``(round * K + worker) * N_PAIRS`` namespace; both broadcast over the
    tile.  Returns ``(z_gamma, u0, u1, u2, z_binom)`` float32 arrays.
    """
    c0 = c0.astype(jnp.uint32)
    c1_base = c1_base.astype(jnp.uint32)
    a0, a1 = threefry2x32(k0, k1, c0, c1_base)
    b0, b1 = threefry2x32(k0, k1, c0, c1_base + _U32(1))
    c0_, _ = threefry2x32(k0, k1, c0, c1_base + _U32(2))
    d0, d1 = threefry2x32(k0, k1, c0, c1_base + _U32(3))
    z_gamma = _box_muller(uniform01(a0), uniform01(a1))
    z_binom = _box_muller(uniform01(d0), uniform01(d1))
    return (z_gamma, uniform01(b0), uniform01(b1), uniform01(c0_), z_binom)


def gamma_mt(z: jnp.ndarray, u0: jnp.ndarray, u1: jnp.ndarray,
             u2: jnp.ndarray, alpha: jnp.ndarray,
             inv_rate: jnp.ndarray) -> jnp.ndarray:
    """Mean-exact MT transform for any ``alpha > 0``: raw transform at
    shape ``alpha + 3`` below 3, pulled back through the exact identity
    ``Gamma(a) = Gamma(a+1) U^{1/a}`` chained three times (the chained
    mean telescopes exactly, as in the jax sampler backend)."""
    boost = alpha < 3.0
    a = jnp.where(boost, alpha + 3.0, alpha)
    d = a - jnp.float32(1.0 / 3.0)
    c = jnp.maximum(1.0 + z / (3.0 * jnp.sqrt(d)), 0.0)
    raw = d * c ** 3 * inv_rate
    log_pow = (jnp.log(u0) / jnp.maximum(alpha, 1e-12)
               + jnp.log(u1) / jnp.maximum(alpha + 1.0, 1e-12)
               + jnp.log(u2) / jnp.maximum(alpha + 2.0, 1e-12))
    return raw * jnp.where(boost, jnp.exp(log_pow), 1.0)


def binomial_normal(z: jnp.ndarray, n: jnp.ndarray,
                    p: jnp.ndarray) -> jnp.ndarray:
    """Binomial(n, p) in its mean/variance-exact normal limit."""
    mean = n * p
    std = jnp.sqrt(jnp.maximum(n * p * (1.0 - p), 0.0))
    return jnp.clip(mean + z * std, 0.0, n)


# ---------------------------------------------------------------------------
# the round pipeline on a (rows, K) tile
# ---------------------------------------------------------------------------

def estimator_prior(lam: jnp.ndarray) -> jnp.ndarray:
    """Initial / no-observation rate estimate per worker column.

    The paper's prior is ``lambda_hat = 1`` everywhere; zero-rate columns
    (masked padding from the K-axis shape buckets) must hold a zero
    estimate instead so the estimator never assigns them work.  Without
    padding this is exactly ``jnp.ones_like(lam)``, bit-for-bit.
    """
    return jnp.where(lam > 0.0, jnp.float32(1.0), jnp.float32(0.0))


def init_state(rows: int, K: int, n0: float, threshold: float,
               known: bool, lam: jnp.ndarray = None,
               with_round: bool = False) -> Dict[str, jnp.ndarray]:
    st = {
        "n_rem": jnp.full((rows, 1), jnp.float32(n0)),
        "n_left": jnp.zeros((rows, K), jnp.float32),
        "t_comp": jnp.zeros((rows, 1), jnp.float32),
        "n_comm": jnp.zeros((rows, 1), jnp.float32),
        "iters": jnp.zeros((rows, 1), jnp.int32),
        # int32, not bool: Mosaic cannot carry i1 vectors through the
        # kernel's while_loop
        "active": jnp.full((rows, 1), int(n0 > threshold), jnp.int32),
    }
    if with_round:
        # scalar trip counter: every *active* row has proceeded on every
        # prior trip, so its ``iters`` equals this counter -- which is why
        # the in-loop drift read can be one dynamic slice instead of a
        # per-row gather
        st["round"] = jnp.int32(0)
    if not known:
        prior = (jnp.ones((rows, K), jnp.float32) if lam is None
                 else jnp.broadcast_to(estimator_prior(lam), (rows, K))
                 .astype(jnp.float32))
        st.update(est_done=jnp.zeros((rows, K), jnp.float32),
                  est_time=jnp.zeros((rows, 1), jnp.float32),
                  lam_hat=prior)
    return st


def sched_inv_rates(sched: jnp.ndarray, iters: jnp.ndarray) -> jnp.ndarray:
    """1/rate in effect at each row's current round, from a
    ``(rows, R, K)`` per-round schedule (round >= R holds the last row).

    One-hot masked sum -- O(rows * R * K) per call, so it is reserved for
    the run-once final phase where ``iters`` genuinely differs per row;
    the in-loop read uses the scalar round counter and a dynamic slice
    (``sched_row`` / the kernel's ``pl.ds`` tile read) instead.
    """
    R = sched.shape[1]
    r_idx = jnp.minimum(iters, R - 1)                       # (rows, 1)
    rounds = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    sel = (r_idx == rounds).astype(sched.dtype)             # (rows, R)
    return 1.0 / (sched * sel[:, :, None]).sum(1)           # (rows, K)


def sched_row(sched: jnp.ndarray, rnd: jnp.ndarray) -> jnp.ndarray:
    """Rates row of a ``(rows, R, K)`` schedule at scalar round ``rnd``
    (clamped to the last row), as a direct round-indexed load."""
    r = jnp.minimum(rnd, sched.shape[1] - 1)
    return jax.lax.dynamic_slice_in_dim(sched, r, 1, axis=1)[:, 0, :]


def sched_inv_rates_gather(sched: jnp.ndarray,
                           iters: jnp.ndarray) -> jnp.ndarray:
    """``sched_inv_rates`` as a per-row gather: same selected values
    bit-for-bit, O(rows * K) instead of O(rows * R * K).  XLA-only (the
    full-batch reference); the kernel keeps the one-hot form, which
    lowers in Pallas and is cheap on a single tile."""
    r_idx = jnp.minimum(iters, sched.shape[1] - 1)          # (rows, 1)
    cur = jnp.take_along_axis(sched, r_idx[:, :, None], axis=1)[:, 0, :]
    return 1.0 / cur


def round_body(st: Dict[str, jnp.ndarray], lam: jnp.ndarray,
               inv_lam: jnp.ndarray, row_ids: jnp.ndarray, k0, k1, *,
               K: int, cap: float, threshold: float, known: bool,
               max_iter: int, sched_at=None,
               known_col: jnp.ndarray = None) -> Dict[str, jnp.ndarray]:
    """One fluid exchange round on a tile (shared by kernel and oracle).

    The RNG round index is the row's own ``iters`` (== the global loop
    count while a row is active), so frozen rows recompute already-spent
    counters into fully-masked lanes and the result is independent of how
    many extra trips the surrounding ``while_loop`` makes.

    ``sched_at`` (optional callable ``round -> (rows, K)`` rates) supplies
    each round's true service rates (drifting scenarios): the Gamma draws
    use them, the assignment shares keep using ``lam`` / the online
    estimate.  It is indexed by the scalar ``st["round"]`` trip counter --
    active rows always have ``iters == round`` (a row that fails to
    proceed goes inactive for good), and frozen rows' stale reads are
    fully masked -- so one row load per trip replaces the old
    O(rows * R * K) one-hot masked sum.

    ``known_col`` (optional ``(rows, 1)`` bool) is the fused-panel mixed
    mode: each row carries its own known-heterogeneity flag (known rows
    assign by ``lam`` with no storage cap, unknown rows by the online
    estimate under ``cap``).  Callers pass ``known=False`` alongside it so
    the estimator state exists for every row; known rows simply never
    read it.
    """
    worker = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    c1 = ((st["iters"] * K + worker) * N_PAIRS).astype(jnp.uint32)
    z_g, u0, u1, u2, z_b = round_uniforms(k0, k1, row_ids, c1)

    if sched_at is not None:
        inv_lam = 1.0 / sched_at(st["round"])
    if known_col is not None:
        rates = jnp.where(known_col, lam, st["lam_hat"])
        cap_eff = jnp.where(known_col, jnp.inf, jnp.float32(cap))
    else:
        rates = lam if known else st["lam_hat"]
        cap_eff = jnp.float32(cap)
    share = rates * (st["n_rem"] / rates.sum(1, keepdims=True))
    assign = jnp.minimum(share, cap_eff)
    busy = assign > 0.5        # sub-half slivers carry over as leftover
    t_raw = gamma_mt(z_g, u0, u1, u2, jnp.maximum(assign, 0.5), inv_lam)
    t_k = jnp.where(busy, t_raw, jnp.inf)
    t_star = t_k.min(1, keepdims=True)
    proceed = (st["active"] > 0) & jnp.isfinite(t_star)
    fin = t_k == t_star                     # finisher clears its queue
    p = jnp.clip(t_star / t_k, 0.0, 1.0)
    done = binomial_normal(z_b, jnp.maximum(assign - 1.0, 0.0), p)
    done = jnp.where(fin, assign, jnp.where(busy, done, 0.0))
    n_rem = st["n_rem"] - done.sum(1, keepdims=True)

    started = st["iters"] > 0
    comm = jnp.maximum(assign - st["n_left"], 0.0).sum(1, keepdims=True)
    upd = lambda new, old: jnp.where(proceed, new, old)  # noqa: E731
    iters = st["iters"] + proceed
    n_rem_m = upd(n_rem, st["n_rem"])
    out = {
        "n_rem": n_rem_m,
        "n_left": upd(assign - done, st["n_left"]),
        "t_comp": upd(st["t_comp"] + t_star, st["t_comp"]),
        "n_comm": upd(st["n_comm"] + jnp.where(started, comm, 0.0),
                      st["n_comm"]),
        "iters": iters,
        "active": (proceed & (n_rem_m > threshold)
                   & (iters < max_iter)).astype(jnp.int32),
    }
    if "round" in st:
        out["round"] = st["round"] + jnp.int32(1)
    if not known:
        # accumulators go unmasked; frozen rows only read them through
        # lam_hat, which IS masked
        ed = st["est_done"] + done
        et = st["est_time"] + t_star
        out["est_done"] = ed
        out["est_time"] = et
        out["lam_hat"] = upd(jnp.where(ed > 0.0, ed / jnp.maximum(et, 1e-30),
                                       estimator_prior(lam)),
                             st["lam_hat"])
    return out


def final_phase(st: Dict[str, jnp.ndarray], lam: jnp.ndarray,
                inv_lam: jnp.ndarray, row_ids: jnp.ndarray, k0, k1, *,
                K: int, known: bool, max_iter: int,
                sched: jnp.ndarray = None, sched_gather: bool = False,
                known_col: jnp.ndarray = None):
    """Below the threshold: assign the remainder, wait for all workers.
    Uses the reserved round index ``max_iter`` (the loop never reaches it:
    in-loop draws happen at ``iters < max_iter``).  ``sched_gather``
    selects the XLA per-row gather for the drift read (the full-batch
    reference path); the default one-hot lowers inside the kernel.
    ``known_col`` is the fused-panel per-row flag (see ``round_body``)."""
    worker = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    c1 = ((jnp.int32(max_iter) * K + worker) * N_PAIRS).astype(jnp.uint32)
    z_g, u0, u1, u2, _ = round_uniforms(
        k0, k1, jnp.broadcast_to(row_ids, (row_ids.shape[0], 1)), c1)
    has_rem = st["n_rem"] > 1e-6
    if sched is not None:
        inv_lam = (sched_inv_rates_gather(sched, st["iters"])
                   if sched_gather else sched_inv_rates(sched, st["iters"]))
    if known_col is not None:
        rates = jnp.where(known_col, lam, st["lam_hat"])
    else:
        rates = lam if known else st["lam_hat"]
    share = rates * (st["n_rem"] / rates.sum(1, keepdims=True))
    comm = jnp.maximum(share - st["n_left"], 0.0).sum(1, keepdims=True)
    t_k = jnp.where(share > 1e-9,
                    gamma_mt(z_g, u0, u1, u2, jnp.maximum(share, 1e-9),
                             inv_lam), 0.0)
    t_comp = st["t_comp"] + jnp.where(has_rem, t_k.max(1, keepdims=True),
                                      0.0)
    n_comm = st["n_comm"] + jnp.where(has_rem & (st["iters"] > 0), comm,
                                      0.0)
    iters = st["iters"] + has_rem
    return t_comp[:, 0], iters[:, 0].astype(jnp.float32), n_comm[:, 0]


# ---------------------------------------------------------------------------
# full-batch jnp oracle (the pallas backend's CPU execution path)
# ---------------------------------------------------------------------------

def we_rounds_reference(lam_rows: jnp.ndarray, seed: jnp.ndarray,
                        sched: jnp.ndarray = None, *,
                        n0: float, threshold: float, cap: float,
                        known: bool, max_iter: int):
    """The whole ``(B, K)`` batch through one ``lax.while_loop``.

    Bit-identical to the Pallas kernel in interpret mode on shared rows
    for any tiling, because every draw is a pure function of
    ``(seed, row, worker, round, slot)``.  ``sched`` (optional
    ``(B, R, K)``) is the per-round service-rate schedule of the
    drifting scenarios -- the RNG keying is unchanged, so interpreted
    kernel and reference stay bit-identical with or without drift.
    """
    B, K = lam_rows.shape
    lam = lam_rows.astype(jnp.float32)
    inv_lam = 1.0 / lam
    k0, k1 = seed[0], seed[1]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    sched_at = None if sched is None else (lambda r: sched_row(sched, r))

    def cond(st):
        return (st["active"] > 0).any()

    def body(st):
        return round_body(st, lam, inv_lam, row_ids, k0, k1, K=K, cap=cap,
                          threshold=threshold, known=known,
                          max_iter=max_iter, sched_at=sched_at)

    st = jax.lax.while_loop(cond, body,
                            init_state(B, K, n0, threshold, known, lam=lam,
                                       with_round=sched is not None))
    return final_phase(st, lam, inv_lam, row_ids, k0, k1, K=K, known=known,
                       max_iter=max_iter, sched=sched, sched_gather=True)


def we_rounds_reference_panel(lam_rows: jnp.ndarray, seed: jnp.ndarray,
                              known_flags: jnp.ndarray,
                              sched: jnp.ndarray = None, *,
                              n0: float, threshold: float, cap: float,
                              max_iter: int):
    """``we_rounds_reference`` with a per-row known-heterogeneity flag.

    The fused-panel path: known and unknown work-exchange rows of a whole
    figure stack into ONE batch (one launch), each row reading its own
    ``known_flags`` entry (float32/bool ``(B,)`` or ``(B, 1)``; nonzero =
    known).  Counters are keyed by the global row id exactly as in the
    single-scheme path, so the panel keeps the interpret/reference
    bit-identity -- but it is a *different* (equally valid) bit stream
    than two separate launches, whose rows sit at different ids.
    """
    B, K = lam_rows.shape
    lam = lam_rows.astype(jnp.float32)
    inv_lam = 1.0 / lam
    k0, k1 = seed[0], seed[1]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    known_col = jnp.reshape(known_flags, (B, 1)) > 0
    sched_at = None if sched is None else (lambda r: sched_row(sched, r))

    def cond(st):
        return (st["active"] > 0).any()

    def body(st):
        return round_body(st, lam, inv_lam, row_ids, k0, k1, K=K, cap=cap,
                          threshold=threshold, known=False,
                          max_iter=max_iter, sched_at=sched_at,
                          known_col=known_col)

    st = jax.lax.while_loop(cond, body,
                            init_state(B, K, n0, threshold, False, lam=lam,
                                       with_round=sched is not None))
    return final_phase(st, lam, inv_lam, row_ids, k0, k1, K=K, known=False,
                       max_iter=max_iter, sched=sched, sched_gather=True,
                       known_col=known_col)


# ---------------------------------------------------------------------------
# batched Gamma rows (the MDS L-sweep primitive)
# ---------------------------------------------------------------------------

def gamma_rows_reference(shape_rows: jnp.ndarray, scale_rows: jnp.ndarray,
                         seed: jnp.ndarray, *,
                         boost: bool = True) -> jnp.ndarray:
    """Counter-based ``Gamma(shape) * scale`` over an ``(R, K)`` matrix in
    one pass (round namespace 0 -- each call gets a fresh seed).
    ``shape_rows``/``scale_rows`` broadcast against each other.  With
    ``boost=False`` (every shape >= 3, the MDS regime) only the Box-Muller
    pair is generated -- one Threefry call per element instead of three.
    """
    R, K = jnp.broadcast_shapes(shape_rows.shape, scale_rows.shape)
    k0, k1 = seed[0], seed[1]
    c0 = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0).astype(jnp.uint32)
    worker = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    c1 = (worker * N_PAIRS).astype(jnp.uint32)
    a0, a1 = threefry2x32(k0, k1, c0, c1)
    z = _box_muller(uniform01(a0), uniform01(a1))
    alpha = jnp.broadcast_to(shape_rows, (R, K)).astype(jnp.float32)
    scale = scale_rows.astype(jnp.float32)
    if not boost:
        d = alpha - jnp.float32(1.0 / 3.0)
        c = jnp.maximum(1.0 + z / (3.0 * jnp.sqrt(d)), 0.0)
        return d * c ** 3 * scale
    b0, b1 = threefry2x32(k0, k1, c0, c1 + _U32(1))
    c0_, _ = threefry2x32(k0, k1, c0, c1 + _U32(2))
    return gamma_mt(z, uniform01(b0), uniform01(b1), uniform01(c0_),
                    alpha, scale)


# ---------------------------------------------------------------------------
# one-shot schemes: one draw per worker, each row reduced to its finish time
# ---------------------------------------------------------------------------

# Rows of at most this many workers skip the sort: on a v5e the pairwise
# count ran the one-shot program 13% faster than the sort at K = 56, and
# the sort wins at K = 2,048 (K^2 compare-adds there).
PAIRWISE_MAX_K = 128


def cover_time(t: jnp.ndarray, weights: jnp.ndarray,
               need: jnp.ndarray) -> jnp.ndarray:
    """Per row of ``(R, K)`` finish times, the earliest time at which the
    finished workers' weights reach the row's ``need`` (``(R,)``): the
    least ``t_k`` of a weighted worker with ``sum_j w_j [t_j <= t_k] >=
    need``.  Each row's times are sorted (its weights travel with them),
    the weights run as a cumulative sum in that order, and the first time
    at which the sum reaches ``need`` is the answer: O(K log K) per row.
    Rows of at most ``PAIRWISE_MAX_K`` workers count the covered weight
    of each ``t_k`` directly (K^2 compare-adds, no sort), with the same
    answer.  Tied times give the pairwise rule's answer, since the sum
    passes the need inside the tie at the tie's time.  Weights and needs
    are summed as int32, exact up to 2^31.  A weight-0 column never
    counts and never finishes a cover (its time may be anything, NaN
    included); a need of 0 is met at time 0; a row whose weights never
    reach its need reads inf."""
    w = jnp.asarray(weights, jnp.int32)
    t = jnp.where(w > 0, t, jnp.inf)
    need = jnp.asarray(need, jnp.int32)[:, None]
    if t.shape[1] <= PAIRWISE_MAX_K:
        covered = jnp.sum(jnp.where(t[:, None, :] <= t[:, :, None],
                                    w[:, None, :], 0), axis=2)
    else:
        t, w = jax.lax.sort((t, w), dimension=1, num_keys=1)
        covered = jnp.cumsum(w, axis=1)
    first = jnp.min(jnp.where((w > 0) & (covered >= need), t, jnp.inf),
                    axis=1)
    return jnp.where(need[:, 0] > 0, first, 0.0)


def one_shot_reference(loads: jnp.ndarray, scales: jnp.ndarray,
                       weights: jnp.ndarray, need: jnp.ndarray,
                       seed: jnp.ndarray, *, trials: int,
                       boost: bool) -> jnp.ndarray:
    """Completion times of a one-shot scheme over ``G`` points x
    ``trials`` rows, grid-major: each point's ``(K,)`` loads, scales
    (``1 / lambda``) and cover weights, and its ``need``, repeat to its
    rows here; each worker finishes at a ``gamma_rows_reference`` draw
    ``Gamma(load) * scale``, and each row reduces to its ``cover_time``."""
    R = loads.shape[0] * trials

    def rows(x):
        return jnp.repeat(x, trials, axis=0, total_repeat_length=R)

    t = gamma_rows_reference(rows(loads), rows(scales), seed, boost=boost)
    return cover_time(t, rows(weights), rows(need))
