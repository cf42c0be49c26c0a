"""Plain reference of the paper's batch schemes (Attia & Tandon, 2017).

Written from the paper's description alone, in straightforward numpy
and float64, batched over trials and nothing else.  It imports nothing
of the program.  One row is one independent run of the master protocol
over a cluster with Poisson service at rates ``lam``:

* work exchange (Algorithm 1, rates known; Algorithm 3, rates learnt
  online as units done over elapsed time, prior 1, with the per-worker
  storage cap ``ceil(cap_frac N / K)``): each round splits the
  remaining units by largest-remainder rounding of the rates, the round
  ends when the first worker drains its queue (its finish time is
  ``Gamma(n_k, 1/lam_k)``), every other worker has done a
  ``Binomial(n_k - 1, t*/t_k)`` share of its queue by then (the
  Poisson arrival times of its units are uniform below its own finish
  time), and leftovers return to the pool.  Below the cutting threshold
  ``threshold_frac N / K`` the remainder is split once more and the
  master waits for every worker.

The one-shot schemes of the paper's Fig. 5 panel, each one draw per
worker and no reassignment:

* ``fixed`` (Section 5.1): units split in proportion to the rates,
  ``T = max_k Gamma(n_k, 1/lam_k)``;
* ``mds`` (Section 3): a (K, L) MDS code, ``ceil(N / L)`` coded units
  per worker, ``T`` the L-th smallest finish time; the code length is
  chosen where the mean is least (eq. 6), so the reference gives the
  mean of every L (``mds_curve``);
* ``het_mds`` (heterogeneous coded loads, HCMM): ``ceil(r N)`` coded
  units split in proportion to the rates, ``T`` the first finish time
  at which the finished workers' loads cover N;
* ``hedged`` (replicate the slowest): the fastest worker is held back
  and the others take proportional shares of N; it runs a copy of the
  lowest-rate loaded worker's shard, and the first of the two copies
  to finish counts.

``q`` rounds every stored quantity to a precision: float64 is the
reference, bfloat16 is the lower-precision control of the correctness
check.
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np


def as_f64(x):
    return np.asarray(x, dtype=np.float64)


def as_bf16(x):
    import ml_dtypes
    return np.asarray(x, dtype=np.float64).astype(
        ml_dtypes.bfloat16).astype(np.float64)


def lr_round(weights: np.ndarray, totals: np.ndarray) -> np.ndarray:
    """Split ``totals[b]`` units over the columns of ``weights[b]`` in
    proportion, as whole units that sum to the total: floors first, the
    rest one each to the largest fractional parts (lowest column first
    among ties)."""
    shares = weights / weights.sum(axis=1, keepdims=True) * totals[:, None]
    base = np.floor(shares)
    rest = (totals - base.sum(axis=1)).astype(np.int64)
    rank = np.argsort(np.argsort(-(shares - base), axis=1, kind="stable"),
                      axis=1, kind="stable")
    return base + (rank < rest[:, None])


def work_exchange(lam_rows: np.ndarray, N: int, known: bool,
                  threshold_frac: float, cap_frac: float, max_iter: int,
                  rng: np.random.Generator, q: Callable = as_f64
                  ) -> np.ndarray:
    """T_comp of one run per row of ``lam_rows`` (B, K)."""
    lam = as_f64(lam_rows)
    B, K = lam.shape
    threshold = threshold_frac * N / K
    cap = np.inf if known else float(np.ceil(cap_frac * N / K))
    n_rem = np.full(B, float(N))
    t = np.zeros(B)
    iters = np.zeros(B, dtype=np.int64)
    done_est = np.zeros((B, K))
    time_est = np.zeros(B)
    active = n_rem > threshold
    while active.any():
        i = np.flatnonzero(active)
        rates = lam[i] if known else np.where(
            done_est[i] > 0, done_est[i] / np.maximum(time_est[i], 1e-300)
            [:, None], 1.0)
        assign = np.minimum(lr_round(rates, n_rem[i]), cap)
        carried = n_rem[i] - assign.sum(axis=1)
        busy = assign > 0
        t_k = np.full(assign.shape, np.inf)
        t_k[busy] = q(rng.gamma(assign[busy], 1.0 / lam[i][busy]))
        first = np.argmin(t_k, axis=1)
        t_star = t_k[np.arange(i.size), first]
        p = np.where(busy, t_star[:, None] / t_k, 0.0)
        done = rng.binomial(np.maximum(assign - 1, 0).astype(np.int64),
                            np.clip(p, 0.0, 1.0)).astype(np.float64)
        done[np.arange(i.size), first] = assign[np.arange(i.size), first]
        t[i] = q(t[i] + t_star)
        n_rem[i] = q(carried + (assign - done).sum(axis=1))
        iters[i] += 1
        if not known:
            done_est[i] = q(done_est[i] + done)
            time_est[i] = q(time_est[i] + t_star)
        active[i] = (n_rem[i] > threshold) & (iters[i] < max_iter)
    i = np.flatnonzero(n_rem > 0)
    if i.size:
        rates = lam[i] if known else np.where(
            done_est[i] > 0, done_est[i] / np.maximum(time_est[i], 1e-300)
            [:, None], 1.0)
        assign = lr_round(rates, np.round(n_rem[i]))
        t_k = np.zeros(assign.shape)
        busy = assign > 0
        t_k[busy] = q(rng.gamma(assign[busy], 1.0 / lam[i][busy]))
        t[i] = q(t[i] + t_k.max(axis=1))
    return t


def _finish(loads: np.ndarray, lam_rows: np.ndarray,
            rng: np.random.Generator, q: Callable) -> np.ndarray:
    """Finish time of every loaded worker, ``inf`` for idle ones."""
    t = np.full(loads.shape, np.inf)
    busy = loads > 0
    t[busy] = q(rng.gamma(loads[busy], 1.0 / lam_rows[busy]))
    return t


def fixed(lam_rows, N, rng, q=as_f64):
    lam = as_f64(lam_rows)
    loads = lr_round(lam, np.full(lam.shape[0], float(N)))
    t = _finish(loads, lam, rng, q)
    return np.where(loads > 0, t, -np.inf).max(axis=1)


def het_mds(lam_rows, N, redundancy, rng, q=as_f64):
    lam = as_f64(lam_rows)
    total = float(np.ceil(redundancy * N))
    loads = lr_round(lam, np.full(lam.shape[0], total))
    t = _finish(loads, lam, rng, q)
    order = np.argsort(t, axis=1, kind="stable")
    covered = np.cumsum(np.take_along_axis(loads, order, axis=1),
                        axis=1) >= N
    return np.take_along_axis(t, order, axis=1)[
        np.arange(lam.shape[0]), np.argmax(covered, axis=1)]


def hedged(lam_rows, N, rng, q=as_f64):
    lam = as_f64(lam_rows)
    B, K = lam.shape
    rows = np.arange(B)
    spare = np.argmax(lam, axis=1)
    others = np.ones((B, K), dtype=bool)
    others[rows, spare] = False
    loads = lr_round(np.where(others, lam, 0.0), np.full(B, float(N)))
    strag = np.argmin(np.where(loads > 0, lam, np.inf), axis=1)
    t = _finish(loads, lam, rng, q)
    t_copy = q(rng.gamma(loads[rows, strag], 1.0 / lam[rows, spare]))
    t[rows, strag] = np.minimum(t[rows, strag], t_copy)
    return np.where(loads > 0, t, -np.inf).max(axis=1)


def mds(lam_rows, N, L, rng, q=as_f64):
    """T of a (K, L) MDS code per row: the L-th smallest finish time."""
    lam = as_f64(lam_rows)
    t = q(rng.gamma(float(np.ceil(N / L)), 1.0 / lam))
    return np.sort(t, axis=1)[:, L - 1]


def simulate(scheme: str, lam_rows: np.ndarray, config: Dict,
             rng: np.random.Generator, q: Callable = as_f64) -> np.ndarray:
    """T_comp per row for one of the panel's schemes (MDS: ``mds``)."""
    N = int(config["N"])
    if scheme == "fixed":
        return fixed(lam_rows, N, rng, q)
    if scheme == "het_mds":
        return het_mds(lam_rows, N, float(config["het_mds"]["redundancy"]),
                       rng, q)
    if scheme == "hedged":
        return hedged(lam_rows, N, rng, q)
    ex = config["exchange"]
    known = {"work_exchange": True, "work_exchange_unknown": False}[scheme]
    return work_exchange(lam_rows, N, known, float(ex["threshold_frac"]),
                         float(ex["storage_cap_frac"]),
                         int(config["max_iterations"]), rng, q)


def _stats(t: np.ndarray) -> np.ndarray:
    """(mean, std, runs) over the last axis."""
    return np.stack([t.mean(axis=-1), t.std(axis=-1),
                     np.full(t.shape[:-1], float(t.shape[-1]))], axis=-1)


def point_stats(scheme: str, lam: np.ndarray, config: Dict, trials: int,
                rng: np.random.Generator, q: Callable = as_f64,
                block: int = 8192) -> np.ndarray:
    """``(G, 3)`` rows of (mean T_comp, std, trials) over ``trials`` runs
    per grid point, simulated in blocks of rows; for ``mds``, ``(G, K,
    3)``: the same for every code length L = 1 .. K."""
    if scheme == "mds":
        return mds_curve(lam, config, trials, rng, q)
    rows = np.repeat(as_f64(lam), trials, axis=0)
    t = np.concatenate([simulate(scheme, rows[s:s + block], config, rng, q)
                        for s in range(0, rows.shape[0], block)])
    return _stats(t.reshape(lam.shape[0], trials))


def mds_curve(lam: np.ndarray, config: Dict, trials: int,
              rng: np.random.Generator, q: Callable = as_f64) -> np.ndarray:
    N = int(config["N"])
    G, K = lam.shape
    out = np.empty((G, K, 3))
    for g in range(G):
        rows = np.broadcast_to(as_f64(lam[g]), (trials, K))
        for L in range(1, K + 1):
            out[g, L - 1] = _stats(mds(rows, N, L, rng, q))
    return out
