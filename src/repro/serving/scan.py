"""The ``jax`` serving backend: one jitted ``lax.scan`` per load sweep.

The numpy engine (``repro.serving.engine``) walks slots in a Python loop,
one ``simulate_serving`` call per offered load.  Here the whole per-slot
step -- arrivals offer -> deadline admission -> placement -> surplus-only
exchange / purge-on-decode -> FIFO service up to per-worker Poisson
budgets -> completion/SLO accounting -- is compiled as ONE ``lax.scan``
over slots, and the ``loads`` sweep rides along as extra trial-block
rows: state is ``(B, Q, K)`` int32/float32 with ``B = len(loads) *
trials``, so a single dispatch produces the whole load-vs-latency curve
for a policy.

Shape discipline is the PR-8 sampler machinery applied to queueing:

* ``Q`` (``max_queue_jobs``), ``K`` (``bucket_cols``), the slot horizon
  ``S`` and the batch ``B`` are padded to pow2 buckets (opt-out
  ``REPRO_SHAPE_BUCKETS=0``) so every ``ServingConfig`` shape family
  shares one compilation -- and one persistent-cache entry.  The
  true sizes travel as traced scalars; the numpy engine's dynamic
  ``q_hi`` slicing becomes masking, padded slots are dead (``live``
  flag), padded workers carry rate 0.
* per-slot schedule rows (drifting / trace scenarios) are pre-stretched
  on the host and read by the scan as indexed xs loads, like the pallas
  drift kernel's direct row read.
* with a grid mesh active (``repro.core.samplers.grid_sharding``) the
  stacked (load x trial) rows shard over the 1-D mesh via ``shard_map``
  with per-device key streams, exactly like ``work_exchange_grid``.

The step body is sort- and scatter-free by construction: XLA CPU
serializes ``sort``/``scatter``/``cumsum`` (reduce-window) per row, and
at one call per slot they dominate the scan wall.  Instead the queue is
stored physically in FIFO order -- active jobs are a contiguous prefix,
admission appends at ``n_active``, completion compacts survivors left
via a comparison-count rank + gather -- so every FIFO prefix sum is a
log-step doubling cumsum and largest-remainder ranks come from
comparison counts.  All replacements are exact (same winners, same
integer sums), so the engine's numbers are bit-identical to the sorted
formulation's.

Two further measured wins shape the dispatch (each proven bitwise
against the plain formulation before landing):

* **host-drawn service budgets.** The per-(slot, row, worker) Poisson
  caps are state-independent, so they are drawn once on the host and
  streamed through the scan's xs instead of folding keys per slot.
  Fixed-units configs then carry *no* in-scan RNG at all -- which is
  what makes the sharded run bitwise equal to the single-device run --
  and only geometric job sizes still consume keys inside the step.
* **dead-state elision + two-tier queue width.** The carry is a dict
  pytree and policy state nobody reads (coded thresholds, hedged
  mirrors, per-job unit counts under fixed sizing) is dropped at trace
  time.  Per-step cost is ~linear in the physical queue width, so
  fixed-units sweeps first run every row at ``_TIER_Q`` physical rows
  with the TRUE admission cap, carry a per-row overflow flag, and
  re-run exactly the flagged rows at full width -- an exact splice
  (rng-free rows are independent), pinned bitwise by
  ``test_queue_tier_splice_bitwise``.

Policies run as scan-compatible pure functions (``_build_policy``),
derived from the same ``DispatchPolicy`` adapters the numpy loop uses;
adapters without a scan form (the ``GenericPolicy`` fallback for future
schemes) transparently drop to the numpy sweep, so registering a scheme
never breaks the jax backend.

Correctness contract: the int32 conservation ledger is carried through
the scan and the exact identity (shipped == served + cancelled +
backlog) is asserted on the final scanned ledger; sojourn percentiles
are recovered from an integer histogram over completion slot-counts
(sojourns are exact multiples of ``slot_dt``), so the host percentile
math is identical to the oracle's pooled path.  The conformance battery
pins this backend to the numpy oracle at 6 combined standard errors.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.samplers import (_shape_buckets_enabled, active_grid_mesh,
                                 bucket_cols)
from repro.core.schemes import MCReport
from repro.core.types import HetSpec

from .config import AUTO_SLOTS_PER_JOB, ServingConfig
from .policies import (CoverPolicy, ExchangePolicy, ExchangeUnknownPolicy,
                       GradientCodedPolicy, HedgedPolicy, MDSPolicy,
                       PooledPolicy, StaticPolicy, UniformPolicy,
                       dispatch_policy)

__all__ = ["scan_sweep"]

# physical queue rows for the first Q-tier pass (see scan_sweep); tests
# may pin it (sys.maxsize disables tiering) to compare against the
# single full-width dispatch
_TIER_Q = 16

def _pow2(n: int, floor: int = 1) -> int:
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# scan-compatible policy forms
# ---------------------------------------------------------------------------
# Exact-type dispatch (not isinstance): every concrete adapter maps to a
# (kind, static_args) pair; anything else -- GenericPolicy or a future
# adapter class -- returns None and the sweep falls back to numpy.

def _policy_static(policy) -> Optional[Tuple[str, Tuple]]:
    t = type(policy)
    if t in (ExchangePolicy, ExchangeUnknownPolicy, PooledPolicy,
             StaticPolicy):
        return ("prop", ())
    if t is UniformPolicy:
        return ("uniform", ())
    if t is MDSPolicy:
        return ("mds", (int(policy.L),))
    if t is CoverPolicy:
        return ("cover", ())
    if t is HedgedPolicy:
        return ("hedged", (int(policy.spare),))
    if t is GradientCodedPolicy:
        return ("gc", (int(policy.s), int(policy.K_eff),
                       int(policy.groups)))
    return None


def _cumsum(jnp, x, axis):
    """Inclusive cumsum by log-step doubling.  XLA CPU lowers
    ``jnp.cumsum`` to a reduce-window -- O(n^2) work per call, and the
    scan body pays it every slot -- while the doubling form is O(n log n)
    shifted adds, ~3x cheaper at the engine's (B, Q, K) shapes.  Exact
    for ints (addition is associative)."""
    n = x.shape[axis]
    d = 1
    while d < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (d, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, n - d)
        x = x + jnp.pad(x[tuple(sl)], pad)
        d *= 2
    return x


def _lr_round_rows_jnp(jnp, w, totals, fallback):
    """``repro.serving.policies.lr_round_rows`` in jnp: row-wise
    largest-remainder rounding; all-zero weight rows fall back to a
    uniform split over ``fallback`` (the real-column mask, so padded
    workers never receive units).

    The remainder ranks come from a comparison-count (stable-descending
    position = #{larger} + #{equal at lower index}), not ``argsort``:
    bitwise-identical winners, and XLA CPU's serial per-row sort -- the
    scan body's dominant cost at (B, K) per arrival -- never runs."""
    s = w.sum(axis=1, keepdims=True)
    w = jnp.where(s > 0, w, fallback[None, :])
    shares = w / w.sum(axis=1, keepdims=True) \
        * totals[:, None].astype(jnp.float32)
    base = jnp.floor(shares).astype(jnp.int32)
    deficit = jnp.clip(totals - base.sum(axis=1), 0, None)
    frac = shares - base
    col = jnp.arange(w.shape[1])
    gt = frac[:, None, :] > frac[:, :, None]
    tie = (frac[:, None, :] == frac[:, :, None]) \
        & (col[None, None, :] < col[None, :, None])
    rank = (gt | tie).sum(axis=2)
    return base + (rank < deficit[:, None]).astype(jnp.int32)


def _build_policy(jnp, kind: str, pargs: Tuple, Kb: int):
    """(place, done) pure functions for one policy kind.

    ``place(u, believed, ctx) -> (shares (B, Kb) i32, ptag (B,) i32)``;
    ``done(R, S0, units, active, aux, ctx) -> (B, Qb) bool``.  ``ctx``
    carries the traced per-sweep values: ``lam_nom`` (Kb,), ``col_mask``
    (Kb,) bool, ``col_mask_f`` (Kb,) f32, ``redundancy`` scalar.
    """
    def drain(R, S0, units, active, aux, ctx):
        return R.sum(axis=2) == 0

    no_tag = None  # placement without a per-job tag

    if kind == "prop":
        def place(u, believed, ctx):
            return _lr_round_rows_jnp(jnp, believed, u,
                                      ctx["col_mask_f"]), no_tag
        return place, drain

    if kind == "uniform":
        def place(u, believed, ctx):
            w = jnp.broadcast_to(ctx["col_mask_f"][None, :],
                                 believed.shape)
            return _lr_round_rows_jnp(jnp, w, u, ctx["col_mask_f"]), no_tag
        return place, drain

    if kind == "mds":
        (L,) = pargs

        def place(u, believed, ctx):
            m = -(-u // L)
            shares = m[:, None] * ctx["col_mask"].astype(jnp.int32)[None, :]
            return shares, no_tag

        def done(R, S0, units, active, aux, ctx):
            return ((S0 > 0) & (R == 0)).sum(axis=2) >= L
        return place, done

    if kind == "cover":
        def place(u, believed, ctx):
            total = jnp.ceil(ctx["redundancy"]
                             * u.astype(jnp.float32)).astype(jnp.int32)
            return _lr_round_rows_jnp(
                jnp, believed, jnp.maximum(total, u),
                ctx["col_mask_f"]), no_tag

        def done(R, S0, units, active, aux, ctx):
            return (S0 * (R == 0)).sum(axis=2) >= units
        return place, done

    if kind == "hedged":
        (spare,) = pargs
        if spare < 0:                       # K == 1: degenerate drain
            def place(u, believed, ctx):
                shares = jnp.zeros((u.shape[0], Kb), dtype=jnp.int32)
                return shares.at[:, 0].set(u), no_tag
            return place, drain

        def place(u, believed, ctx):
            w = believed * ctx["col_mask_f"][None, :]
            w = w.at[:, spare].set(0.0)
            fb = ctx["col_mask_f"].at[spare].set(0.0)
            prim = _lr_round_rows_jnp(jnp, w, u, fb)
            loaded = prim > 0
            keyk = jnp.where(loaded, w, jnp.inf)
            strag = jnp.argmin(keyk, axis=1)
            has = loaded.any(axis=1)
            strag_val = jnp.take_along_axis(prim, strag[:, None],
                                            axis=1)[:, 0]
            shares = prim.at[:, spare].set(jnp.where(has, strag_val, 0))
            ptag = jnp.where(has, strag, -1).astype(jnp.int32)
            return shares, ptag

        def done(R, S0, units, active, aux, ctx):
            col = jnp.arange(Kb)
            prim = (col != spare)[None, None, :] & (S0 > 0)
            undrained = (prim & (R > 0)).sum(axis=2)
            idx = jnp.maximum(aux, 0)[..., None]
            strag_rem = jnp.take_along_axis(R, idx, axis=2)[..., 0]
            strag_und = (aux >= 0) & (strag_rem > 0)
            spare_drained = R[..., spare] == 0
            ok = (undrained - strag_und.astype(jnp.int32) == 0) \
                & (~strag_und | spare_drained)
            return jnp.where(aux >= 0, ok, R.sum(axis=2) == 0)
        return place, done

    if kind == "gc":
        s_, K_eff, groups = pargs

        def place(u, believed, ctx):
            w = jnp.ones((u.shape[0], groups), dtype=jnp.float32)
            blocks = _lr_round_rows_jnp(jnp, w, u,
                                        jnp.ones(groups, jnp.float32))
            shares = jnp.zeros((u.shape[0], Kb), dtype=jnp.int32)
            return shares.at[:, :K_eff].set(
                jnp.repeat(blocks, s_ + 1, axis=1)), no_tag

        def done(R, S0, units, active, aux, ctx):
            B, Q = R.shape[0], R.shape[1]
            grouped = R[..., :K_eff].reshape(B, Q, groups, s_ + 1)
            return (grouped == 0).any(axis=3).all(axis=2)
        return place, done

    raise AssertionError(f"unknown scan policy kind {kind!r}")


# ---------------------------------------------------------------------------
# the compiled engine, one entry per (policy x engine-config x mesh)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _compiled_sweep(static: Tuple):
    """Jitted sweep runner.  ``static`` pins everything that shapes the
    traced program -- policy kind + its static args, the engine flags,
    admission / unit-dist modes, the arrival fori trip count ``A_max``,
    and the active mesh (None = single device).  Array shapes retrace
    inside jit as usual; shape bucketing keeps them stable across
    ServingConfig families."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    (kind, pargs, exchanges, count_comm, purge, uses_est, admission,
     units_dist, A_max, Kb, mesh) = static
    place, done_fn = _build_policy(jnp, kind, pargs, Kb)

    # the block closes over nothing traced; every per-sweep value rides
    # in as an argument so shard_map can partition them explicitly
    def block(seeds, counts, caps, lam_sched, live, warm_f, do_exch,
              slot_idx, q_mask, lam_nom, scal):
        B = counts.shape[1]
        Qb = q_mask.shape[0]
        Hb = counts.shape[0] + 1
        key0 = jax.random.PRNGKey(seeds[0])
        dt, deadline_t, lam_sum, geo_p = (scal[0], scal[1], scal[2],
                                          scal[3])
        n_units = scal[4].astype(jnp.int32)
        k_cap = scal[5].astype(jnp.int32)
        redundancy = scal[6]
        col_mask = jnp.arange(Kb) < k_cap
        col_mask_f = col_mask.astype(jnp.float32)
        ctx = {"lam_nom": lam_nom, "col_mask": col_mask,
               "col_mask_f": col_mask_f, "redundancy": redundancy}

        def believed_of(served_w, busy_w):
            if uses_est:
                return ((served_w + 1.0) / (busy_w + 1.0)
                        ) * col_mask_f[None, :]
            return jnp.broadcast_to(lam_nom[None, :], (B, Kb)) \
                * col_mask_f[None, :]

        iota_q = jnp.arange(Qb)
        # true queue capacity (cfg.max_queue_jobs), NOT the physical row
        # count: under Q-tiering the state may carry fewer rows than the
        # configured cap, and admission must follow the configured cap so
        # a row that never outgrows the physical rows is bit-identical
        # to the full-width run (rows that would outgrow them raise the
        # ``over`` flag and are rerun at full width by the host)
        q_cap = scal[7].astype(jnp.int32)

        # dead-state elision (compile-time): S0 only feeds coded
        # completion tests, per-job units only exist under geometric
        # sizes (fixed sizes fold to the n_units scalar -- integer
        # products, so bit-identical), and the aux tag is hedged-only.
        # Dropping a dead (B, Q, K) array saves its write + compaction
        # gather every slot.
        need_S0 = (kind in ("mds", "cover")
                   or (kind == "hedged" and pargs[0] >= 0))
        need_aux = kind == "hedged" and pargs[0] >= 0
        need_units = units_dist != "fixed"

        def step(st, xs):
            st = dict(st)
            counts_s, cap_s, live_s, warm_s, exch_s, s = xs
            # geometric job sizes are the only in-scan randomness left
            # (service caps ride in as xs); fixed-units configs are
            # rng-free inside the scan, so single-device and sharded
            # runs are bitwise equal
            key_s = (jax.random.fold_in(key0, s)
                     if units_dist == "geometric" else None)
            R = st["R"]
            n_active = st["n"]
            # invariant: active jobs are the queue prefix, in FIFO order
            # (admission appends, completion compacts), and inactive rows
            # carry R == 0 (drain policies finish empty, coded policies
            # purge) -- so FIFO prefix sums are plain cumsums, no sort
            active = iota_q[None, :] < n_active[:, None]

            # -- 1. rebalance: surplus-only re-deal (exchange class) ----
            if exchanges:
                weights = believed_of(st["served_w"], st["busy_w"])
                b = R.sum(axis=1)
                targets = _lr_round_rows_jnp(jnp, weights,
                                             b.sum(axis=1), col_mask_f)
                surplus = jnp.clip(b - targets, 0, None)
                deficit = jnp.clip(targets - b, 0, None)
                behind = b[:, None, :] - _cumsum(jnp, R, 1)
                rm = jnp.clip(jnp.minimum(
                    R, surplus[:, None, :] - behind), 0, None)
                rm_q = rm.sum(axis=2)
                end = _cumsum(jnp, rm_q, 1)
                start = end - rm_q
                db = jnp.concatenate(
                    [jnp.zeros((B, 1), jnp.int32),
                     _cumsum(jnp, deficit, 1)], axis=1)
                add = jnp.clip(
                    jnp.minimum(end[:, :, None], db[:, None, 1:])
                    - jnp.maximum(start[:, :, None], db[:, None, :-1]),
                    0, None)
                apply = exch_s & live_s
                R = jnp.where(apply, R - rm + add, R)
                if count_comm:
                    st["moved_w"] = st["moved_w"] + jnp.where(
                        apply & warm_s,
                        add.sum(axis=(1, 2)).astype(jnp.float32), 0.0)
            st["R"] = R

            def _service(st, active):
                st = dict(st)
                # -- 4. service: per-worker FIFO up to Poisson budgets --
                # the queue is stored in FIFO order, so "work ahead of
                # me" is the exclusive prefix sum -- no per-slot sort;
                # the Poisson budgets are state-independent, so they are
                # drawn host-side and ride in as the ``cap_s`` xs row
                R = st["R"]
                bk_before = R.sum(axis=1)
                ahead = _cumsum(jnp, R, 1) - R
                srv = jnp.minimum(
                    R, jnp.clip(cap_s[:, None, :] - ahead, 0, None))
                R = R - srv
                srv_k = srv.sum(axis=1)
                st["served"] = st["served"] + srv_k.sum(axis=1)
                st["served_w"] = st["served_w"] + srv_k.astype(jnp.float32)
                st["busy_w"] = st["busy_w"] \
                    + dt * (bk_before > 0).astype(jnp.float32)

                # -- 5. completions ------------------------------------
                S0 = st.get("S0")
                units = st["units"] if need_units else n_units
                aux = st.get("aux")
                done = done_fn(R, S0, units, active, aux, ctx) \
                    & active & live_s
                if purge:
                    st["cancelled"] = st["cancelled"] \
                        + (R * done[:, :, None]).sum(axis=(1, 2))
                    R = jnp.where(done[:, :, None], 0, R)
                n_done = done.sum(axis=1)
                st["completed"] = st["completed"] + n_done
                wdone = done & warm_s
                if need_units:
                    st["goodput_w"] = st["goodput_w"] \
                        + (units * wdone).sum(axis=1)
                else:
                    st["goodput_w"] = st["goodput_w"] \
                        + n_units * wdone.sum(axis=1)
                soj = jnp.clip(s + 1 - st["arr"], 0, Hb - 1)
                st["hist"] = st["hist"].at[
                    jnp.arange(B)[:, None], soj].add(
                    wdone.astype(jnp.int32))

                # -- 6. compaction: survivors slide left, order kept ----
                # src index per destination via one-hot reduce (cheap);
                # a sort or scatter here would serialize on CPU like the
                # FIFO sort did
                keep = (active & ~done).astype(jnp.int32)
                kc = _cumsum(jnp, keep, 1)
                n_active = kc[:, -1]
                dest_ok = iota_q[None, :] < n_active[:, None]
                oh = (keep[:, None, :] > 0) \
                    & ((kc - keep)[:, None, :] == iota_q[None, :, None])
                src = (oh * iota_q[None, None, :]).sum(axis=2)
                gather = functools.partial(jnp.take_along_axis,
                                           indices=src, axis=1)
                st["R"] = jnp.where(
                    dest_ok[:, :, None],
                    jnp.take_along_axis(R, src[:, :, None], axis=1), 0)
                if need_S0:
                    st["S0"] = jnp.where(
                        dest_ok[:, :, None],
                        jnp.take_along_axis(S0, src[:, :, None],
                                            axis=1), 0)
                if need_units:
                    st["units"] = jnp.where(dest_ok, gather(units), 0)
                st["arr"] = jnp.where(dest_ok, gather(st["arr"]), 0)
                if need_aux:
                    st["aux"] = jnp.where(dest_ok, gather(aux), -1)
                st["n"] = n_active

                st["qd_sum"] = st["qd_sum"] + jnp.where(
                    warm_s, st["R"].sum(axis=(1, 2)).astype(jnp.float32),
                    0.0)
                st["su_w"] = st["su_w"] \
                    + jnp.where(warm_s, srv_k.sum(axis=1), 0)
                return st, None

            # -- 2+3. arrivals, admission, placement --------------------
            # a new job appends at position n_active (the active prefix
            # grows in arrival order -- first free slot == prefix end).
            # fixed job sizes admit a closed form for the whole slot's
            # arrivals: every candidate carries the same u, so capacity
            # and deadline admission are both "first a of counts_s
            # candidates" thresholds and the A_max fori collapses to one
            # masked write (bit-identical: the loop consumed no rng)
            if units_dist == "fixed":
                st["offered"] = st["offered"] \
                    + jnp.where(warm_s, counts_s, 0)
                a = jnp.minimum(counts_s, q_cap - n_active)
                if admission == "deadline":
                    room = deadline_t * lam_sum \
                        - R.sum(axis=(1, 2)).astype(jnp.float32)
                    a_dl = jnp.floor(
                        room / jnp.maximum(n_units, 1)).astype(jnp.int32)
                    a = jnp.minimum(a, jnp.clip(a_dl, 0, None))
                a = jnp.clip(a, 0, None)
                # exact overflow detection for Q-tiering: the admitted
                # prefix would not fit the physical rows, so this row's
                # trajectory diverges from the full-width run from here
                # on -- flag it for a full-width rerun
                st["over"] = st["over"] | (n_active + a > Qb)
                st["rejected"] = st["rejected"] \
                    + jnp.where(warm_s, counts_s - a, 0)
                u = jnp.full((B,), n_units, jnp.int32)
                believed = believed_of(st["served_w"], st["busy_w"])
                shares, ptag = place(u, believed, ctx)
                newm = (iota_q[None, :] >= n_active[:, None]) \
                    & (iota_q[None, :] < (n_active + a)[:, None])
                st["R"] = jnp.where(newm[:, :, None],
                                    shares[:, None, :], R)
                if need_S0:
                    st["S0"] = jnp.where(newm[:, :, None],
                                         shares[:, None, :], st["S0"])
                st["arr"] = jnp.where(newm, s, st["arr"])
                if need_aux:
                    if ptag is None:
                        ptag = jnp.full((B,), -1, jnp.int32)
                    st["aux"] = jnp.where(newm, ptag[:, None], st["aux"])
                st["n"] = n_active + a
                st["shipped"] = st["shipped"] + a * shares.sum(axis=1)
                active = iota_q[None, :] < st["n"][:, None]
                return _service(st, active)

            def arr_body(j, st2):
                st2 = dict(st2)
                n_act = st2["n"]
                cand = counts_s > j
                st2["offered"] = st2["offered"] + (cand & warm_s)
                kj = jax.random.fold_in(key_s, j)
                uu = jax.random.uniform(kj, (B,))
                u = jnp.maximum(jnp.ceil(
                    jnp.log1p(-uu) / jnp.log1p(-geo_p)), 1.0
                ).astype(jnp.int32)
                ok = cand & (n_act < q_cap)
                if admission == "deadline":
                    pred = (st2["R"].sum(axis=(1, 2)) + u
                            ).astype(jnp.float32) / lam_sum
                    ok = ok & (pred <= deadline_t)
                st2["rejected"] = st2["rejected"] + ((cand & ~ok) & warm_s)
                believed = believed_of(st2["served_w"], st2["busy_w"])
                shares, ptag = place(u, believed, ctx)
                onehot = (iota_q[None, :] == n_act[:, None]) \
                    & ok[:, None]
                st2["R"] = jnp.where(onehot[:, :, None],
                                     shares[:, None, :], st2["R"])
                if need_S0:
                    st2["S0"] = jnp.where(onehot[:, :, None],
                                          shares[:, None, :], st2["S0"])
                st2["units"] = jnp.where(onehot, u[:, None], st2["units"])
                st2["arr"] = jnp.where(onehot, s, st2["arr"])
                if need_aux:
                    if ptag is None:
                        ptag = jnp.full((B,), -1, jnp.int32)
                    st2["aux"] = jnp.where(onehot, ptag[:, None],
                                           st2["aux"])
                st2["n"] = n_act + ok.astype(jnp.int32)
                st2["shipped"] = st2["shipped"] \
                    + jnp.where(ok, shares.sum(axis=1), 0)
                return st2

            st = lax.fori_loop(0, A_max, arr_body, st)
            active = iota_q[None, :] < st["n"][:, None]
            return _service(st, active)

        zi = functools.partial(jnp.zeros, dtype=jnp.int32)
        zf = functools.partial(jnp.zeros, dtype=jnp.float32)
        st0 = {"R": zi((B, Qb, Kb)), "arr": zi((B, Qb)), "n": zi((B,)),
               "served_w": zf((B, Kb)), "busy_w": zf((B, Kb)),
               "shipped": zi((B,)), "served": zi((B,)),
               "cancelled": zi((B,)), "hist": zi((B, Hb)),
               "completed": zi((B,)), "goodput_w": zi((B,)),
               "moved_w": zf((B,)), "qd_sum": zf((B,)),
               "su_w": zi((B,)), "offered": zi((B,)),
               "rejected": zi((B,)), "over": jnp.zeros((B,), bool)}
        if need_S0:
            st0["S0"] = zi((B, Qb, Kb))
        if need_units:
            st0["units"] = zi((B, Qb))
        if need_aux:
            st0["aux"] = jnp.full((B, Qb), -1, jnp.int32)
        xs = (counts, caps, live, warm_f, do_exch, slot_idx)
        st, _ = lax.scan(step, st0, xs)
        backlog = st["R"].sum(axis=(1, 2))
        return (st["shipped"], st["served"], st["cancelled"], backlog,
                st["hist"], st["completed"], st["goodput_w"],
                st["moved_w"], st["qd_sum"], st["su_w"], st["offered"],
                st["rejected"], st["over"])

    if mesh is None or mesh.size <= 1:
        return jax.jit(block)

    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    rows = P(axis)
    rep1 = P(None)
    sharded = jax.shard_map(
        block, mesh=mesh,
        in_specs=(rows,                 # seeds: one stream per device
                  P(None, axis),        # counts (S, B): rows sharded
                  P(None, axis, None),  # caps (S, B, K): rows sharded
                  P(None, None),        # lam_sched, replicated
                  rep1, rep1, rep1, rep1,   # live / warm / exch / slot
                  rep1,                 # q_mask
                  rep1,                 # lam_nom
                  rep1),                # scal
        out_specs=(rows, rows, rows, rows, P(axis, None), rows, rows,
                   rows, rows, rows, rows, rows, rows),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# the sweep: host-side assembly around the compiled scan
# ---------------------------------------------------------------------------

def scan_sweep(het: HetSpec, scheme_name: str,
               params: Optional[Dict[str, Any]], cfg: ServingConfig,
               N: int, trials: int, seed: int, grid_index: int,
               rate_schedule: Optional[np.ndarray] = None
               ) -> List[MCReport]:
    """Every load of one (het, scheme, schedule) cell in ONE dispatch;
    returns one ``MCReport`` per load in ``cfg.loads`` order, extras
    keyed identically to the numpy oracle (plus ``serving_backend``)."""
    policy = dispatch_policy(scheme_name, dict(params or {}), het, N)
    arrival = cfg.build_arrival()
    if arrival.closed_loop:
        raise ValueError(
            "closed-loop arrivals are engine-driven (the resubmission "
            "ring needs per-slot completions); the jax serving backend "
            "cannot precompute the stream -- use the numpy backend")
    static_policy = _policy_static(policy)
    if static_policy is None:
        # adapter without a scan form (GenericPolicy / future classes):
        # future schemes keep working, honestly labelled
        from .backends import _numpy_sweep
        reports = _numpy_sweep(het, scheme_name, params, cfg, N, trials,
                               seed, grid_index, rate_schedule)
        for rep in reports:
            rep.extra["serving_backend"] = "numpy"
        return reports

    T, K, S = int(trials), het.K, int(cfg.slots)
    if T < 1:
        raise ValueError("trials must be >= 1")
    N = int(N)
    lam = np.asarray(het.lambdas, dtype=np.float64)
    lam_sum = float(het.lambda_sum)
    dt = (float(cfg.slot_dt) if cfg.slot_dt is not None
          else N / lam_sum / AUTO_SLOTS_PER_JOB)
    warm = int(float(cfg.warmup_frac) * S)
    window_t = (S - warm) * dt
    horizon_t = S * dt
    deadline_t = (None if cfg.deadline_slo is None
                  else float(cfg.deadline_slo) * N / lam_sum)
    loads = [float(x) for x in cfg.loads]
    L = len(loads)

    buckets = _shape_buckets_enabled()
    Sb = _pow2(S) if buckets else S
    Qb = _pow2(int(cfg.max_queue_jobs)) if buckets \
        else int(cfg.max_queue_jobs)
    Kb = bucket_cols(K)
    mesh = active_grid_mesh()
    D = int(mesh.size) if mesh is not None else 1
    B0 = L * T
    Bb = _pow2(B0, floor=8) if buckets else B0
    Bb = -(-Bb // D) * D                    # device-divisible rows

    # arrivals: each load keeps its own default_rng([seed, g, li]) stream
    # (the engine seed discipline -- cells are independent of the sweep)
    counts = np.zeros((Bb, Sb), dtype=np.int32)
    for li, load in enumerate(loads):
        rng = np.random.default_rng(
            [int(seed) & (2 ** 63 - 1), int(grid_index), li])
        jobs_per_slot = load * lam_sum * dt / N
        counts[li * T:(li + 1) * T, :S] = np.asarray(
            arrival.job_counts(T, S, jobs_per_slot, rng), dtype=np.int32)
    A_max = _pow2(int(counts.max()), floor=1)

    # per-slot true-rate rows, pre-stretched over the horizon; padded
    # worker columns carry rate 0 so Poisson budgets stay dead
    lam_pad = np.zeros(Kb, dtype=np.float32)
    lam_pad[:K] = lam
    lam_sched = np.broadcast_to(lam_pad, (Sb, Kb)).copy()
    if rate_schedule is not None:
        sched = np.asarray(rate_schedule, dtype=np.float64)
        if sched.ndim != 2 or sched.shape[1] != K:
            raise ValueError(f"rate_schedule must be (rounds, K={K}); "
                             f"got {sched.shape}")
        rows = np.minimum(np.arange(S) * sched.shape[0] // S,
                          sched.shape[0] - 1)
        lam_sched[:S, :K] = sched[rows].astype(np.float32)

    sl = np.arange(Sb)
    live = sl < S
    warm_f = (sl >= warm) & live
    every = int(cfg.exchange_every)
    do_exch = (policy.exchanges & (sl > 0) & (sl % every == 0) & live)
    q_mask = np.arange(Qb) < int(cfg.max_queue_jobs)

    rng_dev = np.random.default_rng(
        [int(seed) & (2 ** 63 - 1), int(grid_index), 2 ** 31])
    seeds = rng_dev.integers(0, 2 ** 32, size=(D,), dtype=np.uint32)

    # per-(slot, row, worker) Poisson service budgets: iid given the
    # rate schedule, so drawn up front on the host (dead slots and
    # padded workers carry rate 0 -> cap 0) and streamed in as xs
    rng_cap = np.random.default_rng(
        [int(seed) & (2 ** 63 - 1), int(grid_index), 2 ** 31 + 1])
    caps = rng_cap.poisson(
        lam_sched[:, None, :].astype(np.float64) * dt
        * live[:, None, None], size=(Sb, Bb, Kb)).astype(np.int32)

    redundancy = float(getattr(policy.scheme, "redundancy", 0.0) or 0.0)
    scal = np.array([dt,
                     np.inf if deadline_t is None else deadline_t,
                     lam_sum,
                     1.0 / max(N, 1),
                     float(N),
                     float(K),
                     redundancy,
                     float(cfg.max_queue_jobs)], dtype=np.float32)

    kind, pargs = static_policy
    fn = _compiled_sweep((kind, pargs, bool(policy.exchanges),
                          bool(policy.count_comm), bool(policy.purge),
                          bool(policy.uses_estimates),
                          str(cfg.admission), str(cfg.job_units_dist),
                          A_max, Kb, mesh))
    counts_T = np.ascontiguousarray(counts.T)

    def dispatch(Q_phys: int, counts_x, caps_x):
        qm = np.arange(Q_phys) < int(cfg.max_queue_jobs)
        out = fn(seeds, counts_x, caps_x, lam_sched, live, warm_f,
                 do_exch, sl.astype(np.int32), qm, lam_pad, scal)
        return [np.array(x) for x in out]   # copies: splice writes below

    # Q-tiering: per-step cost is ~linear in the physical queue rows,
    # but the configured cap covers worst-case bursts most rows never
    # reach.  Fixed-units configs are rng-free inside the scan and rows
    # are fully independent, so: run everything with _TIER_Q rows, then
    # rerun exactly the rows whose ``over`` flag shows the admitted
    # prefix outgrew them.  Spliced output is bit-identical to a direct
    # full-width run.  Geometric sizes draw per-(step, batch-position)
    # uniforms, so row subsets would shift their streams -- no tiering.
    use_tier = (str(cfg.job_units_dist) == "fixed" and buckets
                and Qb > _TIER_Q)
    if use_tier:
        out = dispatch(_TIER_Q, counts_T, caps)
        over = out[12].astype(bool)
        if over.any():
            rows = np.nonzero(over)[0]
            B2 = len(rows)
            B2b = _pow2(B2, floor=8) if buckets else B2
            B2b = -(-B2b // D) * D
            c2 = np.zeros((Sb, B2b), np.int32)
            c2[:, :B2] = counts_T[:, rows]
            k2 = np.zeros((Sb, B2b, Kb), np.int32)
            k2[:, :B2] = caps[:, rows, :]
            out2 = dispatch(Qb, c2, k2)
            for i in range(12):
                out[i][rows] = out2[i][:B2]
    else:
        out = dispatch(Qb, counts_T, caps)
    (shipped, served, cancelled, backlog, hist, completed_full,
     goodput_w, moved_w, qd_sum, served_units_w, offered,
     rejected) = out[:12]

    # exact conservation identity on the final scanned ledger
    ok = shipped[:B0] == (served[:B0] + cancelled[:B0] + backlog[:B0])
    if not ok.all():
        bad = int(np.nonzero(~ok)[0][0])
        raise AssertionError(
            f"work conservation violated in the scan backend "
            f"({scheme_name}, row {bad}): shipped {int(shipped[bad])} != "
            f"served {int(served[bad])} + cancelled {int(cancelled[bad])}"
            f" + backlog {int(backlog[bad])}")

    bin_vals = np.arange(Sb + 1, dtype=np.float64) * dt
    reports: List[MCReport] = []
    for li, load in enumerate(loads):
        r = slice(li * T, (li + 1) * T)
        h = hist[r]                              # (T, Hb) warm completions
        cw = h.sum(axis=1)
        sum_soj = (h * bin_vals[None, :]).sum(axis=1)
        per_trial = np.where(cw > 0, sum_soj / np.maximum(cw, 1),
                             horizon_t)
        pooled = h.sum(axis=0)
        if pooled.sum() > 0:
            soj_pool = np.repeat(bin_vals, pooled)
            p50, p95, p99 = (float(x) for x in
                             np.percentile(soj_pool,
                                           [50.0, 95.0, 99.0]))
            latency_censored = False
        else:
            p50 = p95 = p99 = horizon_t
            latency_censored = True
        censored = int((cw == 0).sum())
        extra: Dict[str, Any] = {
            "serving": 1.0,
            "offered_load": float(load),
            "slot_dt": float(dt),
            "p50": p50, "p95": p95, "p99": p99,
            "throughput_jobs": float(cw.mean() / window_t),
            "goodput_units": float(goodput_w[r].mean() / window_t),
            "occupancy": float(served_units_w[r].mean()
                               / (lam_sum * window_t)),
            "queue_depth": float(qd_sum[r].mean() / max(S - warm, 1)),
            "reject_rate": float(rejected[r].sum()
                                 / max(offered[r].sum(), 1)),
            "completed_jobs": float(completed_full[r].mean()),
            "units_admitted": float(shipped[r].mean()),
            "units_served": float(served[r].mean()),
            "units_cancelled": float(cancelled[r].mean()),
            "units_backlog": float(backlog[r].mean()),
        }
        if deadline_t is not None:
            extra["deadline_s"] = float(deadline_t)
            miss_bins = bin_vals > deadline_t + 1e-12
            extra["slo_miss_rate"] = float(
                (pooled * miss_bins).sum() / max(cw.sum(), 1))
        extra["latency_censored"] = 1.0 if latency_censored else 0.0
        if censored:
            extra["censored"] = float(censored)
            extra["censored_frac"] = float(censored / T)
        extra["serving_backend"] = "jax"
        per_cw = cw.astype(np.float64)
        reports.append(MCReport(
            scheme=policy.scheme.name, trials=T,
            t_comp=float(per_trial.mean()),
            t_comp_std=float(per_trial.std()),
            iterations=float(per_cw.mean()),
            iterations_std=float(per_cw.std()),
            n_comm=float(moved_w[r].mean()),
            n_comm_std=float(moved_w[r].std()),
            extra=extra))
    return reports
