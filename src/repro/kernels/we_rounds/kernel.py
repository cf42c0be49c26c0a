"""Pallas kernel for the work-exchange exchange-round pipeline.

One ``pallas_call`` fuses counter-based bit generation (Threefry-2x32,
keyed per ``(trial, worker, round)``), the Marsaglia-Tsang Gamma
transform, the per-trial argmin straggler selection, and the normal-limit
Binomial into a single tiled pass over the ``(trials x K)`` grid: grid =
``(B / block_b,)``, each program owns a ``(block_b, K)`` tile of trials
and runs the whole exchange-round ``while_loop`` to completion in VMEM --
state never round-trips to HBM between rounds, and the only HBM traffic
is one read of the rate tile and one write of the three per-trial stats.

Because every draw is a pure function of ``(seed, row, worker, round,
slot)`` (see ``ref.py``, which owns all the math), the kernel in
interpret mode is bit-identical to ``we_rounds_reference`` for any
``block_b``, and padding rows cannot perturb real ones; compiled for a
TPU it matches the reference statistically (see ``ref.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

DEFAULT_BLOCK_B = 128     # the tallest tile, and the rows' padding quantum
# Bytes of live round state a tile may hold: ``ref.round_body`` keeps
# about ``LIVE_TILES`` float32 ``(block_b, K)`` arrays live (the state, a
# round's five variates, the Gamma and Binomial temporaries, the shares),
# each laid out over whole 128-lane vregs.
TILE_STATE_BUDGET = 2 * 1024 * 1024
LIVE_TILES = 20


def tile_rows(K: int) -> int:
    """The kernel's tile height for ``K`` worker columns: the tallest
    power of two from 8 (one sublane tile) to ``DEFAULT_BLOCK_B`` whose
    ``LIVE_TILES`` float32 ``(rows, K)`` arrays fit ``TILE_STATE_BUDGET``.
    A power of two divides every row bucket (``core.samplers._rows_target``
    pads to 128 or more).  128 at K <= 128; 8 at K = 2,048."""
    lanes = -(-int(K) // 128) * 128
    fit = TILE_STATE_BUDGET // (LIVE_TILES * 4 * lanes)
    return max(8, min(DEFAULT_BLOCK_B, 1 << max(fit, 1).bit_length() - 1))


def _we_rounds_kernel(seed_ref, lam_ref, out_ref, *, K: int, block_b: int,
                      n0: float, threshold: float, cap: float, known: bool,
                      max_iter: int):
    _we_rounds_body(seed_ref, lam_ref, None, None, out_ref, K=K,
                    block_b=block_b, n0=n0, threshold=threshold, cap=cap,
                    known=known, max_iter=max_iter)


def _we_rounds_drift_kernel(seed_ref, lam_ref, sched_ref, out_ref, *,
                            K: int, block_b: int, n0: float,
                            threshold: float, cap: float, known: bool,
                            max_iter: int):
    _we_rounds_body(seed_ref, lam_ref, sched_ref, None, out_ref, K=K,
                    block_b=block_b, n0=n0, threshold=threshold, cap=cap,
                    known=known, max_iter=max_iter)


def _we_rounds_panel_kernel(seed_ref, lam_ref, flags_ref, out_ref, *,
                            K: int, block_b: int, n0: float,
                            threshold: float, cap: float, known: bool,
                            max_iter: int):
    _we_rounds_body(seed_ref, lam_ref, None, flags_ref, out_ref, K=K,
                    block_b=block_b, n0=n0, threshold=threshold, cap=cap,
                    known=known, max_iter=max_iter)


def _we_rounds_panel_drift_kernel(seed_ref, lam_ref, sched_ref, flags_ref,
                                  out_ref, *, K: int, block_b: int,
                                  n0: float, threshold: float, cap: float,
                                  known: bool, max_iter: int):
    _we_rounds_body(seed_ref, lam_ref, sched_ref, flags_ref, out_ref, K=K,
                    block_b=block_b, n0=n0, threshold=threshold, cap=cap,
                    known=known, max_iter=max_iter)


def _we_rounds_body(seed_ref, lam_ref, sched_ref, flags_ref, out_ref, *,
                    K: int, block_b: int, n0: float, threshold: float,
                    cap: float, known: bool, max_iter: int):
    k0 = seed_ref[0, 0]
    k1 = seed_ref[0, 1]
    lam = lam_ref[...]
    inv_lam = 1.0 / lam
    # fused-panel mixed mode: per-row known flag, estimator state for all
    known_col = None if flags_ref is None else flags_ref[...] > 0
    if sched_ref is None:
        sched_at = None
    else:
        R = sched_ref.shape[1]

        def sched_at(rnd):
            # direct round-indexed row load from the (block_b, R, K)
            # schedule tile: one dynamic slice per trip instead of the
            # old O(block_b * R * K) one-hot masked sum
            r = jnp.minimum(rnd, R - 1)
            return sched_ref[:, pl.ds(r, 1), :][:, 0, :]
    base = pl.program_id(0) * block_b
    row_ids = base + jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)

    def cond(st):
        return (st["active"] > 0).any()

    def body(st):
        return ref.round_body(st, lam, inv_lam, row_ids, k0, k1, K=K,
                              cap=cap, threshold=threshold, known=known,
                              max_iter=max_iter, sched_at=sched_at,
                              known_col=known_col)

    st = jax.lax.while_loop(
        cond, body, ref.init_state(block_b, K, n0, threshold, known,
                                   lam=lam, with_round=sched_ref is not None))
    sched = None if sched_ref is None else sched_ref[...]
    t, it, cm = ref.final_phase(st, lam, inv_lam, row_ids, k0, k1, K=K,
                                known=known, max_iter=max_iter, sched=sched,
                                known_col=known_col)
    out_ref[...] = jnp.stack([t, it, cm], axis=1)


def we_rounds_pallas(lam_rows: jnp.ndarray, seed: jnp.ndarray,
                     sched_rows: jnp.ndarray = None,
                     known_flags: jnp.ndarray = None, *,
                     n0: float, threshold: float, cap: float, known: bool,
                     max_iter: int, block_b: int = None,
                     interpret: bool = False) -> jnp.ndarray:
    """Run the fused round pipeline; returns ``(B, 3)``:
    ``[:, 0] = t_comp``, ``[:, 1] = iterations``, ``[:, 2] = n_comm``.

    ``B`` must be a multiple of ``block_b`` (default ``tile_rows(K)``;
    callers pad -- see ``ops.we_rounds_grid``); ``seed`` is a ``(1, 2)``
    uint32 array shared by every tile.  ``sched_rows`` (optional
    ``(B, R, K)``) adds the drifting-scenario per-round rate schedule as
    a third input: each
    program carries its tile's ``(block_b, R, K)`` schedule in VMEM and
    reads the current round's rates with one ``pl.ds`` dynamic slice on
    the trip counter (counters are untouched, so drift runs keep the same
    bit streams as the reference).  ``known_flags`` (optional ``(B, 1)``
    float32, nonzero = known) is the fused-panel mixed mode: known and
    unknown rows of a whole figure share ONE launch, each row reading its
    own flag (``known`` is then ignored; pass ``known=False``).
    """
    B, K = lam_rows.shape
    block_b = tile_rows(K) if block_b is None else int(block_b)
    assert B % block_b == 0, f"pad B={B} to a multiple of {block_b}"
    kern_fn = {
        (False, False): _we_rounds_kernel,
        (True, False): _we_rounds_drift_kernel,
        (False, True): _we_rounds_panel_kernel,
        (True, True): _we_rounds_panel_drift_kernel,
    }[(sched_rows is not None, known_flags is not None)]
    kernel = functools.partial(kern_fn, K=K, block_b=block_b, n0=n0,
                               threshold=threshold, cap=cap, known=known,
                               max_iter=max_iter)
    in_specs = [
        pl.BlockSpec((1, 2), lambda i: (0, 0)),
        pl.BlockSpec((block_b, K), lambda i: (i, 0)),
    ]
    args = (seed, lam_rows)
    if sched_rows is not None:
        R = sched_rows.shape[1]
        in_specs.append(pl.BlockSpec((block_b, R, K), lambda i: (i, 0, 0)))
        args += (sched_rows,)
    if known_flags is not None:
        in_specs.append(pl.BlockSpec((block_b, 1), lambda i: (i, 0)))
        args += (known_flags,)
    return pl.pallas_call(
        kernel,
        grid=(B // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 3), jnp.float32),
        interpret=interpret,
        name="we_rounds",
    )(*args)
