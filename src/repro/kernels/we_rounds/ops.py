"""Dispatch layer for the ``we_rounds`` kernel package.

``we_rounds_grid`` is what the ``pallas`` sampler backend calls: it pads
the batch to a tile multiple, picks an execution mode, and returns numpy
arrays.  Modes (``REPRO_WE_ROUNDS_MODE`` or the ``mode=`` kwarg):

``auto``
    Compiled Pallas kernel when a Pallas-lowering backend (TPU) is
    attached, otherwise the jitted jnp reference -- the path CPU CI runs.
``kernel`` / ``interpret``
    Force the Pallas kernel, compiled / in interpreter mode.  Interpret
    mode executes the *actual kernel code* on CPU (slowly), which is what
    the ``pallas-interpret`` CI job exercises.
``reference``
    Force the jitted jnp oracle.

``interpret`` and ``reference`` are bit-identical on real rows
(counter-based draws -- see ``ref.py``).  ``kernel`` draws the same bits
but its float math rounds differently on a TPU, so it agrees with them
statistically; mode selection is a performance choice.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import numpy as np

from repro.tracing import count, span

from .kernel import tile_rows, we_rounds_pallas
from .ref import (gamma_rows_reference, one_shot_reference,
                  we_rounds_reference, we_rounds_reference_panel)

ENV_MODE = "REPRO_WE_ROUNDS_MODE"
MODES = ("auto", "kernel", "interpret", "reference")


def lowering_available() -> bool:
    """True when the attached jax backend compiles Pallas TPU kernels."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_mode(mode: Optional[str] = None) -> str:
    name = mode or os.environ.get(ENV_MODE) or "auto"
    if name not in MODES:
        raise KeyError(f"unknown we_rounds mode {name!r}; have {MODES}")
    if name == "auto":
        return "kernel" if lowering_available() else "reference"
    return name


# The jitted entries are named functions: the device trace names each
# program after its function (``jit_we_rounds``, ``jit_we_rounds_panel``,
# ``jit_gamma_rows``), so readers of a trace need not match generated names.

@functools.lru_cache(maxsize=None)
def _jit_reference(n0: float, threshold: float, cap: float, known: bool,
                   max_iter: int):
    import jax

    def we_rounds(lam_rows, seed, sched=None):
        return we_rounds_reference(lam_rows, seed, sched, n0=n0,
                                   threshold=threshold, cap=cap,
                                   known=known, max_iter=max_iter)

    return jax.jit(we_rounds)


@functools.lru_cache(maxsize=None)
def _jit_kernel(n0: float, threshold: float, cap: float, known: bool,
                max_iter: int, block_b: int, interpret: bool):
    import jax

    def we_rounds(lam_rows, seed, sched=None):
        return we_rounds_pallas(lam_rows, seed, sched, n0=n0,
                                threshold=threshold, cap=cap, known=known,
                                max_iter=max_iter, block_b=block_b,
                                interpret=interpret)

    return jax.jit(we_rounds)


@functools.lru_cache(maxsize=None)
def _jit_reference_panel(n0: float, threshold: float, cap: float,
                         max_iter: int):
    import jax

    def we_rounds_panel(lam_rows, seed, flags, sched=None):
        return we_rounds_reference_panel(lam_rows, seed, flags, sched,
                                         n0=n0, threshold=threshold,
                                         cap=cap, max_iter=max_iter)

    return jax.jit(we_rounds_panel)


@functools.lru_cache(maxsize=None)
def _jit_kernel_panel(n0: float, threshold: float, cap: float,
                      max_iter: int, block_b: int, interpret: bool):
    import jax

    def we_rounds_panel(lam_rows, seed, flags, sched=None):
        return we_rounds_pallas(lam_rows, seed, sched, flags, n0=n0,
                                threshold=threshold, cap=cap, known=False,
                                max_iter=max_iter, block_b=block_b,
                                interpret=interpret)

    return jax.jit(we_rounds_panel)


@functools.lru_cache(maxsize=None)
def _jit_sharded(mesh, n0: float, threshold: float, cap: float, known: bool,
                 max_iter: int, block_b: int, mode: str,
                 drift: bool = False, panel: bool = False):
    """shard_map wrapper over the per-mode fn, cached per (mesh, config).

    Each device runs the whole pipeline on its block of rows with its own
    seed pair (one ``(D, 2)`` seed matrix, one row per device), so shards
    never synchronize.  ``drift`` adds the per-round rate
    schedule as a batch-sharded input; ``panel`` is the fused mixed-mode
    launch, which adds the per-row known flags (row-sharded like the
    rates -- a flag travels with its row).
    """
    import jax
    from jax.sharding import PartitionSpec

    if panel:
        if mode == "reference":
            fn = _jit_reference_panel(n0, threshold, cap, max_iter)

            def block(seeds_b, lam_b, flags_b):
                return fn(lam_b, seeds_b[0], flags_b)

            def block_drift(seeds_b, lam_b, flags_b, sched_b):
                return fn(lam_b, seeds_b[0], flags_b, sched_b)
        else:
            fn = _jit_kernel_panel(n0, threshold, cap, max_iter, block_b,
                                   mode == "interpret")

            def block(seeds_b, lam_b, flags_b):
                out = fn(lam_b, seeds_b, flags_b)
                return out[:, 0], out[:, 1], out[:, 2]

            def block_drift(seeds_b, lam_b, flags_b, sched_b):
                out = fn(lam_b, seeds_b, flags_b, sched_b)
                return out[:, 0], out[:, 1], out[:, 2]
    elif mode == "reference":
        fn = _jit_reference(n0, threshold, cap, known, max_iter)

        def block(seeds_b, lam_b):
            return fn(lam_b, seeds_b[0])

        def block_drift(seeds_b, lam_b, sched_b):
            return fn(lam_b, seeds_b[0], sched_b)
    else:
        fn = _jit_kernel(n0, threshold, cap, known, max_iter, block_b,
                         mode == "interpret")

        def block(seeds_b, lam_b):
            out = fn(lam_b, seeds_b)
            return out[:, 0], out[:, 1], out[:, 2]

        def block_drift(seeds_b, lam_b, sched_b):
            out = fn(lam_b, seeds_b, sched_b)
            return out[:, 0], out[:, 1], out[:, 2]

    spec = PartitionSpec(mesh.axis_names[0])
    n_in = 2 + (1 if panel else 0)
    if drift:
        return jax.jit(jax.shard_map(block_drift, mesh=mesh,
                                     in_specs=(spec,) * (n_in + 1),
                                     out_specs=spec, check_vma=False))
    return jax.jit(jax.shard_map(block, mesh=mesh, in_specs=(spec,) * n_in,
                                 out_specs=spec, check_vma=False))


def _pad_rows(rows: Optional[np.ndarray], pad: int) -> Optional[np.ndarray]:
    if rows is None or pad == 0:
        return rows
    return np.concatenate([rows, np.repeat(rows[:1], pad, axis=0)])


def we_rounds_grid(lam_rows: np.ndarray, seed, *, n0: float,
                   threshold: float, cap: float, known,
                   max_iter: int, mode: Optional[str] = None,
                   block_b: Optional[int] = None, mesh=None,
                   rate_schedule: Optional[np.ndarray] = None,
                   real_rows: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused round pipeline over ``(B, K)`` rate rows -> per-row
    ``(t_comp, iterations, n_comm)`` float64 numpy arrays.

    ``seed`` is a pair of uint32 (any sequence of two ints).  ``B`` is
    padded to a multiple of ``block_b`` (default ``kernel.tile_rows(K)``)
    with copies of row 0 (counters are per global row, so padding never
    alters real rows).

    ``known`` is a bool (the single-scheme path) or a ``(B,)`` per-row
    flag array -- the fused-panel mixed mode, where known and unknown
    work-exchange rows of a whole figure run in ONE launch (``cap``
    applies to the unknown rows; known rows are uncapped).

    ``mesh`` (a 1-D jax Mesh, e.g. from ``grid_sharding``) shards the row
    axis across its devices via ``shard_map``; ``seed`` must then be a
    ``(mesh.size, 2)`` matrix, one independent seed pair per device.
    Sharded runs are NOT bit-identical to single-device runs (different
    counter keying), but interpret and reference agree bitwise at a
    fixed layout.

    ``rate_schedule`` (optional ``(B, R, K)``, row-aligned with
    ``lam_rows``) is the drifting-scenario per-round schedule; every mode
    (kernel / interpret / reference) consumes it identically, so drift
    runs keep the interpret/reference bit-identity.

    ``real_rows`` (default ``B``) is how many leading rows are real
    trials; the rest are the caller's padding.  Each call adds to the
    ``repro.tracing`` counters (see ``count_row_rounds``).
    """
    import jax.numpy as jnp

    with span("repro.we_rounds"):
        lam_rows = np.asarray(lam_rows, dtype=np.float32)
        if lam_rows.ndim != 2:
            raise ValueError(f"lam_rows must be (B, K); got "
                             f"{lam_rows.shape}")
        B, K = lam_rows.shape
        block_b = tile_rows(K) if block_b is None else int(block_b)
        sched = None
        if rate_schedule is not None:
            sched = np.asarray(rate_schedule, dtype=np.float32)
            if sched.ndim != 3 or sched.shape[0] != B:
                raise ValueError(f"rate_schedule must be (B={B}, R, K); "
                                 f"got {sched.shape}")
        flags = None
        if not isinstance(known, (bool, np.bool_)):
            flags = np.asarray(known, dtype=np.float32).reshape(-1, 1)
            if flags.shape[0] != B:
                raise ValueError(f"per-row known flags must have one entry "
                                 f"per row (B={B}); got {flags.shape[0]}")
            known = False
        mode = resolve_mode(mode)
        sharded = mesh is not None and mesh.size > 1
        if sharded:
            D = int(mesh.size)
            seed_arr = np.asarray(seed, dtype=np.uint32).reshape(D, 2)
            # every device block must be a whole number of kernel tiles
            quantum = D if mode == "reference" else D * block_b
            pad = (-B) % quantum
            fn = _jit_sharded(mesh, float(n0), float(threshold), float(cap),
                              bool(known), int(max_iter), int(block_b), mode,
                              drift=sched is not None,
                              panel=flags is not None)
            # in reference mode each device runs one loop over its block
            tile = (B + pad) // D if mode == "reference" else block_b
        else:
            seed_arr = np.asarray(seed, dtype=np.uint32).reshape(2)
            pad = 0 if mode == "reference" else (-B) % block_b
            if mode == "reference":
                fn = (_jit_reference(float(n0), float(threshold), float(cap),
                                     bool(known), int(max_iter))
                      if flags is None else
                      _jit_reference_panel(float(n0), float(threshold),
                                           float(cap), int(max_iter)))
                tile = B
            else:
                interpret = mode == "interpret"
                fn = (_jit_kernel(float(n0), float(threshold), float(cap),
                                  bool(known), int(max_iter), int(block_b),
                                  interpret)
                      if flags is None else
                      _jit_kernel_panel(float(n0), float(threshold),
                                        float(cap), int(max_iter),
                                        int(block_b), interpret))
                seed_arr = seed_arr[None, :]
                tile = block_b
        rows = tuple(_pad_rows(a, pad) for a in (lam_rows, flags, sched))
        # the sharded entry takes the seeds first, the others second
        host = ((seed_arr,) + rows if sharded
                else (rows[0], seed_arr) + rows[1:])
        with span("repro.we_rounds.h2d"):
            args = tuple(jnp.asarray(a) for a in host if a is not None)
        with span("repro.we_rounds.launch"):
            out = fn(*args)
            if not isinstance(out, tuple):     # the kernel's (B, 3) block
                out = out[:, 0], out[:, 1], out[:, 2]
        with span("repro.we_rounds.wait"):
            t, it, cm = (np.asarray(a, dtype=np.float64) for a in out)
        count_row_rounds(it, B if real_rows is None else int(real_rows),
                         tile)
    return t[:B], it[:B], cm[:B]


def count_row_rounds(it: np.ndarray, real_rows: int, tile: int) -> None:
    """Add one launch's rows, tiles and row-rounds to the
    ``repro.tracing`` counters.

    ``we_rounds.rows`` gains ``real_rows`` and ``we_rounds.tiles`` the
    launch's tiles.  ``it`` is the launch's whole per-row ``iterations``
    output, padding included, in tile order; a tile of ``tile`` rows
    loops until its slowest row is done.
    ``we_rounds.row_rounds_useful`` gains the sum of ``it`` over the
    first ``real_rows`` rows, and
    ``we_rounds.row_rounds_executed`` gains ``tile`` times the sum over
    tiles of the tile's largest ``it``: padding rows are executed, never
    useful.  ``it`` counts the final phase's one round (``ref.py``'s
    ``final_phase``) on both sides, so a tile whose slowest row had
    rounds left at the threshold executes its loop's trips plus one.
    ``tile`` is ``block_b`` for the Pallas kernel and the whole batch
    (one ``while_loop``) for the reference.
    """
    count("we_rounds.rows", int(real_rows))
    count("we_rounds.tiles", it.size // tile)
    count("we_rounds.row_rounds_useful", int(it[:real_rows].sum()))
    count("we_rounds.row_rounds_executed",
          tile * int(it.reshape(-1, tile).max(axis=1).sum()))


@functools.lru_cache(maxsize=4)
def _jit_gamma_rows(boost: bool):
    import jax

    def gamma_rows(shape_rows, scale_rows, seed):
        return gamma_rows_reference(shape_rows, scale_rows, seed,
                                    boost=boost)

    return jax.jit(gamma_rows)


def gamma_rows_grid(shape_rows: np.ndarray, scale_rows: np.ndarray,
                    seed) -> np.ndarray:
    """Counter-based ``Gamma(shape) * scale`` over ``(R, K)`` rows in one
    jitted dispatch (the MDS L-sweep primitive of the pallas backend;
    shape/scale broadcast against each other).  The boost chain -- and
    its two extra Threefry calls per element -- is compiled in only when
    some shape is below 3.  Output stays float32 (the pipeline dtype)."""
    import jax.numpy as jnp

    shape_rows = np.asarray(shape_rows, dtype=np.float32)
    scale_rows = np.asarray(scale_rows, dtype=np.float32)
    seed_arr = np.asarray(seed, dtype=np.uint32).reshape(2)
    boost = bool((shape_rows < 3.0).any())
    out = _jit_gamma_rows(boost)(jnp.asarray(shape_rows),
                                 jnp.asarray(scale_rows),
                                 jnp.asarray(seed_arr))
    return np.asarray(out)


@functools.lru_cache(maxsize=8)
def _jit_one_shot(trials: int, boost: bool):
    import jax

    def one_shot(loads, scales, weights, need, seed):
        return one_shot_reference(loads, scales, weights, need, seed,
                                  trials=trials, boost=boost)

    return jax.jit(one_shot)


def one_shot_grid(loads: np.ndarray, scales: np.ndarray,
                  weights: np.ndarray, need: np.ndarray, trials: int,
                  seed) -> np.ndarray:
    """Completion times of a one-shot scheme, ``(G * trials,)`` float32,
    grid-major, in one jitted dispatch (``ref.one_shot_reference``).

    Only the compact ``(G, K)`` loads, scales and cover weights and the
    ``(G,)`` needs go to the device; the rows are repeated, drawn and
    reduced there, and one float per trial comes back.  Every one-shot
    rule is a weighted cover, so one program serves them all: ``seed``
    (2 x uint32) and every other input are traced, and a grid shape
    compiles once.  Weights and needs go as int32, so the cover's sums
    are exact (``ref.cover_time``).  The boost chain compiles in only
    when some positive load is below 3.  Adds the rows to the
    ``schemes.cover_rows`` counter.
    """
    loads = np.asarray(loads, dtype=np.float32)
    boost = bool(((loads > 0) & (loads < 3)).any())
    with span("repro.one_shot"):
        out = _jit_one_shot(int(trials), boost)(
            loads, np.asarray(scales, dtype=np.float32),
            np.asarray(weights, dtype=np.int32),
            np.asarray(need, dtype=np.int32),
            np.asarray(seed, dtype=np.uint32).reshape(2))
        out = np.asarray(out)
    count("schemes.cover_rows", out.size)
    return out
