"""The live workers' real payload: row-block shards of one A @ x.

Unit ``u`` is the row block ``A[u*rows:(u+1)*rows]``; a worker assigned
a queue of units computes the concatenated block's matvec in ONE jitted
call per round (padded to a power-of-two unit count so a handful of
traces serve every queue length).

The drawn Exp(1/lambda_k) service clock -- not the matmul wall time --
governs pacing (the worker sleeps out the remainder), so the executed
run matches the paper's service model statistically while still doing
real FLOPs whose throughput the telemetry records.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _matvec(a, x):
    return a @ x


def _bucket(n: int) -> int:
    """Next power-of-two unit count: few shapes, few (re)traces."""
    return 1 << max(int(n) - 1, 0).bit_length()


class MatmulPayload:
    """One shared ``A @ x`` product, computed live in unit row-blocks."""

    def __init__(self, units: int, unit_rows: int, unit_dim: int,
                 seed: int = 0):
        self.units = int(units)
        self.unit_rows = int(unit_rows)
        self.unit_dim = int(unit_dim)
        rng = np.random.default_rng(seed)
        rows = self.units * self.unit_rows
        self.A = rng.standard_normal((rows, self.unit_dim)).astype(
            np.float32)
        self.x = rng.standard_normal(self.unit_dim).astype(np.float32)
        self.y = np.zeros(rows, dtype=np.float32)
        self.done = np.zeros(self.units, dtype=bool)
        self.flops = 0              # multiply-adds issued so far

    def _rows_for(self, unit_ids: Sequence[int]) -> np.ndarray:
        ids = np.asarray(unit_ids, dtype=np.int64)
        return (ids[:, None] * self.unit_rows
                + np.arange(self.unit_rows)[None, :]).reshape(-1)

    def compute(self, unit_ids: Sequence[int]) -> Tuple[int, int]:
        """Compute the blocks for ``unit_ids``; returns (units, rows)."""
        if len(unit_ids) == 0:
            return 0, 0
        rows = self._rows_for(unit_ids)
        block = self.A[rows]
        pad_units = _bucket(len(unit_ids))
        pad_rows = pad_units * self.unit_rows
        if pad_rows > block.shape[0]:
            block = np.concatenate(
                [block, np.zeros((pad_rows - block.shape[0],
                                  self.unit_dim), dtype=np.float32)])
        y = np.asarray(_matvec(jnp.asarray(block), jnp.asarray(self.x)))
        self.y[rows] = y[: rows.size]
        self.done[np.asarray(unit_ids, dtype=np.int64)
                  % self.units] = True
        self.flops += rows.size * self.unit_dim
        return len(unit_ids), int(rows.size)

    def warmup(self, max_units: int) -> None:
        """Trace/compile every bucket up to ``max_units`` ahead of the
        episode clock, so compile time never pollutes measured spans."""
        n = 1
        while True:
            ids = list(range(min(n, self.units)))
            self.compute(ids)
            if n >= max_units:
                break
            n *= 2
        self.done[:] = False
        self.flops = 0

    def verify(self) -> bool:
        """Every computed block matches the reference product."""
        if not self.done.any():
            return True
        rows = self._rows_for(np.nonzero(self.done)[0])
        ref = (self.A[rows] @ self.x).astype(np.float32)
        return bool(np.allclose(self.y[rows], ref, rtol=1e-4, atol=1e-4))


__all__ = ["MatmulPayload"]
