"""Fused work-exchange round-pipeline kernel (the ``pallas`` sampler
backend): counter-based Threefry bits + Marsaglia-Tsang Gammas + argmin
straggler selection + normal-limit Binomials in one tiled pass."""
from .kernel import DEFAULT_BLOCK_B, tile_rows, we_rounds_pallas
from .ops import (ENV_MODE, MODES, gamma_rows_grid, lowering_available,
                  one_shot_grid, resolve_mode, we_rounds_grid)
from .ref import cover_time, gamma_rows_reference, we_rounds_reference

__all__ = [
    "DEFAULT_BLOCK_B", "ENV_MODE", "MODES", "cover_time", "gamma_rows_grid",
    "gamma_rows_reference", "lowering_available", "one_shot_grid",
    "resolve_mode", "tile_rows", "we_rounds_grid", "we_rounds_pallas",
    "we_rounds_reference",
]
