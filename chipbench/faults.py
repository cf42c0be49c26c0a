"""The control and the planted faults the correctness check must fail.

The control is the plain reference put in the program's place, one step
down from the float32 the configuration states: bfloat16.  Each fault
breaks the timed path where it produces its answer.
``chipbench.calibrate`` reads them on the chip at a cell's own size;
the CPU tests plant them at a small one.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator
from unittest import mock

import numpy as np

from chipbench import reference_mc, workloads


def control_answers(cell: workloads.Cell, seed: int) -> Dict[str, np.ndarray]:
    """What the control returns for one call of ``cell``, shaped as
    ``Cell.answers``: an MDS scheme takes the L of its own least mean."""
    rng = np.random.default_rng(seed)
    lam = workloads.het_rates(cell.config)
    out = {}
    for key in cell.schemes:
        st = reference_mc.point_stats(key, lam, cell.config, cell.trials,
                                      rng, reference_mc.as_bf16)
        L = np.zeros(lam.shape[0])
        if st.ndim == 3:
            L = st[..., 0].argmin(axis=1)
            st = st[np.arange(lam.shape[0]), L]
            L = L + 1.0
        out[key] = np.column_stack([st, L])
    return out


@contextlib.contextmanager
def control() -> Iterator[None]:
    """Every call of the window returns the control's answers."""
    def answers(self, result):
        return control_answers(self, int(result.spec.seed))
    with mock.patch.object(workloads.Cell, "answers", answers):
        yield


def _pallas_pair(alter):
    from repro.core import samplers, schemes
    real = samplers.work_exchange_panel_pallas
    backend = dataclasses.replace(
        samplers.get_backend("pallas"),
        work_exchange_panel=lambda *a, **kw: alter(real, *a, **kw))
    get = schemes.get_backend
    return mock.patch.object(
        schemes, "get_backend",
        lambda name: backend if name == "pallas" else get(name))


def _altered_answer(real, lam, N, ck, cu, trials, rng, **kw):
    """One point's T_comp of the known scheme 1% high."""
    out = real(lam, N, ck, cu, trials, rng, **kw)
    t, it, cm = out["known"]
    t = t.copy()
    t[:trials] *= 1.01
    out["known"] = (t, it, cm)
    return out


def _half_batch(real, lam, N, ck, cu, trials, rng, **kw):
    """Half of each point's trials computed, the rest copies of them."""
    out = real(lam, N, ck, cu, trials // 2, rng, **kw)
    G = lam.shape[0]
    return {k: tuple(np.tile(a.reshape(G, trials // 2), 2).ravel()
                     for a in v) for k, v in out.items()}


def _shifted_L():
    """The MDS code length moved 2 below the one the sweep chose (up
    where that would leave fewer than 1), and T_comp drawn at it."""
    from repro.core import schemes
    real = schemes._mds_select_L_grid

    def select(specs, *a, **kw):
        K = specs[0].K
        return [(L - 2 if L > 2 else min(L + 2, K), None)
                for L, _ in real(specs, *a, **kw)]
    return mock.patch.object(schemes, "_mds_select_L_grid", select)


FAULTS = {
    "altered_answer": lambda: _pallas_pair(_altered_answer),
    "half_batch": lambda: _pallas_pair(_half_batch),
    "mds_L_shifted": _shifted_L,
}


def planted(name: str):
    """Context manager that plants fault ``name`` (``FAULTS``)."""
    return FAULTS[name]()
