"""The comparison that decides ``correct``.

Every call the window ran returned per-point estimates; the plain
reference (``reference_mc``) computes the same estimates from its own
runs once the window has closed.  The numbers compared, each against
the limit in ``limits/<workload>.json`` (per scheme and grid point; an
MDS scheme's answer is compared with the reference's at the code length
L that the call chose):

``call_gap_se``
    Widest gap of one call's mean T_comp from the reference's, in
    combined standard errors (the call's over its trials, the
    reference's over its own).  An answer altered where it is produced
    shows here.
``pooled_gap_se``
    The same for the mean over all calls of the window: a small
    systematic bias, such as lower precision, shows here.
``dispersion``
    How far the spread of the calls' means from call to call departs
    from what each call's own standard error says, as
    ``|mean(var_between / se^2) - 1|`` over a scheme's points, the
    widest over schemes (an MDS scheme's calls are grouped by the L
    they chose).  A call that computes half its trials and copies them
    reads about 1.
``mds_L_excess_pct``
    How far the reference's mean T_comp at the L a call chose lies above
    the reference's least mean over every L, in percent, the widest
    over calls and points: a code length chosen badly shows here.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _se2(std: np.ndarray, n: np.ndarray) -> np.ndarray:
    """Squared standard error of a mean from a population std (ddof 0)
    over ``n`` runs."""
    return std ** 2 / np.maximum(n - 1.0, 1.0)


def _between_var(m: np.ndarray, groups: np.ndarray) -> np.ndarray:
    """Per point (column), the variance of ``m`` (C, G) about the mean of
    its group (calls that chose the same L), pooled over groups; NaN
    where fewer than two degrees of freedom remain."""
    out = np.full(m.shape[1], np.nan)
    for g in range(m.shape[1]):
        ss, dof = 0.0, 0
        for key in np.unique(groups[:, g]):
            sel = m[groups[:, g] == key, g]
            ss += float(((sel - sel.mean()) ** 2).sum())
            dof += sel.size - 1
        if dof >= 2:
            out[g] = ss / dof
    return out


def mc_numbers(answers: Sequence[Dict[str, np.ndarray]],
               ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``answers``: one dict per call, scheme -> ``(G, 4)`` rows (mean,
    std, trials, L); ``ref``: the reference's ``(G, 3)`` rows (mean, std,
    trials) per scheme, or ``(G, K, 3)`` for every L of an MDS scheme."""
    call_gap = pooled_gap = 0.0
    excess: List[float] = []
    out: Dict[str, float] = {}
    for key, r in ref.items():
        a = np.stack([ans[key] for ans in answers])      # (C, G, 4)
        m, s, n = a[..., 0], a[..., 1], a[..., 2]
        L = a[..., 3].astype(np.int64)
        if r.ndim == 3:                                  # per call's L
            G = r.shape[0]
            best = r[..., 0].min(axis=1)
            r = r[np.arange(G)[None, :], L - 1]          # (C, G, 3)
            out["mds_L_excess_pct"] = max(
                out.get("mds_L_excess_pct", 0.0),
                float(np.max(100.0 * (r[..., 0] / best - 1.0))))
        else:
            r = np.broadcast_to(r, m.shape + (3,))
        se2_ref = _se2(r[..., 1], r[..., 2])
        se2 = _se2(s, n)
        call_gap = max(call_gap, float(np.max(
            np.abs(m - r[..., 0]) / np.sqrt(se2 + se2_ref))))
        C = m.shape[0]
        pooled_gap = max(pooled_gap, float(np.max(
            np.abs((m - r[..., 0]).mean(axis=0))
            / np.sqrt(se2.mean(axis=0) / C + se2_ref.mean(axis=0)))))
        var = _between_var(m, L)
        ok = np.isfinite(var)
        if C >= 3 and ok.any():
            excess.append(abs(float(np.mean(
                var[ok] / se2.mean(axis=0)[ok])) - 1.0))
    out.update(call_gap_se=call_gap, pooled_gap_se=pooled_gap)
    if excess:
        out["dispersion"] = max(excess)
    return out


def judge(numbers: Dict[str, float],
          limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each limited number beside its limit; a limit whose number this
    run could not read stands with the reading ``None`` and fails."""
    return {name: {"value": numbers.get(name), "limit": limit}
            for name, limit in limits.items()}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
