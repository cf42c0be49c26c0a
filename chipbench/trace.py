"""Profiler trace -> the numbers the per-layer readers take.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small plain
dict (the *reduced trace*): for each device plane its program runs and
per-op totals, and the host spans the harness wrote around each call
(``jax.profiler.TraceAnnotation``).  Everything after that works on the
plain dict, so the arithmetic is tested on a small recorded trace
(``testdata/``) without a chip.

Times are nanoseconds on the profiler's common clock.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

CALL_SPAN = "chipbench.call"
MODULE_LINE = "XLA Modules"          # one event per program run
OP_LINE = "XLA Ops"                  # one event per op (per loop trip)
NAME_CHARS = 400                     # op names are whole HLO lines
HOST_MIN_NS = 10_000                 # shorter host events name no gap

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


def short(name: str) -> str:
    """An op's name without its HLO text (``%while.3 = (...)`` ->
    ``%while.3``)."""
    return name.split(" = ", 1)[0][:120]


def load_xplane(path: Path) -> Dict[str, Any]:
    """Reduce one ``.xplane.pb`` to a small plain dict:

    ``devices``: per TPU plane, the program runs of its module line
    (the op line where a plane has none), whose union is the busy time;
    ``ops``: per TPU plane, total nanoseconds and count per op name
    (names cut to ``NAME_CHARS``), summed over the whole trace -- a
    loop's body ops run once per trip, so they are summed, not kept;
    ``calls``: the harness's call spans; ``host``: the other host
    events, which name what the host did in the device's idle gaps."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    ops: Dict[str, Dict[str, List[int]]] = {}
    calls: List[List[int]] = []
    host: Dict[str, List[Event]] = {}
    for plane in prof.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            tot: Dict[str, List[int]] = {}
            for ev in (lines[OP_LINE].events if OP_LINE in lines else ()):
                t = tot.setdefault(ev.name[:NAME_CHARS], [0, 0])
                t[0] += int(ev.duration_ns)
                t[1] += 1
            runs = lines.get(MODULE_LINE, lines.get(OP_LINE))
            if runs is None:
                continue
            devices[plane.name] = [(ev.name[:NAME_CHARS], int(ev.start_ns),
                                    int(ev.duration_ns))
                                   for ev in runs.events]
            ops[plane.name] = tot
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == CALL_SPAN:
                        calls.append([int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)])
                    elif ev.duration_ns >= HOST_MIN_NS:
                        host.setdefault(line.name, []).append(
                            (ev.name[:NAME_CHARS], int(ev.start_ns),
                             int(ev.duration_ns)))
    calls.sort()
    return {"devices": devices, "ops": ops, "calls": calls, "host": host}


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def save(reduced: Dict[str, Any], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f)


def load(path: Path) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip_total(merged: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the part of ``merged`` (disjoint) inside ``[lo, hi]``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def window(reduced: Dict[str, Any]) -> Tuple[int, int]:
    """The traced window: first call span's start to last one's end."""
    calls = reduced["calls"]
    if not calls:
        raise ValueError("the trace holds no call span")
    return calls[0][0], calls[-1][1]


def device_busy(reduced: Dict[str, Any]) -> Dict[str, List[Tuple[int, int]]]:
    return {plane: union((s, s + d) for _, s, d in evs)
            for plane, evs in reduced["devices"].items()}


def busy_ns(reduced: Dict[str, Any]) -> Optional[float]:
    """Device-busy nanoseconds inside the window, averaged over the
    device planes that ran anything (None when none did)."""
    lo, hi = window(reduced)
    per = [clip_total(m, lo, hi) for m in device_busy(reduced).values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else None


def idle_share(reduced: Dict[str, Any]) -> Optional[float]:
    busy = busy_ns(reduced)
    if busy is None:
        return None
    lo, hi = window(reduced)
    return 1.0 - busy / (hi - lo)


def kernel_ns(reduced: Dict[str, Any], needle: str) -> Optional[int]:
    """Summed device durations of the ops whose name contains
    ``needle``, over all devices (None if none ran)."""
    hits = [t[0] for tot in reduced["ops"].values()
            for name, t in tot.items() if needle in name]
    return sum(hits) if hits else None


def host_ns_per_call(reduced: Dict[str, Any]) -> Optional[float]:
    """Mean over calls of the call span minus the device-busy time
    inside it (on the busiest device)."""
    merged = list(device_busy(reduced).values())
    if not reduced["calls"] or not merged:
        return None
    out = []
    for s, e in reduced["calls"]:
        inside = max(clip_total(m, s, e) for m in merged)
        out.append((e - s) - inside)
    return sum(out) / len(out)


def top_ops(reduced: Dict[str, Any], n: int = 10) -> List[list]:
    """The device operations that took the most time (seconds,
    averaged over devices), by short name."""
    tot: Dict[str, float] = {}
    for per in reduced["ops"].values():
        for name, (ns, _) in per.items():
            tot[short(name)] = tot.get(short(name), 0.0) + ns
    ndev = max(len(reduced["devices"]), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / ndev / 1e9] for name, ns in ranked]


def idle_gaps(reduced: Dict[str, Any], n: int = 10) -> List[list]:
    """The longest idle gaps of the busiest device inside the window,
    each named by the host event that covers most of it, where one
    covers at least half of it; else ``untraced`` (the host ran code
    that records no event, such as numpy)."""
    lo, hi = window(reduced)
    planes = device_busy(reduced)
    if not planes:
        return []
    merged = max(planes.values(), key=lambda m: clip_total(m, lo, hi))
    gaps, cur = [], lo
    for s, e in merged:
        if s > cur and cur < hi:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [ev for evs in reduced["host"].values() for ev in evs]
    out = []
    for g0, g1 in gaps[:n]:
        best, cover = "untraced", (g1 - g0) / 2
        for name, s, d in host:
            c = max(0, min(s + d, g1) - max(s, g0))
            if c >= cover:
                best, cover = name, c
        out.append([short(best), (g1 - g0) / 1e9])
    return out
