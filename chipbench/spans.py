"""The program's own spans in a reduced trace -> per-call milliseconds.

The program names its layers with ``jax.profiler.TraceAnnotation``
spans called ``repro.*`` (``repro.tracing``); they land among the host
events that ``trace.load_xplane`` keeps (those of 10 us or more, by
thread line), beside the runtime's own events such as ``XlaLinearize``.
Everything here works on that plain dict.  A reader returns None when
the trace holds none of the spans it reads, as a trace of a program
without them does.

Times are nanoseconds; every total is clipped to the harness's call
spans and divided by the number of calls traced.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace

PREFIX = "repro."
STUDY = "repro.study"                 # the whole call: no layer of its own
WE_ROUNDS = "repro.we_rounds"
LINEARIZE = "XlaLinearize"            # the runtime's host-side input layout

Interval = Tuple[int, int]


def intervals(reduced: Dict[str, Any],
              names: Iterable[str]) -> List[Interval]:
    """``(start, end)`` of every host event named in ``names``, on any
    thread line."""
    names = set(names)
    return [(s, s + d) for evs in reduced["host"].values()
            for name, s, d in evs if name in names]


def span_names(reduced: Dict[str, Any]) -> set:
    return {name for evs in reduced["host"].values() for name, _, _ in evs
            if name.startswith(PREFIX)}


def in_calls_ns(merged: Sequence[Interval],
                calls: Sequence[Sequence[int]]) -> int:
    """Length of the part of ``merged`` (disjoint) inside the calls."""
    return sum(trace.clip_total(merged, s, e) for s, e in calls)


def per_call_ms(reduced: Dict[str, Any], ns: float) -> Optional[float]:
    calls = len(reduced["calls"])
    return ns / calls / 1e6 if calls else None


def span_ms_per_call(reduced: Dict[str, Any],
                     names: Iterable[str]) -> Optional[float]:
    """Milliseconds per call in which any span of ``names`` is open
    (their union, clipped to the calls); None when none is in the
    trace."""
    found = intervals(reduced, names)
    if not found:
        return None
    return per_call_ms(reduced, in_calls_ns(trace.union(found),
                                            reduced["calls"]))


def linearize_ms_per_call(reduced: Dict[str, Any]) -> Optional[float]:
    """Milliseconds per call of the runtime's ``XlaLinearize`` events
    (on any host thread) that start inside a ``repro.we_rounds`` span:
    the host laying out the kernel's inputs for the transfer.  Summed
    over threads, each thread's events merged first.  None when the
    trace holds no ``repro.we_rounds`` span."""
    spans = trace.union(intervals(reduced, [WE_ROUNDS]))
    if not spans:
        return None
    total = 0
    for evs in reduced["host"].values():
        hits = [(s, s + d) for name, s, d in evs if name == LINEARIZE
                and any(a <= s < b for a, b in spans)]
        total += in_calls_ns(trace.union(hits), reduced["calls"])
    return per_call_ms(reduced, total)


def busiest_plane(reduced: Dict[str, Any]) -> List[Interval]:
    """The busy intervals of the device plane busiest in the window
    (none when no plane ran anything)."""
    planes = list(trace.device_busy(reduced).values())
    if not planes or not reduced["calls"]:
        return []
    lo, hi = trace.window(reduced)
    return max(planes, key=lambda m: trace.clip_total(m, lo, hi))


def untraced_ms_per_call(reduced: Dict[str, Any]) -> Optional[float]:
    """Milliseconds per call in which the busiest device is idle and no
    ``repro.*`` span but ``repro.study`` is open: host time the
    program's spans do not name.  None when the trace holds no such
    span."""
    layers = span_names(reduced) - {STUDY}
    if not layers:
        return None
    covered = trace.union(intervals(reduced, layers)
                          + busiest_plane(reduced))
    total = sum(e - s for s, e in reduced["calls"])
    return per_call_ms(reduced, total - in_calls_ns(covered,
                                                    reduced["calls"]))
