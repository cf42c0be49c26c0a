"""Named spans and counters on the Monte Carlo path.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: with no profiler
running it costs well under a microsecond, and under
``jax.profiler.trace`` it lands in the profiler's own trace, on the
clock of the device events, under the ``repro.*`` names the call sites
give.  It is a shared no-op when jax has not been imported, so the
numpy engines never import jax.  Spans carry no arguments: the name is
all a reader of the trace gets.

``count(name, n)`` adds to a process-wide integer counter;
``counters()`` returns a copy of them all.
"""
from __future__ import annotations

import contextlib
import sys
from typing import ContextManager, Dict

_NO_SPAN = contextlib.nullcontext()
_COUNTERS: Dict[str, int] = {}


def span(name: str) -> ContextManager:
    """A profiler span named ``name`` (a no-op until jax is imported)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    """A copy of every counter of this process."""
    return dict(_COUNTERS)


__all__ = ["count", "counters", "span"]
