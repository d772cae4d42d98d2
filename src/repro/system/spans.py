"""Host spans of one served call: where its wall time goes, phase by phase.

``run_query`` makes one ``Spans`` per call and hands it to the frontend
and to every engine stage.  ``span(name, **ids)`` wraps a phase where it
runs and does two things at once:

* it adds the phase's inclusive seconds, and its self seconds (inclusive
  less the time its child spans cover), to per-name totals, from which
  ``QueryReport.stage_timings`` is filled;
* it opens a ``jax.profiler.TraceAnnotation`` of the same name and ids,
  so under a profiler session the phase lands on the profiler's host
  plane, on the same clock as the device's ``XLA Ops`` line.

Every span carries ``call=<n>``, a per-process call counter, so all spans
of one call share an identifier; tick spans add ``tick=<k>``.  Spans
cover phases, never single items or events: per-item work is timed by
the span of the phase that loops over the items.
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, List

import jax

_CALLS = itertools.count(1)


class Spans:
    """Per-name inclusive and self seconds of one call's spans."""

    def __init__(self):
        self.call = next(_CALLS)
        self.inclusive: Dict[str, float] = {}
        self.exclusive: Dict[str, float] = {}
        self._open: List[float] = []     # child seconds of each open span

    def span(self, name: str, **ids) -> "_Span":
        return _Span(self, name, ids)

    def total(self, name: str) -> float:
        """Inclusive seconds of every ``name`` span so far (0 if none)."""
        return self.inclusive.get(name, 0.0)

    def self_s(self, name: str) -> float:
        """Self seconds of every ``name`` span so far (0 if none)."""
        return self.exclusive.get(name, 0.0)


class _Span:
    __slots__ = ("spans", "name", "note", "t0")

    def __init__(self, spans: Spans, name: str, ids: Dict[str, int]):
        self.spans, self.name = spans, name
        self.note = jax.profiler.TraceAnnotation(name, call=spans.call, **ids)

    def __enter__(self) -> "_Span":
        self.note.__enter__()
        self.spans._open.append(0.0)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        sp, name = self.spans, self.name
        child = sp._open.pop()
        sp.inclusive[name] = sp.inclusive.get(name, 0.0) + dt
        sp.exclusive[name] = sp.exclusive.get(name, 0.0) + dt - child
        if sp._open:
            sp._open[-1] += dt
        self.note.__exit__(*exc)
