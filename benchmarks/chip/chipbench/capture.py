"""Wrappers around the program's layer entry points, and a compile clock.

``Capture``, ``SuperstepCapture`` and ``CompileClock`` are copied from the
repository's ``chip_smoke.py`` and changed in two ways: a capture keeps a
seeded random sample of its calls (a reservoir) instead of the largest
one, and, when tracing, it wraps each call in a ``jax.profiler``
``TraceAnnotation`` so the trace has a host span per layer call.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np


class Reservoir:
    """A uniform sample of at most ``k`` items from a stream of unknown
    length, drawn from ``seed``."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.items: List[Tuple[int, Any]] = []
        self.seen = 0

    def offer(self, make: Callable[[], Any]) -> None:
        """Count one item; build and keep it (``make()``) if drawn."""
        i = self.seen
        self.seen += 1
        if i < self.k:
            self.items.append((i, make()))
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.k:
            self.items[j] = (i, make())

    def sample(self) -> List[Any]:
        return [item for _, item in sorted(self.items, key=lambda t: t[0])]


def span(name: Optional[str]):
    return jax.profiler.TraceAnnotation(name) if name \
        else contextlib.nullcontext()


class Capture:
    """Wraps ``owner.<name>`` while entered: counts its calls, puts each in
    a host span named ``span_name`` (when given), and offers every call to
    ``keep(index, args, kwargs, out)``.  ``method=True`` wraps a method of
    the class ``owner``, whose instance then comes first in ``args``."""

    def __init__(self, owner, name: str, span_name: Optional[str] = None,
                 keep: Optional[Callable] = None, method: bool = False):
        self.owner, self.name = owner, name
        self.span_name, self.keep = span_name, keep
        self.method = method
        self.calls = 0

    def __enter__(self):
        self.real = getattr(self.owner, self.name)
        wrapped = self._wrapped
        if self.method:
            def wrapped(obj, *args, **kwargs):
                return self._wrapped(obj, *args, **kwargs)
        setattr(self.owner, self.name, wrapped)
        return self

    def __exit__(self, *exc):
        setattr(self.owner, self.name, self.real)

    def _wrapped(self, *args, **kwargs):
        with span(self.span_name):
            out = self.real(*args, **kwargs)
        i = self.calls
        self.calls += 1
        if self.keep is not None:
            self.keep(i, args, kwargs, out)
        return out


class FinishLog:
    """Wraps the event engine's ``_finish`` while entered: appends each
    finished item's ``(item, node, decision, finish time)`` to the last
    list of ``log``.  No span: it runs once per item."""

    def __init__(self, driver_cls, log: List[List]):
        self.cls, self.log = driver_cls, log

    def __enter__(self):
        real = self.real = self.cls._finish
        log = self.log

        def _finish(drv, t, node, it, decision, serve_t=None):
            log[-1].append((it, node, decision,
                            t if serve_t is None else serve_t))
            return real(drv, t, node, it, decision, serve_t)
        self.cls._finish = _finish
        return self

    def __exit__(self, *exc):
        self.cls._finish = self.real


class SuperstepCapture(Capture):
    """``superstep._superstep_fn`` returns a jitted program; wrap that
    program so each launch is spanned and offered to ``keep`` with its
    host inputs and its outputs as NumPy arrays."""

    def __init__(self, superstep, span_name=None, keep=None):
        super().__init__(superstep, "_superstep_fn", span_name, keep)

    def _wrapped(self, capacity, n_shards):
        fn = self.real(capacity, n_shards)

        def launch(*args):
            with span(self.span_name):
                out = tuple(np.asarray(o) for o in fn(*args))
            i = self.calls
            self.calls += 1
            if self.keep is not None:
                self.keep(i, args, {"capacity": capacity}, out)
            return out
        return launch


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling, from its
    own monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.total = 0.0
        self.counts = {"traces": 0, "compiles": 0, "cache_loads": 0}
        self.compiled: List[str] = []      # names of backend compiles
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name=None, **_):
        if event in self.EVENTS:
            self.total += secs
        if event == self.EVENTS[0]:
            self.counts["traces"] += 1
        elif event == self.EVENTS[2]:
            self.counts["compiles"] += 1
            self.compiled.append(str(fun_name))
        elif event == self.LOAD:
            self.counts["cache_loads"] += 1

    def snapshot(self):
        return dict(self.counts)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)
