"""Spans and counters of the served path, on the device trace's clock.

One ``Telemetry`` registry per service (a zoo shares one across its
members).  ``span(name, **ids)`` enters a
``jax.profiler.TraceAnnotation("llms:" + name, **ids)`` — so a
profiler session records it on the host plane of the device trace,
where each device program is dispatched inside the span of the entry
that issued it — and times the span on
``time.perf_counter``, the clock the router stamps requests with.
Per name it keeps ``count``, ``total_s``, ``self_s`` (total minus the
time its child spans on the same thread cover) and ``max_s``;
``count(name, n)`` adds to a counter.  Spans are always recorded:
whether the annotations go anywhere is up to the profiler session.

Thread-safe: the swap tier's workers record spans from their own
threads, each thread keeping its own stack of open spans.  One leaf
lock (DESIGN.md §8) guards the totals; nothing is acquired under it.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict

from jax.profiler import TraceAnnotation

from repro.analysis.runtime import witness_lock

PREFIX = "llms:"


class _Span:
    """One span; while open it is also its thread's stack frame: its
    start, and the time of its children closed since the registry's
    reset ``epoch``."""

    __slots__ = ("tel", "name", "ann", "t0", "child_s", "epoch")

    def __init__(self, tel: "Telemetry", name: str, ids: Dict[str, Any]):
        self.tel = tel
        self.name = name
        self.ann = TraceAnnotation(PREFIX + name, **ids)

    def __enter__(self):
        self.ann.__enter__()
        tel = self.tel
        self.child_s = 0.0
        self.epoch = tel._epoch
        try:
            tel._tls.stack.append(self)
        except AttributeError:
            tel._tls.stack = [self]
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        stack = self.tel._tls.stack
        stack.pop()
        self.tel._close(self, t1, stack[-1] if stack else None)
        self.ann.__exit__(*exc)
        return False


class Telemetry:
    """Span and counter registry (see the module docstring)."""

    def __init__(self):
        self._lock = witness_lock("telemetry")
        self._tls = threading.local()
        self._epoch = 0
        self._t_reset = time.perf_counter()
        self._spans: Dict[str, list] = {}    # name -> [n, total, self, max]
        self._counters: Dict[str, int] = {}

    def span(self, name: str, **ids) -> _Span:
        """Context manager timing one span of ``name``; ``ids`` (such as
        ``ctx=<cid>``) go to the trace event only."""
        return _Span(self, name, ids)

    def count(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter(self, name: str) -> int:
        """The count of ``name`` since the last reset (0 if none)."""
        with self._lock:
            return self._counters.get(name, 0)

    def _close(self, span: _Span, t1: float, parent):
        with self._lock:
            epoch = self._epoch
            # a span open across reset() counts from the reset on, with
            # only the children that closed after it
            dt = t1 - max(span.t0, self._t_reset)
            if dt < 0.0:            # closed just before a reset
                dt = 0.0
            own = dt - span.child_s if span.epoch == epoch else dt
            if own < 0.0:           # rounding of the children's sum
                own = 0.0
            if parent is not None:
                if parent.epoch != epoch:
                    parent.epoch, parent.child_s = epoch, 0.0
                parent.child_s += dt
            s = self._spans.get(span.name)
            if s is None:
                s = self._spans[span.name] = [0, 0.0, 0.0, 0.0]
            s[0] += 1
            s[1] += dt
            s[2] += own
            if dt > s[3]:
                s[3] = dt

    def reset(self):
        """Clear every span total and counter; ``elapsed_s`` restarts."""
        with self._lock:
            self._epoch += 1
            self._t_reset = time.perf_counter()
            self._spans = {}
            self._counters = {}

    def snapshot(self) -> Dict[str, Any]:
        """-> {"elapsed_s", "spans": {name: {"count", "total_s",
        "self_s", "max_s"}}, "counters": {name: n}}.  Spans still open
        are not in it."""
        with self._lock:
            return {
                "elapsed_s": time.perf_counter() - self._t_reset,
                "spans": {k: {"count": n, "total_s": tot, "self_s": slf,
                              "max_s": mx}
                          for k, (n, tot, slf, mx) in self._spans.items()},
                "counters": dict(self._counters),
            }
