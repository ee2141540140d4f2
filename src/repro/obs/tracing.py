"""Structured tracing: span context managers -> Chrome trace-event JSON
and/or the jax profiler's trace.

One process-global :class:`Tracer` records *complete* events ("ph": "X",
wall-clock microseconds + duration) for ``span(...)`` blocks and
*instant* events ("ph": "i") for point occurrences.  The export is the
Chrome trace-event format — load it at ``chrome://tracing`` or
https://ui.perfetto.dev (File > Open).

Disabled (the default) the hot path is one attribute check returning a
shared null context manager: no event objects, no timestamps, no
allocations that survive the call.  Enable explicitly:
``tracing.enable("run.trace.json")`` is what the launch CLIs'
``--trace-out`` does.

Sinks (any combination; the hot path checks one ``_active`` attribute
that folds them together, so the unobserved path stays exactly one
attribute check regardless of how many exist):

- the Chrome export list (``enable()`` / ``enable(out)``);
- the jax profiler (``enable(annotate=True)``): every span enters a
  ``jax.profiler.TraceAnnotation`` carrying the attributes given when
  it opens (a list or other container as its length, ``n_<key>``), so
  spans sit on the profiler's clock beside the device's XLA ops and a
  profile reader gets them as host events with those stats.  Without
  ``out`` this sink keeps no Chrome event list.  Attributes added later
  with ``.set()`` reach the Chrome export only.  jax is imported once,
  when the sink is enabled — this module itself stays stdlib-only;
- a bounded *ring* (``attach_ring``), which the flight recorder keeps
  attached for the whole run: the last N events are always available
  for a post-incident dump even when ``--trace-out`` was never passed.

Thread-safe: events carry the recording thread's id (Perfetto lays
threads out as separate tracks) and the event list is appended under a
lock.
"""
from __future__ import annotations

import atexit
import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Shared do-nothing context manager returned while disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


_CONTAINERS = (list, tuple, set, frozenset, dict)


def _profiler_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """A span's attributes as profiler stats: a container as its
    length under ``n_<key>`` (the profiler keeps scalars and strings)."""
    return {(f"n_{k}" if isinstance(v, _CONTAINERS) else k):
            (len(v) if isinstance(v, _CONTAINERS) else v)
            for k, v in attrs.items()}


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._ann = None

    def set(self, **attrs):
        """Attach/override attributes mid-span (recorded at exit, in the
        Chrome export and the ring; the profiler has the span's opening
        attributes only)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        ann = self._tracer.annotation
        if ann is not None:
            self._ann = (ann(self.name, **_profiler_attrs(self.attrs))
                         if self.attrs else ann(self.name))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t = self._tracer
        if t.enabled or t.ring is not None:
            t._record(self.name, self._t0, t1, self.attrs)
        return False


class Tracer:
    """In-memory trace-event collector (see module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self.enabled = False          # the Chrome export list
        # jax.profiler.TraceAnnotation while the profiler sink is on
        self.annotation: Optional[type] = None
        self.out: Optional[str] = None
        # bounded always-on sink for the flight recorder; None unless
        # attached.  _active = any sink on — the single attribute the
        # hot path checks.
        self.ring: Optional[collections.deque] = None
        self._active = False
        # perf_counter epoch so ts starts near 0 (Perfetto dislikes
        # huge absolute timestamps)
        self._epoch = time.perf_counter()
        self._pid = os.getpid()

    # -- recording --------------------------------------------------------
    def span(self, name: str, attrs: Optional[Dict[str, Any]] = None):
        if not self._active:
            return NULL_SPAN
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        if not (self.enabled or self.ring is not None):
            return
        ts = (time.perf_counter() - self._epoch) * 1e6
        ev = {"name": name, "cat": name.split(".")[0], "ph": "i",
              "s": "t", "ts": ts, "pid": self._pid,
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        with self._lock:
            if self.enabled:
                self.events.append(ev)
            if self.ring is not None:
                self.ring.append(ev)

    def _record(self, name: str, t0: float, t1: float,
                attrs: Optional[Dict[str, Any]]) -> None:
        ev = {"name": name, "cat": name.split(".")[0], "ph": "X",
              "ts": (t0 - self._epoch) * 1e6,
              "dur": (t1 - t0) * 1e6,
              "pid": self._pid, "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        with self._lock:
            if self.enabled:
                self.events.append(ev)
            if self.ring is not None:
                self.ring.append(ev)

    # -- lifecycle --------------------------------------------------------
    def _refresh_active(self) -> None:
        self._active = (self.enabled or self.ring is not None
                        or self.annotation is not None)

    def enable(self, out: Optional[str] = None,
               annotate: bool = False) -> None:
        """Turn the Chrome export list on (written to ``out`` by
        ``export``/atexit) and, with ``annotate``, the profiler sink;
        ``annotate`` without ``out`` turns on the profiler sink alone."""
        if annotate:
            from jax.profiler import TraceAnnotation
            self.annotation = TraceAnnotation
        self.enabled = out is not None or not annotate
        if out is not None:
            self.out = out
        self._refresh_active()

    def disable(self) -> None:
        """Turn the export list and the profiler sink off (an attached
        ring stays)."""
        self.enabled = False
        self.annotation = None
        self._refresh_active()

    def attach_ring(self, maxlen: int = 2048) -> collections.deque:
        """Attach (or resize) the bounded always-on sink; returns the
        deque the flight recorder snapshots at dump time."""
        with self._lock:
            old = list(self.ring) if self.ring is not None else []
            self.ring = collections.deque(old, maxlen=maxlen)
        self._refresh_active()
        return self.ring

    def detach_ring(self) -> None:
        with self._lock:
            self.ring = None
        self._refresh_active()

    def clear(self) -> None:
        with self._lock:
            self.events = []

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            evs = list(self.events)
        return {"displayTimeUnit": "ms", "traceEvents": evs}

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace JSON; returns the path written (None
        when there is nowhere to write)."""
        path = path or self.out
        if path is None:
            return None
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, **attrs):
    """The hot-path entry point: a context manager timing ``name``.
    While no sink is active this is one attribute check and returns
    the shared :data:`NULL_SPAN` (nothing is recorded or kept)."""
    t = _TRACER
    if not t._active:
        return NULL_SPAN
    return _Span(t, name, attrs or None)


def instant(name: str, **attrs) -> None:
    """Record a point event (preemption, retirement, ...)."""
    t = _TRACER
    if t._active:
        t.instant(name, **attrs)


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """Record an already-measured interval in the Chrome export and the
    ring (the profiler takes live spans only); ``t0``/``t1`` must be
    ``time.perf_counter()`` readings (the tracer's clock)."""
    t = _TRACER
    if t.enabled or t.ring is not None:
        t._record(name, t0, t1, attrs or None)


def enabled() -> bool:
    return _TRACER.enabled


def enable(out: Optional[str] = None, annotate: bool = False) -> None:
    _TRACER.enable(out, annotate)


def disable() -> None:
    _TRACER.disable()


def export(path: Optional[str] = None) -> Optional[str]:
    return _TRACER.export(path)


@atexit.register
def _export_atexit() -> None:
    t = _TRACER
    if t.enabled and t.out and t.events:
        try:
            t.export()
        except OSError:
            pass
