"""Trace spans: Chrome-trace-format timelines for the serving/adaptation loop.

A :class:`TraceRecorder` collects events in the `Chrome Trace Event format
<https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU>`_
(load the saved JSON in ``chrome://tracing`` / Perfetto): an
admission -> prefill -> splice -> decode -> retire request lifetime renders
as one visually inspectable timeline.  Recording is **opt-in and host-side
only**: with no recorder installed every hook is a global read + early
return, and nothing here ever enters a traced computation — instrumented
paths stay bit-identical (tested).

Surface:

* ``with span("prefill", rid=3):`` — a complete ("X") event timing the
  block.  Each carries an ``id``, and ``args.parent`` holds the id of the
  span that encloses it on its thread (None at the top).  While a recorder
  is installed the block is also a ``jax.profiler.TraceAnnotation``, so a
  running profiler shows the span on its host plane, on the device
  trace's clock, next to the device ops.
* ``instant("splice", slot=2)`` — a zero-duration marker ("i").
* ``async_begin("request", 7)`` / ``async_end("request", 7)`` — an async
  ("b"/"e") pair spanning a request's whole queue->retire lifetime across
  waves/steps (Chrome draws them as arrows above the thread tracks).
* ``device_trace(logdir)`` — opt-in context manager around
  ``jax.profiler.start_trace`` for device-level deep dives next to the
  host-side timeline (XLA/TensorBoard trace; heavyweight, never on by
  default).
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import Optional

__all__ = [
    "TraceRecorder",
    "install_recorder",
    "current_recorder",
    "span",
    "instant",
    "async_begin",
    "async_end",
    "device_trace",
]


class TraceRecorder:
    """In-memory Chrome-trace event buffer (microsecond timestamps relative
    to recorder creation; ``pid`` is the OS pid, ``tid`` the Python thread
    ident, so multi-threaded servers get one track per thread)."""

    def __init__(self):
        self._events = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._ids = itertools.count(1)
        self._local = threading.local()      # per-thread stack of open spans

    # -- clock ---------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _push(self, ev) -> None:
        with self._lock:
            self._events.append(ev)

    def _base(self, name: str, ph: str, cat: str, args: dict) -> dict:
        return dict(name=name, ph=ph, cat=cat, pid=os.getpid(),
                    tid=threading.get_ident(), ts=self.now_us(),
                    args={k: _jsonable(v) for k, v in args.items()})

    # -- span nesting --------------------------------------------------
    def _open_span(self):
        """A new span id and the id of the span open around it on this
        thread (None at the top); the new span is open until
        :meth:`_close_span`."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        stack.append(sid)
        return sid, parent

    def _close_span(self, sid: int) -> None:
        stack = self._local.stack
        if stack[-1] == sid:
            stack.pop()
        else:                                # closed out of order
            stack.remove(sid)

    # -- event kinds ---------------------------------------------------
    def _push_span(self, name, cat, start_us, dur_us, sid, parent,
                   args) -> None:
        # the hot path of span(): a tuple now, the event dict in events()
        self._push((name, cat, threading.get_ident(), start_us, dur_us, sid,
                    parent, args))

    def instant(self, name: str, cat: str = "serve", **args) -> None:
        ev = self._base(name, "i", cat, args)
        ev["s"] = "t"                      # thread-scoped instant
        self._push(ev)

    def async_begin(self, name: str, ident, cat: str = "request",
                    **args) -> None:
        ev = self._base(name, "b", cat, args)
        ev["id"] = str(ident)
        self._push(ev)

    def async_end(self, name: str, ident, cat: str = "request",
                  **args) -> None:
        ev = self._base(name, "e", cat, args)
        ev["id"] = str(ident)
        self._push(ev)

    # -- output --------------------------------------------------------
    def events(self) -> list:
        with self._lock:
            evs = list(self._events)
        pid = os.getpid()
        return [e if isinstance(e, dict) else _span_event(pid, *e)
                for e in evs]

    def to_json(self) -> str:
        return json.dumps({"traceEvents": self.events(),
                           "displayTimeUnit": "ms"}, indent=None)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
            f.write("\n")
        return path


_PLAIN = (int, float, str, bool, type(None))


def _span_event(pid, name, cat, tid, start_us, dur_us, sid, parent,
                args) -> dict:
    args = {k: _jsonable(v) for k, v in args.items()}
    args["parent"] = parent
    return dict(name=name, ph="X", cat=cat, pid=pid, tid=tid, ts=start_us,
                args=args, dur=dur_us, id=sid)


def _jsonable(v):
    if type(v) in _PLAIN:                # the common case, without a dump
        return v
    try:
        json.dumps(v)
        return v
    except TypeError:
        return repr(v)


_CURRENT: Optional[TraceRecorder] = None


def install_recorder(rec: Optional[TraceRecorder]) -> Optional[TraceRecorder]:
    """Install (or, with ``None``, remove) the process trace recorder;
    returns the previous one so callers can restore it."""
    global _CURRENT
    prev, _CURRENT = _CURRENT, rec
    return prev


def current_recorder() -> Optional[TraceRecorder]:
    return _CURRENT


_ANNOTATION = None       # jax.profiler.TraceAnnotation, imported on first use


class _Span:
    """One recorded span (see :func:`span`)."""

    __slots__ = ("rec", "name", "cat", "args", "sid", "parent", "t0", "ann")

    def __init__(self, rec: TraceRecorder, name: str, cat: str, args: dict):
        self.rec, self.name, self.cat, self.args = rec, name, cat, args

    def __enter__(self):
        global _ANNOTATION
        if _ANNOTATION is None:
            from jax.profiler import TraceAnnotation as _ANNOTATION
        self.sid, self.parent = self.rec._open_span()
        self.ann = _ANNOTATION(self.name)
        self.ann.__enter__()
        self.t0 = self.rec.now_us()
        return self

    def __exit__(self, *exc):
        rec = self.rec
        t1 = rec.now_us()
        self.ann.__exit__(*exc)
        rec._close_span(self.sid)
        rec._push_span(self.name, self.cat, self.t0, t1 - self.t0, self.sid,
                       self.parent, self.args)
        return False


_NO_SPAN = contextlib.nullcontext()


def span(name: str, cat: str = "serve", **args):
    """Time a block as a complete trace event (a context manager).  The
    no-recorder case returns a shared null context — safe to leave on hot
    host loops."""
    rec = _CURRENT
    if rec is None:
        return _NO_SPAN
    return _Span(rec, name, cat, args)


def instant(name: str, cat: str = "serve", **args) -> None:
    rec = _CURRENT
    if rec is not None:
        rec.instant(name, cat=cat, **args)


def async_begin(name: str, ident, cat: str = "request", **args) -> None:
    rec = _CURRENT
    if rec is not None:
        rec.async_begin(name, ident, cat=cat, **args)


def async_end(name: str, ident, cat: str = "request", **args) -> None:
    rec = _CURRENT
    if rec is not None:
        rec.async_end(name, ident, cat=cat, **args)


@contextlib.contextmanager
def device_trace(logdir: str):
    """Opt-in ``jax.profiler`` device trace around a block (writes an
    XLA/TensorBoard trace under ``logdir``).  Heavyweight — pair it with the
    host-side spans only for deep dives (``launch/serve --device-trace``)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
