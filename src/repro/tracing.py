"""Spans and counters of the serve path (DESIGN.md §19).

One mechanism for every layer, from the service loop down to the engine
and the judge pool:

- ``span(name, rows=0, **args)`` is a context manager. It opens a
  ``jax.profiler.TraceAnnotation`` named ``krites.<name>``, so under a
  running profiler every span sits in the trace on the device trace's
  clock (without one the annotation is inert), and it always adds to an
  in-memory aggregate per name: calls, seconds, self seconds (minus its
  direct children on the same thread), rows and the longest call.
- ``add(name, value)`` is a counter: count, sum and largest value. Each
  value is also a zero-length ``krites.<name>`` event with a ``value``
  argument, so a trace carries the counters too.
- ``snapshot(reset=False)`` returns a plain-JSON copy of everything.

Two runtime hooks are installed at import: backend compiles counted
under the innermost open span as ``compile.<span>`` (seconds as the
value; a load from the persistent compile cache is not a compile), and
each garbage collection recorded as the span ``gc`` with its
generation.

There is no switch: a span costs a few microseconds against batches of
hundreds of milliseconds.
"""
from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager

import jax

PREFIX = "krites."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.RLock()     # re-entered by a collection inside a record
_spans: dict = {}   # name -> [calls, seconds, self_seconds, rows, max_seconds]
_counters: dict = {}          # name -> [n, sum, max]
_local = threading.local()    # .stack of open spans, .loads, .gc


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """``with span("policy.embed", rows=n):`` — see the module doc. The
    annotation's arguments are ``args`` and ``rows`` when not 0."""

    __slots__ = ("name", "rows", "_ann", "_t0", "_child")

    def __init__(self, name: str, rows: int = 0, **args):
        self.name, self.rows = name, rows
        if rows:
            args["rows"] = rows
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        self._ann.__enter__()
        self._child = 0.0
        _stack().append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        st = _stack()
        st.pop()
        if st:
            st[-1]._child += dt
        self._ann.__exit__(*exc)
        with _lock:
            a = _spans.get(self.name)
            if a is None:
                a = _spans[self.name] = [0, 0.0, 0.0, 0, 0.0]
            a[0] += 1
            a[1] += dt
            a[2] += dt - self._child
            a[3] += self.rows
            a[4] = max(a[4], dt)
        return False


@contextmanager
def locked(lock, name: str):
    """Hold ``lock`` for the block; the wait to acquire it is the span
    ``name``."""
    with span(name):
        lock.acquire()
    try:
        yield
    finally:
        lock.release()


def add(name: str, value: float) -> None:
    """Count ``value`` under counter ``name``."""
    value = float(value)
    with jax.profiler.TraceAnnotation(PREFIX + name, value=value):
        pass
    with _lock:
        c = _counters.get(name)
        if c is None:
            _counters[name] = [1, value, value]
        else:
            c[0] += 1
            c[1] += value
            c[2] = max(c[2], value)


def snapshot(reset: bool = False) -> dict:
    """``{"spans": {name: {calls, seconds, self_seconds, rows,
    max_seconds}}, "counters": {name: {n, sum, max}}}`` since import or
    the last reset; ``reset`` clears the aggregates after copying."""
    with _lock:
        spans, counters = dict(_spans), dict(_counters)
        if reset:
            _spans.clear()
            _counters.clear()
    keys_s = ("calls", "seconds", "self_seconds", "rows", "max_seconds")
    return {"spans": {k: dict(zip(keys_s, v)) for k, v in spans.items()},
            "counters": {k: dict(zip(("n", "sum", "max"), v))
                         for k, v in counters.items()}}


def compiles() -> tuple:
    """(backend compiles, their seconds) counted since import or the last
    reset, cache loads excluded."""
    with _lock:
        cs = [v for k, v in _counters.items()
              if k == "compile" or k.startswith("compile.")]
    return sum(c[0] for c in cs), sum(c[1] for c in cs)


# -- runtime hooks ----------------------------------------------------------

def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:      # fires inside the compile it replaces
        _local.loads = getattr(_local, "loads", 0) + 1


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    if getattr(_local, "loads", 0):
        _local.loads -= 1             # a persistent-cache load
        return
    st = _stack()
    add(f"compile.{st[-1].name}" if st else "compile", secs)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _local.gc = span("gc", gen=info["generation"]).__enter__()
    else:
        s = getattr(_local, "gc", None)
        if s is not None:
            _local.gc = None
            s.__exit__(None, None, None)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
gc.callbacks.append(_on_gc)
