"""Stages inside one shard call, and the process's own gc and compile spans.

The plan's ``shard`` stage times one call into a backend, and inside that
call sit the host→device upload, every program launch and the wait for the
result.  Code below the plan marks each step with ``with stage("upload",
bytes=n):`` (or ``"launch"`` with ``programs=n``, or ``"fetch"``); while
``ExecutionPlan._timed`` runs the call it holds a :class:`Sink` in a
thread-local, which sums each stage's intervals into one sample per call
and, when the call is traced, keeps each run of adjacent intervals of one
stage for a span under the ``shard:<label>`` span.  Outside a shard call
``stage`` hands out a shared do-nothing context: one thread-local lookup.

Two kinds of host time belong to the process rather than to a request, and
a device idle gap can only be named by a span that covers it:

  * ``compile`` — one process-wide ``jax.monitoring`` listener on the
    backend-compile event (``cached`` when the program came from the
    persistent cache).  Inside a shard call it adds a ``compile`` stage
    sample, so ``stats()["stages"]["compile"]`` counts recompiles with
    tracing off; elsewhere, or in an untraced call, it records a process
    span on every watching tracer.
  * ``gc`` — a ``gc.callbacks`` hook (:class:`ProcessSpans`, installed by a
    gateway that holds an enabled tracer) records every generation-2
    collection and any younger one over ``GC_MIN_NS``.
"""
from __future__ import annotations

import gc
import threading
import time

__all__ = ["GC_MIN_NS", "ProcessSpans", "Sink", "install", "stage",
           "watch_compiles"]

#: younger collections shorter than this are not worth a span
GC_MIN_NS = 1_000_000
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# recorded inside the backend-compile event when the persistent cache hits
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_tls = threading.local()
_watchers: tuple = ()  # ProcessSpans that receive compile spans
_watchers_lock = threading.Lock()
_listening = False


class Sink:
    """The stage intervals of one shard call on one thread.

    ``totals``: stage -> summed ns.  ``runs`` (traced calls only, else
    None): ``[stage, t0_ns, t1_ns, attrs]`` in order, adjacent intervals of
    one stage merged and their numeric attributes summed."""

    __slots__ = ("totals", "runs")

    def __init__(self, traced: bool = False):
        self.totals: dict = {}
        self.runs = [] if traced else None

    def add(self, name: str, t0: int, t1: int, attrs: dict,
            merge: bool = True) -> None:
        self.totals[name] = self.totals.get(name, 0) + (t1 - t0)
        if self.runs is None:
            return
        last = self.runs[-1] if self.runs else None
        if merge and last is not None and last[0] == name:
            last[2] = t1
            for k, v in attrs.items():
                numeric = isinstance(v, (int, float))
                last[3][k] = last[3].get(k, 0) + v if numeric else v
        else:
            self.runs.append([name, t0, t1, dict(attrs)])


def install(sink):
    """Make ``sink`` this thread's stage sink (None: none); -> the previous."""
    prev = getattr(_tls, "sink", None)
    _tls.sink = sink
    return prev


class _Stage:
    __slots__ = ("sink", "name", "attrs", "t0")

    def __init__(self, sink: Sink, name: str, attrs: dict):
        self.sink, self.name, self.attrs = sink, name, attrs

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.sink.add(self.name, self.t0, time.perf_counter_ns(), self.attrs)
        return False

    def note(self, **attrs) -> None:
        """Add attributes known only inside the block."""
        self.attrs.update(attrs)


class _NullStage:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **attrs) -> None:
        pass


_NULL_STAGE = _NullStage()


def stage(name: str, **attrs):
    """Time the ``with`` block as stage ``name`` of the current shard call;
    numeric ``attrs`` are summed over the stage's intervals in its span, and
    any other keeps its last value.  ``note(**attrs)`` on the entered stage
    adds attributes from inside the block."""
    sink = getattr(_tls, "sink", None)
    return _NULL_STAGE if sink is None else _Stage(sink, name, attrs)


# ---------------------------------------------------------------------------
# process-level spans
# ---------------------------------------------------------------------------

def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        _tls.cached = True
        return
    if event != COMPILE_EVENT:
        return
    t1 = time.perf_counter_ns()
    t0 = t1 - int(duration_secs * 1e9)
    attrs = {"fun": kw.get("fun_name", "?"),
             "cached": getattr(_tls, "cached", False)}
    _tls.cached = False
    sink = getattr(_tls, "sink", None)
    if sink is not None:
        sink.add("compile", t0, t1, attrs, merge=False)
        if sink.runs is not None:
            return  # recorded under the traced shard span
    for w in _watchers:
        w.tracer.record_process("compile", t0, t1, **attrs)


def watch_compiles() -> None:
    """Register the process-wide compile listener (once; idempotent)."""
    global _listening
    with _watchers_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class ProcessSpans:
    """A tracer's process-level spans: the ``gc`` hook and the compiles
    outside traced shard calls, from :meth:`install` until :meth:`close`."""

    def __init__(self, tracer):
        self.tracer = tracer
        self._t0 = None
        self._hook = self._on_gc  # one bound method, so remove() finds it

    def install(self) -> "ProcessSpans":
        global _watchers
        watch_compiles()
        with _watchers_lock:
            _watchers = _watchers + (self,)
        gc.callbacks.append(self._hook)
        return self

    def close(self) -> None:
        global _watchers
        if self._hook in gc.callbacks:
            gc.callbacks.remove(self._hook)
        with _watchers_lock:
            _watchers = tuple(w for w in _watchers if w is not self)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:
            return
        t1 = time.perf_counter_ns()
        if info["generation"] == 2 or t1 - t0 > GC_MIN_NS:
            self.tracer.record_process("gc", t0, t1,
                                       generation=info["generation"],
                                       collected=info["collected"])
