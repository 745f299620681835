"""Runtime spans: the trainer's, checkpoint manager's and server's own
timing, on the profiler's clock.

``span(name, **ids)`` is a context manager that always

* enters ``jax.profiler.TraceAnnotation(name, **ids)``, so a profiler
  capture (``jax.profiler.trace``/``start_trace``, the profiler server,
  a TensorBoard capture) holds it in its host plane beside the device's
  ops;
* reads ``time.perf_counter_ns()`` at entry and exit, and gives the
  duration as ``.seconds``: the runtime's reports read that.

Only while a capture runs (``TraceAnnotation.is_enabled()``) does it also
append a :class:`Record` (name, start, end, ids, enclosing span on the
same thread) to a bounded process-wide list, which :func:`captured`
returns and :func:`clear` empties.  The capture is the only switch: with
none running a span costs one TraceMe, two clock reads, one check and its
own three Python calls (about 2 us on a TPU v5e host).

A ``jax.monitoring`` listener, registered at import, appends a
``repro.compile`` record for every jaxpr trace, MLIR lowering, backend
compile and persistent-cache retrieval, with ``fun_name`` and ``event``.
It records with or without a capture: compiles are rare, and come before
any capture would.  A cache retrieval lies inside the backend compile
that asked for it, and a nested jit's trace inside its caller's, so the
compile time of a stretch is the union of its records, not their sum.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import NamedTuple, Optional

import jax

__all__ = ["COMPILE", "Record", "captured", "clear", "span"]

COMPILE = "repro.compile"
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
})
MAX_RECORDS = 1 << 16

_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_local = threading.local()     # .span: the innermost open span, per thread
_annotation = jax.profiler.TraceAnnotation
_capturing = _annotation.is_enabled
_now = time.perf_counter_ns


class Record(NamedTuple):
    name: str
    start_ns: int          # time.perf_counter_ns()
    end_ns: int
    ids: dict
    parent: Optional[str]  # the enclosing span's name, on the same thread

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


# a capture traces every Python call, so records are built without one
_record = functools.partial(tuple.__new__, Record)


class span:
    """``with span("repro.train.step", step=n) as s: ...``, then
    ``s.seconds`` (see the module docstring)."""

    __slots__ = ("name", "ids", "start_ns", "end_ns", "_outer", "_trace")

    def __init__(self, name: str, **ids):
        self.name, self.ids = name, ids
        self._trace = _annotation(name, **ids)

    def __enter__(self) -> "span":
        self._outer = getattr(_local, "span", None)
        _local.span = self
        self._trace.__enter__()
        self.start_ns = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = _now()
        self._trace.__exit__(*exc)
        outer = _local.span = self._outer
        if _capturing():
            _records.append(_record((self.name, self.start_ns, self.end_ns,
                                     self.ids, outer and outer.name)))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def captured() -> list:
    """The records kept so far, oldest first."""
    return list(_records)


def clear() -> None:
    _records.clear()


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    if event in COMPILE_EVENTS:
        end = _now()
        _records.append(Record(
            COMPILE, end - int(duration_s * 1e9), end,
            {"fun_name": kwargs.get("fun_name", ""), "event": event}, None))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
