"""The program's own spans and scopes.

* :func:`scope` names a region of traced code.  It is ``jax.named_scope``:
  it acts while a function is traced, writes its name into the
  ``metadata.op_name`` of every HLO instruction the region lowers to, and
  costs nothing when the compiled program runs.  Scope names are fixed
  (``union/down<l>/bucket``, ``engine/update``, ...) so that a reader of
  the compiled text can attribute device time to them.
* :func:`span` times a region of host code.  Off, the default, it returns
  one shared null context after one branch.  After :func:`enable` it enters
  a ``jax.profiler.TraceAnnotation`` of the same name, so the span lands on
  the profiler's host plane when a trace is being recorded, and keeps
  ``(name, parent, start_ns, end_ns)`` in memory on the
  ``time.perf_counter_ns`` clock, read by :func:`spans`.  Every host span
  name starts with ``repro.``.

Counts have no API here: they live on the per-instance dicts that already
exist (``SparseAllreduce.union_plan_stats``, ``GraphEngine.report``).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import List, NamedTuple, Optional

import jax

scope = jax.named_scope

_NULL = contextlib.nullcontext()
_on = False
_records: List["Span"] = []
_open = threading.local()      # per thread: names of the spans entered


class Span(NamedTuple):
    """One finished host span; ``parent`` is the enclosing span's name."""
    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int


class _Timed:
    __slots__ = ("name", "parent", "start", "note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.note = jax.profiler.TraceAnnotation(self.name)
        self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.note.__exit__(*exc)
        _open.stack.pop()
        _records.append(Span(self.name, self.parent, self.start, end))
        return False


def span(name: str):
    """A context timing the host code it wraps (module docstring)."""
    if not _on:
        return _NULL
    return _Timed(name)


def enable(on: bool = True) -> None:
    """Turn host spans on (or off again with ``on=False``)."""
    global _on
    _on = bool(on)


def spans() -> List[Span]:
    """The spans finished since the last :func:`reset`, in finishing order."""
    return list(_records)


def reset() -> None:
    """Forget the recorded spans."""
    _records.clear()
