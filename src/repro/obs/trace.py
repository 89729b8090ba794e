"""Host-side spans on the profiler's clock.

Every :func:`span` is a ``jax.profiler.TraceAnnotation`` under its bare
name: while a profiler trace records (any ``jax.profiler.start_trace``)
the span lands on the trace's host plane, on the same clock as the device
ops, nested in the spans open around it on its thread.  At TRACE level the span is also timed and emitted as a
JSONL ``span`` event with ``parent_id`` nesting.

Spans measure HOST latency (queueing, trace/compile, dispatch+wait).
They are never entered inside a jitted function; device-side layers are
named by ``jax.named_scope`` in the traced code instead.

With no profiler recording and below TRACE level, :func:`span` returns
a shared null span after one check of the profiler: no clock read, no
allocation.  jax is never imported here: the annotation class is taken
from ``sys.modules``, so a process that has not imported jax traces
nothing (no profiler can be recording) and ``repro.obs`` stays importable
before jax is configured.

Each garbage collection is a ``gc`` span while a profiler records
(a ``gc.callbacks`` hook); otherwise the hook returns after the same
check.
"""

from __future__ import annotations

import gc
import itertools
import sys
import threading
import time
from typing import Any, Dict, Optional

from repro.obs import sink

_ids = itertools.count(1)
_tls = threading.local()
_annotation_cls = None   # jax.profiler.TraceAnnotation, once jax is imported


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _recording():
    """The annotation class while a profiler trace records, else None."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        # never triggers an import: None until jax.profiler has loaded
        cls = _annotation_cls = getattr(sys.modules.get("jax.profiler"),
                                        "TraceAnnotation", None)
        if cls is None:
            return None
    return cls if cls.is_enabled() else None


class _NullSpan:
    """What :func:`span` returns below TRACE: ``add`` does nothing and
    ``span_id`` is None."""

    __slots__ = ()
    span_id = None
    parent_id = None
    dur_us = 0.0

    def add(self, **fields: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL = _NullSpan()


class _Annotated(_NullSpan):
    """Below TRACE while a profiler records: the annotation alone."""

    __slots__ = ("_ann",)

    def __init__(self, ann) -> None:
        self._ann = ann

    def __enter__(self) -> "_Annotated":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)


class Span:
    """One timed region (TRACE level).  ``dur_us`` is valid after the
    context exits; :meth:`add` attaches extra fields to the emitted
    event."""

    __slots__ = ("name", "span_id", "parent_id", "attrs", "_t0", "dur_us",
                 "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any], ann) -> None:
        self.name = name
        self.span_id = next(_ids)
        self.parent_id: Optional[int] = None
        self.attrs = attrs
        self._t0 = 0
        self.dur_us = 0.0
        self._ann = ann

    def add(self, **fields: Any) -> None:
        self.attrs.update(fields)

    def __enter__(self) -> "Span":
        st = _stack()
        self.parent_id = st[-1].span_id if st else None
        st.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, etype, exc, tb) -> None:
        self.dur_us = (time.perf_counter_ns() - self._t0) / 1e3
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        _stack().pop()
        if etype is not None:
            # a raising body must not look like a clean span: stamp the
            # exception type on the event and let it propagate
            self.attrs.setdefault("error", etype.__name__)
        sink.emit("span", name=self.name, dur_us=self.dur_us,
                  span_id=self.span_id, parent_id=self.parent_id,
                  tid=threading.get_ident(), **self.attrs)


def span(name: str, **attrs: Any):
    """A host region: a profiler annotation named ``name``, and at TRACE
    level a ``span`` event.

    Usage::

        with obs.span("serve.bucket", schema="X0,X1") as sp:
            ...
            sp.add(batch=8)

    ``attrs`` go on the JSONL event only; the profiler sees the bare name.
    Nesting records ``parent_id`` so a flush span owns its bucket spans.
    """
    cls = _recording()
    ann = None if cls is None else cls(name)
    if sink.level() < sink.TRACE:
        return _NULL if ann is None else _Annotated(ann)
    return Span(name, attrs, ann)


def current_span() -> Optional[Span]:
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


_gc_open = None


def _gc_span(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks`` hook: a ``gc`` annotation around each collection
    while a profiler records."""
    global _gc_open
    if phase == "start":
        cls = _recording()
        if cls is not None:
            _gc_open = cls("gc")
            _gc_open.__enter__()
    elif _gc_open is not None:
        ann, _gc_open = _gc_open, None
        ann.__exit__(None, None, None)


if _gc_span not in gc.callbacks:
    gc.callbacks.append(_gc_span)
