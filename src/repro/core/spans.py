"""Host spans at the program's layer boundaries.

:func:`span` always enters a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands in the trace's host plane on the device
trace's clock, and otherwise it costs next to nothing. Inside
:func:`record`, each span is also kept in memory as a :class:`Span`, timed
by ``time.perf_counter``, with the index of the span that encloses it on
the same thread.

A span that starts in one call and ends in another (``engine.sweep_async``
to ``PendingSweep.result``) is :func:`begin`-ed, made the enclosing span of
each piece of work with :func:`within`, and :func:`end`-ed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Iterator, List, NamedTuple, Optional

import jax

clock = time.perf_counter


class Span(NamedTuple):
    name: str
    parent: Optional[int]  # index of the enclosing span in the record
    t0: float  # clock, seconds
    t1: float


_record: Optional[List[Span]] = None  # the active record, when recording
_open = threading.local()  # .stack: (record, index) of the open spans


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


@dataclasses.dataclass
class Begun:
    """A span begun and not yet ended."""

    name: str
    t0: float
    annotation: jax.profiler.TraceAnnotation
    record: Optional[List[Span]] = None
    index: Optional[int] = None


def begin(name: str) -> Begun:
    """Start span ``name``; the span open on this thread encloses it."""
    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    b = Begun(name, clock(), ann)
    rec = _record
    if rec is not None:
        stack = _stack()
        parent = stack[-1][1] if stack and stack[-1][0] is rec else None
        b.record, b.index = rec, len(rec)
        rec.append(Span(name, parent, b.t0, float("nan")))
    return b


def end(b: Begun) -> None:
    """End a :func:`begin`-ed span (``t1`` is now)."""
    t1 = clock()
    b.annotation.__exit__(None, None, None)
    if b.record is not None:
        b.record[b.index] = b.record[b.index]._replace(t1=t1)


@contextlib.contextmanager
def within(b: Begun) -> Iterator[None]:
    """Make ``b`` the enclosing span of the spans begun in the block."""
    if b.record is None:
        yield
        return
    stack = _stack()
    stack.append((b.record, b.index))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    b = begin(name)
    try:
        with within(b):
            yield
    finally:
        end(b)


@contextlib.contextmanager
def record() -> Iterator[List[Span]]:
    """Keep every span begun in the block, in the order they began."""
    global _record
    prev, _record = _record, []
    try:
        yield _record
    finally:
        _record = prev
