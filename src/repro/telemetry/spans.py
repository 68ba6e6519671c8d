"""Spans of the serving path, kept in memory on the profiler's clock.

A span is one row ``(name, start, end, parent, attrs)``: ``start`` and
``end`` on ``time.perf_counter()``, ``parent`` the index (in the same
list) of the span that encloses it, None at the top, and ``attrs`` a
small dict of counts and ids.  The first three fields are those of the
chip benchmark's own host spans (``benchmarks/chip/serveloop.Spans``),
on the same clock, so that the two read alike.

The recorder is off until :func:`enable` is called, and off again after
:func:`disable`.  Off, :func:`span` checks one flag and returns a shared
null context: it records nothing, allocates nothing and enters no
annotation.  On, each span is also entered as a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
trace taken meanwhile holds it on the device trace's clock.  A span that
begins in one call and ends in another (:func:`begin` / :func:`end`: a
request waiting in the queue) is kept in memory only, with no parent.

    with spans.span("engine.decode", batch=b, cohort=c):
        ...
    with spans.span("sched.admit") as sp:
        ...
        sp.set(admitted=n)          # attributes known only at the end

Rows wait in one bounded list until :func:`drain` hands them out; spans
past the bound are counted by :func:`dropped` and not kept.  Spans nest
on one stack, so record them from the one thread that drives the
scheduler.  The names are :data:`NAMES`; ``PERF.md`` says what each one
wraps and which benchmark metric reads it.
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional

NAMES = frozenset({
    "sched.iteration", "sched.admit", "sched.decode_round", "sched.finalize",
    "request.queued",
    "engine.prefill", "engine.decode", "engine.embed", "engine.cache_init",
    "engine.launch", "engine.wait",
})
MAX_ROWS = 200_000        # a 30 s chat window on one v5e keeps about 20,000


class Row(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[int]
    attrs: dict


class _Null:
    """What :func:`span` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NULL = _Null()

_on = False
_rows: list = []          # Row, or None while its span is open
_stack: list = []         # indices of the open spans, innermost last
_begun: dict = {}         # (name, key) -> (start, attrs) of begun spans
_dropped = 0
_annotation = None        # jax.profiler.TraceAnnotation, set by enable()


class _Span:
    __slots__ = ("name", "attrs", "index", "parent", "start", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self.parent = _stack[-1] if _stack else None
        self.index = len(_rows)
        _rows.append(None)
        _stack.append(self.index)
        self._ann = _annotation(self.name)
        self.start = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = time.perf_counter()
        _stack.pop()
        _rows[self.index] = Row(self.name, self.start, end, self.parent,
                                self.attrs)
        return False

    def set(self, **attrs):
        self.attrs.update(attrs)


def _room(name: str) -> bool:
    global _dropped
    if name not in NAMES:
        raise ValueError(f"unknown span {name!r}; spans.NAMES has "
                         f"{sorted(NAMES)}")
    if len(_rows) >= MAX_ROWS:
        _dropped += 1
        return False
    return True


def span(name: str, **attrs):
    """A context manager that records one span named ``name`` (one of
    :data:`NAMES`) around its body; its ``set(**attrs)`` adds attributes
    before it closes."""
    if not _on:
        return _NULL
    return _Span(name, attrs) if _room(name) else _NULL


def recording() -> bool:
    """Whether spans are recorded now: an attribute that costs work to
    compute (a read from the device) is computed only then."""
    return _on


def begin(name: str, key, **attrs) -> None:
    """Open a span that a later call closes with :func:`end` under the
    same ``name`` and ``key``."""
    if _on:
        _begun[(name, key)] = (time.perf_counter(), attrs)


def end(name: str, key) -> None:
    """Close the span :func:`begin` opened under ``name`` and ``key``;
    nothing where none is open."""
    if not _on:
        return
    begun = _begun.pop((name, key), None)
    if begun is not None and _room(name):
        start, attrs = begun
        _rows.append(Row(name, start, time.perf_counter(), None, attrs))


def enable() -> None:
    """Start recording, keeping at most :data:`MAX_ROWS` rows until the
    next :func:`drain`."""
    global _on, _annotation, _dropped
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _dropped = 0
    _on = True


def disable() -> None:
    """Stop recording.  Rows already closed wait for :func:`drain`; spans
    begun and not ended are forgotten."""
    global _on
    _on = False
    _begun.clear()


def drain() -> list:
    """The rows closed since the last drain, spans in the order they
    began (a span opened by :func:`begin` where it ended), and keep none.
    Spans begun and not yet ended stay open.  Not inside an open span."""
    global _rows
    if _stack:
        raise RuntimeError("spans.drain() inside an open span")
    rows, _rows = _rows, []
    return rows


def dropped() -> int:
    """Spans not kept since :func:`enable` because the list was full."""
    return _dropped


def self_seconds(rows: list) -> list:
    """Per row of ``rows`` (one :func:`drain`), its duration less that of
    its children: the time the span spent outside the spans it holds."""
    out = [r.end - r.start for r in rows]
    for r in rows:
        if r.parent is not None:
            out[r.parent] -= r.end - r.start
    return out
