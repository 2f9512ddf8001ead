"""Spans and counters of the FL round loop, kept in memory.

A process-wide tracer in the style of ``jax.profiler``::

    from repro.engine import spans

    spans.start()
    result = engine.run_sweep(sweep)
    recorded, counters = spans.stop()

``span(name, **attrs)`` is a context manager around one piece of host
work; ``count(name, n)`` adds ``n`` to a counter. Both do nothing while
the tracer is off (``on`` is False): ``span`` hands back one shared
no-op context manager, so the round loop pays a ``with`` block per span
and nothing else. Callers guard work done only to feed a counter (such
as summing ``nbytes``) with ``if spans.on:``.

``stop`` returns each span as ``(name, start, end, parent, attrs)``:
``start`` and ``end`` in ns on the ``time.time_ns`` clock (the clock of
a profiler trace's ``profile_start_time``, so spans and device events
line up), ``parent`` the index in the returned list of the span open
around it (None at the top), ``attrs`` the span's own attributes over
those of its parent. Stamps are taken with ``time.perf_counter_ns`` and
moved onto the ``time.time_ns`` clock through one pair of readings made
at ``start``, so a span's length is monotonic.

The engine opens ``fl.sweep`` around each ``run_sweep`` call (attribute
``job``), ``fl.round`` around each round (``round``) and, inside it,
``fl.round.batches`` (``for_round``; children ``.draw`` and, where
the host gathers the batches, ``.gather``), ``fl.round.prepass``,
``fl.round.train``, ``fl.round.wait``, ``fl.round.select``,
``fl.round.merge``, ``fl.round.record`` and ``fl.round.eval``
(``lane``). Counters: ``batches.h2d_bytes`` (host arrays passed to the
training programs: the batches, or the index tensor of a round gathered
on the device), ``batches.device_gathers`` (dense sweep rounds, whose
round program gathers its batches), ``batches.host_gathers``
(winner-sparse rounds, gathered on the host), ``batches.resident_bytes``
(the data stack, when it is put on the device), ``eval.h2d_bytes`` (test
slices passed to the eval model) and ``eval.fetches`` (logits copied
back to the host by the eval).

The tracer never waits for the device and draws no random numbers: a
run gives the same results with it on or off. One thread records at a
time.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

#: whether spans and counters are being recorded
on = False

_records: list = []       # [name, start, end, parent, attrs], start order
_open: list = []          # indices of the spans open now, innermost last
_counters: Dict[str, int] = {}
_anchor = (0, 0)          # (time.time_ns(), time.perf_counter_ns())
_jobs = 0

Span = Tuple[str, int, int, Optional[int], dict]


class _Off:
    """The context manager every ``span`` call returns while off."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "attrs", "rec", "stack")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        parent = _open[-1] if _open else None
        attrs = (self.attrs if parent is None
                 else {**_records[parent][4], **self.attrs})
        # the recording this span belongs to: a ``stop`` inside the
        # span starts new lists, and the span's exit then touches only
        # the old ones
        self.stack = _open
        _open.append(len(_records))
        self.rec = [self.name, time.perf_counter_ns(), None, parent, attrs]
        _records.append(self.rec)
        return None

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter_ns()
        self.stack.pop()
        return False


def span(name: str, **attrs):
    """A context manager that records one span while the tracer is on."""
    return _Span(name, attrs) if on else _OFF


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while the tracer is on."""
    if on:
        _counters[name] = _counters.get(name, 0) + int(n)


def host_nbytes(tree) -> int:
    """Bytes of the numpy arrays in ``tree``: what passing it to a
    jitted program copies from the host to the device."""
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree)
               if isinstance(a, np.ndarray))


def next_job() -> int:
    """A fresh job id for the spans of one ``run_sweep`` call."""
    global _jobs
    _jobs += 1
    return _jobs - 1


def start() -> None:
    """Start recording, with no spans or counters yet; job ids restart
    from 0."""
    global on, _records, _open, _counters, _anchor, _jobs
    _records, _open, _counters, _jobs = [], [], {}, 0
    _anchor = (time.time_ns(), time.perf_counter_ns())
    on = True


def stop() -> Tuple[List[Span], Dict[str, int]]:
    """Stop recording; returns ``(spans, counters)``. A span still open
    ends at the call."""
    global on, _records, _open, _counters
    now = time.perf_counter_ns()
    on = False
    shift = _anchor[0] - _anchor[1]
    out = [(name, a + shift, (now if b is None else b) + shift, parent,
            attrs) for name, a, b, parent, attrs in _records]
    counters = _counters
    _records, _open, _counters = [], [], {}
    return out, counters
