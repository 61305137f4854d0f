"""Spans: where the serving program's host time goes, tick by tick.

Each unit of work the AFD engine and runtime do on the host is wrapped in

    with obs.span("afd.attn", layer=i, mb=m):
        ...

which does two things:

* enters ``jax.profiler.TraceAnnotation(name, **ids)``, so that a profiled
  run shows the span beside the device's operations, on the device trace's
  clock, under xprof or TensorBoard;
* on exit, appends a ``Span`` to a bounded in-memory ring, timed with
  ``time.perf_counter_ns()``. ``parent`` is the ``sid`` of the span that was
  open around it (per thread), and ``ids`` holds the span's own ids merged
  over its parent's, so every span of one engine tick carries that tick's
  ``tick``, and every span of a micro-batch or layer its ``mb`` or ``layer``.

Recording is always on; there is nothing to switch. After a slow tick or a
stall, read the ring with ``spans()``, a list snapshot oldest first. The
spans of the last tick, each with its duration:

    from repro import obs
    sp = obs.spans()
    tick = sp[-1].ids.get("tick")
    for s in sp:
        if s.ids.get("tick") == tick:
            print(s.name, s.ids, (s.t1_ns - s.t0_ns) / 1e6, "ms")

The ring keeps the newest ``RING_SPANS`` (65,536) spans: a served
Granite-MoE decode tick writes about 420 spans (24 layers, 3 micro-batches,
a host read per slot), a two-layer Mixtral tick about 90, so the ring holds
the last 150 and 700 ticks. A recorded span takes about 380 bytes of host
memory (the tuple, its two times and its ids), so a full ring holds about
25 MB, and never more.

Compiles: a ``jax.monitoring`` listener records each program JAX compiles,
or loads from its persistent compilation cache, as a ``jax.compile`` span
(``ids["program"]`` is the program's name) whose parent is the innermost
span open when it was compiled, so a compile is traced to the step that
caused it.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax
from jax import monitoring

RING_SPANS = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    t0_ns: int                    # time.perf_counter_ns() at entry
    t1_ns: int                    # ... at exit
    parent: Optional[int]         # sid of the enclosing span
    ids: Dict[str, object]        # own ids over the enclosing span's
    sid: int


_ring: "collections.deque[Span]" = collections.deque(maxlen=RING_SPANS)
_sids = itertools.count()
_local = threading.local()        # .open: (sid, ids) of each open span
_now = time.perf_counter_ns
_annotation = jax.profiler.TraceAnnotation
_new = tuple.__new__              # builds a Span without its Python __new__


def _open() -> list:
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


def spans() -> List[Span]:
    """The recorded spans, oldest first (in the order they closed)."""
    return list(_ring)


class span:
    """Context manager: one span named ``name``, with ``ids`` such as
    ``tick``, ``mb`` and ``layer``."""

    __slots__ = ("name", "ids", "sid", "_ann", "_parent", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids

    def __enter__(self) -> "span":
        stack = _open()
        if stack:
            self._parent, outer = stack[-1]
            self.ids = {**outer, **self.ids}
        else:
            self._parent = None
        self.sid = next(_sids)
        stack.append((self.sid, self.ids))
        self._ann = _annotation(self.name, **self.ids)
        self._ann.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _now()
        self._ann.__exit__(*exc)
        _open().pop()
        _ring.append(_new(Span, (self.name, self._t0, t1, self._parent,
                                 self.ids, self.sid)))


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    t1 = time.perf_counter_ns()
    stack = _open()
    parent, outer = stack[-1] if stack else (None, {})
    _ring.append(Span("jax.compile", t1 - int(duration * 1e9), t1, parent,
                      {**outer, "program": kw.get("fun_name", "")},
                      next(_sids)))


monitoring.register_event_duration_secs_listener(_on_duration)
