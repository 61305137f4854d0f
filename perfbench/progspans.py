"""The program's own spans (``repro.obs``) in a run's measured window: per
decode tick, their self times, and the device's idle time by the innermost
program span open in each idle gap.

The program keeps its spans in memory, timed on ``time.perf_counter``, the
clock on which the harness reads ``t_open``, ``t_close`` and each tick's
``t0``/``t1``. The profiler's trace has a clock of its own. The harness
opens its ``bench.window`` span immediately before it reads ``t_open``, so
``trace.window()[0] - t_open`` moves a span onto the trace's clock; the same
offset taken at the window's end must agree with it to within ``ALIGN_NS``,
or no idle time is attributed.

A program without ``repro.obs`` has no spans: every reading here is then
``None``. So is one whose ring of spans no longer holds the window's start.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import tracereduce

ALIGN_NS = 1_000_000
# A-role ops: embedding, attention (with its cache write) or Mamba, router,
# dense MLPs, final norm and LM head.
A_ROLE = ("afd.embed", "afd.attn", "afd.route", "afd.dense_ffn", "afd.head")
# The AFD runtime's M2N cycle: dispatch to F, the F program's launch, combine.
AFD = ("afd.dispatch", "afd.ffn", "afd.combine")


def _ns(t: float) -> int:
    return int(round(t * 1e9))


def recorded() -> Optional[list]:
    """Every span the program has recorded, oldest first, or ``None``."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans()


def window_spans(served, spans: Optional[list] = None) -> Optional[list]:
    """The spans that lie wholly inside [t_open, t_close]: ``None`` where
    the program records none, or where its ring had already dropped a span
    that ended after ``t_open`` (it wrapped)."""
    spans = recorded() if spans is None else spans
    if not spans:
        return None
    lo, hi = _ns(served.t_open), _ns(served.t_close)
    if spans[0].t1_ns > lo:
        return None
    return [s for s in spans if lo <= s.t0_ns and s.t1_ns <= hi]


def decode_ticks(served) -> List[Tuple[int, int]]:
    """(t0, t1) in ns of the window's decode ticks: ticks that decoded and
    prefilled nothing."""
    return [(_ns(t.t0), _ns(t.t1)) for t in served.ticks
            if t.decode_tokens and not t.prefill_tokens]


def by_tick(spans: Sequence, ticks: Sequence[Tuple[int, int]]) -> List[list]:
    """Each tick's spans: those that start and end inside it."""
    starts = [t0 for t0, _ in ticks]
    out: List[list] = [[] for _ in ticks]
    for s in spans:
        k = bisect.bisect_right(starts, s.t0_ns) - 1
        if k >= 0 and s.t1_ns <= ticks[k][1]:
            out[k].append(s)
    return out


def self_ns(spans: Sequence) -> Dict[int, int]:
    """Self time of each span by ``sid``: its duration less the time its
    children cover."""
    own = {s.sid: s.t1_ns - s.t0_ns for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= s.t1_ns - s.t0_ns
    return own


def per_tick(served) -> Optional[Tuple[List[list], Dict[int, int]]]:
    """The spans of each window decode tick, and every span's self time."""
    spans = window_spans(served)
    ticks = decode_ticks(served)
    if spans is None or not ticks:
        return None
    return by_tick(spans, ticks), self_ns(spans)


def tick_self_ns(served, names: Sequence[str]) -> Optional[List[int]]:
    """Per window decode tick, the summed self time of the spans named in
    ``names``; ``None`` where no tick has any."""
    got = per_tick(served)
    if got is None:
        return None
    groups, own = got
    if not any(s.name in names for g in groups for s in g):
        return None
    return [sum(own[s.sid] for s in g if s.name in names) for g in groups]


def clock_offset(served) -> Optional[int]:
    """Nanoseconds that move a ``perf_counter`` time onto the trace's clock,
    from the window's start; ``None`` where the offset at its end differs
    by more than ``ALIGN_NS``."""
    lo, hi = served.trace.window()
    at_open = lo - _ns(served.t_open)
    at_close = hi - _ns(served.t_close)
    return at_open if abs(at_open - at_close) <= ALIGN_NS else None


def innermost(spans: Sequence) -> List[Tuple[int, int, str]]:
    """(start, end, name) segments, in order and disjoint: over each part
    of the spans' union, the innermost span open there."""
    segs: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []          # (end, name) of open spans
    cursor = 0
    for s in sorted(spans, key=lambda s: (s.t0_ns, -s.t1_ns)):
        while stack and stack[-1][0] <= s.t0_ns:
            end, name = stack.pop()
            if end > cursor:
                segs.append((cursor, end, name))
                cursor = end
        if stack and s.t0_ns > cursor:
            segs.append((cursor, s.t0_ns, stack[-1][1]))
        stack.append((s.t1_ns, s.name))
        cursor = s.t0_ns
    while stack:
        end, name = stack.pop()
        if end > cursor:
            segs.append((cursor, end, name))
            cursor = end
    return segs


def idle_by_program_span(view) -> Optional[Dict[str, float]]:
    """Idle nanoseconds of the chips used, in the trace's window, by the
    innermost program span open at each idle gap's midpoint (``"none"``
    where none was), averaged over the chips."""
    sv = view.served
    if sv.trace is None or not view.planes:
        return None
    spans = window_spans(sv)
    off = None if spans is None else clock_offset(sv)
    if off is None:
        return None
    segs = innermost(spans)
    starts = [a for a, _, _ in segs]
    lo, hi = sv.trace.window()
    out: Dict[str, float] = {}
    for plane in view.planes:
        for a, b in tracereduce.idle_gaps(sv.trace.ops[plane], lo, hi):
            mid = (a + b) // 2 - off
            k = bisect.bisect_right(starts, mid) - 1
            name = segs[k][2] if k >= 0 and mid < segs[k][1] else "none"
            out[name] = out.get(name, 0.0) + (b - a) / len(view.planes)
    return out
