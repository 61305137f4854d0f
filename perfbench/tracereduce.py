"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the device's busy intervals, operation time by name and by
program, and the host span that was open during each idle gap.

On a TPU, device planes are named ``/device:TPU:<n>``. Their ``XLA Ops``
line holds one event per operation run, named by the operation's HLO text
(``%local_ffn.2 = bf16[...] custom-call(...)``), and their ``XLA Modules``
line one event per program run (``jit_local_ffn(<fingerprint>)``), which
encloses its operations in time. Host spans are the events whose names start
with ``bench.``, written by the harness with ``jax.profiler.TraceAnnotation``.
Device and host events are on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    start: int                    # ns
    end: int
    name: str
    module: str


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]      # device plane name -> its operations
    spans: List[Tuple[int, int, str]]   # host spans (start, end, name)

    def window(self, name: str = "bench.window") -> Tuple[int, int]:
        found = [(s, e) for s, e, n in self.spans if n == name]
        if len(found) != 1:
            raise ValueError(f"expected one {name!r} span, found {len(found)}")
        return found[0]


def op_name(text: str) -> str:
    """``%local_ffn.2 = bf16[...] custom-call(...)`` -> ``local_ffn.2``."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_local_ffn(2019383627061493373)`` -> ``jit_local_ffn``."""
    return text.split("(", 1)[0]


def _assign_modules(ops: List[Op], modules: List[Tuple[int, int, str]]
                    ) -> None:
    """Give each operation the program whose run encloses its start."""
    modules.sort()
    j = 0
    for o in ops:
        while j < len(modules) and modules[j][1] <= o.start:
            j += 1
        if j < len(modules) and modules[j][0] <= o.start:
            o.module = modules[j][2]


def from_events(planes) -> Trace:
    """Build a ``Trace`` from objects shaped like ``ProfileData.planes``:
    each with ``name`` and ``lines``; each line with ``name`` and
    ``events``; each event with ``name``, ``start_ns`` and
    ``duration_ns``."""
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lst: List[Op] = []
            modules: List[Tuple[int, int, str]] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        lst.append(Op(s, s + int(ev.duration_ns),
                                      op_name(ev.name), ""))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((s, s + int(ev.duration_ns),
                                        module_name(ev.name)))
            if lst:
                lst.sort(key=lambda o: o.start)
                _assign_modules(lst, modules)
                ops[plane.name] = lst
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    return Trace(ops=ops, spans=spans)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under a ``jax.profiler`` log dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_events(ProfileData.from_file(paths[-1]).planes)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def busy_intervals(ops: List[Op], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the operations' intervals, clipped to [lo, hi], sorted."""
    ivs = sorted((max(o.start, lo), min(o.end, hi)) for o in ops
                 if o.end > lo and o.start < hi)
    out: List[List[int]] = []
    for s, e in ivs:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: List[Op], lo: int, hi: int) -> int:
    return sum(e - s for s, e in busy_intervals(ops, lo, hi))


def idle_gaps(ops: List[Op], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals of [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for s, e in busy_intervals(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost_span(spans: List[Tuple[int, int, str]], t: int
                   ) -> Optional[str]:
    """Name of the shortest host span open at time ``t``."""
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return None if best is None else best[1]


def idle_by_span(trace: Trace, device: str, lo: int, hi: int,
                 skip=("bench.window",)) -> Dict[str, int]:
    """Idle nanoseconds of ``device`` in [lo, hi], by the innermost host
    span open at each gap's midpoint (``"none"`` where no span was open).
    Spans named in ``skip`` are not counted as innermost."""
    spans = [sp for sp in trace.spans if sp[2] not in skip]
    out: Dict[str, int] = {}
    for s, e in idle_gaps(trace.ops.get(device, []), lo, hi):
        name = innermost_span(spans, (s + e) // 2) or "none"
        out[name] = out.get(name, 0) + (e - s)
    return out


def op_time(ops: List[Op], lo: int, hi: int, *, module: str = "",
            name_prefix: str = "") -> int:
    """Nanoseconds of operations starting in [lo, hi) whose program name
    contains ``module`` and whose own name starts with ``name_prefix``."""
    return sum(o.end - o.start for o in ops
               if lo <= o.start < hi and module in o.module
               and o.name.startswith(name_prefix))


def top_ops(ops: List[Op], lo: int, hi: int, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` operation names (``program/op``) that took most device
    time in [lo, hi), in seconds."""
    tot: Dict[str, int] = {}
    for o in ops:
        if lo <= o.start < hi:
            key = f"{o.module}/{o.name}" if o.module else o.name
            tot[key] = tot.get(key, 0) + (o.end - o.start)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in best]
