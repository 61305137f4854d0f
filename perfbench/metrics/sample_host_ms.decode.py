"""Host time the engine takes per decode tick to turn logits into tokens, in
milliseconds: the median over the window's decode ticks of the program's
``engine.sample`` span (argmax, per-slot bookkeeping, completions), its
host reads included. Moves ``out_tok_s``."""

import statistics

from perfbench import progspans


def read(view):
    got = progspans.per_tick(view.served)
    if got is None:
        return None
    per = [sum(s.t1_ns - s.t0_ns for s in g if s.name == "engine.sample")
           for g in got[0]]
    return statistics.median(per) * 1e-6 if any(per) else None
