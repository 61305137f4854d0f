"""Host time of the AFD runtime's M2N cycle per decode tick, in
milliseconds: the median over the window's decode ticks of the summed self
time of the program's ``afd.dispatch``, ``afd.ffn`` (the launch of the F
role's program) and ``afd.combine`` spans. Moves ``out_tok_s``."""

import statistics

from perfbench import progspans


def read(view):
    per = progspans.tick_self_ns(view.served, progspans.AFD)
    return statistics.median(per) * 1e-6 if per else None
