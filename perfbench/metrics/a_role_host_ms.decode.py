"""Host time of the A role's ops per decode tick, in milliseconds: the
median over the window's decode ticks of the summed self time of the
program's ``afd.embed``, ``afd.attn``, ``afd.route``, ``afd.dense_ffn`` and
``afd.head`` spans. Moves ``out_tok_s``."""

import statistics

from perfbench import progspans


def read(view):
    per = progspans.tick_self_ns(view.served, progspans.A_ROLE)
    return statistics.median(per) * 1e-6 if per else None
