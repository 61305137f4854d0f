"""Share of the traced window in which no operation ran on the device
(1 - union of busy intervals / window), in percent, averaged over the chips
used. Moves ``out_tok_s``."""

from perfbench import tracereduce


def read(view):
    sv = view.served
    if sv.trace is None or not view.planes:
        return None
    lo, hi = sv.trace.window()
    busy = [tracereduce.busy_ns(sv.trace.ops[d], lo, hi) for d in view.planes]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
