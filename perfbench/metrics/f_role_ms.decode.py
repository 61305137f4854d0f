"""Device time of the F role's expert program (``local_ffn``) per decode
tick of the traced window, in milliseconds, averaged over the chips used.
Moves ``out_tok_s``."""

from perfbench import tracereduce


def read(view):
    sv = view.served
    ticks = sum(1 for t in sv.ticks if t.decode_tokens)
    if not ticks or sv.trace is None or not view.planes:
        return None
    lo, hi = sv.trace.window()
    ns = [tracereduce.op_time(sv.trace.ops[d], lo, hi, module="local_ffn")
          for d in view.planes]
    if not any(ns):
        return None
    return sum(ns) / len(ns) * 1e-6 / ticks
