"""Median host-clock length of the window's decode ticks (ticks that decoded
and prefilled nothing), in milliseconds. Moves ``itl_p95_ms``."""

import statistics


def read(view):
    ts = [t.t1 - t.t0 for t in view.served.ticks
          if t.decode_tokens and not t.prefill_tokens]
    return statistics.median(ts) * 1e3 if ts else None
