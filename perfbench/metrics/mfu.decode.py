"""Whole-step model FLOP utilisation of the window: operations its decoded
tokens needed (each at its own context length) over the window's host-clock
span times the chips' bf16 peak, in percent. Moves ``out_tok_s``."""

from perfbench import arith


def read(view):
    sv = view.served
    flops = 0.0
    for rid, n0 in sv.tokens_at_open.items():
        r = sv.requests[rid]
        p = len(r.prompt)
        # token j (j >= 1) is decoded at context p + j
        flops += sum(arith.token_flops(view.arch, p + j)
                     for j in range(max(n0, 1), len(r.output)))
    span = sv.t_close - sv.t_open
    if not flops:
        return None
    return 100.0 * flops / (span * view.peak["bf16_flops_per_s"]
                            * view.cell.chips)
