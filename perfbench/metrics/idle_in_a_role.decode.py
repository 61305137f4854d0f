"""Share of the traced window in which the device sat idle while the
innermost open program span was one of the A role's ops (``afd.embed``,
``afd.attn``, ``afd.route``, ``afd.dense_ffn``, ``afd.head``), in percent,
averaged over the chips used. Moves ``out_tok_s``."""

from perfbench import progspans


def read(view):
    idle = progspans.idle_by_program_span(view)
    if idle is None:
        return None
    lo, hi = view.served.trace.window()
    return 100.0 * sum(idle.get(n, 0.0) for n in progspans.A_ROLE) / (hi - lo)
