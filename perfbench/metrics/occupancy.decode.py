"""Mean share of the engine's slots that decoded a token, over the window's
decode ticks, in percent (the engine's own token counts). Moves
``out_tok_s``."""


def read(view):
    mix = view.cell.traffic
    slots = int(mix["n_bo"]) * int(mix["mb_slots"])
    occ = [t.decode_tokens / slots for t in view.served.ticks
           if t.decode_tokens]
    return 100.0 * sum(occ) / len(occ) if occ else None
