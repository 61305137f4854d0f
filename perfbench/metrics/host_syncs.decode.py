"""Reads of device values on the host per decode tick: the mean over the
window's decode ticks of the program's ``engine.host_sync`` spans (each
micro-batch's argmax, each slot's position). A program that reads nothing
on the host reads 0; one whose decode ticks hold no program span at all
cannot be read. Moves ``out_tok_s``."""

from perfbench import progspans


def read(view):
    got = progspans.per_tick(view.served)
    if got is None or not any(got[0]):
        return None
    per = [sum(s.name == "engine.host_sync" for s in g) for g in got[0]]
    return sum(per) / len(per)
