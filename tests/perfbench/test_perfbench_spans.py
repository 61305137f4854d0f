"""The program's spans in a run's window (``perfbench/progspans.py``) and
the five readers that use them, on a synthetic trace and synthetic recorded
spans; then the host readers on a tiny run on the CPU."""

import os
import sys
import types

import jax
import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))

from perfbench import harness, progspans, tracereduce  # noqa: E402
from repro import obs  # noqa: E402

DEV = "/device:TPU:0"
T_OPEN = 10_000_000_000          # ns on the program's clock
TICK = 1_000_000
OFF = 7_000_000_000_000          # trace clock minus the program's


class _Spans:
    def __init__(self):
        self.out, self.sid = [], 0

    def add(self, name, t0, t1, parent=None, **ids):
        self.sid += 1
        self.out.append(obs.Span(name, t0, t1, parent, ids, self.sid))
        return self.sid


def _tick_spans(rec, b, k, extra_attn=False):
    """One engine tick starting at ``b``: decode with every layer kind,
    then sampling with three host reads."""
    t = rec.add("engine.tick", b + 10_000, b + 990_000, tick=k)
    d = rec.add("engine.decode", b + 20_000, b + 800_000, t)
    rec.add("afd.embed", b + 20_000, b + 50_000, d)
    rec.add("afd.attn", b + 60_000, b + 300_000, d)
    rec.add("afd.route", b + 300_000, b + 350_000, d)
    rec.add("afd.dispatch", b + 350_000, b + 400_000, d, rows=16)
    f = rec.add("afd.ffn", b + 400_000, b + 500_000, d)
    rec.add("jax.compile", b + 410_000, b + 430_000, f)
    rec.add("afd.combine", b + 500_000, b + 600_000, d)
    rec.add("afd.head", b + 600_000, b + 700_000, d)
    if extra_attn:
        rec.add("afd.attn", b + 700_000, b + 790_000, d)
    s = rec.add("engine.sample", b + 800_000, b + 980_000, t)
    for a in (800_000, 860_000, 900_000):
        rec.add("engine.host_sync", b + a, b + a + 10_000, s)


def _run(close_drift=0):
    """Three window ticks (the last one prefilled) and a set-up span before
    the window; the device is busy except in three gaps a tick."""
    rec = _Spans()
    rec.add("engine.tick", T_OPEN - 500_000, T_OPEN - 100_000, tick=-1)
    ticks, ops = [], []
    for k in range(3):
        b = T_OPEN + k * TICK
        _tick_spans(rec, b, k, extra_attn=(k == 2))
        ticks.append(harness.Tick(t0=(b + 5_000) * 1e-9,
                                  t1=(b + 995_000) * 1e-9, decode_tokens=48,
                                  first_tokens=0,
                                  prefill_tokens=128 if k == 2 else 0))
        for a, e in ((0, 100_000), (280_000, 420_000), (480_000, 990_000)):
            ops.append(tracereduce.Op(OFF + b + a, OFF + b + e, "fusion", ""))
    t_close = T_OPEN + 3 * TICK
    trace = tracereduce.Trace(
        ops={DEV: ops},
        spans=[(OFF + T_OPEN, OFF + t_close + close_drift, "bench.window")])
    served = types.SimpleNamespace(ticks=ticks, t_open=T_OPEN * 1e-9,
                                   t_close=t_close * 1e-9, trace=trace)
    view = harness.RunView(cell=None, served=served, arch=None, peak={},
                           planes=[DEV])
    return rec.out, view


@pytest.fixture
def recorded(monkeypatch):
    def use(spans):
        monkeypatch.setattr(obs, "spans", lambda: list(spans))
    return use


def test_window_ticks_and_self_time(recorded):
    spans, view = _run()
    recorded(spans)
    inside = progspans.window_spans(view.served)
    assert all(s.ids.get("tick", 0) >= 0 for s in inside)
    assert len(inside) == len(spans) - 1
    ticks = progspans.decode_ticks(view.served)
    assert len(ticks) == 2                       # the prefill tick is out
    groups = progspans.by_tick(inside, ticks)
    assert [len(g) for g in groups] == [14, 14]
    assert {s.ids["tick"] for s in groups[1] if s.name == "engine.tick"} == {1}
    own = progspans.self_ns(inside)
    by_name = {s.name: own[s.sid] for s in groups[0]}
    assert by_name["afd.ffn"] == 100_000 - 20_000
    assert by_name["engine.decode"] == 780_000 - 670_000
    assert by_name["engine.sample"] == 180_000 - 30_000
    assert by_name["engine.tick"] == 980_000 - 780_000 - 180_000


def test_five_readers(recorded):
    spans, view = _run()
    recorded(spans)

    def read(name):
        return harness.read_metric(name, view)
    assert read("a_role_host_ms.decode") == pytest.approx(0.42)
    assert read("afd_host_ms.decode") == pytest.approx(0.23)
    assert read("sample_host_ms.decode") == pytest.approx(0.18)
    assert read("host_syncs.decode") == 3.0
    # gaps a tick: [100k, 280k) inside afd.attn, [420k, 480k) inside
    # jax.compile's parent afd.ffn, [990k, 1000k) between ticks
    idle = progspans.idle_by_program_span(view)
    assert idle == {"afd.attn": 3 * 180_000, "afd.ffn": 3 * 60_000,
                    "none": 3 * 10_000}
    assert read("idle_in_a_role.decode") == pytest.approx(18.0)


def test_clock_alignment_within_a_millisecond(recorded):
    spans, view = _run(close_drift=900_000)
    recorded(spans)
    assert progspans.clock_offset(view.served) == OFF
    assert harness.read_metric("idle_in_a_role.decode", view) is not None
    spans, view = _run(close_drift=1_100_000)
    recorded(spans)
    assert progspans.clock_offset(view.served) is None
    assert harness.read_metric("idle_in_a_role.decode", view) is None
    # the host-clock readers need no trace clock
    assert harness.read_metric("host_syncs.decode", view) == 3.0


def test_host_syncs_reads_zero_without_reads(recorded):
    """Decode ticks with program spans and no host read read 0, not
    nothing; a ring that no longer holds the window still reads nothing."""
    spans, view = _run()
    no_reads = [s for s in spans if s.name != "engine.host_sync"]
    recorded(no_reads)
    assert harness.read_metric("host_syncs.decode", view) == 0.0
    recorded(no_reads[1:])
    assert harness.read_metric("host_syncs.decode", view) is None
    # decode ticks that hold no program span cannot be read
    recorded([s for s in spans if s.ids.get("tick", 0) < 0])
    assert harness.read_metric("host_syncs.decode", view) is None


def test_innermost_segments():
    rec = _Spans()
    a = rec.add("a", 0, 100)
    b = rec.add("b", 10, 40, a)
    rec.add("c", 20, 30, b)
    rec.add("d", 50, 60, a)
    rec.add("e", 200, 210)
    assert progspans.innermost(rec.out) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 50, "a"), (50, 60, "d"), (60, 100, "a"), (200, 210, "e")]


def test_wrapped_ring_or_no_spans_reads_nothing(recorded, monkeypatch):
    names = ["a_role_host_ms.decode", "afd_host_ms.decode",
             "sample_host_ms.decode", "host_syncs.decode",
             "idle_in_a_role.decode"]
    spans, view = _run()
    recorded(spans[1:])                # the oldest kept span is in the window
    assert progspans.window_spans(view.served) is None
    assert [harness.read_metric(n, view) for n in names] == [None] * 5
    recorded([])
    assert [harness.read_metric(n, view) for n in names] == [None] * 5
    # a program without the span facility
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    assert progspans.recorded() is None
    assert [harness.read_metric(n, view) for n in names] == [None] * 5


def test_host_readers_on_a_tiny_run():
    """The served program's own spans, on the CPU: the reader gives the
    mean count of ``engine.host_sync`` spans per window decode tick,
    counted here from the recorded spans: at most a read of each
    micro-batch's argmax and of each slot's position, and as few as none."""
    from test_perfbench_check import SEED, tiny_cell
    cell = tiny_cell()
    served = harness.serve(cell, SEED, 0.0, jax.devices()[:1], n_ticks=6)
    view = harness.RunView(cell=cell, served=served, arch=None, peak={},
                           planes=[])
    mix = cell.traffic
    syncs = harness.read_metric("host_syncs.decode", view)
    ticks = [(round(t.t0 * 1e9), round(t.t1 * 1e9)) for t in served.ticks
             if t.decode_tokens and not t.prefill_tokens]
    assert ticks
    reads = [s for s in obs.spans() if s.name == "engine.host_sync"]
    per = [sum(a <= s.t0_ns and s.t1_ns <= b for s in reads)
           for a, b in ticks]
    assert syncs == pytest.approx(sum(per) / len(per))
    assert 0 <= syncs <= int(mix["n_bo"]) * (1 + int(mix["mb_slots"]))
    for name in ("a_role_host_ms.decode", "afd_host_ms.decode",
                 "sample_host_ms.decode"):
        assert harness.read_metric(name, view) > 0, name
    assert harness.read_metric("idle_in_a_role.decode", view) is None
