"""The benchmark's trace reduction and metric arithmetic, on small traces."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))

from perfbench import arith, tracereduce  # noqa: E402
from perfbench.configs import moe_transformer as mt  # noqa: E402
from repro.core import planner  # noqa: E402

DEV = "/device:TPU:0"


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=evs)
                          for n, evs in lines.items()])


def _op(name, start, dur):
    return _ev(f"%{name} = bf16[8,128]{{1,0}} op(bf16[8,128]{{1,0}} %p)",
               start, dur)


def _synthetic():
    ops = [_op("fusion.1", 100, 50),
           _op("local_ffn.2", 200, 100),
           _op("fusion.3", 250, 100),          # overlaps the kernel
           _op("local_ffn.3", 600, 50),
           _op("copy.1", 900, 200)]            # runs past the window
    modules = [_ev("jit_add(1234)", 95, 60), _ev("jit_local_ffn(99)", 190, 470),
               _ev("jit_copy(7)", 890, 220)]
    host = [_ev("bench.window", 0, 1000), _ev("bench.tick", 0, 1000),
            _ev("bench.decode_step_3bo", 50, 500),
            _ev("PjitFunction(add)", 60, 5)]
    return tracereduce.from_events([
        _plane(DEV, {"XLA Modules": modules, "XLA Ops": ops,
                     "Async XLA Ops": [_op("copy-start", 0, 1000)]}),
        _plane("/host:CPU", {"python": host})])


def test_busy_union_idle_and_attribution():
    tr = _synthetic()
    lo, hi = tr.window()
    assert (lo, hi) == (0, 1000)
    ops = tr.ops[DEV]
    # [100,150) ∪ [200,350) ∪ [600,650) ∪ [900,1000) clipped
    assert tracereduce.busy_intervals(ops, lo, hi) == [
        (100, 150), (200, 350), (600, 650), (900, 1000)]
    assert tracereduce.busy_ns(ops, lo, hi) == 350
    assert tracereduce.idle_gaps(ops, lo, hi) == [
        (0, 100), (150, 200), (350, 600), (650, 900)]
    idle = tracereduce.idle_by_span(tr, DEV, lo, hi)
    # gap midpoints 50 (decode span starts at 50), 175, 475 -> decode span;
    # 775 -> only the tick span is open.
    assert idle == {"bench.decode_step_3bo": 100 + 50 + 250,
                    "bench.tick": 250}
    assert tracereduce.op_time(ops, lo, hi, module="local_ffn",
                               name_prefix="local_ffn") == 150
    assert tracereduce.op_time(ops, lo, hi, module="local_ffn") == 250
    top = tracereduce.top_ops(ops, lo, hi, n=2)
    assert top[0][0] == "jit_copy/copy.1"
    assert top[0][1] == pytest.approx(200e-9)
    assert top[1][0] in ("jit_local_ffn/local_ffn.2", "jit_local_ffn/fusion.3")


def test_names_from_hlo_text():
    assert tracereduce.op_name(
        "%local_ffn.2 = bf16[128,1024]{1,0} custom-call(s32[32]{0} %f)") \
        == "local_ffn.2"
    assert tracereduce.module_name("jit_local_ffn(2019383627061493373)") \
        == "jit_local_ffn"
    tr = _synthetic()
    assert [(o.name, o.module) for o in tr.ops[DEV]] == [
        ("fusion.1", "jit_add"), ("local_ffn.2", "jit_local_ffn"),
        ("fusion.3", "jit_local_ffn"), ("local_ffn.3", "jit_local_ffn"),
        ("copy.1", "jit_copy")]


def test_one_window_span_is_required():
    tr = _synthetic()
    tr.spans.append((5, 6, "bench.window"))
    with pytest.raises(ValueError):
        tr.window()


RECORDED = os.path.join(HERE, "data", "granite_decode_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    """A slice of a traced ``granite-decode`` window on a TPU v5e: the
    reduction finds the window, the F role's kernels and the idle gaps."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    planes = [_plane(p["name"], {ln["name"]: [_ev(*e) for e in ln["events"]]
                                 for ln in p["lines"]})
              for p in rec["planes"]]
    tr = tracereduce.from_events(planes)
    lo, hi = tr.window()
    dev = rec["device"]
    want = rec["expect"]
    assert tracereduce.busy_ns(tr.ops[dev], lo, hi) == want["busy_ns"]
    assert tracereduce.op_time(tr.ops[dev], lo, hi, module="local_ffn",
                               name_prefix="local_ffn") == want["gmm_ns"]
    idle = tracereduce.idle_by_span(tr, dev, lo, hi)
    assert sum(idle.values()) == (hi - lo) - want["busy_ns"]
    assert max(idle, key=idle.get) == want["top_idle_span"]


def test_m2n_bytes_match_the_planner():
    for n, d, k, b in [(16, 1024, 8, 2), (512, 4096, 2, 2), (1, 64, 4, 4)]:
        assert arith.m2n_cycle_bytes(n, d, k, b) == \
            planner.predict_m2n_cycle_bytes(n, d, k, dtype_bytes=b)
    # a run: decode cycles of mb_slots rows plus each prompt token once
    d, c = arith.m2n_run_bytes(decode_ticks=3, n_bo=2, mb_slots=4,
                               prefill_tokens=10, moe_layers=5, hidden=8,
                               top_k=2, dtype_bytes=2)
    assert d == 3 * 2 * 5 * (4 * 8 * 2 + 4 * 2 * 8) + 5 * (10 * 8 * 2 + 10 * 2 * 8)
    assert c == 3 * 2 * 5 * (4 * 8 * 2) + 5 * (10 * 8 * 2)


def test_roofline_and_token_flops():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # Granite decode cycle: 16 tokens x top-8 = 128 rows; nearly every one
    # of the 32 experts is reached, so ~100 MB of weights bound it.
    flops, nbytes = arith.gmm_cycle_cost(16, 1024, 512, 32, 8)
    assert flops == 2 * 128 * 1024 * 1024 + 2 * 128 * 512 * 1024
    assert 31.0 < arith.expected_experts_hit(16, 32, 8) < 32.0
    assert 31 * 3 * 1024 * 512 * 2 < nbytes < 33 * 3 * 1024 * 512 * 2
    t, bound = arith.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    # 4096 Mixtral tokens give each expert 1024 rows: compute-bound
    assert arith.roofline_seconds(*arith.gmm_cycle_cost(
        4096, 4096, 14336, 8, 2), peak)[1] == "compute"
    a = mt.Arch(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, d_head=4,
                n_experts=4, top_k=2, d_expert=6, vocab=10, rope_theta=1e4,
                rms_eps=1e-6, tied=True, emb_mult=1.0, attn_mult=0.5,
                resid_mult=1.0, logits_div=1.0)
    attn_w = 8 * (2 + 2) * 4 + 2 * 4 * 8
    moe_w = 8 * 4 + 2 * 3 * 8 * 6
    assert arith.token_flops(a, 5) == \
        2 * (2 * (attn_w + moe_w) + 4 * 2 * 4 * 5) + 2 * 8 * 10


def test_peaks_table_refuses_an_unknown_device():
    assert arith.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks("cpu")
