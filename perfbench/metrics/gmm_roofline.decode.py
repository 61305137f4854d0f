"""The F role's grouped-GEMM kernels against their roofline, in percent:
the least time the chip could take for the window's decode cycles (the
larger of operations over peak FLOP/s and bytes over HBM bandwidth, from
shapes in ``perfbench/arith.py``) over the kernels' device time in the
trace. The kernels are the program's custom calls, which XLA names after
the program (``local_ffn.<n>``). Moves ``out_tok_s``."""

from perfbench import arith, tracereduce


def read(view):
    sv, a, mix = view.served, view.arch, view.cell.traffic
    ticks = [t for t in sv.ticks if t.decode_tokens and not t.prefill_tokens]
    if sv.trace is None or not ticks or len(ticks) != len(sv.ticks):
        return None
    lo, hi = sv.trace.window()
    ns = [tracereduce.op_time(sv.trace.ops[d], lo, hi, module="local_ffn",
                                 name_prefix="local_ffn")
          for d in view.planes]
    if not ns or not all(ns):
        return None
    flops, nbytes = arith.gmm_cycle_cost(
        int(mix["mb_slots"]), a.d_model, a.d_expert, a.n_experts, a.top_k)
    least, _ = arith.roofline_seconds(flops, nbytes, view.peak)
    cycles = len(ticks) * int(mix["n_bo"]) * a.n_layers
    return 100.0 * least * cycles / (sum(ns) / len(ns) * 1e-9)
