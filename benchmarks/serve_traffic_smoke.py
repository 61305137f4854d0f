"""Serve-traffic smoke — the two-role AFD engine under a seeded Poisson
burst trace on a tiny MoE, with the measured-vs-predicted records that
the golden-diff gate locks down.

Everything except wall time runs on the engine's *virtual* clock, so the
derived values (arrival/completion counts, goodput, TTFT percentiles,
byte counters, HFU operating point, scheduler σ) are deterministic across
machines; the wall-clock column is normalized out by check_golden.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro import configs
from repro.api import registry
from repro.core import planner as pln
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, role_devices
from repro.serving.afd_engine import AFDServeEngine, HFUProbe
from repro.serving.scheduler import SLOConfig, SLOScheduler
from repro.serving.workload import generate_trace, get_profile

ARCH = "granite-moe-1b-a400m"
PROFILE = "poisson-burst"
SEED = 0
MAX_REQUESTS = 10


def main() -> None:
    cfg = configs.get_smoke_config(ARCH)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    a_dev, f_dev = role_devices(jax.devices())
    rt = AFDRuntime(cfg, params, a_dev, f_dev)

    spec = registry.spec_from_arch_config(cfg)
    hw = registry.resolve_hardware("H800")
    plan = pln.plan_afd(spec, hw)
    probe = HFUProbe(model=spec, hardware=hw, plan=plan)
    sch = SLOScheduler(SLOConfig(tpot=0.05), mode="ep")

    eng = AFDServeEngine(rt, max_len=32, n_bo=2, mb_slots=2,
                         scheduler=sch, probe=probe,
                         tick_seconds=0.01, window_ticks=8)
    trace = generate_trace(get_profile(PROFILE), seed=SEED,
                           max_requests=MAX_REQUESTS)
    t0 = time.perf_counter()
    windows = eng.run(trace, max_ticks=2000)
    wall_us = (time.perf_counter() - t0) * 1e6 / max(eng.stats.decode_ticks, 1)
    s = eng.summary()

    busy = [w for w in windows if w.tokens_routed]
    hfu_bounded = all(w.hfu_measured <= w.hfu_predicted + 1e-15 for w in busy)
    print("name,us_per_call,derived")
    print(f"serve_traffic_run,{wall_us:.0f},"
          f"profile={PROFILE};seed={SEED};arrivals={s['arrivals']};"
          f"completed={s['completed']};ticks={s['decode_ticks']};"
          f"tokens_out={s['tokens_out']};windows={len(windows)}")
    print(f"serve_traffic_bytes,0,"
          f"dispatch={s['dispatch_bytes']};combine={s['combine_bytes']};"
          f"match_all={s['bytes_match_all']}")
    print(f"serve_traffic_slo,0,"
          f"goodput_rps={s['goodput_rps']:.3f};"
          f"goodput_tps={s['goodput_tps']:.3f};"
          f"ttft_p95={s['ttft_p95']:.4f};"
          f"tpot_mean={s['tpot_mean']:.4f};slo_ok={s['slo_ok_frac']:.3f}")
    print(f"serve_traffic_hfu,0,"
          f"measured_mean={s['hfu_measured_mean']:.3e};"
          f"predicted={s['hfu_predicted']:.4e};"
          f"b_rank_util={s['b_rank_utilization_mean']:.3e};"
          f"bounded={hfu_bounded}")
    sig = [w.sigma for w in windows if w.sigma is not None]
    print(f"serve_traffic_policy,0,mode=ep;"
          f"sigma_mean={float(np.mean(sig)):.3f};"
          f"decisions={len(eng.decisions)}")

    # chunked-prefill run on the same trace: prompts ride whole chunks
    # through the M2N cycle instead of token-by-token teacher forcing.
    # Acceptance: ≥4× fewer prefill cycles, strictly lower mean TTFT,
    # identical outputs, bytes still exact (Eq. 9/17 is linear in n).
    rt2 = AFDRuntime(cfg, params, a_dev, f_dev)
    eng2 = AFDServeEngine(rt2, max_len=32, n_bo=2, mb_slots=2,
                          tick_seconds=0.01, window_ticks=8,
                          prefill_chunk=64)
    t0 = time.perf_counter()
    eng2.run(trace, max_ticks=2000)
    wall2_us = (time.perf_counter() - t0) * 1e6 / max(
        eng2.stats.engine_ticks, 1)
    s2 = eng2.summary()
    out1 = {r.rid: tuple(r.output) for r in eng.completed}
    out2 = {r.rid: tuple(r.output) for r in eng2.completed}
    cycle_ratio = s["prefill_chunks"] / max(s2["prefill_chunks"], 1)
    print(f"serve_traffic_chunked,{wall2_us:.0f},"
          f"chunk=64;completed={s2['completed']};"
          f"prefill_tokens={s2['prefill_tokens']};"
          f"prefill_cycles={s2['prefill_chunks']};"
          f"cycle_ratio={cycle_ratio:.1f};"
          f"ttft_mean={s2['ttft_mean']:.4f};"
          f"ttft_mean_legacy={s['ttft_mean']:.4f};"
          f"ttft_lower={s2['ttft_mean'] < s['ttft_mean']};"
          f"outputs_match={out1 == out2};"
          f"match_all={s2['bytes_match_all']}")


if __name__ == "__main__":
    main()
