"""Analytic-vs-measured calibration for the provisioning verdict.

The search prices points with the Eq. 6–9 *analytic* bound. This module
re-prices the analytic stage budget against what the two-role serving
runtime actually achieves: it drives ``AFDServeEngine`` over a seeded
traffic trace (the serve-traffic smoke path) on a tiny MoE, collects the
per-window measured HFU operating points, and reports

    scale = mean(HFU_measured) / HFU_predicted   ∈ (0, 1]

— the engine's measured HFU is provably ≤ the prediction (the Eq. 9 cap is
an upper bound), so the scale is a derate. ``recommend(...,
calibration_scale=...)`` applies it to the champion before the EP
comparison, turning the analytic verdict into one with a measured error
bar attached.

This is the only provisioning path that needs jax; everything is imported
lazily so ``python -m repro provision`` stays jax-free unless
``--calibrate`` is passed.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class CalibrationReport:
    arch: str
    profile: str
    seed: int
    windows: int                  # measurement windows with routed tokens
    hfu_predicted: float          # plan's analytic Eq. 6–9 operating point
    hfu_measured_mean: float      # mean over busy windows
    b_rank_utilization: float     # measured inflow / Eq. 9 cap, mean
    scale: float                  # hfu_measured_mean / hfu_predicted
    t_budget_analytic: float      # the plan's t_B (s)
    t_budget_effective: float     # t_B the measured inflow actually fills

    def to_obj(self) -> dict:
        return dataclasses.asdict(self)


def calibrate(arch: str = "granite-moe-1b-a400m",
              profile: str = "poisson-burst", seed: int = 0,
              max_requests: int = 10, hardware: str = "H800",
              max_ticks: int = 2000) -> CalibrationReport:
    """Run the serve-traffic path and derive the analytic derate.

    Deterministic for a fixed (arch, profile, seed): the engine runs on a
    virtual clock, so the measured windows — and hence the scale — are
    reproducible across machines (same invariant the serve-smoke golden
    locks down).
    """
    import jax

    from repro import configs
    from repro.api import registry
    from repro.core import planner as pln
    from repro.models.model import make_model
    from repro.parallel.afd import AFDRuntime, role_devices
    from repro.serving.afd_engine import AFDServeEngine, HFUProbe
    from repro.serving.scheduler import SLOConfig, SLOScheduler
    from repro.serving.workload import generate_trace, get_profile

    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    a_dev, f_dev = role_devices(jax.devices())
    rt = AFDRuntime(cfg, params, a_dev, f_dev)

    spec = registry.spec_from_arch_config(cfg)
    hw = registry.resolve_hardware(hardware)
    plan = pln.plan_afd(spec, hw)
    probe = HFUProbe(model=spec, hardware=hw, plan=plan)
    sch = SLOScheduler(SLOConfig(tpot=0.05), mode="ep")
    eng = AFDServeEngine(rt, max_len=32, n_bo=2, mb_slots=2,
                         scheduler=sch, probe=probe,
                         tick_seconds=0.01, window_ticks=8)
    trace = generate_trace(get_profile(profile), seed=seed,
                           max_requests=max_requests)
    windows = eng.run(trace, max_ticks=max_ticks)
    s = eng.summary()

    busy = [w for w in windows if w.tokens_routed]
    if not busy:
        raise RuntimeError(
            f"calibration trace produced no routed tokens "
            f"(arch={arch}, profile={profile}, seed={seed})")
    predicted = float(s["hfu_predicted"])
    measured = float(s["hfu_measured_mean"])
    util = float(s["b_rank_utilization_mean"])
    scale = measured / predicted if predicted > 0 else 1.0
    return CalibrationReport(
        arch=arch, profile=profile, seed=seed, windows=len(busy),
        hfu_predicted=predicted, hfu_measured_mean=measured,
        b_rank_utilization=util, scale=min(max(scale, 1e-9), 1.0),
        t_budget_analytic=plan.t_budget,
        t_budget_effective=plan.t_budget * util)
