"""Fleet smoke — three AFD serve replicas behind the KV-aware router on a
seeded Poisson burst, with a mid-burst replica failure and the elastic
N_F rescaler closed loop live.

Locks down the fleet layer's acceptance behaviors in the golden gate:

  * deterministic routing: arrival/dispatch/completion counts are exact
    under the fixed seed (fleet time is virtual; wall time is normalized
    out by check_golden);
  * per-replica byte-exactness: every fleet window's measured dispatch +
    combine bytes match the Eq. 9/17 ``predict_m2n_cycle_bytes`` price;
  * zero-loss failure drain: the replica-1 failure at t=1.8 requeues its
    in-flight work onto the survivors, nothing is dropped;
  * the §3.3 rescaler fires on the burst (≥ 1 discrete N_F re-plan) and
    each event agrees with ``core.planner.rescale_n_f`` recomputed from
    the event's own (σ, old N_F, threshold).
"""

from __future__ import annotations

import time

import jax

from repro import configs
from repro.api import registry
from repro.core import planner as pln
from repro.fleet.controller import FleetController, FleetReplica
from repro.fleet.events import FailureEvent
from repro.fleet.rescaler import ElasticRescaler
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, role_devices
from repro.serving.afd_engine import AFDServeEngine, HFUProbe
from repro.serving.workload import generate_trace, get_profile

ARCH = "granite-moe-1b-a400m"
PROFILE = "poisson-burst"
SEED = 0
MAX_REQUESTS = 48
SHAPES = [(1, 2), (1, 2), (1, 2)]        # (n_bo, mb_slots) per replica
ROUTER = "least-kv"
FAILURE = FailureEvent(t=1.8, replica=1)  # full loss mid-burst


def main() -> None:
    cfg = configs.get_smoke_config(ARCH)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    a_dev, f_dev = role_devices(jax.devices())

    spec = registry.spec_from_arch_config(cfg)
    hw = registry.resolve_hardware("H800")
    plan = pln.plan_afd(spec, hw)
    probe = HFUProbe(model=spec, hardware=hw, plan=plan)
    rescaler = ElasticRescaler(spec, hw, plan)

    replicas = []
    for i, (bo, slots) in enumerate(SHAPES):
        rt = AFDRuntime(cfg, params, a_dev, f_dev)
        eng = AFDServeEngine(rt, max_len=32, n_bo=bo, mb_slots=slots,
                             probe=probe, seed=SEED,
                             tick_seconds=0.01, window_ticks=8)
        replicas.append(FleetReplica(name=f"replica{i}", engine=eng))
    fleet = FleetController(replicas, router=ROUTER, rescaler=rescaler,
                            window_ticks=8)

    trace = generate_trace(get_profile(PROFILE), seed=SEED,
                           max_requests=MAX_REQUESTS)
    t0 = time.perf_counter()
    windows = fleet.run(trace, failures=[FAILURE], max_ticks=5000)
    wall_us = (time.perf_counter() - t0) * 1e6 / max(len(windows), 1)
    s = fleet.summary()

    # Recompute each rescale event's planner decision from the event's own
    # fields — the closed loop must agree with §3.3 run standalone.
    agree = all(
        pln.rescale_n_f(
            pln.plan_afd(spec, hw, n_f=e.old_n_f), e.sigma, e.threshold
        ).new_n_f == e.new_n_f
        for e in fleet.rescales)
    traj = "->".join(str(n) for n in
                     [plan.n_f] + [e.new_n_f for e in fleet.rescales])
    dispatch = ";".join(
        f"{name}={r['dispatched']}" for name, r in s["per_replica"].items())

    print("name,us_per_call,derived")
    print(f"fleet_run,{wall_us:.0f},"
          f"profile={PROFILE};seed={SEED};replicas={len(SHAPES)};"
          f"router={ROUTER};arrivals={s['arrivals']};"
          f"completed={s['completed']};windows={len(windows)};"
          f"fleet_ticks={s['fleet_ticks']}")
    print(f"fleet_bytes,0,"
          f"match_all={s['bytes_match_all']};"
          f"windows_ok={sum(1 for w in windows if w.bytes_match)}"
          f"/{len(windows)}")
    print(f"fleet_failure,0,"
          f"t={FAILURE.t};replica={FAILURE.replica};"
          f"requeued={s['requeued']};lost={s['lost']};"
          f"goodput_rps={s['goodput_rps']:.3f}")
    print(f"fleet_rescale,0,"
          f"events={s['rescale_events']};traj={traj};"
          f"planner_agree={agree}")
    print(f"fleet_routing,0,{dispatch}")


if __name__ == "__main__":
    main()
