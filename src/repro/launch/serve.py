"""End-to-end serving driver: continuous-batching decode with the SLO
scheduler, optional AFD two-role execution, and a fault-injection drill.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch kimi-k2-1t-a32b --preset smoke --requests 16 --slots 4 \
        --mode ep
    ... --mode afd      # two-role AFD runtime (roles colocate on one device)
    ... --fail-at 5                              # kill a node mid-run
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import use_compile_cache
from repro.launch.train import preset_config
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, role_devices
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import SLOConfig, SLOScheduler


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--mode", default="ep", choices=["ep", "afd"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="tick at which to simulate a node failure")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = preset_config(args.arch, args.preset)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.RandomState(args.seed)
    print(f"serving {cfg.name} ({args.mode}); "
          f"params≈{cfg.param_count()/1e6:.1f}M")

    if args.mode == "afd":
        if not cfg.is_moe:
            raise SystemExit(f"{cfg.name} is dense — AFD inapplicable "
                             "(DESIGN.md §Arch-applicability); use --mode ep")
        a_dev, f_dev = role_devices(jax.devices())
        rt = AFDRuntime(cfg, params, a_dev, f_dev)
        caches, pos = rt.init_cache(args.slots, args.max_len)
        toks = jnp.asarray(rng.randint(1, cfg.vocab_size,
                                       size=(args.slots,)), jnp.int32)
        t0 = time.time()
        n_steps = args.max_new
        for step in range(n_steps):
            logits, caches, pos = rt.decode_step(toks, caches, pos)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        dt = time.time() - t0
        print(f"AFD: {n_steps} steps × {args.slots} seqs in {dt:.2f}s "
              f"({n_steps*args.slots/dt:.1f} tok/s)")
        print(f"M2N traffic: dispatch {rt.stats.dispatch_bytes/1e3:.1f} kB, "
              f"combine {rt.stats.combine_bytes/1e3:.1f} kB over "
              f"{rt.stats.dispatches} transfers")
        return

    engine = DecodeEngine(model, params, n_slots=args.slots,
                          max_len=args.max_len)
    for i in range(args.requests):
        prompt = rng.randint(1, cfg.vocab_size,
                             size=(args.prompt_len,)).astype(np.int32)
        engine.submit(Request(rid=i, prompt=prompt,
                              max_new_tokens=args.max_new))

    sched = SLOScheduler(SLOConfig(), mode="ep", lam=4.0)
    t0 = time.time()
    tick = 0
    while engine.queue or any(s is not None for s in engine.slots):
        ts = time.time()
        engine.tick()
        sched.observe(time.time() - ts)
        tick += 1
        if args.fail_at is not None and tick == args.fail_at:
            n = engine.simulate_failure(0.25)
            print(f"[tick {tick}] simulated node failure: "
                  f"requeued {n} requests")
        if tick > 10_000:
            break
    wall = time.time() - t0
    st = engine.stats
    print(f"EP: {st.tokens_out} tokens, {st.prefills} prefills, "
          f"{st.ticks} ticks in {wall:.2f}s "
          f"({st.throughput(wall):.1f} tok/s); requeued={st.requeued}")
    d = sched.decide(t_budget=np.median(sched.samples))
    print(f"scheduler: σ̂={d.sigma:.3f} α_ep={d.alpha:.3f} "
          f"straggler_rate={d.straggler_rate:.2f}")


if __name__ == "__main__":
    main()
