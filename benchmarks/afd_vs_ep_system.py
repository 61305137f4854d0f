"""AFD vs EP on OUR system (§5.2) — end-to-end decode on a smoke-scale MoE.

Runs the same decode workload through (a) the single-program EP path and
(b) the two-role AFD runtime, asserting logit equivalence and comparing:

  * wall-clock per decode step (CPU — relative only),
  * AFD's measured M2N dispatch/combine bytes per layer per micro-batch
    against the Eq. 9/17 wire-payload prediction (3·H bytes/token at the
    paper's fp8+bf16 mix; ours is dtype-accurate),
  * the planner's verdict for the same model on H800 vs GB200.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.api import Deployment
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, role_devices

ARCH = "granite-moe-1b-a400m"


def main() -> None:
    cfg = configs.get_smoke_config(ARCH)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, steps = 4, 8
    toks0 = jax.random.randint(jax.random.PRNGKey(1), (B,), 1,
                               cfg.vocab_size).astype(jnp.int32)

    # --- EP single-program path ---------------------------------------------
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(B, 64)
    t = toks0
    logits = None
    decode(params, cache, t)                    # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        logits, cache = decode(params, cache, t)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
    ep_us = (time.perf_counter() - t0) * 1e6 / steps
    ep_logits = logits

    # --- AFD two-role path ---------------------------------------------------
    a_dev, f_dev = role_devices(jax.devices())

    rt = AFDRuntime(cfg, params, a_dev, f_dev)
    caches, pos = rt.init_cache(B, 64)
    t = toks0
    rt.decode_step(t, caches, pos)              # warm (caches unchanged refs)
    caches, pos = rt.init_cache(B, 64)
    t0 = time.perf_counter()
    for _ in range(steps):
        logits, caches, pos = rt.decode_step(t, caches, pos)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
    afd_us = (time.perf_counter() - t0) * 1e6 / steps

    err = float(jnp.max(jnp.abs(logits - ep_logits)))
    # Eq. 17-style prediction, dtype-accurate: dispatch+combine = 2·B·H·itemsize
    per_cycle = rt.stats.dispatch_bytes / max(rt.stats.dispatches, 1)
    pred = B * cfg.d_model * 4 + B * cfg.top_k * 8   # f32 tokens + gating meta
    print("name,us_per_call,derived")
    print(f"afd_vs_ep_equivalence,0,max_logit_err={err:.2e}")
    print(f"afd_vs_ep_ep_decode,{ep_us:.0f},tok_per_step={B}")
    print(f"afd_vs_ep_afd_decode,{afd_us:.0f},"
          f"slowdown={afd_us/max(ep_us,1e-9):.2f}")
    print(f"afd_vs_ep_m2n_bytes,0,"
          f"measured_per_dispatch={per_cycle:.0f};predicted={pred};"
          f"cycles={rt.stats.dispatches};"
          f"match={abs(per_cycle - pred)/pred < 0.05}")

    # planner verdicts (Table 3 narrative on the paper's own models),
    # through the repro.api façade
    for hw_name in ("H800", "GB200"):
        v = Deployment("DeepSeek-V3", hw_name).verdict()
        print(f"afd_vs_ep_verdict_DSv3_{hw_name},0,"
              f"recommended={v.afd_recommended};"
              f"ceiling={v.afd_hfu_ceiling:.3f}")


if __name__ == "__main__":
    main()
