"""AFD two-role serving demo: attention role vs FFN role on disjoint
devices, with M2N dispatch/combine byte accounting checked against the
paper's Eq. 9/17 wire model.

Run with multiple placeholder devices to see real role placement:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_afd_two_role.py
"""

import jax
import jax.numpy as jnp

from repro import configs
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, role_devices


def main() -> None:
    cfg = configs.get_smoke_config("kimi-k2-1t-a32b")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    a_dev, f_dev = role_devices(jax.devices())
    print(f"A-role: {len(a_dev)} device(s); F-role: {len(f_dev)} device(s)")

    rt = AFDRuntime(cfg, params, a_dev, f_dev)
    B, steps = 4, 6
    caches, pos = rt.init_cache(B, 32)
    toks = jnp.ones((B,), jnp.int32)
    for s in range(steps):
        logits, caches, pos = rt.decode_step(toks, caches, pos)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        print(f"  step {s}: next tokens {list(map(int, toks))}")

    st = rt.stats
    moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    per = st.dispatch_bytes / st.dispatches
    pred = B * cfg.d_model * 4 + B * cfg.top_k * 8
    print(f"\nM2N accounting over {st.dispatches} dispatch cycles "
          f"({moe_layers} MoE layers × {steps} steps):")
    print(f"  dispatch {st.dispatch_bytes/1e3:.1f} kB, "
          f"combine {st.combine_bytes/1e3:.1f} kB")
    print(f"  per-cycle measured {per:.0f} B vs wire-model {pred} B "
          f"({'MATCH' if abs(per-pred) < 1 else 'MISMATCH'})")

    # 3BO driver: three micro-batches rotating through the roles
    mbs = []
    for k in range(3):
        c, p = rt.init_cache(B, 16)
        mbs.append((jnp.full((B,), k + 1, jnp.int32), c, p))
    outs = rt.decode_step_3bo(mbs)
    print(f"\n3BO driver: {len(outs)} micro-batches decoded "
          f"({[o[0].shape for o in outs]})")


if __name__ == "__main__":
    main()
