"""Ablations beyond the paper's tables.

1. **Capacity factor vs token drops** — the EP dispatch path uses
   fixed-capacity buffers (deterministic, static shapes); the capacity
   factor trades memory for drop probability under routing imbalance.
   We route real top-k assignments through the shard_map EP train path and
   measure the drop fraction and output error vs the dropless oracle —
   the executable face of the paper's EP-imbalance σ.

2. **Batch-overlap cardinality sweep** — utilization vs number of
   micro-batches (1..6) for balanced and comm-bound stage times, locating
   the paper's "3BO is the minimum for AFD" knee and showing the
   diminishing returns beyond it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import overlap as ov
from repro.kernels.ref import moe_ffn_ref
from repro.models import moe as moe_mod
from repro.models.common import ArchConfig
from repro.parallel import ep as ep_mod


def capacity_ablation() -> None:
    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                     n_experts=8, top_k=2, moe_d_ff=16)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k).reshape(x.shape)
    from jax.sharding import PartitionSpec as P
    for cf in (0.5, 1.0, 1.25, 2.0, 4.0):
        ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",),
                             capacity_factor=cf)

        def body(x_l, rw, wi, wo):
            out, _aux, drop = ep_mod._moe_ep_train_local(
                x_l, rw, wi, wo, cfg=cfg, ep=ep)
            return out, drop

        with mesh:
            out, drop = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(None, None), P(None, None),
                          P(None, None, None), P(None, None, None)),
                out_specs=(P(None, None), P()),
                check_vma=False,
            )(x.reshape(-1, 32), p["router"], p["wi"], p["wo"])
        err = float(jnp.max(jnp.abs(out.reshape(x.shape) - ref)))
        print(f"ablation_capacity_cf{cf},0,"
              f"drop_frac={float(drop):.4f};max_err={err:.2e}")


def overlap_cardinality_ablation() -> None:
    cases = {
        "balanced": ov.StageTimes(t_attn=1.0, t_ffn=1.0, t_dispatch=0.4,
                                  t_combine=0.4),
        "comm_bound": ov.StageTimes(t_attn=0.5, t_ffn=0.5, t_dispatch=0.7,
                                    t_combine=0.7),
    }
    for cname, st in cases.items():
        for n in range(1, 7):
            res = ov.simulate("3BO", st, n_layers=24, n_micro=n)
            print(f"ablation_overlap_{cname}_n{n},0,"
                  f"a_util={res.a_util:.3f};f_util={res.f_util:.3f}")


def main() -> None:
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    capacity_ablation()
    overlap_cardinality_ablation()
    print(f"ablation_total,{(time.perf_counter()-t0)*1e6:.0f},done")


if __name__ == "__main__":
    main()
