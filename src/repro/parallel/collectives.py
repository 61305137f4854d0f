"""Distributed collective helpers.

The centerpiece is split-KV decode attention: the KV cache's sequence dim
is sharded over the "model" mesh axis, every shard runs the flash-decode
kernel over its slice, and partials are combined with a log-sum-exp
weighted psum — flash-decoding adapted to TPU (DESIGN.md §5). This removes
the all-gather XLA otherwise inserts for softmax over a sharded axis, which
is the dominant collective in the naive decode lowering (§Perf iteration
log in EXPERIMENTS.md).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops


def splitkv_combine(out_i: jax.Array, lse_i: jax.Array,
                    axis: str) -> jax.Array:
    """Combine per-shard attention partials across ``axis``.

    out_i: (B, Hq, d) shard-local normalised outputs;
    lse_i: (B, Hq) shard-local log-sum-exp. Dead shards (no valid keys)
    carry lse ≈ -1e30 and vanish under the max-shifted weighting.
    """
    m = jax.lax.pmax(lse_i, axis)                              # (B, Hq)
    w = jnp.exp(lse_i - m)[..., None]                          # (B, Hq, 1)
    num = jax.lax.psum(out_i.astype(jnp.float32) * w, axis)
    den = jax.lax.psum(w, axis)
    return (num / den).astype(out_i.dtype)


def splitkv_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             pos: jax.Array, mesh: Mesh,
                             axis: str = "model",
                             impl: Optional[str] = None) -> jax.Array:
    """Decode attention with the cache sequence dim sharded over ``axis``.

    q:   (B, Hq, d)        replicated over ``axis``
    k,v: (B, T, Hkv, d)    T sharded over ``axis``
    pos: (B,)              current positions (valid keys = [0, pos])
    Returns (B, Hq, d) replicated over ``axis``.
    """
    import numpy as np

    n_shards = mesh.shape[axis]
    t_global = k.shape[1]
    t_local = t_global // n_shards

    def local(q_l, k_l, v_l, pos_l):
        idx = jax.lax.axis_index(axis)
        start = idx * t_local
        lengths = jnp.clip(pos_l + 1 - start, 0, t_local).astype(jnp.int32)
        out, lse = kops.splitkv_attention(q_l, k_l, v_l, lengths,
                                          impl=impl, return_lse=True)
        return splitkv_combine(out, lse, axis)

    other = tuple(a for a in mesh.axis_names if a != axis)
    dp_size = int(np.prod([mesh.shape[a] for a in other])) if other else 1
    b = (other if len(other) > 1 else (other[0] if other else None)) \
        if (other and q.shape[0] % dp_size == 0) else None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None),
                  P(b, axis, None, None),
                  P(b, axis, None, None),
                  P(b)),
        out_specs=P(b, None, None),
        check_vma=False,
    )(q, k, v, pos)


def ring_all_gather_tokens(x: jax.Array, axis: str) -> jax.Array:
    """all_gather along a named axis (tiled) — used by ETP expert layers."""
    return jax.lax.all_gather(x, axis, axis=0, tiled=True)
