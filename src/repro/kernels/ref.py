"""Pure-jnp oracles for the Pallas kernels.

These are the correctness references: simple, obviously-right formulations
with no tiling, masking tricks, or online accumulation. Every kernel test
asserts allclose against these across shape/dtype sweeps.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def grouped_gemm_ref(lhs: jax.Array, rhs: jax.Array,
                     group_sizes: jax.Array) -> jax.Array:
    """Reference grouped GEMM.

    lhs: (M, K) rows sorted by group (group g occupies rows
         [offsets[g], offsets[g+1])); rhs: (G, K, N); group_sizes: (G,).
    Returns (M, N): out[r] = lhs[r] @ rhs[group_of(r)].

    Rows beyond sum(group_sizes) belong to no group and yield zeros.
    Implemented as G masked full matmuls — O(G·M·K·N) but unambiguous.
    """
    m = lhs.shape[0]
    g = rhs.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                               jnp.cumsum(group_sizes)])
    rows = jnp.arange(m)
    out = jnp.zeros((m, rhs.shape[-1]), jnp.float32)
    for gi in range(g):
        mask = (rows >= offsets[gi]) & (rows < offsets[gi + 1])
        partial = jnp.dot(lhs.astype(jnp.float32),
                          rhs[gi].astype(jnp.float32))
        out = out + jnp.where(mask[:, None], partial, 0.0)
    return out.astype(lhs.dtype if lhs.dtype == rhs.dtype else jnp.float32)


def grouped_gemm_fused_ref(lhs: jax.Array, rhs: jax.Array,
                           group_sizes: jax.Array,
                           row_index: Optional[jax.Array] = None,
                           out_index: Optional[jax.Array] = None,
                           out_rows: Optional[int] = None) -> jax.Array:
    """Oracle for the fused-permute grouped GEMM: explicit gather →
    ``grouped_gemm_ref`` → explicit scatter.

    GEMM row r consumes ``lhs[row_index[r]]`` and its result lands in
    ``out[out_index[r]]`` (``out_index`` must hit distinct destinations
    over valid rows — a router unpermute always does). Rows of ``out``
    no GEMM row targets are zero.
    """
    x = lhs if row_index is None else jnp.take(lhs, row_index, axis=0)
    y = grouped_gemm_ref(x, rhs, group_sizes)
    if out_index is None:
        return y
    n_out = y.shape[0] if out_rows is None else out_rows
    return jnp.zeros((n_out, y.shape[1]), y.dtype).at[out_index].set(y)


def row_groups_ref(group_sizes: jax.Array, m: int) -> jax.Array:
    """group id per row (G for out-of-group padding rows)."""
    offsets = jnp.cumsum(group_sizes)
    rows = jnp.arange(m)
    return jnp.searchsorted(offsets, rows, side="right")


def splitkv_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                          lengths: jax.Array) -> jax.Array:
    """Reference single-token GQA attention with per-batch valid lengths.

    q: (B, Hq, d); k, v: (B, T, Hkv, d); lengths: (B,) — slots [0, len)
    are live. Returns (B, Hq, d). float32 softmax, no online trick.
    """
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, kf) / jnp.sqrt(
        jnp.asarray(d, jnp.float32))
    mask = jnp.arange(t)[None, :] < lengths[:, None]          # (B, T)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, vf)
    return out.reshape(b, hq, d).astype(q.dtype)


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, window: Optional[int] = None,
                      q_offset=0, t_valid=None) -> jax.Array:
    """Reference prefill attention: dense masked float32 softmax.

    q: (B, S, Hq, d); k, v: (B, T, Hkv, d). Query row j of sequence b sits
    at absolute position ``q_offset[b] + j`` and sees KV slots
    ``< t_valid[b]`` (both scalars or (B,) arrays).
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    rows = off[:, None, None] + jnp.arange(s)[None, :, None]   # (B, S, 1)
    cols = jnp.arange(t)[None, None, :]
    mask = jnp.ones((b, s, t), bool)
    if t_valid is not None:
        tv = jnp.broadcast_to(jnp.asarray(t_valid, jnp.int32), (b,))
        mask = mask & (cols < tv[:, None, None])
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (rows - cols < window)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def moe_ffn_ref(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
                w_out: jax.Array, top_k: int,
                renorm: bool = True,
                shared_in: Optional[jax.Array] = None,
                shared_out: Optional[jax.Array] = None) -> jax.Array:
    """Dead-simple per-token MoE oracle (loop over k slots, dense gather).

    x: (N, D); router_w: (D, E); w_in: (E, D, 2M) fused gate|up;
    w_out: (E, M, D). Dropless by construction (no capacity).
    """
    xf = x.astype(jnp.float32)
    logits = xf @ router_w.astype(jnp.float32)                # (N, E)
    topw, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renorm:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    out = jnp.zeros_like(xf)
    for slot in range(top_k):
        wi = w_in[topi[:, slot]].astype(jnp.float32)          # (N, D, 2M)
        wo = w_out[topi[:, slot]].astype(jnp.float32)         # (N, M, D)
        h = jnp.einsum("nd,ndf->nf", xf, wi)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
        y = jnp.einsum("nf,nfd->nd", h, wo)
        out = out + topw[:, slot:slot + 1] * y
    if shared_in is not None:
        h = xf @ shared_in.astype(jnp.float32)
        gate, up = jnp.split(h, 2, axis=-1)
        out = out + (jax.nn.silu(gate) * up) @ shared_out.astype(jnp.float32)
    return out.astype(x.dtype)
