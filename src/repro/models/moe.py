"""Mixture-of-Experts FFN: top-k router + two execution paths.

  * ``moe_capacity``  — GShard-style capacity-bounded one-hot dispatch,
    expressed as dense einsums. Fully differentiable; used for training
    and as the single-device correctness oracle. Tokens overflowing an
    expert's capacity are dropped (standard; capacity_factor controls it).

  * ``moe_sorted``    — dropless sort-based dispatch feeding the grouped
    GEMM (the paper's central operator): replicate each token top_k times,
    sort by expert id, run ``kernels.ops.grouped_gemm`` over the ragged
    groups, unsort, and gate-combine. This is the decode/serving path and
    the per-shard body of the expert-parallel layer (parallel/ep.py).

Routing follows the softmax-then-topk convention with optional gate
renormalisation (Qwen/Mixtral style; ``cfg.router_renorm``).

Shared experts (DeepSeek/Kimi style) are a plain gated MLP added to the
routed output — they stay on the attention role under AFD (DESIGN.md §1).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.common import ArchConfig, dense_init, shard
from repro.models.layers import activation, init_mlp, apply_mlp


def init_moe(key, name: str, cfg: ArchConfig) -> Dict[str, jax.Array]:
    D, E, M = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": dense_init(key, f"{name}.router", (D, E), jnp.float32,
                             fan_in=D),
        "wi": dense_init(key, f"{name}.wi", (E, D, 2 * M), cfg.params_dtype,
                         fan_in=D),
        "wo": dense_init(key, f"{name}.wo", (E, M, D), cfg.params_dtype,
                         fan_in=M),
    }
    if cfg.n_shared_experts:
        ms = (cfg.shared_d_ff or cfg.moe_d_ff) * cfg.n_shared_experts
        p["shared"] = init_mlp(key, f"{name}.shared", cfg, d_ff=ms)
    return p


def route(params, cfg: ArchConfig,
          x_flat: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. x_flat: (N, D) → (probs (N,E), weights (N,k), ids (N,k))."""
    logits = x_flat.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_renorm:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    return probs, topw, topi


def aux_load_balance_loss(probs: jax.Array, topi: jax.Array,
                          n_experts: int) -> jax.Array:
    """Switch-style auxiliary load-balance loss: E · Σ_e f_e · P_e."""
    onehot = jax.nn.one_hot(topi, n_experts, dtype=jnp.float32)  # (N,k,E)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)                 # fraction per e
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_ffn(cfg: ArchConfig, h: jax.Array) -> jax.Array:
    gate, up = jnp.split(h, 2, axis=-1)
    return activation(cfg, gate) * up


# ---------------------------------------------------------------------------
# Capacity-bounded dense dispatch (training / oracle)
# ---------------------------------------------------------------------------

def capacity(cfg: ArchConfig, n_tokens: int,
             factor: Optional[float] = None) -> int:
    f = factor if factor is not None else cfg.moe_capacity_factor
    cap = int(math.ceil(n_tokens * cfg.top_k * f / cfg.n_experts))
    return max(cap, 4)


def moe_capacity(params, cfg: ArchConfig, x: jax.Array,
                 cap: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Capacity-dispatch MoE over x (..., D). Returns (out, aux_loss)."""
    orig_shape = x.shape
    x_flat = x.reshape(-1, orig_shape[-1])
    n, d = x_flat.shape
    e, k = cfg.n_experts, cfg.top_k
    c = cap if cap is not None else capacity(cfg, n)

    probs, topw, topi = route(params, cfg, x_flat)
    aux = aux_load_balance_loss(probs, topi, e)

    # Position of each (token, slot) within its expert's queue.
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)           # (N, k, E)
    flat_oh = onehot.reshape(n * k, e)
    pos_in_expert = jnp.cumsum(flat_oh, axis=0) * flat_oh - 1   # (N·k, E)
    pos = jnp.max(pos_in_expert, axis=-1).reshape(n, k)         # (N, k)
    keep = pos < c

    # Dispatch tensor (N, k, E, C) — contracted immediately, never kept.
    disp = (onehot.astype(x_flat.dtype) * keep[..., None].astype(x_flat.dtype))
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), c, dtype=x_flat.dtype)
    dispatch = jnp.einsum("nke,nkc->nkec", disp, pos_oh)
    combine = dispatch * topw[..., None, None].astype(x_flat.dtype)

    x_e = jnp.einsum("nkec,nd->ecd", dispatch, x_flat)          # (E, C, D)
    x_e = shard(x_e, "experts", None, "embed")
    h = jnp.einsum("ecd,edf->ecf", x_e, params["wi"].astype(x_flat.dtype))
    h = _expert_ffn(cfg, h)
    h = shard(h, "experts", None, "mlp")
    y_e = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x_flat.dtype))
    out = jnp.einsum("nkec,ecd->nd", combine, y_e)

    if "shared" in params:
        out = out + apply_mlp(params["shared"], cfg, x_flat)
    return out.reshape(orig_shape), aux


# ---------------------------------------------------------------------------
# Sort-based dropless dispatch → grouped GEMM (serving path)
# ---------------------------------------------------------------------------

def sort_by_expert(topi: jax.Array, n_experts: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flatten (N, k) expert assignments into a group-sorted order.

    Returns (sort_idx (N·k,), inv_idx (N·k,), group_sizes (E,)) where
    ``sort_idx`` gathers replicated tokens into expert-contiguous rows.
    """
    flat = topi.reshape(-1)
    sort_idx = jnp.argsort(flat, stable=True)
    inv_idx = jnp.argsort(sort_idx, stable=True)
    group_sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    return sort_idx, inv_idx, group_sizes


def moe_sorted(params, cfg: ArchConfig, x: jax.Array,
               impl: Optional[str] = None) -> jax.Array:
    """Dropless MoE via sort + grouped GEMM. x: (..., D) → (..., D)."""
    orig_shape = x.shape
    x_flat = x.reshape(-1, orig_shape[-1])
    n = x_flat.shape[0]
    e, k = cfg.n_experts, cfg.top_k

    _, topw, topi = route(params, cfg, x_flat)
    sort_idx, _, group_sizes = sort_by_expert(topi, e)

    # Fused router permute: the dispatch gather (token_idx) rides into the
    # first GEMM as row_index — no (N·k, D) sorted copy is materialized —
    # and the combine unpermute rides out of the second as an out_index
    # scatter (out[sort_idx[r]] = row r, the inverse of the inv_idx take).
    token_idx = sort_idx // k                                   # source token
    h = kops.grouped_gemm(x_flat, params["wi"].astype(x_flat.dtype),
                          group_sizes, impl=impl, row_index=token_idx)
    h = _expert_ffn(cfg, h)
    ys = kops.grouped_gemm(h, params["wo"].astype(x_flat.dtype),
                           group_sizes, impl=impl, out_index=sort_idx,
                           out_rows=n * k)
    y = ys.reshape(n, k, -1)
    out = jnp.einsum("nkd,nk->nd", y, topw.astype(x_flat.dtype))

    if "shared" in params:
        out = out + apply_mlp(params["shared"], cfg, x_flat)
    return out.reshape(orig_shape)


# Distributed strategy hook — parallel.ep installs the expert-parallel
# shard_map implementation here; None means single-program execution.
_EP_FORWARD = None


def set_ep_forward(fn) -> None:
    global _EP_FORWARD
    _EP_FORWARD = fn


def moe_forward(params, cfg: ArchConfig, x: jax.Array,
                mode: str = "train") -> Tuple[jax.Array, jax.Array]:
    """Dispatch by phase: capacity path for train (differentiable),
    dropless sorted/grouped path for serving (prefill and decode), so a
    prompt's logits do not depend on how many of its tokens share an
    expert. Under an installed EP strategy, prefill takes the capacity
    all-to-all lowering of train. Returns (out, aux_loss)."""
    if _EP_FORWARD is not None:
        return _EP_FORWARD(params, cfg, x,
                           "train" if mode == "prefill" else mode)
    if mode == "train":
        return moe_capacity(params, cfg, x)
    out = moe_sorted(params, cfg, x)
    return out, jnp.zeros((), jnp.float32)
