"""Cache pytrees for autoregressive decoding.

Three kinds, one per mixer family:

  * full KV         — (B, T, n_kv, d_head) k/v planes, T = max context.
  * sliding ring    — same planes with T = window; slot = pos mod window.
    This is what makes h2o-danube's `long_500k` cell O(window) instead of
    O(seq): the cache never exceeds the attention window.
  * SSM state       — Mamba-2 conv tail (B, d_conv-1, conv_dim) and the
    recurrent state (B, n_heads, head_dim, d_state); O(1) in sequence.

Cross-attention (whisper) uses a static precomputed KV from the encoder —
built once at prefill, never updated.

Caches for scanned layer periods carry a leading ``stack`` axis so the scan
can thread them as carry/ys. All shapes are static; positions are data.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, LayerSpec, shard


def attn_cache_len(cfg: ArchConfig, max_len: int) -> int:
    """Ring length for sliding-window archs, else the full context."""
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int,
                    dtype=None) -> Dict[str, jax.Array]:
    t = attn_cache_len(cfg, max_len)
    dtype = dtype or cfg.compute_dtype
    shape = (batch, t, cfg.n_kv_heads, cfg.d_head)
    k = shard(jnp.zeros(shape, dtype), "batch", "kv_seq", "kv_heads", None)
    v = shard(jnp.zeros(shape, dtype), "batch", "kv_seq", "kv_heads", None)
    return {"k": k, "v": v}


def init_ssm_cache(cfg: ArchConfig, batch: int,
                   dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or cfg.compute_dtype
    conv = jnp.zeros((batch, cfg.ssm_conv - 1, cfg.conv_dim), dtype)
    state = jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32)
    return {"conv": shard(conv, "batch", None, None),
            "state": shard(state, "batch", "heads", None, None)}


def init_layer_cache(cfg: ArchConfig, spec: LayerSpec, batch: int,
                     max_len: int) -> Dict[str, jax.Array]:
    if spec.kind == "mamba":
        return init_ssm_cache(cfg, batch)
    return init_attn_cache(cfg, batch, max_len)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, object]:
    """Whole-model cache: prefix list + stacked period caches + position.

    Structure mirrors the transformer stack:
      {"prefix": [cache, ...],
       "stack":  [cache-with-leading-n_periods-axis per period slot],
       "cross":  optional whisper encoder KV,
       "pos":    (B,) int32 next write position}
    """
    plan = cfg.layer_plan()
    prefix = [init_layer_cache(cfg, s, batch, max_len) for s in plan.prefix]

    def stacked(spec: LayerSpec):
        one = init_layer_cache(cfg, spec, batch, max_len)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (plan.n_periods,) + x.shape),
            one)

    stack = [stacked(s) for s in plan.period]
    cache: Dict[str, object] = {
        "prefix": prefix,
        "stack": stack,
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    # Enc-dec cross-KV is attached by Model.prefill (computed from the
    # encoder output), not preallocated here.
    return cache


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))


def write_kv(cfg: ArchConfig, cache: Dict[str, jax.Array],
             k_new: jax.Array, v_new: jax.Array,
             pos: jax.Array) -> Dict[str, jax.Array]:
    """Scatter one step's k/v (B, 1, n_kv, d_head) at per-sequence ``pos``.

    Sliding-window caches wrap: slot = pos mod window.
    """
    t = cache["k"].shape[1]
    slot = pos % t if cfg.sliding_window is not None else pos
    b = k_new.shape[0]
    idx = jnp.arange(b)
    k = cache["k"].at[idx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[idx, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    return {"k": k, "v": v}


def write_kv_rows(cfg: ArchConfig, cache: Dict[str, jax.Array],
                  k_new: jax.Array, v_new: jax.Array,
                  pos: jax.Array) -> Dict[str, jax.Array]:
    """``write_kv`` as one update slice per sequence, for a program that
    owns a donated, unsharded cache (the AFD runtime's A role).

    The TPU lays a cache whose head is narrower than 128 lanes out with the
    positions minor; a scatter there copies the whole cache out of that
    layout and back, where an update slice writes in place. The B slices
    are unrolled, so the program grows with the batch.

    A position past a full cache is clamped into its own row's last slot
    (``write_kv`` drops it). Only a row already past its cache reads that
    slot afterwards: any other row writes slot T-1 at position T-1 before
    it reads it.
    """
    t = cache["k"].shape[1]
    slot = pos % t if cfg.sliding_window is not None else pos
    k, v = cache["k"], cache["v"]
    for b in range(k_new.shape[0]):
        at = (b, slot[b], 0, 0)
        k = jax.lax.dynamic_update_slice(k, k_new[b:b + 1].astype(k.dtype), at)
        v = jax.lax.dynamic_update_slice(v, v_new[b:b + 1].astype(v.dtype), at)
    return {"k": k, "v": v}


def write_kv_chunk(cfg: ArchConfig, cache: Dict[str, jax.Array],
                   k_new: jax.Array, v_new: jax.Array,
                   pos: jax.Array) -> Dict[str, jax.Array]:
    """Scatter a chunk's k/v (B, C, n_kv, d_head) at per-sequence offsets.

    Row j of the chunk lands at absolute position ``pos + j`` — the batched
    form of ``write_kv`` applied C times, and bit-identical to that loop:
    the scatter indices are disjoint except under ring wrap, where
    ``.at[].set`` keeps the *last* write per slot, exactly like sequential
    single-position writes (position p always lives in slot p mod window).
    """
    t = cache["k"].shape[1]
    b, c = k_new.shape[0], k_new.shape[1]
    if cfg.sliding_window is not None and c > t:
        # Ring wrap: only the last ``t`` positions survive a sequential
        # write loop; drop the overwritten head so every slot is scattered
        # exactly once (duplicate scatter indices are undefined in XLA).
        k_new, v_new = k_new[:, c - t:], v_new[:, c - t:]
        pos = pos + (c - t)
        c = t
    positions = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]
    slot = positions % t if cfg.sliding_window is not None else positions
    bidx = jnp.arange(b)[:, None]
    k = cache["k"].at[bidx, slot].set(k_new.astype(cache["k"].dtype))
    v = cache["v"].at[bidx, slot].set(v_new.astype(cache["v"].dtype))
    return {"k": k, "v": v}


def write_kv_prefill(cfg: ArchConfig, cache: Dict[str, jax.Array],
                     k: jax.Array, v: jax.Array) -> Dict[str, jax.Array]:
    """Bulk-write a prefill segment starting at position 0.

    For ring caches only the last ``window`` positions survive, with the
    ring phase chosen so that subsequent decode writes continue seamlessly
    (slot of position p is always p mod window).
    """
    t = cache["k"].shape[1]
    s = k.shape[1]
    if cfg.sliding_window is not None and s > t:
        # keep positions [s - t, s); position p lands in slot p mod t.
        tail_k, tail_v = k[:, s - t:], v[:, s - t:]
        pos = jnp.arange(s - t, s)
        slots = pos % t
        k_out = cache["k"].at[:, slots].set(tail_k.astype(cache["k"].dtype))
        v_out = cache["v"].at[:, slots].set(tail_v.astype(cache["v"].dtype))
        return {"k": k_out, "v": v_out}
    k_out = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
    v_out = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    return {"k": k_out, "v": v_out}


def valid_mask(cfg: ArchConfig, cache_len: int, pos: jax.Array) -> jax.Array:
    """(B, T) bool — which cache slots hold live keys when querying at pos.

    Full cache: slots [0, pos]. Ring cache: the most recent ``window``
    positions; slot j holds position (pos - ((slot_of_pos - j) mod T)).
    """
    slots = jnp.arange(cache_len)[None, :]                   # (1, T)
    p = pos[:, None]                                         # (B, 1)
    if cfg.sliding_window is None:
        return slots <= p
    t = cache_len
    cur_slot = p % t
    age = (cur_slot - slots) % t                              # 0 = current pos
    return (age <= p) & (age < t)


def valid_mask_chunk(cfg: ArchConfig, cache_len: int, pos: jax.Array,
                     chunk: int) -> jax.Array:
    """(B, C, T) bool — ``valid_mask`` evaluated at ``pos + j`` per chunk row.

    Row j sees exactly what a decode step at position pos+j would see, so
    attention over the full cache under this mask is causally correct for
    the whole chunk (later chunk rows occupy slots > pos+j and mask out)
    and bit-identical to C sequential decode masks.
    """
    slots = jnp.arange(cache_len)[None, None, :]             # (1, 1, T)
    p = (pos[:, None] + jnp.arange(chunk, dtype=pos.dtype)[None, :])[..., None]
    if cfg.sliding_window is None:
        return slots <= p
    t = cache_len
    cur_slot = p % t
    age = (cur_slot - slots) % t
    return (age <= p) & (age < t)
