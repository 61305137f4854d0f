"""Pallas TPU flash-attention prefill kernel (causal + sliding window).

    out (B, S, Hq, d) = flash(q (B, S, Hq, d), k/v (B, T, Hkv, d))

The canonical tiled online-softmax formulation: the grid walks
(batch, q-head, q-tile, kv-tile) with the kv-tile axis fastest, so the
running max / denominator / accumulator for one q-tile stay resident in
VMEM scratch while KV streams through. GQA is handled in the index_map:
q-head h reads kv-head h // group — no KV broadcasting in memory.

Masking is positional (global row/col ids), covering causal, sliding
window (h2o-danube), bidirectional (whisper encoder), and the T-padding
tail in one predicate. Fully-masked *leading* tiles (sliding window) are
safe: their garbage statistics are annihilated by the exp(m_old − m_new)
correction once a live tile arrives (same argument as the decode kernel).

VMEM per step with the default 128/256 tiles at d=128:
q 64 kB + k/v 2×128 kB + acc 64 kB f32 — comfortably double-bufferable.

This is the prefill counterpart of kernels/splitkv_attention.py; the XLA
fallback is the q-chunked scan in models/attention.py. Validated in
interpret mode against the dense masked reference.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

_MASK = -1e30


def _kernel(q_off_ref, t_valid_ref,                  # scalar prefetch (B,)
            q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref,
            *, tile_q: int, tile_k: int, scale: float,
            causal: bool, window: Optional[int], out_dtype):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (tq, d)
    k = k_ref[0, 0].astype(jnp.float32)                    # (tk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    # q_offset shifts the query rows to their absolute positions — the
    # chunked-prefill case where q starts mid-sequence against a cache
    # already holding the prior context. Both bounds are per sequence.
    rows = q_off_ref[b] + qi * tile_q + jax.lax.broadcasted_iota(
        jnp.int32, (tile_q, tile_k), 0)
    cols = ki * tile_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (tile_q, tile_k), 1)
    mask = cols < t_valid_ref[b]
    if causal:
        mask = jnp.logical_and(mask, cols <= rows)
    if window is not None:
        mask = jnp.logical_and(mask, rows - cols < window)
    s = jnp.where(mask, s, _MASK)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(out_dtype)


def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True,
                         window: Optional[int] = None,
                         q_offset: Union[int, jax.Array] = 0,
                         t_valid: Union[None, int, jax.Array] = None,
                         tile_q: int = 128, tile_k: int = 256,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, Hq, d); k, v: (B, T, Hkv, d) → (B, S, Hq, d).

    ``q_offset`` (scalar or (B,)) places query row j of sequence b at
    absolute position ``q_offset[b] + j`` (chunked prefill against a live
    cache); ``t_valid`` (scalar or (B,)) bounds how many leading KV slots
    hold real keys (default: all T). Both ride in as scalar prefetch, so
    chunk starts neither recompile the kernel nor need to agree across
    the batch.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    assert hq % hkv == 0

    tile_q = min(tile_q, s)
    tile_k = min(tile_k, t)
    s_pad = -(-s // tile_q) * tile_q
    t_pad = -(-t // tile_k) * tile_k

    qh = jnp.moveaxis(q, 2, 1)                             # (B, Hq, S, d)
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    if s_pad != s:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    t_val = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(t if t_valid is None else t_valid,
                                     jnp.int32), (b,)), t)

    kernel = functools.partial(
        _kernel, tile_q=tile_q, tile_k=tile_k, scale=1.0 / math.sqrt(d),
        causal=causal, window=window, out_dtype=q.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hq, s_pad // tile_q, t_pad // tile_k),
            in_specs=[
                pl.BlockSpec((1, 1, tile_q, d),
                             lambda bi, h, qi, ki, *_: (bi, h, qi, 0)),
                pl.BlockSpec((1, 1, tile_k, d),
                             lambda bi, h, qi, ki, *_: (bi, h // group, ki,
                                                        0)),
                pl.BlockSpec((1, 1, tile_k, d),
                             lambda bi, h, qi, ki, *_: (bi, h // group, ki,
                                                        0)),
            ],
            out_specs=pl.BlockSpec((1, 1, tile_q, d),
                                   lambda bi, h, qi, ki, *_: (bi, h, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile_q, 1), jnp.float32),
                pltpu.VMEM((tile_q, 1), jnp.float32),
                pltpu.VMEM((tile_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, s_pad, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(q_off, t_val, qh, kh, vh)
    return jnp.moveaxis(out[:, :, :s], 1, 2)
