"""Pallas TPU flash-decode (split-KV) attention kernel.

Decode-step GQA attention for one new token against a long KV cache:

    out (B, Hq, d) = attention(q (B, Hq, d), k/v (B, T, Hkv, d), lengths (B,))

The cache's sequence dimension is processed in VMEM-sized chunks with the
online-softmax recurrence (running max m, denominator l, accumulator acc),
so the kernel streams T from HBM exactly once — decode attention is
HBM-bandwidth-bound and this is the operator the AFD paper's attention-side
budget t_a prices.

This is the *flash-decoding* adaptation for TPU (DESIGN.md §5): the same
kernel body runs per KV shard when the cache's sequence dim is sharded over
the "model" mesh axis, and the per-shard partial (acc, l, m) triples are
combined with a log-sum-exp-weighted psum in
``repro.parallel.collectives.splitkv_combine``.

Grid: (B, Hkv, T/chunk) — the chunk axis iterates fastest so the output
block (and the scratch accumulators) stay resident across a query's whole
KV stream. Per-batch valid lengths ride in as scalar prefetch; fully-masked
chunks can only occur past the valid prefix, where the running max is
already finite, so the standard -1e30 masking is numerically safe.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

_MASK = -1e30


def _kernel(lengths,                         # scalar prefetch (B,)
            q_ref, k_ref, v_ref,             # VMEM blocks
            out_ref,
            m_ref, l_ref, acc_ref,           # VMEM scratch
            *, chunk: int, scale: float, out_dtype, return_lse: bool,
            lse_ref=None):
    b = pl.program_id(0)
    t = pl.program_id(2)
    n_chunks = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                       # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)                       # (chunk, d)
    v = v_ref[0, 0].astype(jnp.float32)                       # (chunk, d)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, chunk)
    cols = t * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    s = jnp.where(cols < lengths[b], s, _MASK)

    m_prev = m_ref[...]                                       # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                    # (G, chunk)
    corr = jnp.exp(m_prev - m_new)                            # (G, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(t == n_chunks - 1)
    def _flush():
        out_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(out_dtype)
        if return_lse:
            lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])


def splitkv_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                             lengths: jax.Array, *,
                             chunk: int = 256,
                             return_lse: bool = False,
                             interpret: Optional[bool] = None):
    """q: (B, Hq, d); k, v: (B, T, Hkv, d); lengths: (B,) int32.

    Returns (B, Hq, d), plus per-head log-sum-exp (B, Hq) when
    ``return_lse`` (needed for the cross-shard split-KV combine).
    """
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    assert hq % hkv == 0, (hq, hkv)
    chunk = min(chunk, t)
    n_chunks = (t + chunk - 1) // chunk
    t_pad = n_chunks * chunk

    qg = q.reshape(b, hkv, group, d)
    kh = jnp.moveaxis(k, 2, 1)                                # (B, Hkv, T, d)
    vh = jnp.moveaxis(v, 2, 1)
    if t_pad != t:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    out_shapes = [jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, group, d),
                              lambda bi, h, ti, ln: (bi, h, 0, 0))]
    if return_lse:
        # (group, 1) trailing dims span the whole array, as Mosaic's block
        # rule asks of blocks smaller than an (8, 128) tile.
        out_shapes.append(jax.ShapeDtypeStruct((b, hkv, group, 1),
                                               jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, group, 1),
                                      lambda bi, h, ti, ln: (bi, h, 0, 0)))

    kernel = functools.partial(
        _kernel, chunk=chunk, scale=1.0 / math.sqrt(d), out_dtype=q.dtype,
        return_lse=return_lse)
    if return_lse:
        def kernel(lengths, q_ref, k_ref, v_ref, out_ref, lse_out, m_ref,
                   l_ref, acc_ref):
            return _kernel(lengths, q_ref, k_ref, v_ref, out_ref,
                           m_ref, l_ref, acc_ref, chunk=chunk,
                           scale=1.0 / math.sqrt(d), out_dtype=q.dtype,
                           return_lse=True, lse_ref=lse_out)

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_chunks),
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda bi, h, ti, ln: (bi, h, 0, 0)),
                pl.BlockSpec((1, 1, chunk, d),
                             lambda bi, h, ti, ln: (bi, h, ti, 0)),
                pl.BlockSpec((1, 1, chunk, d),
                             lambda bi, h, ti, ln: (bi, h, ti, 0)),
            ],
            out_specs=out_specs if return_lse else out_specs[0],
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=out_shapes if return_lse else out_shapes[0],
        interpret=interpret_mode(interpret),
    )(lengths.astype(jnp.int32), qg, kh, vh)

    if return_lse:
        out, lse = res
        return out.reshape(b, hq, d), lse.reshape(b, hq)
    return res.reshape(b, hq, d)
