"""Public jit'd wrappers around the Pallas kernels.

Every op has three interchangeable implementations:

  * ``pallas`` — the TPU kernel (interpret mode off the TPU).
  * ``xla``    — the best XLA-native lowering (``lax.ragged_dot`` for the
    grouped GEMM, masked einsum for decode attention). This is what the
    full-scale dry-run lowers, so cost_analysis prices a real path.
  * ``ref``    — the pure-jnp oracle (kernels/ref.py).

``default_impl()`` picks ``xla`` off the TPU (interpret-mode Pallas is an
emulator, far too slow at production shapes) and ``pallas`` on the TPU;
``kernels/backend.py`` is the one place that asks which platform this is.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _autotune
from repro.kernels import ref as _ref
from repro.kernels.backend import on_tpu
from repro.kernels.grouped_gemm import (dequantize_experts,
                                        dequantize_experts_int4,
                                        grouped_gemm_pallas)
from repro.kernels.splitkv_attention import splitkv_attention_pallas

_IMPLS = ("pallas", "xla", "ref")


def default_impl() -> str:
    return "pallas" if on_tpu() else "xla"


# ---------------------------------------------------------------------------
# Grouped GEMM
# ---------------------------------------------------------------------------

def grouped_gemm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                 impl: Optional[str] = None,
                 tile_m: Optional[int] = None, tile_n: Optional[int] = None,
                 tile_k: Optional[int] = None,
                 scales: Optional[jax.Array] = None,
                 row_index: Optional[jax.Array] = None,
                 out_index: Optional[jax.Array] = None,
                 out_rows: Optional[int] = None) -> jax.Array:
    """out[r] = lhs[r] @ rhs[group_of(r)] for group-sorted rows.

    lhs: (M, K); rhs: (G, K, N); group_sizes: (G,) int32 summing to ≤ M
    (surplus rows produce zeros).

    Optional extensions (see kernels/grouped_gemm.py for semantics):
      * ``scales`` — weight-only quantization. (G,) means ``rhs`` holds
        int8 codes; (G, B) means int4 codes packed two-per-int8 along K.
      * ``row_index``/``out_index``/``out_rows`` — fused router permute:
        row r consumes ``lhs[row_index[r]]`` and lands in
        ``out[out_index[r]]``. Under ``pallas`` these fuse into the kernel;
        ``xla``/``ref`` emulate with an explicit gather/scatter (same math,
        so they stay drop-in oracles for the fused path).

    Unpinned tile sizes are resolved from the autotune table keyed on
    (E, tokens/expert, d_ff) — ``python -m repro tune`` populates it.
    """
    impl = impl or default_impl()
    int4 = scales is not None and scales.ndim == 2
    if impl == "pallas":
        m = lhs.shape[0] if row_index is None else row_index.shape[0]
        at_m, at_n, at_k = _autotune.lookup(rhs.shape[0], m, rhs.shape[2])
        tile_m = at_m if tile_m is None else tile_m
        tile_n = at_n if tile_n is None else tile_n
        tile_k = at_k if tile_k is None else tile_k
        if int4:
            # Each weight tile must dequantise with one scalar: force the
            # n-tiling to the quantization block grid.
            tile_n = rhs.shape[2] // scales.shape[1]
        return grouped_gemm_pallas(lhs, rhs, group_sizes, tile_m=tile_m,
                                   tile_n=tile_n, tile_k=tile_k,
                                   scales=scales, row_index=row_index,
                                   out_index=out_index, out_rows=out_rows)
    if impl in ("xla", "ref"):
        if scales is not None:
            rhs = (dequantize_experts_int4(rhs, scales) if int4
                   else dequantize_experts(rhs, scales))
        if row_index is not None:
            lhs = jnp.take(lhs, row_index, axis=0)
        if impl == "xla":
            out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
        else:
            out = _ref.grouped_gemm_ref(lhs, rhs, group_sizes)
        if out_index is not None:
            n_out = out.shape[0] if out_rows is None else out_rows
            out = jnp.zeros((n_out, out.shape[1]), out.dtype
                            ).at[out_index].set(out[:out_index.shape[0]])
        return out
    raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")


# ---------------------------------------------------------------------------
# Split-KV decode attention
# ---------------------------------------------------------------------------

def splitkv_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      lengths: jax.Array, impl: Optional[str] = None,
                      chunk: int = 256, return_lse: bool = False):
    """Single-token GQA attention with per-batch valid lengths.

    q: (B, Hq, d); k, v: (B, T, Hkv, d); lengths: (B,) int32.
    """
    impl = impl or default_impl()
    if impl == "pallas":
        return splitkv_attention_pallas(q, k, v, lengths, chunk=chunk,
                                        return_lse=return_lse)
    if impl in ("xla", "ref"):
        out = _ref.splitkv_attention_ref(q, k, v, lengths)
        if return_lse:
            lse = _attention_lse(q, k, lengths)
            return out, lse
        return out
    raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")


# ---------------------------------------------------------------------------
# Flash prefill attention
# ---------------------------------------------------------------------------

def flash_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True,
                            window: Optional[int] = None,
                            impl: Optional[str] = None,
                            q_offset=0,
                            t_valid=None,
                            tile_q: int = 128,
                            tile_k: int = 256) -> jax.Array:
    """Tiled online-softmax prefill attention (B, S, Hq, d).

    ``q_offset``/``t_valid`` (scalars or (B,) arrays) support chunked
    prefill against a live cache: query row j of sequence b sits at
    absolute position ``q_offset[b] + j`` and only the first
    ``t_valid[b]`` KV slots hold real keys.
    """
    from repro.kernels.flash_prefill import flash_prefill_pallas
    impl = impl or default_impl()
    if impl == "pallas":
        return flash_prefill_pallas(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset, t_valid=t_valid,
                                    tile_q=tile_q, tile_k=tile_k)
    # XLA / ref: dense masked attention (the models/attention.py chunked
    # scan is the production XLA path; this is the oracle form)
    return _ref.flash_prefill_ref(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, t_valid=t_valid)


def _attention_lse(q: jax.Array, k: jax.Array,
                   lengths: jax.Array) -> jax.Array:
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    mask = jnp.arange(t)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    return jax.nn.logsumexp(scores, axis=-1).reshape(b, hq)
