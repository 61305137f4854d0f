"""Attention mixer: MHA/GQA with optional QKV bias (qwen1.5), qk-norm
(qwen3), sliding window (h2o-danube), and cross-attention (whisper).

Three entry points:
  * ``attention_prefill``  — full-sequence causal attention, optionally
    filling a KV cache for subsequent decode.
  * ``attention_decode``   — single-token step against a cache, with
    per-sequence positions (continuous batching) and ring-buffer support.
  * ``cross_attention``    — decoder-side attention over static encoder KV.

GQA is computed in grouped form (no KV head broadcasting in memory):
q is reshaped to (B, S, n_kv, group, d_head) and contracted against
(B, T, n_kv, d_head) keys directly.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import kvcache
from repro.models.common import ArchConfig, dense_init, ones_init, shard, zeros_init
from repro.models.layers import apply_rope, rmsnorm_1d

NEG_INF = -1e30


def init_attention(key, name: str, cfg: ArchConfig,
                   cross: bool = False) -> Dict[str, jax.Array]:
    D = cfg.d_model
    p = {
        "wq": dense_init(key, f"{name}.wq", (D, cfg.q_dim), cfg.params_dtype,
                         fan_in=D),
        "wk": dense_init(key, f"{name}.wk", (D, cfg.kv_dim), cfg.params_dtype,
                         fan_in=D),
        "wv": dense_init(key, f"{name}.wv", (D, cfg.kv_dim), cfg.params_dtype,
                         fan_in=D),
        "wo": dense_init(key, f"{name}.wo", (cfg.q_dim, D), cfg.params_dtype,
                         fan_in=cfg.q_dim),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init(key, f"{name}.bq", (cfg.q_dim,), cfg.params_dtype)
        p["bk"] = zeros_init(key, f"{name}.bk", (cfg.kv_dim,), cfg.params_dtype)
        p["bv"] = zeros_init(key, f"{name}.bv", (cfg.kv_dim,), cfg.params_dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = ones_init(key, f"{name}.q_norm", (cfg.d_head,),
                                cfg.params_dtype)
        p["k_norm"] = ones_init(key, f"{name}.k_norm", (cfg.d_head,),
                                cfg.params_dtype)
    return p


def _project_q(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(*q.shape[:-1], cfg.n_heads, cfg.d_head)
    if "q_norm" in params:
        q = rmsnorm_1d(params["q_norm"], q, cfg.rms_eps)
    return shard(q, "batch", "seq", "heads", None)


def _project_kv(params, cfg: ArchConfig,
                x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(x.dtype))
    if "bk" in params:
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    k = k.reshape(*k.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(*v.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    if "k_norm" in params:
        k = rmsnorm_1d(params["k_norm"], k, cfg.rms_eps)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return k, v


# §Perf lever (H2 iteration 2): cast Q/K to f32 *before* the score einsum.
# Numerically this is what the f32 softmax wants anyway; structurally the
# astype acts as a dtype barrier in the VJP — the f32 score cotangents cast
# back to bf16 before flowing into the projection backward, halving the TP
# activation-gradient all-reduce bytes (EXPERIMENTS.md §Perf).
QK_F32_BARRIER = False


def gqa_scores_softmax_out(cfg: ArchConfig, q: jax.Array, k: jax.Array,
                           v: jax.Array,
                           mask: Optional[jax.Array]) -> jax.Array:
    """Grouped attention core.

    q: (B, S, Hq, d); k, v: (B, T, Hkv, d); mask: broadcastable to
    (B, 1, 1, S, T) or None. Returns (B, S, Hq·d).
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, d)
    scale = 1.0 / math.sqrt(d)
    if QK_F32_BARRIER:
        qg = qg.astype(jnp.float32)
        k = k.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * scale
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    out = out.reshape(b, s, hq * d)
    return shard(out, "batch", "seq", "heads")


def _output_proj(params, x_attn: jax.Array) -> jax.Array:
    out = jnp.einsum("bsh,hd->bsd", x_attn,
                     params["wo"].astype(x_attn.dtype))
    return shard(out, "batch", "seq", "embed")


def causal_mask(cfg: ArchConfig, s: int, t: Optional[int] = None) -> jax.Array:
    """(1, 1, 1, S, T) causal (+ sliding window) mask for prefill.

    Bidirectional stacks (``cfg.causal=False``, e.g. the whisper encoder)
    get full visibility.
    """
    t = t if t is not None else s
    if not cfg.causal:
        return jnp.ones((1, 1, 1, s, t), bool)
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(t)[None, :]
    m = cols <= rows
    if cfg.sliding_window is not None:
        m = m & (rows - cols < cfg.sliding_window)
    return m[None, None, None]


# Above this many query positions, prefill switches to the query-chunked
# scan formulation (peak score memory O(chunk × S) instead of O(S²)).
PREFILL_CHUNK = 1024


def _chunked_causal_attention(cfg: ArchConfig, q: jax.Array, k: jax.Array,
                              v: jax.Array, chunk: int) -> jax.Array:
    """Memory-efficient causal attention: lax.scan over query chunks.

    Each step scores one (B, chunk, Hq, d) query block against the full
    key set with a global-position causal (+ sliding window) mask — the
    O(S²) score tensor never materialises, only O(chunk·S) per step.
    """
    b, s, hq, d = q.shape
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, hq, d), 1, 0)
    cols = jnp.arange(s)[None, :]

    def step(carry, inputs):
        qk, ci = inputs
        rows = ci * chunk + jnp.arange(chunk)[:, None]
        m = cols <= rows
        if cfg.sliding_window is not None:
            m = m & (rows - cols < cfg.sliding_window)
        if not cfg.causal:
            m = jnp.ones_like(m)
        out = gqa_scores_softmax_out(cfg, qk, k, v, m[None, None, None])
        return carry, out

    _, outs = jax.lax.scan(step, 0, (qc, jnp.arange(nc)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, hq * d)


def attention_prefill(params, cfg: ArchConfig, x: jax.Array,
                      positions: jax.Array,
                      cache: Optional[Dict[str, jax.Array]] = None
                      ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full causal attention over x (B, S, D), positions (B, S)."""
    q = _project_q(params, cfg, x)
    k, v = _project_kv(params, cfg, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    if s > PREFILL_CHUNK and s % PREFILL_CHUNK == 0:
        out = _chunked_causal_attention(cfg, q, k, v, PREFILL_CHUNK)
    else:
        mask = causal_mask(cfg, s)
        out = gqa_scores_softmax_out(cfg, q, k, v, mask)
    new_cache = None
    if cache is not None:
        new_cache = kvcache.write_kv_prefill(cfg, cache, k, v)
    return _output_proj(params, out), new_cache


def attention_prefill_cached(params, cfg: ArchConfig, x: jax.Array,
                             cache: Dict[str, jax.Array], pos: jax.Array,
                             impl: Optional[str] = None
                             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token chunk step against a live cache. x: (B, C, D); pos: (B,)
    absolute position of x[:, 0]. The batched form of C ``attention_decode``
    calls: all C keys/values are written first, then every chunk row attends
    over the full cache under its own per-position validity mask
    (``kvcache.valid_mask_chunk``), so row j's arithmetic — scores, masked
    softmax, value contraction — is bit-identical to a decode step at
    pos + j. Future chunk rows mask to exactly-zero probabilities, which
    annihilate their (already written) values.

    ``impl="pallas"`` routes the chunk through the flash-prefill kernel,
    whose per-sequence ``q_offset`` places each row's chunk mid-sequence —
    the TPU path; online softmax is not bit-exact vs the dense reference,
    so the default (None → dense masked) is what the serving engine's
    bit-exactness tests pin. A sliding-window cache is a ring whose slots
    are not in position order, which the kernel cannot address: such
    configs take the dense masked path on every platform.
    """
    b, c, _ = x.shape
    q = _project_q(params, cfg, x)
    k_new, v_new = _project_kv(params, cfg, x)
    positions = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    new_cache = kvcache.write_kv_chunk(cfg, cache, k_new, v_new, pos)
    t = new_cache["k"].shape[1]
    if impl == "pallas" and cfg.sliding_window is None:
        from repro.kernels import ops as kops
        out = kops.flash_prefill_attention(
            q, new_cache["k"], new_cache["v"], causal=cfg.causal,
            impl="pallas", q_offset=pos, t_valid=jnp.minimum(pos + c, t))
        out = out.reshape(b, c, -1)
        out = shard(out, "batch", "seq", "heads")
    else:
        valid = kvcache.valid_mask_chunk(cfg, t, pos, c)      # (B, C, T)
        mask = valid[:, None, None, :, :]                     # (B,1,1,C,T)
        out = gqa_scores_softmax_out(cfg, q, new_cache["k"],
                                     new_cache["v"], mask)
    return _output_proj(params, out), new_cache


# Optional distributed decode-attention strategy (split-KV shard_map with
# LSE combine) — installed by parallel.collectives for the §Perf iteration.
# fn(cfg, q (B,1,Hq,d), k, v, pos) -> (B, 1, Hq·d) or None (= not applicable).
_DECODE_OVERRIDE = None


def set_decode_attention_override(fn) -> None:
    global _DECODE_OVERRIDE
    _DECODE_OVERRIDE = fn


def attention_decode(params, cfg: ArchConfig, x: jax.Array,
                     cache: Dict[str, jax.Array], pos: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token step. x: (B, 1, D); pos: (B,) current absolute positions.

    Keys carry RoPE at their absolute positions (applied at write time), so
    ring-buffer eviction needs no re-rotation.
    """
    q = _project_q(params, cfg, x)
    k_new, v_new = _project_kv(params, cfg, x)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
    new_cache = kvcache.write_kv(cfg, cache, k_new, v_new, pos)
    if _DECODE_OVERRIDE is not None:
        out = _DECODE_OVERRIDE(cfg, q, new_cache["k"], new_cache["v"], pos)
        if out is not None:
            return _output_proj(params, out), new_cache
    t = new_cache["k"].shape[1]
    valid = kvcache.valid_mask(cfg, t, pos)                   # (B, T)
    mask = valid[:, None, None, None, :]                      # (B,1,1,1,T)
    out = gqa_scores_softmax_out(cfg, q, new_cache["k"], new_cache["v"], mask)
    return _output_proj(params, out), new_cache


def cross_attention(params, cfg: ArchConfig, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array,
                    enc_mask: Optional[jax.Array] = None) -> jax.Array:
    """Decoder cross-attention over static encoder KV (whisper)."""
    q = _project_q(params, cfg, x)
    mask = None
    if enc_mask is not None:
        mask = enc_mask[:, None, None, None, :]
    out = gqa_scores_softmax_out(cfg, q, enc_k, enc_v, mask)
    return _output_proj(params, out)


def project_cross_kv(params, cfg: ArchConfig,
                     enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Precompute encoder K/V once per request (prefill-time)."""
    return _project_kv(params, cfg, enc_out)
