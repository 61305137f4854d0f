"""Decoder stack assembly.

The depth dimension is factored by ``cfg.layer_plan()`` into a small
heterogeneous ``prefix`` (unrolled) plus ``n_periods`` repetitions of a
homogeneous ``period`` — the period is executed under ``jax.lax.scan`` over
parameters stacked on a leading axis. This keeps HLO size O(period), not
O(depth): the 61-layer Kimi-K2 compiles as 1 unrolled dense layer + a
60-step scan over one MoE layer's HLO.

Layer structure (pre-norm residual):
    x += mixer(norm1(x))         mixer ∈ {attention, mamba2}
    x += cross_attn(norm_x(x))   (enc-dec only)
    x += ffn(norm2(x))           ffn ∈ {dense MLP, MoE, none (pure SSM)}
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.common import ArchConfig, LayerSpec
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


def has_ffn(cfg: ArchConfig, spec: LayerSpec) -> bool:
    if spec.moe:
        return True
    return cfg.d_ff > 0


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def init_layer(key, name: str, cfg: ArchConfig,
               spec: LayerSpec) -> Dict[str, object]:
    p: Dict[str, object] = {"ln1": init_norm(key, f"{name}.ln1", cfg)}
    if spec.kind == "attn":
        p["attn"] = attn.init_attention(key, f"{name}.attn", cfg)
        if cfg.is_encdec:
            p["ln_cross"] = init_norm(key, f"{name}.ln_cross", cfg)
            p["cross"] = attn.init_attention(key, f"{name}.cross", cfg,
                                             cross=True)
    else:
        p["mamba"] = mamba2.init_mamba(key, f"{name}.mamba", cfg)
    if has_ffn(cfg, spec):
        p["ln2"] = init_norm(key, f"{name}.ln2", cfg)
        if spec.moe:
            p["moe"] = moe.init_moe(key, f"{name}.moe", cfg)
        else:
            p["mlp"] = init_mlp(key, f"{name}.mlp", cfg)
    return p


def layer_forward(params, cfg: ArchConfig, spec: LayerSpec, x: jax.Array,
                  *, mode: str,
                  positions: Optional[jax.Array] = None,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  pos: Optional[jax.Array] = None,
                  cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None
                  ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Apply one layer. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(params["ln1"], cfg, x)

    if spec.kind == "attn":
        if mode == "decode":
            mix, new_cache = attn.attention_decode(params["attn"], cfg, h,
                                                   cache, pos)
        else:
            mix, new_cache = attn.attention_prefill(params["attn"], cfg, h,
                                                    positions, cache)
    else:
        if mode == "decode":
            mix, new_cache = mamba2.mamba_decode(params["mamba"], cfg, h,
                                                 cache)
        else:
            mix, new_cache = mamba2.mamba_prefill(params["mamba"], cfg, h,
                                                  cache)
    x = x + mix

    if spec.kind == "attn" and cfg.is_encdec and cross_kv is not None:
        h = apply_norm(params["ln_cross"], cfg, x)
        x = x + attn.cross_attention(params["cross"], cfg, h,
                                     cross_kv[0], cross_kv[1])

    if has_ffn(cfg, spec):
        h = apply_norm(params["ln2"], cfg, x)
        if spec.moe:
            out, aux = moe.moe_forward(params["moe"], cfg, h, mode=mode)
        else:
            out = apply_mlp(params["mlp"], cfg, h)
        x = x + out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ArchConfig) -> Dict[str, object]:
    plan = cfg.layer_plan()
    prefix = [init_layer(key, f"prefix{i}", cfg, s)
              for i, s in enumerate(plan.prefix)]

    def stacked_layer(j: int, spec: LayerSpec):
        per = [init_layer(key, f"period{p}_slot{j}", cfg, spec)
               for p in range(plan.n_periods)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)

    stack = [stacked_layer(j, s) for j, s in enumerate(plan.period)]
    return {
        "prefix": prefix,
        "stack": stack,
        "final_norm": init_norm(key, "final_norm", cfg),
    }


def stack_forward(params, cfg: ArchConfig, x: jax.Array, *, mode: str,
                  positions: Optional[jax.Array] = None,
                  cache: Optional[Dict[str, object]] = None,
                  pos: Optional[jax.Array] = None,
                  cross_kv=None
                  ) -> Tuple[jax.Array, Optional[Dict[str, object]], jax.Array]:
    """Run prefix + scanned periods. Returns (x, new_cache, aux_total)."""
    plan = cfg.layer_plan()
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix: List = []

    # cross_kv layout: {"prefix": [(k, v) | None per prefix layer],
    #                   "stack": {"k": (n_periods, B, T, kv, dh), "v": ...}}
    for i, spec in enumerate(plan.prefix):
        c = cache["prefix"][i] if cache is not None else None
        ckv = None
        if cross_kv is not None and spec.kind == "attn":
            ckv = cross_kv["prefix"][i]
        x, nc, aux = layer_forward(params["prefix"][i], cfg, spec, x,
                                   mode=mode, positions=positions, cache=c,
                                   pos=pos, cross_kv=ckv)
        new_prefix.append(nc)
        aux_total = aux_total + aux

    new_stack = [None] * len(plan.period)
    if plan.n_periods:
        def body(carry, xs):
            xc, auxc = carry
            layer_ps, caches, ckvs = xs
            new_caches = []
            for j, spec in enumerate(plan.period):
                c = caches[j] if caches is not None else None
                ckv = None
                if ckvs is not None and spec.kind == "attn":
                    ckv = (ckvs["k"], ckvs["v"])
                xc, nc, aux = layer_forward(layer_ps[j], cfg, spec, xc,
                                            mode=mode, positions=positions,
                                            cache=c, pos=pos, cross_kv=ckv)
                new_caches.append(nc)
                auxc = auxc + aux
            return (xc, auxc), new_caches

        if cfg.remat:
            body = jax.checkpoint(body)

        stack_caches = cache["stack"] if cache is not None else None
        ckv_scan = cross_kv["stack"] if cross_kv is not None else None
        (x, aux_total), scanned_caches = jax.lax.scan(
            body, (x, aux_total),
            (params["stack"], stack_caches, ckv_scan))
        new_stack = scanned_caches

    x = apply_norm(params["final_norm"], cfg, x)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["prefix"] = new_prefix
        new_cache["stack"] = new_stack
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# Whisper-style encoder (frontend is a stub: inputs are frame embeddings)
# ---------------------------------------------------------------------------

def encoder_config(cfg: ArchConfig) -> ArchConfig:
    """The encoder twin: bidirectional attention, no cache, no MoE."""
    import dataclasses
    return dataclasses.replace(
        cfg, n_layers=cfg.n_encoder_layers, n_experts=0, top_k=0,
        n_encoder_layers=0, sliding_window=None, causal=False)


def init_encoder(key, cfg: ArchConfig) -> Dict[str, object]:
    ecfg = encoder_config(cfg)
    from repro.models.layers import embed_init
    return {
        "stack": init_stack(key, ecfg),
        "pos": embed_init(key, "enc.pos", (cfg.encoder_seq, cfg.d_model),
                          cfg.params_dtype),
    }


def encode(params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, encoder_seq, D) precomputed stub embeddings."""
    ecfg = encoder_config(cfg)
    x = frames.astype(cfg.compute_dtype) + \
        params["pos"][None].astype(cfg.compute_dtype)
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), x.shape[:2])
    x, _, _ = stack_forward(params["stack"], ecfg, x, mode="train",
                            positions=positions)
    return x
