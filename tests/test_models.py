"""Model substrate: layers, attention, MoE, Mamba-2, caches."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as attn_mod
from repro.models import kvcache, layers, mamba2
from repro.models import moe as moe_mod
from repro.models.attention import (attention_decode, attention_prefill,
                                    init_attention)
from repro.models.common import ArchConfig
from repro.kernels.ref import moe_ffn_ref


def _attn_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv_heads=2, d_head=16, d_ff=128, vocab_size=64)
    base.update(kw)
    return ArchConfig(**base)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def test_rmsnorm_unit_scale_preserves_rms():
    cfg = _attn_cfg()
    p = layers.init_norm(jax.random.PRNGKey(0), "n", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64)) * 3.0
    y = layers.apply_norm(p, cfg, x)
    rms = jnp.sqrt(jnp.mean(jnp.square(y), axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_layernorm_zero_mean():
    cfg = _attn_cfg(norm_type="layernorm")
    p = layers.init_norm(jax.random.PRNGKey(0), "n", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64)) + 5.0
    y = layers.apply_norm(p, cfg, x)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-4)


def test_rope_preserves_norm_and_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    pos = jnp.arange(6)[None]
    y = layers.apply_rope(x, pos, theta=1e4)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # relative property: <R(p)q, R(p+k)v> depends only on k
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    dots = []
    for p0 in (0, 7, 23):
        qr = layers.apply_rope(q, jnp.asarray([[p0]]), 1e4)
        vr = layers.apply_rope(v, jnp.asarray([[p0 + 5]]), 1e4)
        dots.append(float(jnp.sum(qr * vr)))
    np.testing.assert_allclose(dots, dots[0], rtol=1e-4)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def test_gqa_equals_mha_when_kv_heads_match():
    cfg_mha = _attn_cfg(n_kv_heads=4)
    p = init_attention(jax.random.PRNGKey(0), "a", cfg_mha)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64)) * 0.5
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    out, _ = attention_prefill(p, cfg_mha, x, pos)
    assert out.shape == (2, 8, 64)
    assert bool(jnp.isfinite(out).all())


def test_causality():
    cfg = _attn_cfg()
    p = init_attention(jax.random.PRNGKey(0), "a", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 64)) * 0.5
    pos = jnp.arange(10)[None]
    out1, _ = attention_prefill(p, cfg, x, pos)
    x2 = x.at[:, 7:].set(jax.random.normal(jax.random.PRNGKey(2),
                                           (1, 3, 64)))
    out2, _ = attention_prefill(p, cfg, x2, pos)
    np.testing.assert_allclose(np.asarray(out1[:, :7]),
                               np.asarray(out2[:, :7]), atol=1e-5)


def test_sliding_window_matches_masked_reference():
    cfg = _attn_cfg(sliding_window=4)
    cfg_full = _attn_cfg()
    p = init_attention(jax.random.PRNGKey(0), "a", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64)) * 0.5
    pos = jnp.arange(12)[None]
    out_w, _ = attention_prefill(p, cfg, x, pos)
    # reference: full attention but manually windowed scores
    q = attn_mod._project_q(p, cfg_full, x)
    k, v = attn_mod._project_kv(p, cfg_full, x)
    q = layers.apply_rope(q, pos, cfg.rope_theta)
    k = layers.apply_rope(k, pos, cfg.rope_theta)
    rows = jnp.arange(12)[:, None]
    cols = jnp.arange(12)[None, :]
    m = (cols <= rows) & (rows - cols < 4)
    ref = attn_mod.gqa_scores_softmax_out(cfg_full, q, k, v,
                                          m[None, None, None])
    ref = attn_mod._output_proj(p, ref)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref),
                               atol=1e-5)


def test_decode_matches_prefill_step_by_step():
    for kw in ({}, {"qk_norm": True}, {"qkv_bias": True},
               {"sliding_window": 5}):
        cfg = _attn_cfg(**kw)
        p = init_attention(jax.random.PRNGKey(0), "a", cfg)
        S = 9
        x = jax.random.normal(jax.random.PRNGKey(1), (2, S, 64)) * 0.5
        pos = jnp.broadcast_to(jnp.arange(S), (2, S))
        full, _ = attention_prefill(p, cfg, x, pos)
        cache = kvcache.init_attn_cache(cfg, 2, 16)
        outs = []
        for t in range(S):
            o, cache = attention_decode(p, cfg, x[:, t:t + 1], cache,
                                        jnp.full((2,), t, jnp.int32))
            outs.append(o)
        inc = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                                   atol=2e-5, err_msg=str(kw))


def test_ring_cache_wraps_and_masks():
    cfg = _attn_cfg(sliding_window=4)
    cache = kvcache.init_attn_cache(cfg, 1, 32)
    assert cache["k"].shape[1] == 4          # ring length = window
    # brute-force valid_mask check
    for pos in (0, 3, 4, 9):
        vm = kvcache.valid_mask(cfg, 4, jnp.asarray([pos]))
        live = int(vm.sum())
        assert live == min(pos + 1, 4)


def _kv_step(cfg, b, t, seed):
    rng = np.random.default_rng(seed)
    shape = (b, t, cfg.n_kv_heads, cfg.d_head)
    cache = {n: jnp.asarray(rng.normal(size=shape), jnp.float32)
             for n in ("k", "v")}
    new = [jnp.asarray(rng.normal(size=(b, 1) + shape[2:]), jnp.float32)
           for _ in range(2)]
    return cache, new


@pytest.mark.parametrize("window,positions", [
    (None, [0, 5, 15, 7]), (4, [0, 3, 4, 9])])
def test_write_kv_rows_matches_a_scatter(window, positions):
    """The per-sequence update slices write what an independent scatter
    writes, each row at its own position (a ring cache at pos mod window),
    and leave every other slot as it was."""
    cfg = _attn_cfg(sliding_window=window)
    t = 4 if window else 16
    cache, (k_new, v_new) = _kv_step(cfg, len(positions), t, 0)
    got = kvcache.write_kv_rows(cfg, cache, k_new, v_new,
                                jnp.asarray(positions, jnp.int32))
    for name, new in (("k", k_new), ("v", v_new)):
        want = np.array(cache[name])
        for b, p in enumerate(positions):
            want[b, p % t] = np.asarray(new)[b, 0]
        np.testing.assert_array_equal(np.asarray(got[name]), want)


def test_write_kv_rows_past_the_cache_stays_in_its_row():
    """A position past a full cache lands, clamped, in its own row's last
    slot; no other row and no earlier slot changes."""
    cfg = _attn_cfg()
    t = 8
    cache, (k_new, v_new) = _kv_step(cfg, 3, t, 1)
    got = kvcache.write_kv_rows(cfg, cache, k_new, v_new,
                                jnp.asarray([2, t, t + 5], jnp.int32))
    for name, new in (("k", k_new), ("v", v_new)):
        want = np.array(cache[name])
        want[0, 2] = np.asarray(new)[0, 0]
        want[1:, t - 1] = np.asarray(new)[1:, 0]
        np.testing.assert_array_equal(np.asarray(got[name]), want)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dict(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_head=16, d_ff=0, vocab_size=64, n_experts=8,
                top_k=2, moe_d_ff=16, moe_capacity_factor=8.0)
    base.update(kw)
    return ArchConfig(**base)


def test_capacity_and_sorted_match_oracle():
    cfg = _moe_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 7, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k).reshape(x.shape)
    out_c, aux = moe_mod.moe_capacity(p, cfg, x)
    out_s = moe_mod.moe_sorted(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref), atol=1e-5)
    assert float(aux) > 0


def test_capacity_drops_when_tight():
    cfg = _moe_cfg(moe_capacity_factor=0.5)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    out_tight, _ = moe_mod.moe_capacity(p, cfg, x)
    out_ref = moe_mod.moe_sorted(p, cfg, x)
    assert float(jnp.max(jnp.abs(out_tight - out_ref))) > 1e-4


def test_shared_expert_added():
    cfg = _moe_cfg(n_shared_experts=1, shared_d_ff=16)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    assert "shared" in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k, shared_in=p["shared"]["wi"],
                      shared_out=p["shared"]["wo"]).reshape(x.shape)
    out, _ = moe_mod.moe_capacity(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_router_renorm_weights_sum_to_one():
    cfg = _moe_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    _, topw, topi = moe_mod.route(p, cfg, x)
    np.testing.assert_allclose(np.asarray(topw.sum(-1)), 1.0, atol=1e-6)
    assert int(topi.max()) < cfg.n_experts


def test_sort_by_expert_roundtrip():
    topi = jnp.asarray([[3, 1], [0, 3], [2, 2]])
    sort_idx, inv_idx, gs = moe_mod.sort_by_expert(topi, 4)
    flat = topi.reshape(-1)
    assert np.all(np.diff(np.asarray(flat[sort_idx])) >= 0)
    np.testing.assert_array_equal(np.asarray(flat[sort_idx][inv_idx]),
                                  np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(gs), [1, 1, 2, 2])


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------

def _ssm_cfg():
    return ArchConfig(name="t", family="ssm", n_layers=1, d_model=32,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=64,
                      ssm_state=16, ssm_conv=4, ssm_expand=2,
                      ssm_head_dim=8, ssm_groups=2, ssm_chunk=8,
                      attn_layer_period=0)


def test_ssd_chunked_matches_sequential():
    B, S, H, P, N = 2, 40, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, H, N)) * 0.3
    y1, s1 = mamba2.ssd_chunked(x, dt, a, b, c, chunk=8)
    y2, s2 = mamba2.ssd_sequential(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


def test_mamba_prefill_decode_continuation():
    cfg = _ssm_cfg()
    p = mamba2.init_mamba(jax.random.PRNGKey(0), "m", cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32)) * 0.5
    cache0 = kvcache.init_ssm_cache(cfg, 2)
    full, _ = mamba2.mamba_prefill(p, cfg, x, cache0)
    out_p, cache = mamba2.mamba_prefill(p, cfg, x[:, :12], cache0)
    outs = [out_p]
    for t in range(12, 16):
        o, cache = mamba2.mamba_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(o)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full), atol=2e-5)


def test_mamba_state_is_context_length_independent():
    cfg = _ssm_cfg()
    cache = kvcache.init_ssm_cache(cfg, 3)
    assert cache["state"].shape == (3, cfg.ssm_heads, cfg.ssm_head_dim,
                                    cfg.ssm_state)
    assert cache["conv"].shape == (3, cfg.ssm_conv - 1, cfg.conv_dim)
