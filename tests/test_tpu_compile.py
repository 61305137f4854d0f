"""Compile-for-chip tests: the served path's Pallas kernels, the F role's
expert-parallel program and the A role's chunk attention, compiled by the
TPU compiler for a described (not attached) TPU v5e at the published
widths of granite-moe-1b-a400m (E=32 top-8, d_model 1024, moe_d_ff 512,
16/8 heads of 64, bf16).

Nothing runs: these tests catch what interpret mode cannot — block shapes
Mosaic refuses, vector loads from SMEM, unaligned DMAs, and sharded
operands the compiler would all-gather. The v5e:2x2 topology is described
inside a fixture, never at import, so every pytest worker collects the
same tests and only the one running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.kernels import backend
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.grouped_gemm import grouped_gemm_pallas
from repro.kernels.splitkv_attention import splitkv_attention_pallas
from repro.models import attention as attn_mod, kvcache
from repro.parallel.afd import make_chunk_attention, make_expert_ffn

CFG = configs.get_config("granite-moe-1b-a400m")
E, D, M, K = CFG.n_experts, CFG.d_model, CFG.moe_d_ff, CFG.top_k
HQ, HKV, DH = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
BF16 = jnp.bfloat16
# decode batch (8 tokens) and one 128-token prefill chunk
TOKENS = [8, 128]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache):
    """ShapeDtypeStruct factory placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------- grouped GEMM

@pytest.mark.parametrize("n_tok", TOKENS)
def test_grouped_gemm_plain(chip, n_tok):
    _compile(lambda x, w, g: grouped_gemm_pallas(x, w, g, interpret=False),
             chip((n_tok * K, D), BF16), chip((E, D, 2 * M), BF16),
             chip((E,), jnp.int32))


@pytest.mark.parametrize("n_tok", TOKENS)
def test_grouped_gemm_row_index(chip, n_tok):
    """The fused dispatch gather: wi GEMM reading token rows by index."""
    _compile(lambda x, w, g, i: grouped_gemm_pallas(
                 x, w, g, row_index=i, interpret=False),
             chip((n_tok, D), BF16), chip((E, D, 2 * M), BF16),
             chip((E,), jnp.int32), chip((n_tok * K,), jnp.int32))


@pytest.mark.parametrize("n_tok", TOKENS)
def test_grouped_gemm_out_index(chip, n_tok):
    """The fused combine scatter: wo GEMM writing rows in token order."""
    _compile(lambda h, w, g, i: grouped_gemm_pallas(
                 h, w, g, out_index=i, out_rows=n_tok * K, interpret=False),
             chip((n_tok * K, M), BF16), chip((E, M, D), BF16),
             chip((E,), jnp.int32), chip((n_tok * K,), jnp.int32))


@pytest.mark.parametrize("n_tok", TOKENS)
def test_grouped_gemm_int8(chip, n_tok):
    _compile(lambda x, w, g, s: grouped_gemm_pallas(
                 x, w, g, scales=s, interpret=False),
             chip((n_tok * K, D), BF16), chip((E, D, 2 * M), jnp.int8),
             chip((E,), jnp.int32), chip((E,), jnp.float32))


@pytest.mark.parametrize("n_tok", TOKENS)
def test_grouped_gemm_int4(chip, n_tok):
    _compile(lambda x, w, g, s: grouped_gemm_pallas(
                 x, w, g, scales=s, tile_n=128, interpret=False),
             chip((n_tok * K, D), BF16), chip((E, D // 2, 2 * M), jnp.int8),
             chip((E,), jnp.int32), chip((E, 2 * M // 128), jnp.float32))


# ---------------------------------------------------------------- attention

@pytest.mark.parametrize("return_lse", [False, True])
def test_splitkv(chip, return_lse):
    kv = chip((8, 2048, HKV, DH), BF16)
    _compile(lambda q, k, v, n: splitkv_attention_pallas(
                 q, k, v, n, return_lse=return_lse, interpret=False),
             chip((8, HQ, DH), BF16), kv, kv, chip((8,), jnp.int32))


def test_flash_prefill_whole(chip):
    qkv = [chip((1, 512, h, DH), BF16) for h in (HQ, HKV, HKV)]
    _compile(lambda q, k, v: flash_prefill_pallas(q, k, v, interpret=False),
             *qkv)


def test_flash_prefill_chunked(chip):
    """A 128-token chunk against a 2048-slot cache, with per-sequence
    chunk starts and valid lengths riding in as scalar prefetch."""
    kv = chip((2, 2048, HKV, DH), BF16)
    _compile(lambda q, k, v, off, tv: flash_prefill_pallas(
                 q, k, v, q_offset=off, t_valid=tv, interpret=False),
             chip((2, 128, HQ, DH), BF16), kv, kv,
             chip((2,), jnp.int32), chip((2,), jnp.int32))


# ---------------------------------------------------------------- F role

@pytest.mark.parametrize("n_f", [1, 2])
def test_expert_ffn_is_expert_parallel(topo, no_persistent_cache,
                                       monkeypatch, n_f):
    """The F-role program on n_f described chips: each holds E/n_f
    experts, the Pallas kernels compile inside the shard_map, and no
    expert weight is all-gathered."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh = Mesh(topo.devices[2:2 + n_f], ("expert",))
    on = lambda spec: NamedSharding(mesh, spec)           # noqa: E731
    n_tok = 16
    hlo = make_expert_ffn(CFG, mesh, "pallas").lower(
        jax.ShapeDtypeStruct((E, D, 2 * M), BF16, sharding=on(P("expert"))),
        jax.ShapeDtypeStruct((E, M, D), BF16, sharding=on(P("expert"))),
        jax.ShapeDtypeStruct((n_tok, D), BF16, sharding=on(P())),
        jax.ShapeDtypeStruct((n_tok, K), jnp.float32, sharding=on(P())),
        jax.ShapeDtypeStruct((n_tok, K), jnp.int32, sharding=on(P())),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
    assert ("all-reduce" in hlo) == (n_f > 1)


@pytest.mark.parametrize("n_a", [1, 2])
def test_chunk_attention_on_a_role(topo, no_persistent_cache, monkeypatch,
                                   n_a):
    """The A role's flash-prefill chunk attention over n_a described chips
    holding replicated operands: the kernel compiles per device inside
    the shard_map (an unwrapped Pallas call over a multi-device mesh is
    refused as unpartitionable)."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh = Mesh(topo.devices[:n_a], ("model",))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, P())), tree)

    spec = CFG.layer_plan().flat()[0]
    params = jax.eval_shape(lambda: attn_mod.init_attention(
        jax.random.PRNGKey(0), "attn", CFG))
    cache = jax.eval_shape(lambda: kvcache.init_layer_cache(CFG, spec, 2,
                                                            2048))
    hlo = make_chunk_attention(CFG, mesh).lower(
        on(params), on(jax.ShapeDtypeStruct((2, 128, D), BF16)), on(cache),
        on(jax.ShapeDtypeStruct((2,), jnp.int32))).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
