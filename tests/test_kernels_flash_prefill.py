"""Flash prefill attention kernel — interpret-mode allclose vs the dense
masked reference over causal/window/bidirectional × GQA sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from optional_hypothesis import given, settings, strategies as st

from repro.kernels.flash_prefill import flash_prefill_pallas


def _dense_ref(q, k, v, causal=True, window=None):
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k.astype(jnp.float32)) / np.sqrt(d)
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (rows - cols < window)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def _run(b, s, hq, hkv, d, tq, tk, causal=True, window=None,
         dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = flash_prefill_pallas(q, k, v, causal=causal, window=window,
                               tile_q=tq, tile_k=tk, interpret=True)
    ref = _dense_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=1e-2)


@pytest.mark.parametrize("b,s,hq,hkv,d,tq,tk", [
    (2, 64, 4, 2, 16, 16, 16),      # GQA ×2
    (1, 100, 8, 2, 32, 32, 16),     # ragged S, GQA ×4
    (2, 48, 4, 4, 16, 16, 32),      # MHA, tk > rows per tile
])
def test_causal_shapes(b, s, hq, hkv, d, tq, tk):
    _run(b, s, hq, hkv, d, tq, tk)


def test_sliding_window():
    _run(1, 96, 4, 2, 16, 16, 16, window=24)
    _run(1, 64, 2, 2, 16, 8, 8, window=5)      # window < tile


def test_bidirectional_encoder():
    _run(2, 64, 4, 4, 16, 16, 16, causal=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    _run(1, 64, 4, 2, 32, 32, 32, dtype=dtype)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(8, 80), tq=st.sampled_from([8, 16]),
       tk=st.sampled_from([8, 32]), seed=st.integers(0, 99))
def test_hypothesis_sizes(s, tq, tk, seed):
    _run(1, s, 4, 2, 16, tq, tk, seed=seed)


def test_chunk_offsets_per_sequence():
    """Chunked prefill against a live cache with a ragged batch: each
    sequence's chunk starts at its own position, read from scalar
    prefetch — one compiled kernel for every chunk start."""
    from repro.kernels.ref import flash_prefill_ref
    b, c, t = 3, 8, 48
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, c, 4, 16))
    k = jax.random.normal(ks[1], (b, t, 2, 16))
    v = jax.random.normal(ks[2], (b, t, 2, 16))
    off = jnp.asarray([0, 13, 37], jnp.int32)
    out = flash_prefill_pallas(q, k, v, q_offset=off, t_valid=off + c,
                               tile_q=8, tile_k=16, interpret=True)
    ref = flash_prefill_ref(q, k, v, q_offset=off, t_valid=off + c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-2)
