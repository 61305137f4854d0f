"""Pallas TPU grouped GEMM — the paper's central operator (Fig. 3).

Contract (matches `ref.grouped_gemm_ref` and `jax.lax.ragged_dot`):

    out (M, N) = grouped_gemm(lhs (M, K), rhs (G, K, N), group_sizes (G,))

Rows of ``lhs`` are sorted by group: group g owns the contiguous row range
[offsets[g], offsets[g+1]). Rows past sum(group_sizes) produce zeros.

TPU adaptation of the CUDA grouped-GEMM idea (DESIGN.md §3): instead of one
kernel launch per expert (CUTLASS-style), a single kernel iterates
MXU-aligned (tile_m × tile_n) output tiles. Because fine-grained experts
make group boundaries land mid-tile (the paper's "fan-out effect"), the
grid is built over *visits* — (m-tile, group) intersection pairs — so a
tile crossed by multiple groups is visited once per group with row masking,
and no padding compute is wasted on expert boundaries:

  * scalar-prefetch arrays ``visit_m``/``visit_g`` steer the BlockSpec
    index_maps (which lhs row-tile and which expert's weight block to DMA
    into VMEM);
  * an f32 VMEM scratch accumulates across the K dimension and across
    consecutive visits that share an m-tile;
  * the accumulator flushes to HBM on the last visit of each tile.

Fused router permute (Megatron-MoE's permute-fused grouped GEMM, adapted
to the visit grid):

  * ``row_index`` (M,) fuses the dispatch *gather*: GEMM row r reads
    ``lhs[row_index[r]]``, so the router's sorted token order never has to
    be materialized in HBM. The permutation rides the scalar-prefetch
    channel (SMEM); each index is read as a scalar and steers one
    ``make_async_copy`` row DMA from the token buffer in HBM into the
    tile's VMEM staging buffer.
  * ``out_index`` (M,) fuses the combine-side *unpermute scatter*: the
    accumulator epilogue DMAs GEMM row r to ``out[out_index[r]]`` in HBM
    instead of writing tile-contiguous rows, returning outputs already in
    token order. Destinations must be unique per valid row (a permutation,
    which router unpermute always is).

Quantized weight paths (both shift the Eq. 6 operating point — weight
bytes drop 2–8× vs bf16, so the FFN's arithmetic intensity and with it the
paper's dead-zone boundary move; see core/budget.weight_bytes_per_param):

  * int8  — ``rhs`` holds int8 codes with per-expert scales (G,);
  * int4  — ``rhs`` holds two 4-bit codes packed per int8 along K
    (G, K//2, N) with per-expert-per-``tile_n``-block scales (G, N/block);
    the kernel unpacks nibbles (sign-extended via the (x^8)-8 trick) and
    dequantises in VMEM.

VMEM budget per grid step: lhs tile (tile_m × tile_k) + rhs block
(tile_k × tile_n) + f32 accumulator (tile_m × tile_n) — with the default
128×128×512 tiling ≈ 0.5 MB, comfortably inside the ~16 MB v5e VMEM so the
pipeline can double-buffer. The fused variants add one f32 row-staging
buffer (tile_m × tile_k, or tile_m × tile_n for the scatter) whatever the
token count; the dequant scales ride in SMEM as scalar prefetch.

Validated in interpret mode on CPU against ``ref.grouped_gemm_ref`` over
shape/dtype sweeps (tests/test_kernels_grouped_gemm.py,
tests/test_kernels_quant.py); tests/test_tpu_compile.py compiles every
form for a TPU v5e at published widths.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

MXU_SUBLANE = 8                 # f32 sublane multiple of the MXU tile


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def clamp_tile_m(tile_m: int, m: int) -> int:
    """min(tile_m, m) rounded UP to the 8-row MXU sublane multiple.

    A bare ``min(tile_m, m)`` silently mis-tiles when it leaves a
    non-MXU-aligned row count (e.g. m=5 → tile_m=5): Mosaic either rejects
    the block shape or pads each sublane load. Rounding the clamp up keeps
    tiny-M grids one aligned tile (the zero padding is compute-safe).
    """
    return _cdiv(max(1, min(tile_m, m)), MXU_SUBLANE) * MXU_SUBLANE


def build_visits(group_sizes: jax.Array, m: int, tile_m: int,
                 n_groups: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compute (visit_m, visit_g, offsets) with static visit count.

    A visit is one (m-tile, group) pair whose row ranges intersect. The
    static worst case is n_tiles + n_groups - 1 visits (every group boundary
    splits one tile). Surplus slots are filled with duplicate (tile, group)
    pairs whose row mask is empty — they add zeros.

    All arithmetic is jnp (shape-polymorphic in values, static in shapes) so
    the builder can live inside a jit'd wrapper.
    """
    n_tiles = _cdiv(m, tile_m)
    v_max = n_tiles + n_groups - 1
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    # For visit index v, we need the v-th (tile, group) intersection in
    # lexicographic (tile, group) order. Count visits per tile:
    #   tile t spans rows [t·tm, (t+1)·tm); groups intersecting it are those
    #   with offsets[g] < (t+1)·tm and offsets[g+1] > t·tm.
    # first_group[t] = max g such that offsets[g] <= t·tm (with empty groups
    # skipped naturally by the mask), n_visits[t] = count.
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_lo = tiles * tile_m
    tile_hi = jnp.minimum(tile_lo + tile_m, m)
    # group of the first row in the tile (searchsorted right gives the group
    # whose range contains the row; empty groups resolve to later groups)
    first_group = jnp.searchsorted(offsets[1:], tile_lo, side="right"
                                   ).astype(jnp.int32)
    first_group = jnp.minimum(first_group, n_groups - 1)
    last_group = jnp.searchsorted(offsets[1:], tile_hi - 1, side="right"
                                  ).astype(jnp.int32)
    last_group = jnp.minimum(last_group, n_groups - 1)
    n_visits = last_group - first_group + 1                    # (n_tiles,)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(n_visits).astype(jnp.int32)])
    total = starts[-1]
    v_idx = jnp.arange(v_max, dtype=jnp.int32)
    # For each v: which tile? (searchsorted over starts); surplus v -> last.
    vm = jnp.searchsorted(starts[1:], v_idx, side="right").astype(jnp.int32)
    vm = jnp.minimum(vm, n_tiles - 1)
    vg = first_group[vm] + (v_idx - starts[vm])
    # Surplus slots (v >= total): clamp to a valid (tile, group) pair with an
    # empty mask — the kernel masks rows by [offsets[g], offsets[g+1)) ∩ tile
    # and treats vg == n_groups as an explicit empty marker.
    vg = jnp.where(v_idx < total, vg, n_groups)
    vg = jnp.minimum(vg, n_groups).astype(jnp.int32)
    return vm, vg, offsets


def _unpack_int4(packed: jax.Array, tile_k: int, tile_n: int) -> jax.Array:
    """(tile_k//2, tile_n) packed nibbles → (tile_k, tile_n) int32 codes.

    Low nibble holds the even-K code, high nibble the odd-K code; both are
    sign-extended from 4 bits via the (x ^ 8) - 8 two's-complement trick.
    """
    w32 = packed.astype(jnp.int32) & 0xFF
    lo = ((w32 & 0xF) ^ 8) - 8
    hi = (((w32 >> 4) & 0xF) ^ 8) - 8
    return jnp.stack([lo, hi], axis=1).reshape(tile_k, tile_n)


def grouped_gemm_pallas(lhs: jax.Array, rhs: jax.Array,
                        group_sizes: jax.Array,
                        *, tile_m: int = 128, tile_n: int = 128,
                        tile_k: Optional[int] = 512,
                        out_dtype=None,
                        scales: Optional[jax.Array] = None,
                        row_index: Optional[jax.Array] = None,
                        out_index: Optional[jax.Array] = None,
                        out_rows: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Grouped GEMM via the visit-steered Pallas kernel.

    Weight quantization (inferred from ``scales``):
      * ``scales`` (G,)   — int8 codes in ``rhs`` (G, K, N), per-expert
        dequant ``out = lhs · (rhs·scale[g])``;
      * ``scales`` (G, B) — int4 nibbles packed two-per-int8 in ``rhs``
        (G, K//2, N), per-(expert, tile_n-block) scales; requires
        ``tile_n == N / B`` (quantize with ``block_n == tile_n``).

    Fused router permute:
      * ``row_index`` (M,) — GEMM row r consumes ``lhs[row_index[r]]``
        (``lhs`` then has the *token* row count, not M);
      * ``out_index`` (M,) — GEMM row r lands in ``out[out_index[r]]``
        (a permutation over valid rows; ``out_rows`` sets the output row
        count, default M). Un-targeted rows are zero.

    ``interpret=None`` runs the kernel body in the Pallas interpreter off
    the TPU and compiles it with Mosaic on the TPU (``interpret_mode``).
    """
    int4 = scales is not None and scales.ndim == 2
    g = rhs.shape[0]
    k = lhs.shape[1]
    n = rhs.shape[2]
    if int4:
        if rhs.shape[1] * 2 != k:
            raise ValueError(
                f"int4 rhs packs two codes per byte along K: expected "
                f"(G, {k}//2, N), got {rhs.shape}")
    else:
        assert k == rhs.shape[1], (lhs.shape, rhs.shape)
    assert group_sizes.shape == (g,)
    m = lhs.shape[0] if row_index is None else int(row_index.shape[0])
    out_dtype = out_dtype or lhs.dtype

    tile_m = clamp_tile_m(tile_m, m)
    tile_n = min(tile_n, n)
    tile_k = k if tile_k is None else min(tile_k, k)
    if int4:
        if tile_k % 2:
            raise ValueError(f"int4 path needs an even tile_k, got {tile_k}")
        n_blocks = scales.shape[1]
        if n_blocks != _cdiv(n, tile_n):
            raise ValueError(
                f"int4 scales carry {n_blocks} N-blocks but tile_n={tile_n} "
                f"tiles N={n} into {_cdiv(n, tile_n)} — quantize with "
                f"block_n == tile_n")
    # Pad every dim to its tile multiple (zero padding is compute-safe).
    m_pad = _cdiv(m, tile_m) * tile_m
    n_pad = _cdiv(n, tile_n) * tile_n
    k_pad = _cdiv(k, tile_k) * tile_k
    gather = row_index is not None
    scatter = out_index is not None
    # The fused gather DMAs single token rows out of HBM, so the token
    # buffer keeps its own row count (as 32-bit rows); the plain path tiles
    # rows in VMEM.
    lhs_p = jnp.pad(lhs, ((0, 0 if gather else m_pad - m), (0, k_pad - k)))
    if gather:
        lhs_p = _dma_rows(lhs_p)
    if int4:
        rhs_p = jnp.pad(rhs, ((0, 0), (0, k_pad // 2 - rhs.shape[1]),
                              (0, n_pad - n)))
    else:
        rhs_p = jnp.pad(rhs, ((0, 0), (0, k_pad - k), (0, n_pad - n)))

    visit_m, visit_g, offsets = build_visits(group_sizes, m, tile_m, g)
    n_visits = int(visit_m.shape[0])
    n_k_tiles = k_pad // tile_k
    n_n_tiles = n_pad // tile_n
    grid = (n_n_tiles, n_visits, n_k_tiles)
    o_rows = m if out_rows is None else int(out_rows)

    # Scalar-prefetch operands (SMEM): visit steering, the permutations and
    # the dequant scales — every per-row or per-expert value the kernel
    # reads is a scalar load.
    prefetch = [visit_m, visit_g, offsets]
    if gather:
        idx_p = jnp.pad(row_index.astype(jnp.int32), (0, m_pad - m))
        prefetch.append(jnp.clip(idx_p, 0, lhs.shape[0] - 1))
    if scatter:
        prefetch.append(jnp.pad(out_index.astype(jnp.int32), (0, m_pad - m)))
    if scales is not None:
        prefetch.append(scales.astype(jnp.float32).reshape(-1))
    n_pref = len(prefetch)

    def kernel(*refs):
        pref = list(refs[:n_pref])
        vm_ref, vg_ref, off_ref = pref[:3]
        row_ref = pref.pop(3) if gather else None
        dest_ref = pref.pop(3) if scatter else None
        scale_ref = pref[3] if scales is not None else None
        lhs_ref, rhs_ref = refs[n_pref], refs[n_pref + 1]
        out_ref, acc_ref = refs[n_pref + 2 + scatter], refs[n_pref + 3
                                                              + scatter]
        bufs = list(refs[n_pref + 4 + scatter:])

        j = pl.program_id(0)
        v = pl.program_id(1)
        kt = pl.program_id(2)
        n_vis = pl.num_programs(1)
        gid = vg_ref[v]
        mt = vm_ref[v]
        base = mt * tile_m

        # First (visit, k-tile) touching this output tile initialises the
        # accumulator. Visits sharing an m-tile are consecutive in v.
        is_first = jnp.logical_or(v == 0,
                                  vm_ref[jnp.maximum(v - 1, 0)] != mt)

        @pl.when(jnp.logical_and(is_first, kt == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Row mask: rows of this tile belonging to group gid.
        rows = base + jax.lax.broadcasted_iota(jnp.int32, (tile_m, 1), 0)
        valid = jnp.logical_and(gid < g, rows < m)
        lo = off_ref[jnp.minimum(gid, g - 1)]
        hi = off_ref[jnp.minimum(gid + 1, g)]
        mask = jnp.logical_and(valid,
                               jnp.logical_and(rows >= lo, rows < hi))

        if gather:
            # Fused dispatch gather: one row DMA per GEMM row, steered by
            # the prefetched permutation, from the HBM token buffer into
            # the tile's VMEM staging buffer.
            xbuf, sem = bufs.pop(0), bufs.pop(0)

            def row_copy(r):
                return pltpu.make_async_copy(
                    lhs_ref.at[row_ref[base + r], :,
                               pl.ds(kt * tile_k, tile_k)],
                    xbuf.at[r], sem)

            _for_rows(tile_m, lambda r: row_copy(r).start())
            _for_rows(tile_m, lambda r: row_copy(r).wait())
            x = xbuf[...].reshape(tile_m, tile_k).astype(lhs.dtype)
        else:
            x = lhs_ref[...]
        x = jnp.where(mask, x, jnp.zeros_like(x))

        w = rhs_ref[0]
        if scale_ref is None:
            acc_ref[...] += _mxu_dot(x, w)
        else:
            e = jnp.minimum(gid, g - 1)
            if int4:
                w = (_unpack_int4(w, tile_k, tile_n).astype(jnp.float32)
                     * scale_ref[e * n_n_tiles + j])
            else:
                # int8 weight-only quantization: dequantise the VMEM tile
                # with the per-expert scale. HBM→VMEM weight traffic halves
                # vs bf16 — the §Perf H1 "memory-floor" lever.
                w = w.astype(jnp.float32) * scale_ref[e]
            acc_ref[...] += _mxu_dot(x.astype(jnp.float32), w)

        # Flush on the last (visit, k-tile) for this m-tile.
        is_last = jnp.logical_or(
            v == n_vis - 1, vm_ref[jnp.minimum(v + 1, n_vis - 1)] != mt)

        @pl.when(jnp.logical_and(is_last, kt == n_k_tiles - 1))
        def _flush():
            if scatter:
                # Unpermute epilogue: one row DMA per valid GEMM row into
                # its token-order destination. Destinations are unique (a
                # permutation), so no two rows write the same output row.
                obuf, sem = bufs
                obuf[...] = acc_ref[...].astype(out_dtype).astype(
                    jnp.float32).reshape(obuf.shape)

                def row_copy(r):
                    return pltpu.make_async_copy(
                        obuf.at[r],
                        out_ref.at[dest_ref[base + r], :,
                                   pl.ds(j * tile_n, tile_n)], sem)

                def when_valid(fn):
                    return lambda r: pl.when(base + r < m)(
                        lambda: fn(row_copy(r)))

                _for_rows(tile_m, when_valid(lambda c: c.start()))
                _for_rows(tile_m, when_valid(lambda c: c.wait()))
            else:
                out_ref[...] = acc_ref[...].astype(out_dtype)

    def _rhs_index(j, v, kt, *pref):
        # vg == g marks an empty surplus visit; clamp the DMA index into
        # range — the kernel's row mask zeroes its contribution.
        return (jnp.minimum(pref[1][v], g - 1), kt, j)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rhs_block = (1, tile_k // 2, tile_n) if int4 else (1, tile_k, tile_n)
    in_specs = [hbm if gather else pl.BlockSpec(
                    (tile_m, tile_k), lambda j, v, kt, *pref: (pref[0][v], kt)),
                pl.BlockSpec(rhs_block, _rhs_index)]
    operands = prefetch + [lhs_p, rhs_p]
    scratch = [pltpu.VMEM((tile_m, tile_n), jnp.float32)]
    if gather:
        scratch += [pltpu.VMEM((tile_m, 1, tile_k), jnp.float32),
                    pltpu.SemaphoreType.DMA(())]
    aliases = {}
    if scatter:
        # The output lives in HBM and only valid rows are written, so it
        # starts as a zero buffer aliased to the result.
        zeros = jnp.zeros((o_rows, 1, n_pad), jnp.float32)
        in_specs.append(hbm)
        operands.append(zeros)
        aliases = {len(operands) - 1: 0}
        out_spec = hbm
        out_shape = jax.ShapeDtypeStruct(zeros.shape, zeros.dtype)
        scratch += [pltpu.VMEM((tile_m, 1, tile_n), jnp.float32),
                    pltpu.SemaphoreType.DMA(())]
    else:
        out_spec = pl.BlockSpec((tile_m, tile_n),
                                lambda j, v, kt, *pref: (pref[0][v], j))
        out_shape = jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pref,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret_mode(interpret),
    )(*operands)
    if scatter:
        return out.reshape(o_rows, n_pad)[:, :n].astype(out_dtype)
    return out[:m, :n]


# Row DMAs. Mosaic moves a single row only as a whole leading-axis slice
# of a 32-bit (R, 1, W) array: a one-row slice of a tiled 2-D array, or of
# a packed 16-bit one, is refused. So the fused permute carries rows as
# (R, 1, W) float32 — exact for bf16/f16 values, which round-trip through
# f32 unchanged — and casts back on the far side of the DMA.

def _dma_rows(x: jax.Array) -> jax.Array:
    """(R, C) → (R, 1, C) float32 rows for single-row DMAs."""
    return x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])


def _mxu_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32-accumulating dot at the operands' own precision: full for f32,
    one pass for bf16. Explicit, so that an ambient
    ``jax_default_matmul_precision`` cannot ask Mosaic for an fp32
    contraction of bf16 operands, which it refuses."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _for_rows(n: int, fn) -> None:
    """``fn(r)`` for r in [0, n) as a kernel loop (per-row DMA issue)."""
    def body(r, carry):
        fn(r)
        return carry
    jax.lax.fori_loop(0, n, body, 0)


# ---------------------------------------------------------------------------
# Weight-only quantization (int8 per-expert, int4 per-expert-per-N-block)
# ---------------------------------------------------------------------------

def quantize_experts(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-expert symmetric int8 quantization: w ≈ codes · scale[g]."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=(1, 2))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(w.astype(jnp.float32) /
                               scale[:, None, None]), -127, 127
                     ).astype(jnp.int8)
    return codes, scale


def dequantize_experts(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """Exact float form of the int8 codes the kernel sees."""
    return codes.astype(jnp.float32) * scale[:, None, None]


def quantize_experts_int4(w: jax.Array, block_n: int = 128
                          ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int4 quantization, two codes packed per int8 along K.

    w: (G, K, N) with K even and N a multiple of ``block_n``. Returns
    ``(packed (G, K//2, N) int8, scales (G, N//block_n) f32)`` where
    ``w ≈ codes · scales[g, n // block_n]`` and codes ∈ [-7, 7]. Finer
    per-N-block scales recover most of the range lost to 3-bit mantissas;
    ``block_n`` must equal the kernel's ``tile_n`` so each weight tile
    dequantises with a single scalar.
    """
    g, k, n = w.shape
    if k % 2:
        raise ValueError(f"int4 packing needs an even K, got {k}")
    if n % block_n:
        raise ValueError(f"N={n} must be a multiple of block_n={block_n}")
    wf = w.astype(jnp.float32).reshape(g, k, n // block_n, block_n)
    amax = jnp.max(jnp.abs(wf), axis=(1, 3))                 # (G, N/block)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    codes = jnp.clip(jnp.round(wf / scale[:, None, :, None]), -7, 7
                     ).astype(jnp.int32).reshape(g, k, n)
    lo = codes[:, 0::2] & 0xF
    hi = codes[:, 1::2] & 0xF
    packed = (lo | (hi << 4))                                # [0, 255]
    packed = ((packed ^ 128) - 128).astype(jnp.int8)         # two's complement
    return packed, scale


def unpack_experts_int4(packed: jax.Array) -> jax.Array:
    """(G, K//2, N) packed nibbles → (G, K, N) int32 codes (test oracle)."""
    g, kh, n = packed.shape
    w32 = packed.astype(jnp.int32) & 0xFF
    lo = ((w32 & 0xF) ^ 8) - 8
    hi = (((w32 >> 4) & 0xF) ^ 8) - 8
    return jnp.stack([lo, hi], axis=2).reshape(g, 2 * kh, n)


def dequantize_experts_int4(packed: jax.Array, scale: jax.Array
                            ) -> jax.Array:
    """Exact float form of the packed int4 codes the kernel sees."""
    codes = unpack_experts_int4(packed)
    g, k, n = codes.shape
    block_n = n // scale.shape[1]
    cf = codes.astype(jnp.float32).reshape(g, k, scale.shape[1], block_n)
    return (cf * scale[:, None, :, None]).reshape(g, k, n)
