"""Block-size autotuning for the grouped GEMM.

The best (tile_m, tile_n, tile_k) depends on the expert-shard shape: the
number of resident experts E, the tokens each expert sees per step
(decode batches give tens, prefill thousands — the paper's fan-out effect
means small tokens/expert wants small m-tiles so visits don't waste MXU
rows on masked lanes), and d_ff (sets the n extent and the VMEM weight
block). Rather than hardcode one tiling, a small on-disk table maps

    key = (E, tokens_per_expert bucket, d_ff)   →   (tile_m, tile_n, tile_k)

``lookup()`` is consulted by ``ops.grouped_gemm`` whenever the caller does
not pin tiles; missing keys fall back to ``DEFAULT_TILES``. The table is
populated by ``tune()`` (surfaced as ``python -m repro tune``), which
times candidate tilings on synthetic uniform-group workloads and records
the winner. Tokens-per-expert is bucketed to the nearest power of two so
nearby workloads share an entry.

The committed table (``autotune_table.json`` next to this module) was
tuned in interpret mode on the CI CPU — it exercises the full lookup path
and gives sane relative orderings (smaller tiles win at decode shapes);
re-run ``python -m repro tune`` on real TPU hardware to re-populate with
wall-clock-faithful entries.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

DEFAULT_TILES: Tuple[int, int, int] = (128, 128, 512)
TABLE_VERSION = 1
_TABLE_PATH = os.path.join(os.path.dirname(__file__), "autotune_table.json")

# Candidate tilings swept by tune(). Kept deliberately small: the sweep is
# O(shapes × candidates) kernel timings.
CANDIDATE_TILES: Tuple[Tuple[int, int, int], ...] = (
    (8, 128, 128),
    (16, 128, 256),
    (32, 128, 256),
    (64, 128, 512),
    (128, 128, 512),
    (128, 256, 512),
)

_cache: Dict[str, dict] = {}


def bucket_tokens_per_expert(tokens_per_expert: int) -> int:
    """Round up to the nearest power of two (min 1)."""
    t = max(1, int(tokens_per_expert))
    b = 1
    while b < t:
        b *= 2
    return b


def table_key(n_groups: int, tokens_per_expert: int, d_ff: int) -> str:
    return (f"E{int(n_groups)}_tpe{bucket_tokens_per_expert(tokens_per_expert)}"
            f"_dff{int(d_ff)}")


def load_table(path: Optional[str] = None) -> dict:
    p = path or _TABLE_PATH
    if p not in _cache:
        try:
            with open(p) as f:
                data = json.load(f)
            if data.get("version") != TABLE_VERSION:
                data = {"version": TABLE_VERSION, "entries": {}}
        except (OSError, ValueError):
            data = {"version": TABLE_VERSION, "entries": {}}
        _cache[p] = data
    return _cache[p]


def invalidate_cache() -> None:
    _cache.clear()


def lookup(n_groups: int, m: int, d_ff: int,
           path: Optional[str] = None) -> Tuple[int, int, int]:
    """Best-known (tile_m, tile_n, tile_k) for this workload shape.

    m is the total GEMM row count (tokens × top_k for the expert path);
    tokens_per_expert = m / n_groups under the uniform-load assumption the
    table is keyed on. Unknown keys return DEFAULT_TILES.
    """
    tpe = max(1, int(m) // max(1, int(n_groups)))
    entry = load_table(path)["entries"].get(table_key(n_groups, tpe, d_ff))
    if not entry:
        return DEFAULT_TILES
    return (int(entry["tile_m"]), int(entry["tile_n"]), int(entry["tile_k"]))


def _time_tiling(m: int, k: int, n: int, g: int,
                 tiles: Tuple[int, int, int], reps: int,
                 interpret: bool) -> float:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.grouped_gemm import grouped_gemm_pallas

    rng = np.random.default_rng(1234)
    lhs = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    rhs = jnp.asarray(rng.normal(size=(g, k, n)).astype(np.float32))
    gs = jnp.full((g,), m // g, jnp.int32).at[-1].add(m - g * (m // g))
    tm, tn, tk = tiles

    def run():
        return grouped_gemm_pallas(lhs, rhs, gs, tile_m=tm, tile_n=tn,
                                   tile_k=tk, interpret=interpret)

    jax.block_until_ready(run())                       # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(run())
    return (time.perf_counter() - t0) / reps * 1e6     # µs


def tune(shapes: Sequence[Tuple[int, int, int, int]],
         candidates: Sequence[Tuple[int, int, int]] = CANDIDATE_TILES,
         reps: int = 2, path: Optional[str] = None,
         interpret: Optional[bool] = None) -> List[dict]:
    """Time each candidate tiling per shape and persist the winners.

    shapes: (E, tokens_per_expert, d_model, d_ff) tuples — the GEMM is
    (E·tpe, d_model) × (E, d_model, d_ff). Returns one result dict per
    shape (key, winner, per-candidate timings) and rewrites the table at
    ``path`` (module-adjacent default) with the winners merged in.
    """
    from repro.kernels.backend import interpret_mode
    interpret = interpret_mode(interpret)
    p = path or _TABLE_PATH
    table = {"version": TABLE_VERSION,
             "entries": dict(load_table(p)["entries"])}
    results = []
    for (g, tpe, k, n) in shapes:
        m = g * tpe
        timings = {}
        for cand in candidates:
            # Clamp oversize tiles to the shape (dedup via the key) so a
            # small-shape tune always has at least one viable candidate.
            tm, tn, tk = cand
            tn, tk = min(tn, n), min(tk, k)
            label = f"{tm}x{tn}x{tk}"
            if label not in timings:
                timings[label] = _time_tiling(
                    m, k, n, g, (tm, tn, tk), reps, interpret)
        best = min(timings, key=timings.get)
        tm, tn, tk = (int(v) for v in best.split("x"))
        key = table_key(g, tpe, n)
        table["entries"][key] = {
            "tile_m": tm, "tile_n": tn, "tile_k": tk,
            "us": round(timings[best], 1),
            "shape": {"E": g, "tokens_per_expert": tpe,
                      "d_model": k, "d_ff": n},
            "interpret": bool(interpret),
        }
        results.append({"key": key, "best": best, "timings_us": timings})
    with open(p, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    invalidate_cache()
    return results
