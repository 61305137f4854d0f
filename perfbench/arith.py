"""Operations and bytes from shapes: the yardstick for roofline shares, MFU
and the M2N wire check. Nothing here reads the program; every count follows
from the configuration and the traffic.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks, keyed by ``device_kind``. A device that
    is not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['devices'])}") from None


# ---------------------------------------------------------------------------
# M2N wire bytes (Eq. 9/17 at the engine's dtypes)
# ---------------------------------------------------------------------------

def m2n_cycle_bytes(n_tokens: int, hidden: int, top_k: int,
                    dtype_bytes: int, gate_bytes: int = 4,
                    idx_bytes: int = 4) -> Tuple[int, int]:
    """(dispatch, combine) bytes of one M2N cycle of ``n_tokens`` tokens:
    the hidden vectors each way, plus the top-k gate weights and expert ids
    on the dispatch leg. Copied from ``core.planner.predict_m2n_cycle_bytes``.
    """
    payload = n_tokens * hidden * dtype_bytes
    meta = n_tokens * top_k * (gate_bytes + idx_bytes)
    return payload + meta, payload


def m2n_run_bytes(decode_ticks: int, n_bo: int, mb_slots: int,
                  prefill_tokens: int, moe_layers: int, hidden: int,
                  top_k: int, dtype_bytes: int) -> Tuple[int, int]:
    """(dispatch, combine) bytes for a run: every decode tick ships each of
    its ``n_bo`` micro-batches (all ``mb_slots`` rows, live or not) through
    every MoE layer, and prefill ships each prompt token once per MoE layer
    (Eq. 17 is linear in the tokens of a cycle, so chunking does not
    matter)."""
    dd, dc = m2n_cycle_bytes(mb_slots, hidden, top_k, dtype_bytes)
    pd, pc = m2n_cycle_bytes(prefill_tokens, hidden, top_k, dtype_bytes)
    cycles = decode_ticks * n_bo * moe_layers
    return cycles * dd + moe_layers * pd, cycles * dc + moe_layers * pc


# ---------------------------------------------------------------------------
# Grouped GEMM (the F role's expert kernel)
# ---------------------------------------------------------------------------

def expected_experts_hit(n_tokens: int, n_experts: int, top_k: int) -> float:
    """Expected number of distinct experts that ``n_tokens`` tokens route
    to, each choosing ``top_k`` distinct experts uniformly."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** n_tokens)


def gmm_cycle_cost(n_tokens: int, hidden: int, d_expert: int,
                   n_experts: int, top_k: int,
                   dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) the two grouped GEMMs of one expert FFN need for
    ``n_tokens`` tokens: up (hidden -> 2 * d_expert, gate and up fused) and
    down (d_expert -> hidden) over ``n_tokens * top_k`` rows. Bytes are
    each expert's weights once per GEMM for the experts the tokens reach,
    the token rows read once and the result rows written once."""
    rows = n_tokens * top_k
    hit = expected_experts_hit(n_tokens, n_experts, top_k)
    flops = 2.0 * rows * hidden * 2 * d_expert + 2.0 * rows * d_expert * hidden
    w = hit * (hidden * 2 * d_expert + d_expert * hidden) * dtype_bytes
    acts = (n_tokens * hidden + rows * 2 * d_expert        # up: in, out
            + rows * d_expert + rows * hidden) * dtype_bytes  # down: in, out
    return flops, w + acts


def roofline_seconds(flops: float, nbytes: float, peak: Dict
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# Model operations per token (MFU)
# ---------------------------------------------------------------------------

def token_flops(arch, context: float) -> float:
    """Operations one token needs at ``context`` cached positions: twice the
    active weights it multiplies (attention projections, router, top-k
    experts, output head) plus the attention scores and values over its
    context. ``arch`` is the reference's ``Arch``."""
    d, hd = arch.d_model, arch.d_head
    attn_w = d * (arch.n_heads + 2 * arch.n_kv_heads) * hd + arch.n_heads * hd * d
    moe_w = d * arch.n_experts + arch.top_k * 3 * d * arch.d_expert
    per_layer = 2.0 * (attn_w + moe_w) + 4.0 * arch.n_heads * hd * context
    return arch.n_layers * per_layer + 2.0 * d * arch.vocab
