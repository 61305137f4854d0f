"""Plain reference of the pre-norm MoE decoder that both configurations here
describe (Granite-MoE and Mixtral): seeded weights and a float32 forward pass
in straightforward ``jax.numpy``, with no kernels, cache or batching.

Layer equations (one layer, one sequence of L tokens), with the scalar
multipliers a configuration may state (Granite's; 1, or 1/sqrt(d_head) for
the attention scale, where it states none):

    x  = embedding_multiplier * E[tokens]
    h  = rmsnorm(x) * ln1
    q, k, v = h Wq, h Wk, h Wv;  rotary embedding on q and k (half split)
    a  = softmax(q k^T * attention_multiplier + causal) v  Wo   (grouped heads)
    x += residual_multiplier * a
    h  = rmsnorm(x) * ln2
    p  = softmax(h R);  w, e = top_k(p);  w /= sum(w)
    x += residual_multiplier * sum_j w_j * (silu(h Wg_e_j) * (h Wu_e_j)) Wd_e_j
    logits = rmsnorm(x) * ln_f  W_out / logits_scaling   (W_out = E^T when tied)

Departures from the published models are the configuration file's to state
(``departures``); this module computes what the configuration says.

The module imports nothing of the program under test. ``init_params`` also
fixes the parameter layout the benchmark hands to the program, and the
harness checks that layout against the program's own before a run.

``forward_rows(..., precision="fp8")`` is the control: the same reference
with both operands of every matrix product rounded to float8 e4m3 (scaled
per tensor), one step below the bfloat16 the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference reads from a configuration file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    n_experts: int
    top_k: int
    d_expert: int
    vocab: int
    rope_theta: float
    rms_eps: float
    tied: bool
    emb_mult: float
    attn_mult: float
    resid_mult: float
    logits_div: float

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        d_head = c["hidden_size"] // c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"], d_head=d_head,
                   n_experts=c["num_local_experts"],
                   top_k=c["num_experts_per_tok"],
                   d_expert=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]),
                   emb_mult=float(c.get("embedding_multiplier", 1.0)),
                   attn_mult=float(c.get("attention_multiplier",
                                         d_head ** -0.5)),
                   resid_mult=float(c.get("residual_multiplier", 1.0)),
                   logits_div=float(c.get("logits_scaling", 1.0)))


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole number of any size, beyond 32 bits too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path: str, shape, scale: float, dtype, offset: float = 0.0):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32) * scale + offset
    return x.astype(dtype)


def _layer(key, a: Arch, i: int):
    d, hd = a.d_model, a.d_head
    bf = jnp.bfloat16
    p = f"layer{i}."
    q_scale = d ** -0.5 / (a.attn_mult * hd ** 0.5)
    return {
        "ln1": {"scale": _leaf(key, p + "ln1", (d,), 0.1, bf, 1.0)},
        "attn": {
            "wq": _leaf(key, p + "wq", (d, a.n_heads * hd), q_scale, bf),
            "wk": _leaf(key, p + "wk", (d, a.n_kv_heads * hd), d ** -0.5, bf),
            "wv": _leaf(key, p + "wv", (d, a.n_kv_heads * hd), d ** -0.5, bf),
            "wo": _leaf(key, p + "wo", (a.n_heads * hd, d),
                        (a.n_heads * hd) ** -0.5 / a.resid_mult, bf),
        },
        "ln2": {"scale": _leaf(key, p + "ln2", (d,), 0.1, bf, 1.0)},
        "moe": {
            "router": _leaf(key, p + "router", (d, a.n_experts), d ** -0.5,
                            jnp.float32),
            "wi": _leaf(key, p + "wi", (a.n_experts, d, 2 * a.d_expert),
                        d ** -0.5, bf),
            "wo": _leaf(key, p + "wo_e", (a.n_experts, a.d_expert, d),
                        a.d_expert ** -0.5 / a.resid_mult, bf),
        },
    }


@functools.partial(jax.jit, static_argnums=1)
def init_params(key, a: Arch):
    """Every weight, bfloat16 as served (the router float32), in one call on
    the device. Layers are listed whole (the program's unrolled layout).

    Each leaf that a multiplier scales is drawn at its scale divided by that
    multiplier, as muP pairs them, so that the function has the same
    statistics whatever the multipliers. (With Granite's drawn at the plain
    scales, the x12 embedding through the tied head makes the last token
    every row's best logit by far, and no lower precision changes a token.)
    """
    d, bf = a.d_model, jnp.bfloat16
    head = a.logits_div * (a.emb_mult if a.tied else 1.0)
    params = {
        "embed": {"tok": _leaf(key, "embed", (a.vocab, d), 0.02 / a.emb_mult,
                               bf)},
        "decoder": {
            "prefix": [_layer(key, a, i) for i in range(a.n_layers)],
            "stack": [],
            "final_norm": {"scale": _leaf(key, "final_norm", (d,), 0.1 * head,
                                          bf, head)},
        },
        "lm_head": ({} if a.tied else
                    {"w": _leaf(key, "lm_head", (d, a.vocab), d ** -0.5, bf)}),
    }
    return params


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (max |x| -> 448)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs            # (L, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(x, lp, a: Arch, fp8: bool):
    """One layer over one sequence x (L, D) float32. Also returns, per
    position, the router's margin between its last chosen expert and the
    next (where a lower precision can flip the choice)."""
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, lp["ln1"]["scale"], a.rms_eps)
    at = lp["attn"]
    q = _mm("ld,dh->lh", h, at["wq"], fp8).reshape(n, a.n_heads, a.d_head)
    k = _mm("ld,dh->lh", h, at["wk"], fp8).reshape(n, a.n_kv_heads, a.d_head)
    v = _mm("ld,dh->lh", h, at["wv"], fp8).reshape(n, a.n_kv_heads, a.d_head)
    q, k = _rope(q, pos, a.rope_theta), _rope(k, pos, a.rope_theta)
    group = a.n_heads // a.n_kv_heads
    k = jnp.repeat(k, group, axis=1)                            # head h -> h // group
    v = jnp.repeat(v, group, axis=1)
    s = _mm("qhd,khd->hqk", q, k, fp8) * a.attn_mult
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, fp8)
    x = x + a.resid_mult * _mm("lh,hd->ld", o.reshape(n, -1), at["wo"], fp8)

    h = _rms(x, lp["ln2"]["scale"], a.rms_eps)
    m = lp["moe"]
    probs = jax.nn.softmax(_mm("ld,de->le", h, m["router"], fp8), axis=-1)
    topw, topi = jax.lax.top_k(probs, a.top_k)
    # how far the last chosen expert's probability lies above the next one's
    nxt = jax.lax.top_k(probs, min(a.top_k + 1, a.n_experts))[0][:, -1]
    margin = topw[:, -1] - jnp.where(a.top_k < a.n_experts, nxt, 0.0)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    gate = jnp.sum(jnp.where(topi[..., None] == jnp.arange(a.n_experts),
                             topw[..., None], 0.0), axis=1)     # (L, E)

    def expert(y, e):
        wi, wo, g = e
        u = _mm("ld,df->lf", h, wi, fp8)
        u = jax.nn.silu(u[:, :a.d_expert]) * u[:, a.d_expert:]
        return y + g[:, None] * _mm("lf,fd->ld", u, wo, fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (m["wi"], m["wo"], gate.T))
    return x + a.resid_mult * y, margin


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, final_scale, w_out, a: Arch, fp8: bool):
    h = _rms(jnp.take(x, rows, axis=0), final_scale, a.rms_eps)
    return _mm("ld,dv->lv", h, w_out, fp8) / a.logits_div


def forward_rows(params, a: Arch, tokens: np.ndarray, rows: np.ndarray,
                 precision: str = "f32"):
    """Logits (len(rows), V) float32 at positions ``rows`` of one sequence,
    and the smallest router margin over the layers at those positions.

    The sequence is padded at its end to a multiple of 128 positions (the
    causal mask keeps pads out of every real row) so that a few compiled
    shapes serve all lengths."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision must be f32 or fp8, got {precision!r}")
    fp8 = precision == "fp8"
    n = len(tokens)
    padded = -(-n // 128) * 128
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens
    emb = params["embed"]["tok"]
    x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    x = x * a.emb_mult
    margin = jnp.full((padded,), jnp.inf)
    for lp in params["decoder"]["prefix"]:
        x, m = _layer_fwd(x, lp, a, fp8)
        margin = jnp.minimum(margin, m)
    w_out = emb.T if a.tied else params["lm_head"]["w"]
    rows = jnp.asarray(rows, jnp.int32)
    return (_head(x, rows, params["decoder"]["final_norm"]["scale"], w_out,
                  a, fp8), margin[rows])


def served_gaps(params, a: Arch, prompt: np.ndarray, served: List[int],
                precision: str = "f32") -> Dict[str, np.ndarray]:
    """Per served token, how far below the float32 reference's best logit it
    lies, in standard deviations of that logit row.

    ``served[j]`` was produced at position ``len(prompt) - 1 + j``. Returns
    ``{"served": the served tokens' gaps, "margin": the reference's
    smallest router margin at each position}`` and, for
    ``precision="fp8"``, also ``{"control": the gaps of the tokens the fp8
    reference puts first}``.
    """
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref, margin = forward_rows(params, a, seq, rows, "f32")
    best = jnp.max(ref, axis=-1)
    std = jnp.std(ref, axis=-1)
    pick = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
    out = {"served": np.asarray((best - pick) / std),
           "margin": np.asarray(margin)}
    if precision == "fp8":
        low, _ = forward_rows(params, a, seq, rows, "fp8")
        first = jnp.argmax(low, axis=-1)
        pick = jnp.take_along_axis(ref, first[:, None], -1)[:, 0]
        out["control"] = np.asarray((best - pick) / std)
    return out
