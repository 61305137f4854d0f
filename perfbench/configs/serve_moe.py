"""How the program under test serves a configuration of the pre-norm MoE
decoder family that ``moe_transformer.py`` describes (Granite-MoE, Mixtral):
the program's ``ArchConfig`` for the configuration file, and the weights it
is handed.

The program has no scalar multipliers. A configuration that states them
(Granite's embedding, attention, residual and logit multipliers) is served
with them folded into the weights, which computes the same function:

* ``attention_multiplier``: ``Wq`` times ``attention_multiplier *
  sqrt(d_head)``, since the program scales scores by ``1/sqrt(d_head)`` and
  the rotary embedding is linear;
* ``residual_multiplier``: the attention output ``Wo`` and every expert's
  down projection times it;
* ``embedding_multiplier``: the embedding table times it, so the residual
  stream keeps its published scale for every RMS norm;
* ``logits_scaling``: the final norm's scale divided by it, and by
  ``embedding_multiplier`` too where the output head is the (scaled)
  embedding table.

The reference computes the multipliers as stated, from the unfolded weights.
A configuration of another family brings a file of its own like this one.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def arch_config(conf: Dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.common import ArchConfig
    if conf.get("sliding_window") is not None:
        raise ValueError(f"{conf['name']}: sliding windows are not served")
    return ArchConfig(
        name=conf["name"], family="moe", n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["hidden_size"] // conf["num_attention_heads"], d_ff=0,
        vocab_size=conf["vocab_size"], n_experts=conf["num_local_experts"],
        top_k=conf["num_experts_per_tok"], moe_d_ff=conf["intermediate_size"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]), router_renorm=True,
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"],
        force_unroll=True)


def _scale(x, c: float):
    return (x.astype(jnp.float32) * c).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6),
                   donate_argnums=0)
def _fold(params, d_head: int, tied: bool, emb: float, attn: float,
          resid: float, logits: float):
    p = jax.tree_util.tree_map(lambda x: x, params)
    p["embed"]["tok"] = _scale(p["embed"]["tok"], emb)
    for lp in p["decoder"]["prefix"]:
        lp["attn"]["wq"] = _scale(lp["attn"]["wq"], attn * d_head ** 0.5)
        lp["attn"]["wo"] = _scale(lp["attn"]["wo"], resid)
        lp["moe"]["wo"] = _scale(lp["moe"]["wo"], resid)
    fn = p["decoder"]["final_norm"]
    fn["scale"] = _scale(fn["scale"], 1.0 / (logits * (emb if tied else 1.0)))
    return p


def program_params(params, conf: Dict, ref_arch):
    """The weights the program serves: the reference's, with the
    configuration's multipliers folded in (``params`` is consumed)."""
    a = ref_arch
    if (a.emb_mult, a.attn_mult, a.resid_mult, a.logits_div) == (
            1.0, a.d_head ** -0.5, 1.0, 1.0):
        return params
    return _fold(params, a.d_head, a.tied, a.emb_mult, a.attn_mult,
                 a.resid_mult, a.logits_div)
