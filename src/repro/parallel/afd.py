"""Attention-FFN Disaggregation (AFD) runtime — the paper's Fig. 1a
architecture executed on two disjoint device roles.

Role split (node granularity, paper §3.1 assumption):
  * **A-role** — embeddings, every attention/Mamba mixer, norms, dense
    MLPs, shared experts, the router, and the LM head. 1-D TP mesh.
  * **F-role** — the routed-expert weights of every MoE layer, sharded
    expert-parallel over the F devices: each F device holds E/N_F experts
    and runs the grouped GEMM for those alone (``make_expert_ffn``).

Per MoE layer and micro-batch the runtime performs the paper's M2N cycle:

    A: attention sublayer + router           (t_a)
    dispatch: tokens+gating  A-mesh → F-mesh (t_dispatch)  [device_put]
    F: grouped-GEMM expert FFN               (t_f)
    combine: routed outputs  F-mesh → A-mesh (t_combine)   [device_put]

``decode_step_3bo`` drives ``n_bo`` micro-batches through the layer loop
with the rotation schedule of §2.2 — on real hardware JAX's async dispatch
overlaps the three resources; on CPU the schedule is validated structurally
and by the byte accounting, while core/overlap.py prices the timing.

The A role runs as compiled programs, one per span body (``afd.attn``,
``afd.route``, the A side of ``afd.combine``, ``afd.dense_ffn``,
``afd.embed``, ``afd.head``). Each takes the layer's weights and cache as
arguments, so one program serves every layer of a kind; the mixers donate
the cache, whose write then lands in place. The M2N transfers between them
stay ``device_put``s.

The runtime tracks dispatch/combine bytes per micro-batch so the system
benchmark can check them against Eq. 9's B_rank prediction.

Dense architectures have no routed experts — ``AFDRuntime`` refuses them,
matching DESIGN.md §Arch-applicability (AFD degenerates to a pipeline
split; the planner reports it instead).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.kernels import ops as kops
from repro.models import attention as attn_mod
from repro.models import kvcache, mamba2, moe as moe_mod
from repro.models.common import ArchConfig
from repro.models.layers import (apply_lm_head, apply_mlp, apply_norm,
                                 embed_tokens)


# ---------------------------------------------------------------------------
# Parameter surgery: stacked stack → per-layer; split A/F roles
# ---------------------------------------------------------------------------

def unstack_layer_params(params, cfg: ArchConfig) -> List[Dict]:
    """Flatten prefix + scanned-stack params into one dict per layer."""
    plan = cfg.layer_plan()
    layers: List[Dict] = list(params["decoder"]["prefix"])
    for p in range(plan.n_periods):
        for j in range(len(plan.period)):
            layers.append(jax.tree_util.tree_map(
                lambda x: x[p], params["decoder"]["stack"][j]))
    return layers


def split_roles(params, cfg: ArchConfig):
    """Return (a_params, f_expert_params). Experts leave the A side."""
    layers = unstack_layer_params(params, cfg)
    a_layers, f_layers = [], []
    for i, lp in enumerate(layers):
        lp = dict(lp)
        f_entry = None
        if "moe" in lp:
            moe_p = dict(lp["moe"])
            f_entry = {"wi": moe_p.pop("wi"), "wo": moe_p.pop("wo")}
            lp["moe"] = moe_p            # router + shared experts stay on A
        a_layers.append(lp)
        f_layers.append(f_entry)
    a_params = {
        "embed": params["embed"],
        "lm_head": params["lm_head"],
        "final_norm": params["decoder"]["final_norm"],
        "layers": a_layers,
    }
    if "encoder" in params:
        a_params["encoder"] = params["encoder"]
    return a_params, f_layers


# ---------------------------------------------------------------------------
# M2N counters
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AFDStats:
    """M2N wire counters. ``snapshot()``/``since()`` give the serving
    engine per-window deltas to diff against the planner's Eq. 9/17 wire
    prediction (``core.planner.predict_m2n_cycle_bytes``) live."""
    dispatch_bytes: int = 0
    combine_bytes: int = 0
    dispatches: int = 0
    tokens_routed: int = 0

    def record(self, n_tokens: int, hidden: int, dtype_bytes: int,
               meta_bytes: int) -> None:
        self.dispatch_bytes += n_tokens * hidden * dtype_bytes + meta_bytes
        self.combine_bytes += n_tokens * hidden * dtype_bytes
        self.dispatches += 1
        self.tokens_routed += n_tokens

    def snapshot(self) -> "AFDStats":
        return dataclasses.replace(self)

    def since(self, prev: "AFDStats") -> "AFDStats":
        """Counter deltas accumulated after ``prev = stats.snapshot()``."""
        return AFDStats(
            dispatch_bytes=self.dispatch_bytes - prev.dispatch_bytes,
            combine_bytes=self.combine_bytes - prev.combine_bytes,
            dispatches=self.dispatches - prev.dispatches,
            tokens_routed=self.tokens_routed - prev.tokens_routed)


# ---------------------------------------------------------------------------
# A-role programs
# ---------------------------------------------------------------------------
#
# The architecture is a static argument and a layer's weights and cache are
# arguments, so all layers of a kind share one compiled program per shape:
# (B, 1) in decode, (B, C) in a prefill chunk.

_a_program = functools.partial(jax.jit, static_argnames=("cfg",))
_a_mixer = functools.partial(jax.jit, static_argnames=("cfg",),
                             donate_argnames=("cache",))


@_a_mixer
def mix_attn(cfg: ArchConfig, ln1, p, x, cache, pos):
    """Decode ``afd.attn`` on an attention layer: ``ln1``, one-token
    attention with its cache write (an update slice per sequence, in place
    in the donated cache), residual."""
    mix, cache = attn_mod.attention_decode(p, cfg, apply_norm(ln1, cfg, x),
                                           cache, pos,
                                           write_kv=kvcache.write_kv_rows)
    return x + mix, cache


@_a_mixer
def mix_mamba(cfg: ArchConfig, ln1, p, x, cache, pos):
    """``afd.attn`` on a Mamba layer, one token: ``ln1``, one recurrent
    step, residual (``pos`` is unused; the state carries the position)."""
    mix, cache = mamba2.mamba_decode(p, cfg, apply_norm(ln1, cfg, x), cache)
    return x + mix, cache


def _attn_chunk(cfg: ArchConfig, impl: Optional[str], ln1, p, x, cache, pos):
    mix, cache = attn_mod.attention_prefill_cached(
        p, cfg, apply_norm(ln1, cfg, x), cache, pos, impl=impl)
    return x + mix, cache


@_a_mixer
def mix_attn_chunk(cfg: ArchConfig, ln1, p, x, cache, pos):
    """Chunk ``afd.attn`` on an attention layer, dense masked: ``ln1``,
    ``attention_prefill_cached``, residual."""
    return _attn_chunk(cfg, None, ln1, p, x, cache, pos)


# Decode mixer by ``LayerSpec.kind``, which is also the key of the layer's
# mixer weights.
MIXERS = {"attn": mix_attn, "mamba": mix_mamba}


@_a_program
def route_tokens(cfg: ArchConfig, ln2, moe, x):
    """``afd.route``: ``ln2`` and top-k routing of the flattened tokens.
    Returns (tokens (N, D), weights (N, k), expert ids (N, k))."""
    tokens = apply_norm(ln2, cfg, x).reshape(-1, cfg.d_model)
    _, topw, topi = moe_mod.route(moe, cfg, tokens)
    return tokens, topw, topi


@_a_program
def combine_routed(cfg: ArchConfig, shared, x, routed, tokens):
    """The A side of ``afd.combine``: residual plus the routed experts'
    output, plus the shared experts (``shared`` None without them) on the
    normed tokens."""
    out = x + routed.reshape(x.shape)
    if shared is not None:
        out = out + apply_mlp(shared, cfg, tokens.reshape(x.shape))
    return out


@_a_program
def dense_ffn(cfg: ArchConfig, ln2, mlp, x):
    """``afd.dense_ffn``: ``ln2``, dense MLP, residual."""
    return x + apply_mlp(mlp, cfg, apply_norm(ln2, cfg, x))


@_a_program
def embed(cfg: ArchConfig, embed_p, tokens, pos):
    """``afd.embed``: tokens (B,) or (B, C) from positions ``pos`` (B,)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    positions = pos[:, None] + jnp.arange(tokens.shape[1], dtype=pos.dtype)
    return embed_tokens(embed_p, cfg, tokens, positions)


@functools.partial(jax.jit, static_argnames=("cfg", "squeeze"))
def head(cfg: ArchConfig, final_norm, lm_head, embed_p, x, pos,
         squeeze: bool = False):
    """``afd.head``: final norm and LM head over x (B, S, D). Returns the
    logits ((B, V) with ``squeeze``, else (B, S, V)) and ``pos + S``."""
    logits = apply_lm_head(lm_head, embed_p, cfg,
                           apply_norm(final_norm, cfg, x))
    return (logits[:, 0] if squeeze else logits), pos + x.shape[1]


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------

class AFDRuntime:
    """Two-role decode runtime. Devices are split at node granularity.

    Caches are donated: each step writes every layer's new cache into the
    list it was given, in place, and returns that list. An array taken
    from the list before the call is deleted by it and must not be read.
    """

    def __init__(self, cfg: ArchConfig, params, a_devices: Sequence,
                 f_devices: Sequence, gemm_impl: Optional[str] = None):
        if not cfg.is_moe:
            raise ValueError(
                f"{cfg.name}: AFD requires routed experts "
                "(DESIGN.md §Arch-applicability)")
        self.cfg = cfg
        self.plan = cfg.layer_plan()
        self.specs = self.plan.flat()
        self.a_mesh = Mesh(np.array(a_devices), ("model",))
        self.f_mesh = Mesh(np.array(f_devices), ("expert",))
        self.gemm_impl = gemm_impl
        self.stats = AFDStats()

        a_params, f_layers = split_roles(params, cfg)
        self.a_params = jax.device_put(
            a_params, NamedSharding(self.a_mesh, P()))
        # Experts shard evenly over F: an uneven E is padded with zero
        # experts the router never selects.
        pad = -cfg.n_experts % len(f_devices)
        espec = NamedSharding(self.f_mesh, P("expert", None, None))
        self.f_layers = [
            None if fl is None else {
                w: jax.device_put(
                    jnp.pad(fl[w], ((0, pad), (0, 0), (0, 0))), espec)
                for w in ("wi", "wo")}
            for fl in f_layers
        ]

        self._ffn_fn = make_expert_ffn(cfg, self.f_mesh, gemm_impl)
        self._flash_attn = make_chunk_attention(cfg, self.a_mesh)
        self._tok_sharding_f = NamedSharding(self.f_mesh, P())
        self._tok_sharding_a = NamedSharding(self.a_mesh, P())

    # ---- per-layer A-role pieces -------------------------------------------

    def _mixer(self, i: int, mb: int, x, cache, pos):
        lp, kind = self.a_params["layers"][i], self.specs[i].kind
        with obs.span("afd.attn", layer=i, mb=mb):
            return MIXERS[kind](self.cfg, lp["ln1"], lp[kind], x, cache, pos)

    def _ffn(self, i: int, mb: int, x):
        """Layer ``i``'s FFN: the M2N cycle on MoE layers, the A role's
        dense MLP on the others."""
        if self.specs[i].moe:
            return self._moe_cycle(i, mb, x)
        return self._ffn_local(i, mb, x)

    def _ffn_local(self, i: int, mb: int, x):
        """Dense-MLP layers run wholly on the A role."""
        cfg, lp = self.cfg, self.a_params["layers"][i]
        if not ("mlp" in lp or cfg.d_ff > 0):
            return x
        with obs.span("afd.dense_ffn", layer=i, mb=mb):
            return dense_ffn(cfg, lp["ln2"], lp["mlp"], x)

    # ---- the M2N cycle -------------------------------------------------------

    def _moe_cycle(self, i: int, mb: int, x):
        """Norm → route (A) → dispatch → expert FFN (F) → combine (A)."""
        cfg, lp, f_entry = self.cfg, self.a_params["layers"][i], self.f_layers[i]
        with obs.span("afd.route", layer=i, mb=mb):
            tokens, topw, topi = route_tokens(cfg, lp["ln2"], lp["moe"], x)

        # dispatch: M2N transfer A → F
        with obs.span("afd.dispatch", layer=i, mb=mb, rows=tokens.shape[0]):
            tok_f = jax.device_put(tokens, self._tok_sharding_f)
            topw_f = jax.device_put(topw, self._tok_sharding_f)
            topi_f = jax.device_put(topi, self._tok_sharding_f)
            self.stats.record(tokens.shape[0], cfg.d_model,
                              tokens.dtype.itemsize,
                              topi.size * 4 + topw.size * 4)

        with obs.span("afd.ffn", layer=i, mb=mb):
            routed_f = self._ffn_fn(f_entry["wi"], f_entry["wo"], tok_f,
                                    topw_f, topi_f)
        # combine: N2M transfer F → A
        with obs.span("afd.combine", layer=i, mb=mb):
            routed = jax.device_put(routed_f, self._tok_sharding_a)
            return combine_routed(cfg, lp["moe"].get("shared"), x, routed,
                                  tokens)

    def _embed(self, mb: int, tokens, pos):
        with obs.span("afd.embed", mb=mb):
            return embed(self.cfg, self.a_params["embed"], tokens, pos)

    def _head(self, mb: int, x, pos, squeeze: bool = False):
        """Final norm and LM head; the positions advance past x."""
        ap = self.a_params
        with obs.span("afd.head", mb=mb):
            return head(self.cfg, ap["final_norm"], ap["lm_head"],
                        ap["embed"], x, pos, squeeze=squeeze)

    # ---- public decode ---------------------------------------------------------

    def init_cache(self, batch: int, max_len: int):
        """Per-layer caches and positions (B,), placed on the A role."""
        caches = [kvcache.init_layer_cache(self.cfg, s, batch, max_len)
                  for s in self.specs]
        return jax.device_put((caches, jnp.zeros((batch,), jnp.int32)),
                              self._tok_sharding_a)

    def decode_step(self, tokens: jax.Array, caches, pos: jax.Array):
        """One token for one micro-batch. tokens: (B,). ``caches`` is
        consumed: its arrays are donated and the list is updated in place
        to the new caches, which are returned with the logits and pos + 1.
        """
        x = self._embed(0, tokens, pos)
        for i in range(len(self.specs)):
            x, caches[i] = self._mixer(i, 0, x, caches[i], pos)
            x = self._ffn(i, 0, x)
        logits, pos = self._head(0, x, pos, squeeze=True)
        return logits, caches, pos

    def decode_step_3bo(self, micro_batches, n_bo: int = 3):
        """Drive ``n_bo`` micro-batches through the layer loop in the 3BO
        rotation: issue order interleaves (layer ℓ, mb m) so that while one
        micro-batch's experts run on the F role another's attention runs on
        the A role — JAX async dispatch realises the overlap on hardware.

        micro_batches: list of (tokens (B,), caches, pos). Returns the list
        of (logits, caches, pos). Each micro-batch's ``caches`` is consumed:
        its arrays are donated and the list is updated in place to the new
        caches.
        """
        xs = [self._embed(m, tokens, pos)
              for m, (tokens, _, pos) in enumerate(micro_batches)]

        for i in range(len(self.specs)):
            # stage 1: attention for every micro-batch (A role busy)
            for m, (_, caches, pos) in enumerate(micro_batches):
                xs[m], caches[i] = self._mixer(i, m, xs[m], caches[i], pos)
            # stage 2: FFN cycle — dispatches overlap attention of the
            # next micro-batch under async dispatch
            for m in range(len(xs)):
                xs[m] = self._ffn(i, m, xs[m])

        outs = []
        for m, (_, caches, pos) in enumerate(micro_batches):
            logits, pos = self._head(m, xs[m], pos, squeeze=True)
            outs.append((logits, caches, pos))
        return outs

    # ---- public prefill --------------------------------------------------------

    def _mixer_chunk(self, i: int, x, cache, pos, attn_impl: Optional[str]):
        cfg, lp, kind = self.cfg, self.a_params["layers"][i], self.specs[i].kind
        with obs.span("afd.attn", layer=i, mb=0):
            if kind == "attn":
                if attn_impl == "pallas":
                    return self._flash_attn(lp["ln1"], lp["attn"], x, cache,
                                            pos)
                return mix_attn_chunk(cfg, lp["ln1"], lp["attn"], x, cache,
                                      pos)
            # SSM mixers are an O(1)-per-token recurrence with no cached-state
            # batched form here — step the chunk through the decode program
            # token by token (bit-identical to decode by construction; the
            # M2N win lives in the MoE dispatch).
            outs = []
            for j in range(x.shape[1]):
                y, cache = mix_mamba(cfg, lp["ln1"], lp["mamba"],
                                     x[:, j:j + 1], cache, pos)
                outs.append(y)
            return jnp.concatenate(outs, axis=1), cache

    def _prefill_block(self, tokens, caches, pos, attn_impl):
        """One chunk (B, C) through the full layer stack — C tokens per
        M2N cycle instead of 1. Returns (logits, pos + C)."""
        x = self._embed(0, tokens, pos)
        for i in range(len(self.specs)):
            x, caches[i] = self._mixer_chunk(i, x, caches[i], pos, attn_impl)
            x = self._ffn(i, 0, x)
        return self._head(0, x, pos)

    def prefill(self, tokens: jax.Array, caches, pos: jax.Array,
                chunk: Optional[int] = None,
                attn_impl: Optional[str] = None):
        """Native batched prefill: S tokens per sequence in ceil(S/chunk)
        M2N cycles per MoE layer, vs S cycles for token-by-token teacher
        forcing. tokens: (B, S) int32; pos: (B,) start positions.

        Each chunk pushes B·C tokens through ``_moe_cycle`` in one
        dispatch→grouped-GEMM→combine (per-cycle payload B·C·d_model,
        Eq. 17's high-intensity regime) with the fused ``row_index``/
        ``out_index`` permute; attention runs ``attention_prefill_cached``
        (the flash-prefill kernel when ``attn_impl="pallas"``/on TPU, dense
        masked otherwise). Logits are bit-exact vs teacher forcing through
        ``decode_step`` on the dense path — every per-token arithmetic step
        is the same program evaluated batched.

        ``caches`` is consumed: its arrays are donated and the list is
        updated in place to the new caches.

        Returns (logits (B, S, V) f32, caches, pos + S).
        """
        if attn_impl is None and kops.default_impl() == "pallas":
            attn_impl = "pallas"
        s = tokens.shape[1]
        c = s if chunk is None else max(1, int(chunk))
        parts = []
        for off in range(0, s, c):
            lg, pos = self._prefill_block(tokens[:, off:off + c], caches, pos,
                                          attn_impl)
            parts.append(lg)
        logits = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                  axis=1)
        return logits, caches, pos


# ---------------------------------------------------------------------------
# F-role program
# ---------------------------------------------------------------------------

def make_expert_ffn(cfg: ArchConfig, f_mesh: Mesh,
                    gemm_impl: Optional[str] = None):
    """The F role's routed-expert FFN as one program over ``f_mesh``.

    ``fn(wi, wo, tokens, topw, topi)``: ``wi``/``wo`` are sharded over the
    mesh's "expert" axis; tokens and gating (the router ran on the A role)
    are replicated. Expert parallelism is explicit (``jax.shard_map``):
    each F device maps the global expert ids onto the experts it holds,
    runs the grouped GEMM over its local groups alone and contributes a
    partial combine, summed over "expert". No device ever reads another's
    expert weights — XLA cannot partition the Pallas call, so a sharded
    operand handed to it unwrapped would be all-gathered instead.

    The dispatch gather rides into the first grouped GEMM as ``row_index``
    (no (N·k, D) sorted copy materialises) and the combine unpermute rides
    out of the second as an ``out_index`` scatter. With one F device the
    arithmetic is that of the single-program ``moe_sorted``, bit for bit.
    """
    def local_ffn(wi, wo, tokens, topw, topi):
        n, d = tokens.shape
        e_local = wi.shape[0]
        local = topi - jax.lax.axis_index("expert") * e_local
        held = (local >= 0) & (local < e_local)
        # Tokens routed elsewhere sort past the last local group, where the
        # grouped GEMM yields zeros for them.
        sort_idx, _, group_sizes = moe_mod.sort_by_expert(
            jnp.where(held, local, e_local), e_local + 1)
        h = kops.grouped_gemm(tokens, wi.astype(tokens.dtype),
                              group_sizes[:e_local], impl=gemm_impl,
                              row_index=sort_idx // cfg.top_k)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
        ys = kops.grouped_gemm(h, wo.astype(tokens.dtype),
                               group_sizes[:e_local], impl=gemm_impl,
                               out_index=sort_idx, out_rows=n * cfg.top_k)
        y = jnp.einsum("nkd,nk->nd", ys.reshape(n, cfg.top_k, d),
                       topw.astype(tokens.dtype))
        return jax.lax.psum(y, "expert")

    experts = P("expert", None, None)
    return jax.jit(jax.shard_map(
        local_ffn, mesh=f_mesh,
        in_specs=(experts, experts, P(), P(), P()), out_specs=P(),
        check_vma=False))


def make_chunk_attention(cfg: ArchConfig, a_mesh: Mesh):
    """The A role's chunk ``afd.attn`` with the flash-prefill kernel as one
    program over ``a_mesh``: ``fn(ln1, attn_params, x, cache, pos) ->
    (x + attention, new_cache)``, the cache donated.

    A-role operands are replicated over the mesh. XLA cannot partition a
    Pallas call, even over replicated operands, so each A device runs the
    norm, the kernel and the residual on its own copy inside a
    ``shard_map``.
    """
    rep = P()
    return jax.jit(jax.shard_map(
        functools.partial(_attn_chunk, cfg, "pallas"),
        mesh=a_mesh, in_specs=(rep,) * 5, out_specs=(rep, rep),
        check_vma=False), donate_argnums=(3,))


def role_devices(devices: Sequence) -> Tuple[list, list]:
    """(A, F) devices for a flat device list: on one device both roles
    colocate; on N ≥ 2 the split is N/2 : N − N/2."""
    devices = list(devices)
    if len(devices) == 1:
        return devices, devices
    half = len(devices) // 2
    return split_nodes(devices, half, len(devices) - half)


def split_nodes(devices: Sequence, n_a_nodes: int, n_f_nodes: int,
                devices_per_node: int = 1):
    """Split a flat device list into A/F roles at node granularity."""
    need = (n_a_nodes + n_f_nodes) * devices_per_node
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    a = devices[:n_a_nodes * devices_per_node]
    f = devices[n_a_nodes * devices_per_node:need]
    return list(a), list(f)


# ---------------------------------------------------------------------------
# Elastic scaling (§3.3 discrete rescale as a live operation)
# ---------------------------------------------------------------------------

def rescale(runtime: AFDRuntime, a_devices: Sequence,
            f_devices: Sequence) -> AFDRuntime:
    """Rebuild the runtime on a new role split — the paper's discrete
    N_A adjustment (Eq. 16) executed live.

    Used by the scheduler after ``planner.elastic_rescale`` picks the
    floor/ceil fleet under measured imbalance σ, or after a node failure
    shrinks a role. Parameters are re-placed via device_put (on hardware
    this is the DCN weight migration the paper's elasticity discussion
    prices); caches are NOT migrated — in-flight requests drain and
    re-queue exactly as ``serving.engine.simulate_failure`` does.
    """
    # Reassemble the original single-program param pytree from the roles.
    cfg = runtime.cfg
    a = jax.device_get(runtime.a_params)
    f = [None if fl is None else
         {w: jax.device_get(x)[:cfg.n_experts] for w, x in fl.items()}
         for fl in runtime.f_layers]
    layers = []
    for i, lp in enumerate(a["layers"]):
        lp = dict(lp)
        if f[i] is not None:
            lp["moe"] = {**lp["moe"], **f[i]}
        layers.append(lp)
    plan = cfg.layer_plan()
    prefix = layers[:len(plan.prefix)]
    stacked = []
    n_p = plan.n_periods
    for j in range(len(plan.period)):
        per = [layers[len(plan.prefix) + p * len(plan.period) + j]
               for p in range(n_p)]
        stacked.append(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per))
    params = {
        "embed": a["embed"],
        "lm_head": a["lm_head"],
        "decoder": {"prefix": prefix, "stack": stacked,
                    "final_norm": a["final_norm"]},
    }
    if "encoder" in a:
        params["encoder"] = a["encoder"]
    return AFDRuntime(cfg, params, a_devices, f_devices,
                      gemm_impl=runtime.gemm_impl)
