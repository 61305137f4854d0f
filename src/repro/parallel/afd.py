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

The runtime tracks dispatch/combine bytes per micro-batch so the system
benchmark can check them against Eq. 9's B_rank prediction.

Dense architectures have no routed experts — ``AFDRuntime`` refuses them,
matching DESIGN.md §Arch-applicability (AFD degenerates to a pipeline
split; the planner reports it instead).
"""

from __future__ import annotations

import dataclasses
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
# Runtime
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


class AFDRuntime:
    """Two-role decode runtime. Devices are split at node granularity."""

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
        cfg, lp, spec = self.cfg, self.a_params["layers"][i], self.specs[i]
        with obs.span("afd.attn", layer=i, mb=mb):
            h = apply_norm(lp["ln1"], cfg, x)
            if spec.kind == "attn":
                mix, nc = attn_mod.attention_decode(lp["attn"], cfg, h, cache,
                                                    pos)
            else:
                mix, nc = mamba2.mamba_decode(lp["mamba"], cfg, h, cache)
            return x + mix, nc

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
            h = apply_norm(lp["ln2"], cfg, x)
            return x + apply_mlp(lp["mlp"], cfg, h)

    # ---- the M2N cycle -------------------------------------------------------

    def _moe_cycle(self, i: int, mb: int, x):
        """Norm → route (A) → dispatch → expert FFN (F) → combine (A)."""
        cfg, lp, f_entry = self.cfg, self.a_params["layers"][i], self.f_layers[i]
        with obs.span("afd.route", layer=i, mb=mb):
            h = apply_norm(lp["ln2"], cfg, x)
            tokens = h.reshape(-1, cfg.d_model)
            _, topw, topi = moe_mod.route(lp["moe"], cfg, tokens)

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
            out = x + routed.reshape(x.shape)
            if "shared" in lp["moe"]:
                out = out + apply_mlp(lp["moe"]["shared"], cfg, h)
            return out

    def _embed(self, mb: int, tokens, positions):
        with obs.span("afd.embed", mb=mb):
            return embed_tokens(self.a_params["embed"], self.cfg, tokens,
                                positions)

    def _head(self, mb: int, x):
        """Final norm and LM head."""
        with obs.span("afd.head", mb=mb):
            x = apply_norm(self.a_params["final_norm"], self.cfg, x)
            return apply_lm_head(self.a_params["lm_head"],
                                 self.a_params["embed"], self.cfg, x)

    # ---- public decode ---------------------------------------------------------

    def init_cache(self, batch: int, max_len: int):
        return [kvcache.init_layer_cache(self.cfg, s, batch, max_len)
                for s in self.specs], jnp.zeros((batch,), jnp.int32)

    def decode_step(self, tokens: jax.Array, caches, pos: jax.Array):
        """One token for one micro-batch. tokens: (B,)."""
        x = self._embed(0, tokens[:, None], pos[:, None])
        new_caches = []
        for i in range(len(self.specs)):
            x, nc = self._mixer(i, 0, x, caches[i], pos)
            x = self._ffn(i, 0, x)
            new_caches.append(nc)
        return self._head(0, x)[:, 0], new_caches, pos + 1

    def decode_step_3bo(self, micro_batches, n_bo: int = 3):
        """Drive ``n_bo`` micro-batches through the layer loop in the 3BO
        rotation: issue order interleaves (layer ℓ, mb m) so that while one
        micro-batch's experts run on the F role another's attention runs on
        the A role — JAX async dispatch realises the overlap on hardware.

        micro_batches: list of (tokens (B,), caches, pos). Returns the list
        of (logits, caches, pos).
        """
        states = []
        for m, (tokens, caches, pos) in enumerate(micro_batches):
            x = self._embed(m, tokens[:, None], pos[:, None])
            states.append({"x": x, "caches": caches, "new": [], "pos": pos})

        for i in range(len(self.specs)):
            # stage 1: attention for every micro-batch (A role busy)
            for m, st in enumerate(states):
                st["x"], nc = self._mixer(i, m, st["x"], st["caches"][i],
                                          st["pos"])
                st["new"].append(nc)
            # stage 2: FFN cycle — dispatches overlap attention of the
            # next micro-batch under async dispatch
            for m, st in enumerate(states):
                st["x"] = self._ffn(i, m, st["x"])

        return [(self._head(m, st["x"])[:, 0], st["new"], st["pos"] + 1)
                for m, st in enumerate(states)]

    # ---- public prefill --------------------------------------------------------

    def _mixer_chunk(self, i: int, x, cache, pos, attn_impl: Optional[str]):
        cfg, lp, spec = self.cfg, self.a_params["layers"][i], self.specs[i]
        with obs.span("afd.attn", layer=i, mb=0):
            h = apply_norm(lp["ln1"], cfg, x)
            if spec.kind == "attn":
                if attn_impl == "pallas":
                    mix, nc = self._flash_attn(lp["attn"], h, cache, pos)
                else:
                    mix, nc = attn_mod.attention_prefill_cached(
                        lp["attn"], cfg, h, cache, pos)
                return x + mix, nc
            # SSM mixers are an O(1)-per-token recurrence with no cached-state
            # batched form here — step the chunk sequentially (bit-identical
            # to decode by construction; the M2N win lives in the MoE
            # dispatch).
            outs = []
            for j in range(x.shape[1]):
                mj, cache = mamba2.mamba_decode(lp["mamba"], cfg,
                                                h[:, j:j + 1], cache)
                outs.append(mj)
            return x + jnp.concatenate(outs, axis=1), cache

    def _prefill_block(self, tokens, caches, pos, attn_impl):
        """One chunk (B, C) through the full layer stack — C tokens per
        M2N cycle instead of 1."""
        c = tokens.shape[1]
        x = self._embed(0, tokens,
                        pos[:, None] + jnp.arange(c, dtype=pos.dtype))
        new_caches = []
        for i in range(len(self.specs)):
            x, nc = self._mixer_chunk(i, x, caches[i], pos, attn_impl)
            x = self._ffn(i, 0, x)
            new_caches.append(nc)
        return self._head(0, x), new_caches, pos + c

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

        Returns (logits (B, S, V) f32, caches, pos + S).
        """
        if attn_impl is None and kops.default_impl() == "pallas":
            attn_impl = "pallas"
        s = tokens.shape[1]
        c = s if chunk is None else max(1, int(chunk))
        parts = []
        for off in range(0, s, c):
            lg, caches, pos = self._prefill_block(
                tokens[:, off:off + c], caches, pos, attn_impl)
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
    """The A role's flash-prefill chunk attention as one program over
    ``a_mesh``: ``fn(attn_params, x, cache, pos) -> (out, new_cache)``.

    A-role operands are replicated over the mesh. XLA cannot partition a
    Pallas call, even over replicated operands, so each A device runs the
    kernel on its own copy inside a ``shard_map``.
    """
    rep = P()
    return jax.jit(jax.shard_map(
        lambda p, x, cache, pos: attn_mod.attention_prefill_cached(
            p, cfg, x, cache, pos, impl="pallas"),
        mesh=a_mesh, in_specs=(rep,) * 4, out_specs=(rep, rep),
        check_vma=False))


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
