"""Expert-parallel MoE via shard_map — the paper's large-scale EP baseline.

Two execution paths, installed as the model's MoE strategy hook:

  * ``moe_ep_train``  — DeepEP-style all-to-all dispatch/combine across the
    EP axis ("model"). Tokens enter sharded over (pod, data) × model; each
    device routes its local tokens, scatters them into fixed-capacity
    per-destination send buffers, ``lax.all_to_all`` exchanges them, the
    receiver runs its local experts as a batched capacity GEMM
    (differentiable — this is the training path), and the reverse
    all-to-all brings results home for the gate-weighted combine.
    This is the collective the paper prices as t_dispatch/t_combine.

  * ``moe_ep_decode`` — the TPU-native decode variant: with one token per
    sequence the activations are already replicated across the EP axis
    (paid by the attention TP all-reduce), so dispatch is a local mask —
    each shard selects the (token, k) pairs whose expert lives locally,
    runs the grouped GEMM (ragged; Pallas kernel on TPU), and a single
    psum over the EP axis implements combine. M2N traffic collapses to
    one D-wide all-reduce — the ``combine``-only corner of Eq. 9.

Expert weights live sharded (experts → "model", D → "data" FSDP); the
shard_map in_specs declare full-D blocks so XLA inserts the just-in-time
FSDP all-gather at the boundary.

Shared experts are NOT handled here — they stay on the dense/TP path
(under AFD they remain on the attention role; paper §2.2).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from repro.kernels import ops as kops
from repro.models import moe as moe_mod
from repro.models.common import ArchConfig
from repro.models.layers import apply_mlp


@dataclasses.dataclass(frozen=True)
class EPConfig:
    mesh: Mesh
    ep_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("pod", "data")
    capacity_factor: float = 2.0
    gemm_impl: Optional[str] = None     # grouped-GEMM impl for decode
    etp: bool = False                   # weight-stationary ETP decode (§5.1)
    etp_axis: str = "data"              # expert-internal M sharding axis

    @property
    def present_dp_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.dp_axes if a in self.mesh.axis_names)

    @property
    def ep_size(self) -> int:
        return int(self.mesh.shape[self.ep_axis])


# ---------------------------------------------------------------------------
# local helpers (run per-device inside shard_map)
# ---------------------------------------------------------------------------

def _scatter_to_buffers(rows: jax.Array, dest: jax.Array, n_dest: int,
                        cap: int, payload: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter ``rows`` (R, D) into (n_dest, cap, D) by ``dest`` (R,).

    Returns (buffers, slot (R,), kept (R,)). Slot assignment is the
    arrival order within each destination; overflow rows are dropped
    (capacity semantics — counted by the caller for monitoring).
    """
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)       # (R, nd)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot              # (R, nd)
    slot = jnp.sum(pos, axis=-1)                                 # (R,)
    kept = slot < cap
    flat_idx = jnp.where(kept, dest * cap + slot, n_dest * cap)  # OOB drop
    buf = jnp.zeros((n_dest * cap + 1, rows.shape[-1]), rows.dtype)
    buf = buf.at[flat_idx].add(rows)                             # unique slots
    pay = jnp.zeros((n_dest * cap + 1, payload.shape[-1]), payload.dtype)
    pay = pay.at[flat_idx].set(payload)
    return (buf[:-1].reshape(n_dest, cap, -1),
            pay[:-1].reshape(n_dest, cap, -1), slot)


def _expert_capacity_gemm(cfg: ArchConfig, x_buf: jax.Array,
                          wi: jax.Array, wo: jax.Array) -> jax.Array:
    """Batched per-expert GEMM over capacity buffers (E_loc, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", x_buf, wi.astype(x_buf.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(x_buf.dtype))


# ---------------------------------------------------------------------------
# Training path: all-to-all dispatch
# ---------------------------------------------------------------------------

def _moe_ep_train_local(x_loc, router_w, wi_loc, wo_loc, *, cfg: ArchConfig,
                        ep: EPConfig):
    """Per-device body. x_loc: (n_loc, D)."""
    n_shards = ep.ep_size
    e_loc = cfg.n_experts // n_shards
    n_loc, d = x_loc.shape
    k = cfg.top_k

    probs, topw, topi = moe_mod.route({"router": router_w}, cfg, x_loc)
    aux = moe_mod.aux_load_balance_loss(probs, topi, cfg.n_experts)

    # --- dispatch: (token, slot) pairs → destination expert shard ---------
    flat_e = topi.reshape(-1)                                    # (n_loc·k,)
    dest = flat_e // e_loc
    rows = jnp.repeat(x_loc, k, axis=0)                          # (n_loc·k, D)
    cap_send = max(4, int(n_loc * k / n_shards * ep.capacity_factor))
    meta = jnp.stack([
        (flat_e % e_loc).astype(jnp.int32),                      # local expert
        jnp.ones_like(flat_e, jnp.int32),                        # valid flag
    ], axis=-1)
    send_x, send_meta, _ = _scatter_to_buffers(rows, dest, n_shards,
                                               cap_send, meta)

    recv_x = jax.lax.all_to_all(send_x, ep.ep_axis, 0, 0, tiled=False)
    recv_meta = jax.lax.all_to_all(send_meta, ep.ep_axis, 0, 0, tiled=False)

    # --- local expert compute over capacity buffers -----------------------
    rx = recv_x.reshape(-1, d)                                   # (ns·cap, D)
    rexp = recv_meta.reshape(-1, 2)[:, 0]
    rvalid = recv_meta.reshape(-1, 2)[:, 1] > 0
    cap_e = max(4, int(n_loc * k / e_loc * ep.capacity_factor))
    rdest = jnp.where(rvalid, rexp, e_loc)                       # invalid → drop
    x_buf, slot_meta, slot = _scatter_to_buffers(
        rx, rdest, e_loc + 1, cap_e,
        jnp.ones((rx.shape[0], 1), jnp.int32))
    y_buf = _expert_capacity_gemm(cfg, x_buf[:e_loc], wi_loc, wo_loc)
    y_buf = jnp.concatenate(
        [y_buf, jnp.zeros((1, cap_e, d), y_buf.dtype)], axis=0)

    # gather outputs back to recv-row order, a2a home
    flat_back = jnp.where(slot < cap_e, rdest * cap_e + slot,
                          e_loc * cap_e)
    y_rows = y_buf.reshape(-1, d)[flat_back]
    y_rows = jnp.where(rvalid[:, None], y_rows, 0.0)
    y_send = y_rows.reshape(n_shards, cap_send, d)
    y_recv = jax.lax.all_to_all(y_send, ep.ep_axis, 0, 0, tiled=False)

    # --- combine: un-scatter to (token, slot) order, gate-weight ----------
    # Reconstruct each pair's (dest, slot-in-dest) from the dispatch pass.
    onehot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    slot_d = jnp.sum(pos, axis=-1)
    kept = slot_d < cap_send
    flat_idx = jnp.where(kept, dest * cap_send + slot_d,
                         n_shards * cap_send)
    y_flat = jnp.concatenate(
        [y_recv.reshape(-1, d), jnp.zeros((1, d), y_recv.dtype)], axis=0)
    y_pairs = y_flat[flat_idx].reshape(n_loc, k, d)
    out = jnp.einsum("nkd,nk->nd", y_pairs, topw.astype(x_loc.dtype))
    drop_frac = 1.0 - jnp.mean(kept.astype(jnp.float32))
    return out, aux, drop_frac


def moe_ep_train(params, cfg: ArchConfig, x: jax.Array, ep: EPConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) — B sharded over dp axes, S over the EP axis."""
    dp = ep.present_dp_axes
    b, s, d = x.shape

    def body(x_l, router_w, wi_l, wo_l):
        xf = x_l.reshape(-1, d)
        out, aux, _drop = _moe_ep_train_local(xf, router_w, wi_l, wo_l,
                                              cfg=cfg, ep=ep)
        aux = jax.lax.pmean(aux, ep.ep_axis)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(x_l.shape), aux

    out, aux = jax.shard_map(
        body, mesh=ep.mesh,
        in_specs=(P(dp if dp else None, ep.ep_axis, None),
                  P(None, None),
                  P(ep.ep_axis, None, None),
                  P(ep.ep_axis, None, None)),
        out_specs=(P(dp if dp else None, ep.ep_axis, None), P()),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wo"])

    if "shared" in params:
        out = out + apply_mlp(params["shared"], cfg, x)
    return out, aux


# ---------------------------------------------------------------------------
# Decode path: replicated activations, local select + psum combine
# ---------------------------------------------------------------------------

def _moe_ep_decode_local(x_loc, router_w, wi_loc, wo_loc, *,
                         cfg: ArchConfig, ep: EPConfig):
    n_shards = ep.ep_size
    e_loc = cfg.n_experts // n_shards
    n_loc, d = x_loc.shape
    k = cfg.top_k

    _, topw, topi = moe_mod.route({"router": router_w}, cfg, x_loc)
    my = jax.lax.axis_index(ep.ep_axis)
    local_e = topi - my * e_loc                                  # (n, k)
    is_local = (local_e >= 0) & (local_e < e_loc)

    # Sort pairs: local ones first grouped by expert; others pushed to the
    # tail where group_sizes never reach them (grouped GEMM yields zeros).
    key = jnp.where(is_local, local_e, e_loc)
    flat_key = key.reshape(-1)
    order = jnp.argsort(flat_key, stable=True)
    inv = jnp.argsort(order, stable=True)
    rows = jnp.repeat(x_loc, k, axis=0)[order]
    group_sizes = jnp.bincount(jnp.where(flat_key < e_loc, flat_key, e_loc),
                               length=e_loc + 1)[:e_loc].astype(jnp.int32)

    h = kops.grouped_gemm(rows, wi_loc.astype(x_loc.dtype), group_sizes,
                          impl=ep.gemm_impl)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = kops.grouped_gemm(h, wo_loc.astype(x_loc.dtype), group_sizes,
                          impl=ep.gemm_impl)
    y = y[inv].reshape(n_loc, k, d)
    y = jnp.where(is_local[..., None], y, 0.0)
    out = jnp.einsum("nkd,nk->nd", y, topw.astype(x_loc.dtype))
    return jax.lax.psum(out, ep.ep_axis)                        # combine


def moe_ep_decode(params, cfg: ArchConfig, x: jax.Array, ep: EPConfig
                  ) -> jax.Array:
    """x: (B, S=1, D) — B sharded over dp axes, replicated over EP axis."""
    dp = ep.present_dp_axes
    b, s, d = x.shape

    def body(x_l, router_w, wi_l, wo_l):
        xf = x_l.reshape(-1, d)
        out = _moe_ep_decode_local(xf, router_w, wi_l, wo_l, cfg=cfg, ep=ep)
        return out.reshape(x_l.shape)

    out = jax.shard_map(
        body, mesh=ep.mesh,
        in_specs=(P(dp if dp else None, None, None),
                  P(None, None),
                  P(ep.ep_axis, None, None),
                  P(ep.ep_axis, None, None)),
        out_specs=P(dp if dp else None, None, None),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wo"])

    if "shared" in params:
        out = out + apply_mlp(params["shared"], cfg, x)
    return out


# ---------------------------------------------------------------------------
# ETP weight-stationary decode (paper §5.1; §Perf hillclimb H1)
# ---------------------------------------------------------------------------

def moe_ep_decode_etp(params, cfg: ArchConfig, x: jax.Array, ep: EPConfig
                      ) -> jax.Array:
    """Weight-stationary expert-tensor-parallel decode (§5.1 as a lever).

    Experts stay sharded over the EP axis AND each expert's D dimension
    stays sharded over ``etp_axis`` — exactly the FSDP storage layout, so
    the shard_map in_specs match the stored shardings and NO weight bytes
    ever cross the interconnect. Instead the (tiny) decode activations do:

        up-proj:   rows[:, D_loc] · wi (E_loc, D_loc, 2M) → partial h,
                   psum over etp_axis                     (n·k × 2M)
        down-proj: h · wo (E_loc, M, D_loc) → y slice     (no comm)
        combine:   psum over EP axis + all-gather D       (n × D)

    For Kimi-K2 decode_32k that replaces the baseline's ~240 GB/step of
    per-layer expert-weight all-gathers with ~2 GB/step of activation
    collectives (EXPERIMENTS.md §Perf H1).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // ep.ep_size
    n_etp = int(ep.mesh.shape[ep.etp_axis]) if ep.etp_axis in \
        ep.mesh.axis_names else 1
    d_loc = d // n_etp

    def body(x_l, router_w, wi_l, wo_l):
        # x_l: (B, S, D) replicated; wi_l: (E_loc, D_loc, 2M);
        # wo_l: (E_loc, M, D_loc)
        xf = x_l.reshape(-1, d)
        n = xf.shape[0]
        _, topw, topi = moe_mod.route({"router": router_w}, cfg, xf)
        my = jax.lax.axis_index(ep.ep_axis)
        local_e = topi - my * e_loc
        is_local = (local_e >= 0) & (local_e < e_loc)
        key = jnp.where(is_local, local_e, e_loc)
        order = jnp.argsort(key.reshape(-1), stable=True)
        inv = jnp.argsort(order, stable=True)
        rows = jnp.repeat(xf, k, axis=0)[order]
        group_sizes = jnp.bincount(
            jnp.where(key.reshape(-1) < e_loc, key.reshape(-1), e_loc),
            length=e_loc + 1)[:e_loc].astype(jnp.int32)

        # row-parallel up-projection over the local D slice
        me = jax.lax.axis_index(ep.etp_axis) if n_etp > 1 else 0
        rows_l = jax.lax.dynamic_slice_in_dim(rows, me * d_loc, d_loc,
                                              axis=1)
        h = kops.grouped_gemm(rows_l, wi_l.astype(xf.dtype), group_sizes,
                              impl=ep.gemm_impl)          # partial (n·k, 2M)
        if n_etp > 1:
            h = jax.lax.psum(h, ep.etp_axis)
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up                        # (n·k, M)

        # column-parallel down-projection: local D_loc output slice
        y = kops.grouped_gemm(h, wo_l.astype(xf.dtype), group_sizes,
                              impl=ep.gemm_impl)          # (n·k, D_loc)
        y = y[inv].reshape(n, k, d_loc)
        y = jnp.where(is_local[..., None], y, 0.0)
        out = jnp.einsum("nkd,nk->nd", y, topw.astype(xf.dtype))
        out = jax.lax.psum(out, ep.ep_axis)               # top-k combine
        if n_etp > 1:
            out = jax.lax.all_gather(out, ep.etp_axis, axis=1, tiled=True)
        return out.reshape(x_l.shape)

    out = jax.shard_map(
        body, mesh=ep.mesh,
        in_specs=(P(None, None, None),                    # tokens replicated
                  P(None, None),
                  P(ep.ep_axis, ep.etp_axis, None),       # = FSDP storage
                  P(ep.ep_axis, None, ep.etp_axis)),
        out_specs=P(None, None, None),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wo"])

    if "shared" in params:
        out = out + apply_mlp(params["shared"], cfg, x)
    return out


# ---------------------------------------------------------------------------
# Strategy hook installation
# ---------------------------------------------------------------------------

def make_ep_forward(ep: EPConfig):
    """Build the moe_forward strategy hook for models under this mesh."""

    def forward(params, cfg: ArchConfig, x: jax.Array, mode: str):
        if cfg.n_experts % ep.ep_size != 0:
            # e.g. jamba's 16 experts on a 32-wide axis — fall back to the
            # single-program path (XLA shards the capacity einsums).
            return moe_mod.moe_capacity(params, cfg, x) if mode == "train" \
                else (moe_mod.moe_sorted(params, cfg, x),
                      jnp.zeros((), jnp.float32))
        if mode == "train":
            return moe_ep_train(params, cfg, x, ep)
        n_etp = int(ep.mesh.shape.get(ep.etp_axis, 1))
        if ep.etp and cfg.d_model % max(n_etp, 1) == 0:
            return (moe_ep_decode_etp(params, cfg, x, ep),
                    jnp.zeros((), jnp.float32))
        return moe_ep_decode(params, cfg, x, ep), jnp.zeros((), jnp.float32)

    return forward


def install(ep: EPConfig) -> None:
    moe_mod.set_ep_forward(make_ep_forward(ep))


def uninstall() -> None:
    moe_mod.set_ep_forward(None)


class activate:
    def __init__(self, ep: EPConfig):
        self.ep = ep

    def __enter__(self):
        install(self.ep)
        return self

    def __exit__(self, *exc):
        uninstall()
        return False
