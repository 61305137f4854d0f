"""Distribution layer: sharding rules, EP shard_map, split-KV collective,
AFD runtime — all on 1-device meshes in-process (multi-device equivalence
runs in tests/test_multidevice.py via a subprocess with forced devices)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.kernels.ref import moe_ffn_ref
from repro.models import moe as moe_mod
from repro.models.common import ArchConfig
from repro.models.model import make_model
from repro.parallel import collectives as coll
from repro.parallel import ep as ep_mod
from repro.parallel import sharding as shd
from repro.parallel.afd import AFDRuntime, split_roles


def _mesh1():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _moe_cfg(**kw):
    base = dict(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_head=16, d_ff=0, vocab_size=64, n_experts=8,
                top_k=2, moe_d_ff=16)
    base.update(kw)
    return ArchConfig(**base)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_logical_to_spec_divisibility_guard():
    mesh = _mesh1()
    rules = shd.TRAIN_RULES
    # dim not divisible by axis size → replicated (None)
    spec = shd.logical_to_spec(mesh, rules, ("batch", "heads"), (3, 7))
    assert spec == P(None, None) or all(
        s is None or s for s in spec)        # 1-device: everything divides


def test_param_specs_cover_all_leaves():
    cfg = configs.get_smoke_config("kimi-k2-1t-a32b")
    model = make_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = _mesh1()
    shards = shd.params_shardings(params, mesh, shd.TRAIN_RULES)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    n_shards = len(jax.tree_util.tree_leaves(
        shards, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_leaves == n_shards


def test_constraint_hook_noop_without_mesh():
    from repro.models.common import shard
    x = jnp.ones((4, 4))
    assert shard(x, "batch", "embed") is x


def test_activate_context_installs_and_uninstalls():
    from repro.models import common as mc
    mesh = _mesh1()
    with shd.activate(mesh, shd.TRAIN_RULES):
        x = jnp.ones((4, 4))
        y = mc.shard(x, "batch", "embed")
        assert y.shape == x.shape
    assert mc.shard(x, "batch", "embed") is x


# ---------------------------------------------------------------------------
# EP shard_map (1-device mesh exercises the full code path)
# ---------------------------------------------------------------------------

def test_ep_train_and_decode_match_oracle_1dev():
    cfg = _moe_cfg(moe_capacity_factor=8.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    mesh = _mesh1()
    ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",),
                         capacity_factor=8.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k).reshape(x.shape)
    with mesh:
        out_t, aux = jax.jit(
            lambda pp, xx: ep_mod.moe_ep_train(pp, cfg, xx, ep))(p, x)
        out_d = jax.jit(
            lambda pp, xx: ep_mod.moe_ep_decode(pp, cfg, xx, ep))(p, x)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(ref), atol=1e-5)
    assert float(aux) > 0


def test_ep_train_differentiable():
    cfg = _moe_cfg(moe_capacity_factor=4.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    mesh = _mesh1()
    ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",),
                         capacity_factor=4.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32))

    def loss(pp):
        out, aux = ep_mod.moe_ep_train(pp, cfg, x, ep)
        return jnp.sum(out ** 2) + 0.01 * aux

    with mesh:
        g = jax.jit(jax.grad(loss))(p)
    for name in ("wi", "wo", "router"):
        assert float(jnp.linalg.norm(g[name])) > 0, name


def test_ep_hook_installs_into_model():
    mesh = _mesh1()
    ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",))
    assert moe_mod._EP_FORWARD is None
    with ep_mod.activate(ep):
        assert moe_mod._EP_FORWARD is not None
    assert moe_mod._EP_FORWARD is None


def test_ep_fallback_when_experts_not_divisible():
    cfg = _moe_cfg(n_experts=6)      # 6 % anything>6 fails gracefully
    mesh = _mesh1()
    ep = dataclasses.replace(
        ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",)))
    fwd = ep_mod.make_ep_forward(ep)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32))
    # ep_size=1 divides — force the fallback by faking a bigger axis
    out, aux = fwd(p, cfg, x, "train")
    assert out.shape == x.shape


# ---------------------------------------------------------------------------
# split-KV decode collective (1-device mesh)
# ---------------------------------------------------------------------------

def test_splitkv_decode_matches_ref_1dev():
    from repro.kernels.ref import splitkv_attention_ref
    mesh = _mesh1()
    b, hq, hkv, d, t = 2, 4, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, d))
    pos = jnp.asarray([40, 13], jnp.int32)
    with mesh:
        out = jax.jit(lambda *a: coll.splitkv_decode_attention(
            *a, mesh=mesh, axis="model"))(q, k, v, pos)
    ref = splitkv_attention_ref(q, k, v, pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# AFD runtime
# ---------------------------------------------------------------------------

def test_split_roles_moves_experts_off_a_side():
    cfg = configs.get_smoke_config("kimi-k2-1t-a32b")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    a_params, f_layers = split_roles(params, cfg)
    for i, fl in enumerate(f_layers):
        lp = a_params["layers"][i]
        if fl is not None:
            assert "wi" not in lp["moe"] and "wo" not in lp["moe"]
            assert "router" in lp["moe"]        # gating stays on A
        else:
            assert "moe" not in lp or "wi" in lp.get("moe", {})


@pytest.mark.parametrize("arch", ["kimi-k2-1t-a32b", "granite-moe-1b-a400m",
                                  "jamba-v0.1-52b"])
def test_afd_equals_single_program_decode(arch):
    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    cache = model.init_cache(B, S + 2)
    ref = None
    for t in range(S):
        ref, cache = model.decode_step(params, cache, toks[:, t])
    devs = jax.devices()
    rt = AFDRuntime(cfg, params, [devs[0]], [devs[-1]])
    caches, pos = rt.init_cache(B, S + 2)
    out = None
    for t in range(S):
        out, caches, pos = rt.decode_step(toks[:, t], caches, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert rt.stats.dispatches > 0
    # M2N byte accounting: dispatch = tokens·H·itemsize + gating meta
    per = rt.stats.dispatch_bytes / rt.stats.dispatches
    assert per == B * cfg.d_model * 4 + B * cfg.top_k * 8


def test_afd_elastic_rescale_preserves_outputs():
    """§3.3 discrete rescale live: rebuilding the runtime on a shrunken
    A-fleet must produce identical logits (weights migrate, caches drain)."""
    from repro.parallel import afd as afd_mod
    cfg = configs.get_smoke_config("granite-moe-1b-a400m")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    devs = jax.devices()
    rt = AFDRuntime(cfg, params, [devs[0]], [devs[-1]])
    toks = jnp.asarray([3, 5], jnp.int32)
    c1, p1 = rt.init_cache(2, 8)
    ref, _, _ = rt.decode_step(toks, c1, p1)
    rt2 = afd_mod.rescale(rt, [devs[-1]], [devs[0]])   # swapped roles
    c2, p2 = rt2.init_cache(2, 8)
    out, _, _ = rt2.decode_step(toks, c2, p2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_afd_rejects_dense():
    cfg = configs.get_smoke_config("qwen3-8b")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        AFDRuntime(cfg, params, [jax.devices()[0]], [jax.devices()[0]])


def test_afd_3bo_driver_consistent_with_sequential():
    cfg = configs.get_smoke_config("granite-moe-1b-a400m")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    devs = jax.devices()
    rt = AFDRuntime(cfg, params, [devs[0]], [devs[-1]])
    B = 2
    mbs = []
    toks = []
    for k in range(3):
        c, p = rt.init_cache(B, 8)
        t = jax.random.randint(jax.random.PRNGKey(k), (B,), 1,
                               cfg.vocab_size).astype(jnp.int32)
        mbs.append((t, c, p))
        toks.append(t)
    outs = rt.decode_step_3bo(mbs)
    for k, (logits, caches, pos) in enumerate(outs):
        c, p = rt.init_cache(B, 8)
        ref, _, _ = rt.decode_step(toks[k], c, p)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=1e-5)
