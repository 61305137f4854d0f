"""Multi-device equivalence tests — run in a subprocess with 8 forced host
devices so the main pytest process keeps seeing 1 device (task brief).

Covers: EP all-to-all == oracle across real shards, split-KV decode across
real KV shards, AFD two-role placement, and a tiny end-to-end lowering with
the dry-run machinery on a (2, 4) mesh.
"""

import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_ep_8dev_matches_oracle():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.models.common import ArchConfig
    from repro.models import moe as moe_mod
    from repro.parallel import ep as ep_mod
    from repro.kernels.ref import moe_ffn_ref
    assert len(jax.devices()) == 8
    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                     n_experts=8, top_k=2, moe_d_ff=16)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",),
                         capacity_factor=8.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k).reshape(x.shape)
    with mesh:
        out_t, _ = jax.jit(lambda pp, xx: ep_mod.moe_ep_train(
            pp, cfg, xx, ep))(p, x)
        out_d = jax.jit(lambda pp, xx: ep_mod.moe_ep_decode(
            pp, cfg, xx, ep))(p, x)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(ref),
                               atol=1e-5)
    print("EP-8DEV-OK")
    """)


def test_etp_decode_8dev_matches_oracle():
    """Weight-stationary ETP decode (§5.1 / §Perf H1) across real shards."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.models.common import ArchConfig
    from repro.models import moe as moe_mod
    from repro.parallel import ep as ep_mod
    from repro.kernels.ref import moe_ffn_ref
    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                     n_experts=8, top_k=2, moe_d_ff=16)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), "m", cfg)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ep = ep_mod.EPConfig(mesh=mesh, ep_axis="model", dp_axes=("data",),
                         etp=True, etp_axis="data")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 32)) * 0.5
    ref = moe_ffn_ref(x.reshape(-1, 32), p["router"], p["wi"], p["wo"],
                      cfg.top_k).reshape(x.shape)
    with mesh:
        out = jax.jit(lambda pp, xx: ep_mod.moe_ep_decode_etp(
            pp, cfg, xx, ep))(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("ETP-8DEV-OK")
    """)


def test_splitkv_8dev_matches_ref():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.parallel import collectives as coll
    from repro.kernels.ref import splitkv_attention_ref
    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    b, hq, hkv, d, t = 2, 8, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, d))
    pos = jnp.asarray([100, 13], jnp.int32)
    with mesh:
        out = jax.jit(lambda *a: coll.splitkv_decode_attention(
            *a, mesh=mesh, axis="model"))(q, k, v, pos)
    ref = splitkv_attention_ref(q, k, v, pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("SPLITKV-8DEV-OK")
    """)


def test_afd_two_role_8dev():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro import configs
    from repro.models.model import make_model
    from repro.parallel.afd import AFDRuntime, split_nodes
    cfg = configs.get_smoke_config("granite-moe-1b-a400m")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 5
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    cache = model.init_cache(B, S + 2)
    ref = None
    for t in range(S):
        ref, cache = model.decode_step(params, cache, toks[:, t])
    a_dev, f_dev = split_nodes(jax.devices(), 4, 4)
    rt = AFDRuntime(cfg, params, a_dev, f_dev)
    caches, pos = rt.init_cache(B, S + 2)
    out = None
    for t in range(S):
        out, caches, pos = rt.decode_step(toks[:, t], caches, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    # 8 experts over 3 F devices: padded with a zero expert to 3 x 3
    a_dev, f_dev = split_nodes(jax.devices(), 5, 3)
    rt = AFDRuntime(cfg, params, a_dev, f_dev)
    assert rt.f_layers[0]["wi"].shape[0] == 9
    caches, pos = rt.init_cache(B, S + 2)
    for t in range(S):
        out, caches, pos = rt.decode_step(toks[:, t], caches, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    print("AFD-8DEV-OK")
    """)


def test_afd_dryrun_small_roles():
    """AFD-mode dry-run machinery at reduced node counts: both role
    programs lower+compile and the budget pipeline yields sane metrics."""
    _run("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    from repro.launch.afd_dryrun import lower_afd
    rec = lower_afd("granite-moe-1b-a400m", batch=32, context=1024,
                    n_a_nodes=4, n_f_nodes=4)
    assert rec["a_role"]["t_stage"] > 0
    assert rec["f_role"]["t_stage"] > 0
    assert 0 <= rec["ffn_stage"]["hfu"] <= 1
    assert 0 <= rec["pipeline"]["f_util"] <= 1 + 1e-9
    rec8 = lower_afd("granite-moe-1b-a400m", batch=32, context=1024,
                     n_a_nodes=4, n_f_nodes=4, int8=True)
    assert rec8["f_weight_bytes_dev"] < rec["f_weight_bytes_dev"]
    print("AFD-DRYRUN-OK")
    """)


def test_tiny_dryrun_lowering_on_8dev_mesh():
    """The dry-run machinery end-to-end at toy scale: train + prefill +
    decode lower AND compile on a (2, 4) mesh for a smoke MoE arch."""
    _run("""
    import jax, dataclasses
    from repro import configs
    from repro.launch import dryrun as dr, shapes as shp, hlo_analysis as hlo
    from repro.parallel import sharding as shd

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    spec = shp.ShapeSpec("tiny_train", "train", 32, 8)
    cfg = dataclasses.replace(configs.get_smoke_config("granite-moe-1b-a400m"),
                              remat=True)
    epc = dr._ep_config(cfg, spec, mesh)
    c, tl, tc = dr._compile_variant(cfg, spec, mesh, shd.TRAIN_RULES, epc,
                                    False, "granite-moe-1b-a400m")
    cost, coll = dr._cost_raw(c)
    terms = hlo.roofline(cost, coll, 8)
    assert terms.flops_dev > 0
    assert c.memory_analysis().argument_size_in_bytes > 0
    print("TRAIN-LOWER-OK", terms.dominant)

    spec_d = shp.ShapeSpec("tiny_decode", "decode", 64, 8)
    c2, _, _ = dr._compile_variant(cfg, spec_d, mesh, shd.SERVE_RULES, epc,
                                   True, "granite-moe-1b-a400m")
    print("DECODE-LOWER-OK")

    spec_p = shp.ShapeSpec("tiny_prefill", "prefill", 64, 8)
    c3, _, _ = dr._compile_variant(cfg, spec_p, mesh, shd.SERVE_RULES, epc,
                                   False, "granite-moe-1b-a400m")
    print("PREFILL-LOWER-OK")
    """)
