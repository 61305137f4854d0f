"""Per-architecture smoke tests (deliverable f): every assigned arch at a
reduced same-family config runs forward + one train step + prefill/decode
on CPU with finite outputs and correct shapes.

Full configs are exercised only via the dry-run (ShapeDtypeStruct)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.model import make_model
from repro.training import optimizer as opt_mod
from repro.training.train import TrainConfig, make_train_step


def _batch(cfg, key, b=2, s=12):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.vision_seq:
        batch["patch_embeds"] = jax.random.normal(
            key, (b, cfg.vision_seq, cfg.d_model)) * 0.1
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder_seq, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = model.forward(params, batch)
    b, s = batch["tokens"].shape
    s_total = s + (cfg.vision_seq or 0)
    assert logits.shape == (b, s_total, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_one_train_step(arch):
    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = opt_mod.adamw(lr=1e-3)
    state = opt.init(params)
    step = make_train_step(model, opt, TrainConfig(), donate=False)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    new_params, state, metrics = step(params, state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(jax.tree_util.tree_leaves(new_params),
                        jax.tree_util.tree_leaves(params))
        if a.dtype.kind == "f")
    assert moved


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_prefill_matches_forward_and_decode_continues(arch):
    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    b, s = batch["tokens"].shape
    # Serving semantics on both sides: MoE layers route dropless, where
    # the train forward would drop tokens past an expert's capacity.
    logits, _ = model.forward(params, batch, mode="prefill")
    max_len = s + (cfg.vision_seq or 0) + 4
    lp, cache = model.prefill(params, batch, max_len=max_len)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(logits[:, -1]),
                               atol=1e-4)
    nxt = jnp.argmax(lp, -1).astype(jnp.int32)
    dl, cache = model.decode_step(params, cache, nxt)
    assert dl.shape == (b, cfg.vocab_size)
    assert bool(jnp.isfinite(dl).all())


@pytest.mark.parametrize("arch", ["qwen3-8b", "mamba2-2.7b",
                                  "h2o-danube-1.8b", "internvl2-2b"])
def test_decode_matches_teacher_forced_dense(arch):
    """Dense/SSM archs: decode must equal the teacher-forced forward
    exactly (MoE archs differ by capacity-drop semantics, tested in
    test_models with high capacity)."""
    cfg = configs.get_smoke_config(arch)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    b, s = batch["tokens"].shape
    max_len = s + (cfg.vision_seq or 0) + 6
    lp, cache = model.prefill(params, batch, max_len=max_len)
    toks, cur = batch["tokens"], jnp.argmax(lp, -1).astype(jnp.int32)
    for _ in range(2):
        dl, cache = model.decode_step(params, cache, cur)
        b2 = dict(batch)
        b2["tokens"] = jnp.concatenate([toks, cur[:, None]], axis=1)
        fl, _ = model.forward(params, b2)
        np.testing.assert_allclose(np.asarray(dl), np.asarray(fl[:, -1]),
                                   atol=5e-4)
        toks, cur = b2["tokens"], jnp.argmax(dl, -1).astype(jnp.int32)


@pytest.mark.parametrize("arch,moe", [
    ("kimi-k2-1t-a32b", True), ("jamba-v0.1-52b", True),
    ("granite-moe-1b-a400m", True), ("qwen3-8b", False),
])
def test_moe_decode_matches_with_high_capacity(arch, moe):
    if not moe:
        pytest.skip("dense covered elsewhere")
    cfg = dataclasses.replace(configs.get_smoke_config(arch),
                              moe_capacity_factor=16.0)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    lp, cache = model.prefill(params, batch,
                              max_len=batch["tokens"].shape[1] + 4)
    cur = jnp.argmax(lp, -1).astype(jnp.int32)
    dl, _ = model.decode_step(params, cache, cur)
    b2 = dict(batch)
    b2["tokens"] = jnp.concatenate([batch["tokens"], cur[:, None]], axis=1)
    fl, _ = model.forward(params, b2)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(fl[:, -1]),
                               atol=5e-4)


def test_full_configs_match_assigned_table():
    """The exact published numbers from the task brief."""
    t = {
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
    }
    for arch, (L, d, h, kv, ff, v) in t.items():
        cfg = configs.get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h, arch
        assert cfg.n_kv_heads == kv, arch
        ff_actual = cfg.moe_d_ff if cfg.is_moe else cfg.d_ff
        assert ff_actual == ff, arch
        assert cfg.vocab_size == v, arch
    # MoE extras
    kimi = configs.get_config("kimi-k2-1t-a32b")
    assert (kimi.n_experts, kimi.top_k) == (384, 8)
    jamba = configs.get_config("jamba-v0.1-52b")
    assert (jamba.n_experts, jamba.top_k) == (16, 2)
    gmoe = configs.get_config("granite-moe-1b-a400m")
    assert (gmoe.n_experts, gmoe.top_k) == (32, 8)
    m2 = configs.get_config("mamba2-2.7b")
    assert m2.ssm_state == 128
    dan = configs.get_config("h2o-danube-1.8b")
    assert dan.sliding_window == 4096
    q15 = configs.get_config("qwen1.5-0.5b")
    assert q15.qkv_bias
    q3 = configs.get_config("qwen3-8b")
    assert q3.qk_norm


def test_param_counts_sane():
    # Published sizes within ±25 % (embeddings/frontends excluded in some)
    # qwen1.5-"0.5b" computes to 464M from the assigned table (tied embed)
    expect = {"qwen1.5-0.5b": 0.46e9, "qwen3-8b": 8.2e9,
              "granite-8b": 8.0e9, "h2o-danube-1.8b": 1.8e9,
              "kimi-k2-1t-a32b": 1.03e12, "granite-moe-1b-a400m": 1.3e9,
              "mamba2-2.7b": 2.7e9, "jamba-v0.1-52b": 52e9}
    for arch, n in expect.items():
        got = configs.get_config(arch).param_count()
        assert abs(got - n) / n < 0.25, (arch, got, n)
    kimi = configs.get_config("kimi-k2-1t-a32b")
    active = kimi.active_param_count()
    assert abs(active - 33e9) / 33e9 < 0.15     # ≈ A32B
