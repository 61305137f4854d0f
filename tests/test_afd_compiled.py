"""The AFD runtime's A role as compiled programs: the same logits and caches
as the single-program oracle, caches donated and never read again by the
serving engine, a stale state still caught by the benchmark's check, and
one compiled program per layer kind and shape, however deep the stack."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime, unstack_layer_params
from repro.serving.afd_engine import AFDServeEngine
from repro.serving.workload import ArrivalEvent


def _setup(arch, **over):
    cfg = configs.get_smoke_config(arch)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    return cfg, model, params, AFDRuntime(cfg, params, [dev], [dev])


def _per_layer(model_cache, cfg):
    """A ``Model`` cache as the runtime's list of per-layer caches."""
    return unstack_layer_params({"decoder": model_cache}, cfg)


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "kimi-k2-1t-a32b",
                                  "jamba-v0.1-52b"])
def test_3bo_step_matches_single_program_decode(arch):
    """Three micro-batches through ``decode_step_3bo`` for several steps give
    each micro-batch's ``Model.decode_step`` logits and caches (attention,
    dense-MLP and Mamba layers), at that oracle's tolerance. The oracle
    scatters its KV writes; the A role writes update slices."""
    cfg, model, params, rt = _setup(arch)
    n_mb, b, steps = 3, 2, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (n_mb, steps, b), 0,
                              cfg.vocab_size)
    mbs = [(toks[m, 0], *rt.init_cache(b, steps + 2)) for m in range(n_mb)]
    for t in range(steps):
        outs = rt.decode_step_3bo(
            [(toks[m, t], c, p) for m, (_, c, p) in enumerate(mbs)], n_mb)
        mbs = [(None, c, p) for _, c, p in outs]
    # The micro-batches' rows are independent: one oracle batch holds them.
    cache = model.init_cache(n_mb * b, steps + 2)
    for t in range(steps):
        ref, cache = model.decode_step(params, cache,
                                       toks[:, t].reshape(n_mb * b))
    ref_caches = _per_layer(cache, cfg)
    for m, (logits, caches, pos) in enumerate(outs):
        rows = slice(m * b, (m + 1) * b)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[rows]),
                                   atol=1e-4)
        assert np.all(np.asarray(pos) == steps)
        for got, want in zip(caches, ref_caches):
            assert got.keys() == want.keys()
            for k in got:
                np.testing.assert_allclose(
                    np.asarray(got[k], np.float32),
                    np.asarray(want[k][rows], np.float32), atol=1e-4,
                    err_msg=k)


def test_step_donates_the_caches_it_is_given():
    """The arrays a step is handed are deleted by it; the list it was
    handed now holds the live new caches."""
    _, _, _, rt = _setup("granite-moe-1b-a400m")
    toks = jax.numpy.asarray([3, 5], jax.numpy.int32)
    mbs = [(toks, *rt.init_cache(2, 8)) for _ in range(2)]
    before = [list(c) for _, c, _ in mbs]
    outs = rt.decode_step_3bo(mbs, 2)
    for (_, given, _), old, (_, new, _) in zip(mbs, before, outs):
        assert new is given
        for old_c, new_c in zip(old, new):
            for k in old_c:
                assert old_c[k].is_deleted()
                assert not new_c[k].is_deleted()
    caches, pos = rt.init_cache(2, 8)
    old = list(caches)
    _, new, _ = rt.decode_step(toks, caches, pos)
    assert new is caches
    assert all(c[k].is_deleted() for c in old for k in c)


@pytest.mark.parametrize("prefill_chunk", [None, 4])
def test_engine_never_reads_a_donated_cache(prefill_chunk):
    """Serving ticks, with prompts prefilled token by token or in chunks,
    splices and completions, read no donated array (reading one raises)."""
    _, _, _, rt = _setup("granite-moe-1b-a400m")
    eng = AFDServeEngine(rt, max_len=32, n_bo=2, mb_slots=2,
                         tick_seconds=0.01, prefill_chunk=prefill_chunk)
    trace = [ArrivalEvent(rid=r, t=0.01 * r, prompt_len=3 + 2 * r,
                          max_new_tokens=3 + r) for r in range(6)]
    eng.run(trace, max_ticks=200)
    assert eng.stats.completed == 6
    for mb in eng.mbs:
        assert not any(c[k].is_deleted() for c in mb.caches for k in c)


def test_stale_caches_make_the_benchmark_run_incorrect(monkeypatch):
    """The benchmark's fault "state left unchanged", planted with donated
    caches: each step runs on copies, and the caches and positions from
    before it are handed back. The tiny benchmark check reads the run as
    not correct."""
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(__file__),
                                             "perfbench"))
    import test_perfbench_check as tiny
    orig = AFDRuntime.decode_step_3bo

    def step(self, mbs, n_bo=3):
        before = [jax.tree_util.tree_map(jnp.copy, c) for _, c, _ in mbs]
        outs = orig(self, mbs, n_bo)
        return [(lg, c, p) for (lg, _, _), c, (_, _, p)
                in zip(outs, before, mbs)]
    monkeypatch.setattr(AFDRuntime, "decode_step_3bo", step)
    res = tiny.run_once(tiny.tiny_cell())
    assert not res["correct"], res["checks"]


def _attn_compiles(n_layers):
    """``jax.compile`` spans under ``afd.attn`` while an engine serves the
    Granite smoke configuration cut to ``n_layers`` (a configuration no
    other test compiles for, so its programs compile here)."""
    _, _, _, rt = _setup("granite-moe-1b-a400m", n_layers=n_layers,
                         name=f"granite-compile-count-{n_layers}")
    eng = AFDServeEngine(rt, max_len=32, n_bo=2, mb_slots=2,
                         tick_seconds=0.01)
    with obs.span("test.mark") as mark:
        pass
    for rid in range(4):
        eng.submit(ArrivalEvent(rid=rid, t=0.0, prompt_len=3,
                                max_new_tokens=4))
    for _ in range(3):
        eng.tick()
    spans = [s for s in obs.spans() if s.sid > mark.sid]
    attn = {s.sid for s in spans if s.name == "afd.attn"}
    assert len(attn) >= n_layers
    return [s for s in spans if s.name == "jax.compile" and s.parent in attn]


def test_one_attention_program_serves_every_layer():
    """Prompts step through one-sequence decodes, ticks through two-slot
    micro-batches: two shapes of one layer kind, so at most two programs
    compile under ``afd.attn``, for two layers or for six."""
    for n_layers in (2, 6):
        compiles = _attn_compiles(n_layers)
        assert 1 <= len(compiles) <= 2, [s.ids["program"] for s in compiles]
        assert {s.ids["program"] for s in compiles} == {"jit(mix_attn)"}
