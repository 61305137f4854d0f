"""The check that decides a run's ``correct``, driven through the rest of a
run at a tiny size on the CPU (the look for a chip is skipped): a sound run
passes, the fp8 control fails, and so does each fault the decode cells can
have, planted in the timed path.

The tiny window is bounded by ticks, not by the clock, and holds every
request of the mix to its end: the check then compares every token the
mix asks for, the same tokens for a seed on a machine of any speed."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from perfbench import harness  # noqa: E402
from perfbench.traffic import generator  # noqa: E402
from repro.parallel import afd  # noqa: E402

# Limits of the tiny cell, set like the chip cells' from readings on the CPU
# over 12 seeds, on the program and on a copy with its A role compiled: the
# mean served-token gap of sound runs read at most 0.00259 standard
# deviations, the fp8 control's at least 0.0215 (PERF.md).
TINY_LIMITS = {"m2n_bytes_off": 0, "served_gap_mean_std": 0.008}
SEED = 2**33 + 5
# Ticks in the tiny window: every request of the mix has finished by the
# 93rd on every seed tried (PERF.md); the ticks after the last are empty.
TINY_TICKS = 160


def tiny_cell():
    """``granite-decode`` at a size the CPU runs in seconds: the Granite
    configuration with narrow widths (32 experts, top 8, as published), eight
    decode slots and three blocks of requests, every served token
    compared."""
    conf = json.load(open(os.path.join(harness.BENCH, "configs",
                                       "granite-moe-1b-a400m.json")))
    conf.update(hidden_size=128, intermediate_size=32, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                vocab_size=1024)
    mix = generator.load("decode-long-out")
    mix.update(n_bo=2, mb_slots=4, blocks=3, max_len=96, prefill_chunk=16,
               prefill_chunks_per_tick=4, check_tokens=10**6)
    mix["prompt"] = {"buckets": [8, 16]}
    mix["output"] = {"lo": 12, "hi": 40}
    mix["in_flight"] = {"reserve": 8, "step": 8}
    units = {"out_tok_s": "tokens/s", "itl_p95_ms": "ms", "setup_s": "s"}
    return harness.Cell(name="tiny", chips=1, config=conf, traffic=mix,
                        limits=dict(TINY_LIMITS),
                        end_to_end=[{"name": n, "unit": u}
                                    for n, u in units.items()],
                        per_layer=[])


def run_once(cell, seed=SEED):
    res = harness.run(cell, seed, 0.0, False, jax.devices()[:1],
                      time.perf_counter(), log=lambda *a, **k: None,
                      n_ticks=TINY_TICKS)
    return res


def mix_requests(cell, seed=SEED):
    return generator.requests(cell.traffic, seed, cell.config["vocab_size"])


def mix_tokens(cell, seed=SEED):
    """Every token the mix asks for."""
    return sum(r.max_new_tokens for r in mix_requests(cell, seed))


@pytest.fixture(scope="module")
def served_tiny():
    cell = tiny_cell()
    return cell, harness.serve(cell, SEED, 0.0, jax.devices()[:1],
                               n_ticks=TINY_TICKS)


def test_sound_run_is_correct():
    cell = tiny_cell()
    res = run_once(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["m2n_dispatch_bytes_off"]["value"] == 0
    assert res["checks"]["tokens_compared"]["value"] == mix_tokens(cell)
    assert set(res["metrics"]) == {"out_tok_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["attempted"] == len(mix_requests(cell))
    assert res["failed"] == 0


def test_fp8_control_fails_the_limit(served_tiny):
    cell, sv = served_tiny
    assert harness.passed(harness.check(cell, sv, SEED))
    assert not harness.passed(harness.check(cell, sv, SEED, control=True))


def test_check_does_not_depend_on_the_window(served_tiny):
    """A window twice as many ticks long compares the same tokens and reads
    the same gap; neither window's ``seconds`` (0 and 10**6) is read."""
    cell, sv = served_tiny
    longer = harness.serve(cell, SEED, 10**6, jax.devices()[:1],
                           n_ticks=2 * TINY_TICKS)
    assert len(longer.ticks) == 2 * len(sv.ticks)
    a, b = (harness.check(cell, s, SEED) for s in (sv, longer))
    assert a["tokens_compared"]["value"] == mix_tokens(cell)
    for name in ("tokens_compared", "served_gap_mean_std",
                 "m2n_dispatch_bytes_off"):
        assert a[name]["value"] == b[name]["value"], name


def _stale_state(monkeypatch):
    """A decode step that returns its state (cache, positions) unchanged."""
    orig = afd.AFDRuntime.decode_step_3bo

    def step(self, mbs, n_bo=3):
        outs = orig(self, mbs, n_bo)
        return [(lg, c, p) for (lg, _, _), (_, c, p) in zip(outs, mbs)]
    monkeypatch.setattr(afd.AFDRuntime, "decode_step_3bo", step)


def _altered_token(monkeypatch):
    """Every decoded token is altered where it is produced: each slot gets
    its runner-up instead of its best token."""
    orig = afd.AFDRuntime.decode_step_3bo

    def step(self, mbs, n_bo=3):
        outs = orig(self, mbs, n_bo)
        return [(lg.at[jnp.arange(lg.shape[0]), jnp.argmax(lg, -1)]
                 .set(-jnp.inf), c, p) for lg, c, p in outs]
    monkeypatch.setattr(afd.AFDRuntime, "decode_step_3bo", step)


def _no_exchange(monkeypatch):
    """The F role's result never comes back from the expert program."""
    monkeypatch.setattr(afd, "make_expert_ffn",
                        lambda cfg, mesh, impl=None:
                        lambda wi, wo, tok, w, i: jnp.zeros_like(tok))


def _miscounted_bytes(monkeypatch):
    """The dispatch counter leaves out the gating metadata."""
    orig = afd.AFDStats.record

    def record(self, n_tokens, hidden, dtype_bytes, meta_bytes):
        orig(self, n_tokens, hidden, dtype_bytes, 0)
    monkeypatch.setattr(afd.AFDStats, "record", record)


@pytest.mark.parametrize("fault", [_stale_state, _altered_token, _no_exchange,
                                   _miscounted_bytes])
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    fault(monkeypatch)
    res = run_once(tiny_cell())
    assert not res["correct"], res["checks"]


def test_sample_takes_the_longest_then_draws_to_the_token_count():
    import numpy as np
    from types import SimpleNamespace as NS
    reqs = {rid: NS(prompt=np.zeros(p, np.int32), output=[1] * n)
            for rid, (p, n) in enumerate([(8, 5), (16, 9), (8, 7), (4, 3)])}
    sv = NS(requests=reqs)
    pick = harness.check_sample(sv, 3, 12)
    assert pick[0] == 1                        # 16 + 9 is the longest
    assert sum(len(reqs[r].output) for r in pick) >= 12
    assert pick == harness.check_sample(sv, 3, 12)
    assert sorted(harness.check_sample(sv, 3, 10**6)) == [0, 1, 2, 3]


def test_tokens_compared_may_stop_at_every_served_token():
    ok = {"served_gap_mean_std": {"value": 0.001, "limit": 0.01},
          "tokens_compared": {"value": 912.0, "limit": 912}}
    assert harness.passed(ok)
    ok["tokens_compared"]["value"] = 900.0
    assert not harness.passed(ok)


def test_folded_multipliers_serve_the_published_function():
    """Granite's multipliers, folded into the weights the program serves,
    give the reference's logits; served unfolded, they do not. (Folded in
    float32, so that only the fold is tested: the served bfloat16 weights
    round each scaled leaf once more.)"""
    import numpy as np
    from perfbench.configs import moe_transformer as mt, serve_moe
    from repro.models.model import Model
    conf = dict(tiny_cell().config, torch_dtype="float32")
    arch = mt.Arch.from_config(conf)
    assert (arch.emb_mult, arch.resid_mult, arch.logits_div) == (12, .22, 6)
    tokens = np.random.default_rng(3).integers(1, 1024, 40).astype(np.int32)
    want, _ = mt.forward_rows(mt.init_params(mt.seed_key(SEED), arch), arch,
                              tokens, np.arange(40))
    model = Model(serve_moe.arch_config(conf))

    def weights():
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      mt.init_params(mt.seed_key(SEED), arch))

    def off(params):
        logits, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                                  mode="prefill")
        return float(jnp.linalg.norm(logits[0] - want) / jnp.linalg.norm(want))
    assert off(serve_moe.program_params(weights(), conf, arch)) < 1e-5
    assert off(weights()) > 0.5
