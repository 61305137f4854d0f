"""The program's span facility (``repro.obs``): nesting, ids, the ring's
bound, compiles traced to their step, and the spans one engine tick
writes."""

import collections

import jax
import jax.numpy as jnp
import pytest

from repro import configs, obs
from repro.models.model import make_model
from repro.parallel.afd import AFDRuntime
from repro.serving.afd_engine import AFDServeEngine
from repro.serving.workload import ArrivalEvent


def _since(mark):
    """Spans recorded after ``mark`` (a sid), oldest first."""
    return [s for s in obs.spans() if s.sid > mark]


def _mark():
    with obs.span("test.mark") as m:
        pass
    return m.sid


def test_nesting_parents_and_ids():
    mark = _mark()
    with obs.span("outer", tick=7) as outer:
        with obs.span("mid", mb=1) as mid:
            with obs.span("inner", layer=3):
                pass
        with obs.span("sibling"):
            pass
    got = {s.name: s for s in _since(mark)}
    assert [s.name for s in _since(mark)] == ["inner", "mid", "sibling",
                                              "outer"]
    assert got["outer"].parent is None
    assert got["mid"].parent == outer.sid == got["sibling"].parent
    assert got["inner"].parent == mid.sid
    # own ids over the enclosing span's: the tick reaches every descendant
    assert got["outer"].ids == {"tick": 7}
    assert got["mid"].ids == {"tick": 7, "mb": 1}
    assert got["inner"].ids == {"tick": 7, "mb": 1, "layer": 3}
    assert got["sibling"].ids == {"tick": 7}
    for s in got.values():
        assert s.t0_ns <= s.t1_ns
    assert got["outer"].t0_ns <= got["mid"].t0_ns <= got["inner"].t0_ns
    assert got["inner"].t1_ns <= got["mid"].t1_ns <= got["sibling"].t0_ns
    assert got["sibling"].t1_ns <= got["outer"].t1_ns


def test_span_is_recorded_when_its_body_raises():
    mark = _mark()
    with pytest.raises(ValueError):
        with obs.span("failing", tick=1):
            raise ValueError("boom")
    with obs.span("after"):
        pass
    assert [(s.name, s.parent) for s in _since(mark)] == [("failing", None),
                                                          ("after", None)]


def test_ring_is_bounded():
    assert obs._ring.maxlen == obs.RING_SPANS
    first = _mark()
    for i in range(obs.RING_SPANS + 10):
        with obs.span("fill", i=i):
            pass
    kept = obs.spans()
    assert len(kept) == obs.RING_SPANS
    assert kept[0].sid > first
    assert kept[0].ids == {"i": 10}
    assert kept[-1].ids == {"i": obs.RING_SPANS + 9}


def test_compile_is_traced_to_the_open_span():
    mark = _mark()
    with obs.span("engine.step", tick=4) as step:
        # a function JAX has never seen, so it must be compiled here
        jax.jit(lambda x: x * 3.0 + 0.123456)(jnp.ones(5)).block_until_ready()
    compiles = [s for s in _since(mark) if s.name == "jax.compile"]
    assert compiles
    assert all(s.parent == step.sid for s in compiles)
    assert all(s.ids["tick"] == 4 and s.ids["program"] for s in compiles)
    outer = [s for s in _since(mark) if s.name == "engine.step"][0]
    assert all(outer.t0_ns <= s.t0_ns and s.t1_ns <= outer.t1_ns
               for s in compiles)


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "kimi-k2-1t-a32b"])
def test_one_engine_tick_writes_one_span_per_unit_of_work(arch):
    cfg = configs.get_smoke_config(arch)
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    rt = AFDRuntime(cfg, params, [dev], [dev])
    n_bo, mb_slots = 2, 2
    eng = AFDServeEngine(rt, max_len=32, n_bo=n_bo, mb_slots=mb_slots,
                         tick_seconds=0.01, prefill_chunk=4)
    for rid in range(n_bo * mb_slots):
        eng.submit(ArrivalEvent(rid=rid, t=0.0, prompt_len=3 + rid,
                                max_new_tokens=16))
    while eng.decode_live_count() < eng.total_slots:
        eng.tick()
    tick = eng.stats.engine_ticks
    mark = _mark()
    eng.tick()
    spans = _since(mark)
    counts = collections.Counter(s.name for s in spans)
    n_layers = len(rt.specs)
    n_moe = sum(1 for s in rt.specs if s.moe)
    live = eng.decode_live_count()
    assert live == n_bo * mb_slots
    assert counts["engine.tick"] == counts["engine.decode"] == 1
    assert counts["engine.admit"] == counts["engine.sample"] == 1
    assert counts["engine.prefill"] == 0
    assert counts["afd.embed"] == counts["afd.head"] == n_bo
    assert counts["afd.attn"] == n_bo * n_layers
    for name in ("afd.route", "afd.dispatch", "afd.ffn", "afd.combine"):
        assert counts[name] == n_bo * n_moe, name
    assert counts["afd.dense_ffn"] == n_bo * (n_layers - n_moe)
    assert counts["engine.host_sync"] == n_bo + live
    assert all(s.ids["tick"] == tick for s in spans)
    by_sid = {s.sid: s for s in spans}
    for s in spans:
        if s.name.startswith("afd."):
            assert by_sid[s.parent].name == "engine.decode"
            assert {"mb"} <= set(s.ids)
        if s.name == "engine.host_sync":
            assert by_sid[s.parent].name == "engine.sample"
    rows = {s.ids["rows"] for s in spans if s.name == "afd.dispatch"}
    assert rows == {mb_slots}
    assert {s.ids["layer"] for s in spans if s.name == "afd.attn"} == set(
        range(n_layers))
