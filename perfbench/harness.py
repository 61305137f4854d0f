"""One run of one benchmark cell: set-up, a measured window of whole engine
ticks, optional tracing, and the check that decides ``correct``.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<name>.json``, with its plain reference ``configs/<reference>.py``
and the way the program serves it, ``configs/<served_by>.py``, beside it)
under a traffic mix (``traffic/<name>.json``), with the limits of its check
in ``limits/<cell>.json``. Per-layer metrics are readers in
``metrics/<metric>.py``. Everything is found by name; nothing here names a
cell, a configuration or a mix.

The timed entry is ``AFDServeEngine.tick()``, driven here one tick at a time
with ``tick_seconds=None``. The host clock is read before the first timed
tick and after each tick, once the tick's outputs are ready. The window
closes at the end of the first tick that ends at or after ``--seconds``
(or, where ``serve`` is given ``n_ticks``, after that many ticks).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".trace")

if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import arith, tracereduce  # noqa: E402
from perfbench.traffic import generator  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

def _load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict                  # the configuration file's contents
    traffic: Dict                 # the traffic mix's parameters
    limits: Dict                  # limits/<cell>.json
    end_to_end: List[Dict]        # BENCHMARK.json metric entries this cell reports
    per_layer: List[Dict]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"] if workload in m["workloads"]]
    return Cell(name=workload, chips=int(w["chips"]),
                config=_load_json(os.path.join(root, conf["file"])),
                traffic=generator.load(w["traffic"]),
                limits=_load_json(os.path.join(BENCH, "limits",
                                               f"{workload}.json")),
                end_to_end=e2e, per_layer=per_layer)


def reference_module(config: Dict):
    return importlib.import_module(f"perfbench.configs.{config['reference']}")


def served_by(config: Dict):
    return importlib.import_module(f"perfbench.configs.{config['served_by']}")


# ---------------------------------------------------------------------------
# JAX and the chip
# ---------------------------------------------------------------------------

def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    keeping every program however fast it compiled, so that only a cell's
    first run in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chip_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_record(devices) -> Dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs JAX lowers (a compile or a persistent-cache load)
    while ``active``."""

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def check_layout(cfg, params) -> None:
    """The reference's parameter layout must be the program's."""
    import jax
    from repro.models.model import Model

    def sig(tree):
        return [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]
    want = sig(jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0)))
    have = sig(params)
    if want != have:
        diff = sorted(set(want) ^ set(have))[:6]
        raise ValueError(f"parameter layout differs from the program's: {diff}")


def _annotated(fn: Callable, name: str) -> Callable:
    import jax

    def wrapper(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapper


@dataclasses.dataclass
class Tick:
    t0: float                     # host clock at the tick's start (s)
    t1: float                     # ... once its outputs are ready
    decode_tokens: int            # tokens decoded (one per live slot)
    first_tokens: int             # first tokens of prompts that finished
    prefill_tokens: int           # prompt tokens prefilled in the tick


@dataclasses.dataclass
class Served:
    """What one run served: its ticks, inter-token gaps, the requests, and
    the state the check needs."""
    ticks: List[Tick]
    gaps: List[float]             # seconds, every gap that ended in the window
    requests: Dict[int, object]   # rid -> the engine's ServeRequest
    decode_ticks: int             # ticks that decoded, set-up included
    prefill_tokens: int           # prompt tokens prefilled, set-up included
    measured_bytes: tuple         # (dispatch, combine) from the runtime
    compiles: int
    tokens_at_open: Dict[int, int]    # rid -> tokens it had at window open
    attempted: int                # requests given a token in the window
    failed: int                   # decoding requests a window tick skipped
    t_open: float
    t_close: float
    trace: Optional[tracereduce.Trace] = None


def serve(cell: Cell, seed: int, seconds: float, devices, *,
          trace: bool = False, compiles: Optional[CompileCounter] = None,
          n_ticks: Optional[int] = None) -> Served:
    """Build the system from the seed, fill it, warm it, and time whole
    ticks for ``seconds``. With ``n_ticks`` the window is that many ticks
    and the clock does not close it, so that what it serves depends on the
    seed alone, not on the machine's speed (the CPU tests' tiny cell).
    Leaves no device state behind when it returns."""
    import jax
    from repro.parallel.afd import AFDRuntime, role_devices
    from repro.serving.afd_engine import AFDServeEngine, ServeRequest
    from repro.serving.scheduler import ChunkedPrefillPolicy

    mix, conf = cell.traffic, cell.config
    ref, how = reference_module(conf), served_by(conf)
    arch = ref.Arch.from_config(conf)
    cfg = how.arch_config(conf)
    params = how.program_params(ref.init_params(ref.seed_key(seed), arch),
                                conf, arch)
    check_layout(cfg, params)
    a_devs, f_devs = role_devices(devices)
    rt = AFDRuntime(cfg, params, a_devs, f_devs)
    del params
    rt.decode_step_3bo = _annotated(rt.decode_step_3bo, "bench.decode_step_3bo")
    rt.prefill = _annotated(rt.prefill, "bench.prefill")

    eng = AFDServeEngine(
        rt, max_len=int(mix["max_len"]), n_bo=int(mix["n_bo"]),
        mb_slots=int(mix["mb_slots"]), tick_seconds=None,
        window_ticks=1 << 62, prefill_policy=ChunkedPrefillPolicy(
            int(mix["prefill_chunk"]), int(mix["prefill_chunks_per_tick"])))
    pending = list(generator.requests(mix, seed, arch.vocab))[::-1]
    reqs: Dict[int, object] = {}
    seen: Dict[int, list] = {}        # rid -> [tokens seen, time of the last]
    n_done = [0]

    def top_up():
        while pending and eng.live_count() + len(eng.queue) < eng.total_slots:
            r = pending.pop()
            sr = ServeRequest(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens,
                              t_arrive=eng.now)
            reqs[r.rid] = sr
            eng.queue.append(sr)

    def state():
        return [(mb.caches, mb.pos) for mb in eng.mbs]

    gaps: List[float] = []
    window = [False]
    served_in_window, stalled = set(), set()

    def step() -> Tick:
        top_up()
        decoding = [(r, len(r.output)) for r in eng.live_requests()
                    if r.output and not r.done]
        p0 = eng.stats.prefill_tokens
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.tick"):
            eng.tick()
            jax.block_until_ready(state())
        t1 = time.perf_counter()
        dec = first = 0
        active = eng.live_requests() + eng.completed[n_done[0]:]
        n_done[0] = len(eng.completed)
        for r in active:
            n = len(r.output)
            got, last = seen.setdefault(r.rid, [0, None])
            if n <= got:
                continue
            if got == 0:
                first += 1
                dec += n - 1
            else:
                dec += n - got
                if window[0]:
                    gaps.append(t1 - last)
            if window[0]:
                served_in_window.add(r.rid)
            seen[r.rid] = [n, t1]
        if window[0]:
            stalled.update(r.rid for r, n in decoding if len(r.output) == n)
        return Tick(t0, t1, dec, first, eng.stats.prefill_tokens - p0)

    # Set-up: fill every slot, then warm the window's own shapes.
    setup_ticks = []
    while eng.decode_live_count() < eng.total_slots or eng.queue:
        setup_ticks.append(step())
        if len(setup_ticks) > 10 * eng.total_slots:
            raise RuntimeError("set-up could not fill the slots")
    for _ in range(int(mix["warm_ticks"])):
        setup_ticks.append(step())

    gc.collect()
    gc.freeze()             # the window's collections skip set-up's objects
    log_dir = None
    if trace:
        log_dir = TRACE_DIR
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    if compiles is not None:
        compiles.count, compiles.active = 0, True
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    t_open = time.perf_counter()
    at_open = {rid: s[0] for rid, s in seen.items()}
    for s in seen.values():
        s[1] = t_open
    window[0] = True
    ticks = []
    while True:
        ticks.append(step())
        if (len(ticks) >= n_ticks if n_ticks is not None
                else ticks[-1].t1 - t_open >= seconds):
            break
    t_close = ticks[-1].t1
    span.__exit__(None, None, None)
    gc.unfreeze()
    if compiles is not None:
        compiles.active = False
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = tracereduce.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    admitted = eng.live_requests() + eng.completed
    prefilled = (sum(len(r.prompt) for r in admitted)
                 - eng.prefill_backlog_tokens())
    # The annotated methods hold the runtime in a reference cycle: break it
    # so that the weights and caches are freed when this returns.
    del rt.decode_step_3bo, rt.prefill
    out = Served(
        ticks=ticks, gaps=gaps,
        requests={rid: r for rid, r in reqs.items() if r.output},
        decode_ticks=sum(1 for t in setup_ticks + ticks if t.decode_tokens),
        prefill_tokens=prefilled,
        measured_bytes=(rt.stats.dispatch_bytes, rt.stats.combine_bytes),
        compiles=compiles.count if compiles is not None else -1,
        tokens_at_open={rid: at_open.get(rid, 0) for rid, r in reqs.items()
                        if r.output},
        attempted=len(served_in_window | stalled), failed=len(stalled),
        t_open=t_open, t_close=t_close, trace=tr)
    del eng, rt
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

def end_to_end(served: Served, setup_s: float) -> Dict[str, float]:
    span = served.t_close - served.t_open
    toks = sum(t.decode_tokens + t.first_tokens for t in served.ticks)
    out = {"out_tok_s": toks / span, "setup_s": setup_s}
    if served.gaps:
        # numpy's default (linear) percentile
        out["itl_p95_ms"] = float(np.percentile(served.gaps, 95)) * 1e3
    return out


# ---------------------------------------------------------------------------
# The check that decides ``correct``
# ---------------------------------------------------------------------------

def check_sample(served: Served, seed: int, min_tokens: int) -> List[int]:
    """Request ids to compare: the longest served request (prompt plus
    served tokens), then others drawn from the seed until the sample holds
    ``min_tokens`` served tokens."""
    reqs = served.requests
    order = sorted(reqs, key=lambda rid: (-(len(reqs[rid].prompt)
                                            + len(reqs[rid].output)), rid))
    rng = np.random.default_rng(int(seed) + 1)
    rest = [int(x) for x in rng.permutation(order[1:])]
    pick, toks = [], 0
    for rid in [order[0]] + rest:
        pick.append(rid)
        toks += len(reqs[rid].output)
        if toks >= min_tokens:
            break
    return pick


def gap_readings(cell: Cell, served: Served, seed: int,
                 control: bool = False) -> Dict[str, float]:
    """The served tokens of the sampled requests against the reference:
    the widest and the mean gap below the reference's best logit, in
    standard deviations of its row, and the reference's router margin where
    the widest gap lies; with ``control``, the same gaps for the tokens the
    fp8 reference puts first at the same positions."""
    ref = reference_module(cell.config)
    arch = ref.Arch.from_config(cell.config)
    params = ref.init_params(ref.seed_key(seed), arch)
    parts: Dict[str, list] = {}
    for rid in check_sample(served, seed, int(cell.traffic["check_tokens"])):
        r = served.requests[rid]
        g = ref.served_gaps(params, arch, r.prompt, list(r.output),
                            "fp8" if control else "f32")
        for k, v in g.items():
            parts.setdefault(k, []).append(np.asarray(v, np.float64))
    del params
    gaps = {k: np.concatenate(v) for k, v in parts.items()}
    out = {"tokens": float(len(gaps["served"])),
           "margin_at_widest": float(gaps["margin"][np.argmax(gaps["served"])])}
    for k in ("served", "control"):
        if k in gaps:
            out[f"{k}_gap_max"] = float(np.max(gaps[k]))
            out[f"{k}_gap_mean"] = float(np.mean(gaps[k]))
    return out


def check(cell: Cell, served: Served, seed: int, log=None,
          control: bool = False) -> Dict[str, Dict]:
    """Each number compared, beside its limit. Runs after the program's
    state is freed: the reference makes its own weights from the seed. With
    ``control``, the fp8 control's gaps stand in for the served tokens'."""
    conf, mix = cell.config, cell.traffic
    arch = reference_module(conf).Arch.from_config(conf)
    want = arith.m2n_run_bytes(served.decode_ticks, int(mix["n_bo"]),
                               int(mix["mb_slots"]), served.prefill_tokens,
                               arch.n_layers, arch.d_model, arch.top_k,
                               DTYPE_BYTES[conf["torch_dtype"]])
    lim = cell.limits
    out = {
        "m2n_dispatch_bytes_off": {
            "value": abs(served.measured_bytes[0] - want[0]),
            "limit": lim["m2n_bytes_off"]},
        "m2n_combine_bytes_off": {
            "value": abs(served.measured_bytes[1] - want[1]),
            "limit": lim["m2n_bytes_off"]},
    }
    g = gap_readings(cell, served, seed, control)
    if log is not None:
        log("readings " + json.dumps(g), file=sys.stderr)
    out["served_gap_mean_std"] = {
        "value": g["control_gap_mean" if control else "served_gap_mean"],
        "limit": lim["served_gap_mean_std"]}
    served_tokens = sum(len(r.output) for r in served.requests.values())
    out["tokens_compared"] = {"value": g["tokens"],
                              "limit": min(int(mix["check_tokens"]),
                                           served_tokens)}
    return out


def passed(checks: Dict[str, Dict]) -> bool:
    """Every number within its limit: at most the limit, except the count of
    tokens compared, which must reach ``check_tokens`` or every served
    token."""
    ok = True
    for name, c in checks.items():
        if name == "tokens_compared":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    cell: Cell
    served: Served
    arch: object                  # the reference's Arch
    peak: Dict                    # the chip's peaks
    planes: List[str]             # trace planes of the chips used


def read_metric(name: str, view: RunView) -> Optional[float]:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def trace_device_planes(trace: tracereduce.Trace, devices) -> List[str]:
    """The trace planes of the chips this run used, by device id."""
    names = []
    for d in devices:
        want = f"/device:TPU:{d.id}"
        names += [p for p in trace.ops if p == want]
    return names


def breakdown(view: RunView) -> Dict:
    tr, sv = view.served.trace, view.served
    lo, hi = tr.window()
    dev = view.planes[0]
    idle = tracereduce.idle_by_span(tr, dev, lo, hi)
    return {"device_ops": [[n, s] for n, s in
                           tracereduce.top_ops(tr.ops[dev], lo, hi)],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, compiles: Optional[CompileCounter] = None,
        log=print, n_ticks: Optional[int] = None) -> Dict:
    served = serve(cell, seed, seconds, devices, trace=trace,
                   compiles=compiles, n_ticks=n_ticks)
    setup_s = served.t_open - t_start
    device = device_record(devices)
    e2e = end_to_end(served, setup_s)
    names = [m["name"] for m in (cell.per_layer if trace else cell.end_to_end)]
    metrics: Dict[str, Dict] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    extra = {}
    if trace:
        ref = reference_module(cell.config)
        view = RunView(cell=cell, served=served,
                       arch=ref.Arch.from_config(cell.config),
                       peak=arith.peaks(device["kind"]),
                       planes=trace_device_planes(served.trace, devices))
        lo, hi = served.trace.window()
        device["window_s"] = (hi - lo) * 1e-9
        if view.planes:
            busy = [tracereduce.busy_ns(served.trace.ops[p], lo, hi)
                    for p in view.planes]
            device["busy_s"] = float(np.mean(busy)) * 1e-9
            extra["breakdown"] = breakdown(view)
        for n in names:
            v = read_metric(n, view)
            if v is not None:
                metrics[n] = {"value": v, "unit": units[n]}
    else:
        for n in names:
            if n in e2e:
                metrics[n] = {"value": e2e[n], "unit": units[n]}
    served.trace = None
    t_check = time.perf_counter()
    checks = check(cell, served, seed, log)
    log(f"timing setup {setup_s:.1f} s, window {served.t_close - served.t_open:.1f} s "
        f"in {len(served.ticks)} ticks, check {time.perf_counter() - t_check:.1f} s; "
        f"tick ms {[round((t.t1 - t.t0) * 1e3) for t in served.ticks]}",
        file=sys.stderr)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    result = {"correct": passed(checks), "attempted": served.attempted,
              "failed": served.failed,
              "metrics": metrics, "device": device}
    result.update(extra)
    result["checks"] = checks
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    configure_jax()
    try:
        devices = chip_devices(cell.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    arith.peaks(devices[0].device_kind)
    compiles = CompileCounter()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 t_start, compiles)
    print(json.dumps(result), flush=True)
    return 0
