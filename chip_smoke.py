#!/usr/bin/env python3
"""Serve granite-moe-1b-a400m at its published widths on a TPU through the
two-role AFD path, and check what comes out.

    python chip_smoke.py              # one chip: both roles colocated
    python chip_smoke.py --chips 4    # A role on 2 chips, F role on 2

One process drives every chip; it starts no other. Weights are random,
drawn from ``--seed``. Phases on one chip:

  kernels   each Pallas kernel on the served path (plain and fused grouped
            GEMM, flash prefill whole and chunked) against its
            ``kernels/ref.py`` oracle at the model's shapes;
  serve     ``repro serve-traffic`` at ``--preset full`` on a seeded
            profile: wall-clock ticks, chunked prefill, the measured M2N
            bytes asserted equal to the Eq. 9/17 prediction;
  compare   the served greedy tokens against the single-program ``Model``
            path (``DecodeEngine``) for the same prompts;
  prefill   one 512-token prompt through ``AFDRuntime.prefill`` in
            128-token chunks, logits against ``Model.prefill``.

With ``--chips 4`` only the AFD path across chips runs: the F role's
compiled program is checked for all-gathers of expert weights, then serve
and compare (the single-program reference on one chip of this process).

Earlier stdout lines report each check with its tolerance, compile and
wall seconds, tokens served and peak device memory; the last line is one
JSON object ``{"ok": ..., "device": {"platform", "kind", "count"}}``. The
exit code is 0 only if every phase passed on a TPU. Details are written
to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")

ARCH = "granite-moe-1b-a400m"
PROFILE = "poisson-steady"
MAX_REQUESTS = 8
MAX_LEN = 1024
PREFILL_CHUNK = 128
LONG_PROMPT = 512

# Tolerances. Kernel outputs are bf16, so one rounding of the result is
# 2^-9 of its magnitude; 1e-2 of the largest reference magnitude leaves
# room for accumulation order and stays far below any indexing fault (an
# error of the order of the values themselves).
KERNEL_TOL = 1e-2
# Served vs single-program: both run the model in bf16 by different
# schedules (chunked flash prefill and M2N cycles vs one scanned program),
# so greedy tokens can part at a near-tie. A served token is accepted
# where it equals the reference's, or where the reference's logit for it
# is within NEAR_TIE of the reference maximum, in units of the standard
# deviation of that logit row (a token of a wrong model sits ~4 deviations
# below the maximum of 49k logits).
NEAR_TIE = 0.25
# 512-token chunked prefill: relative L2 error of the last position's
# logits against the single-program prefill.
PREFILL_LOGIT_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Runs the phases, keeps their records, and never stops at a failure
    without recording it."""

    def __init__(self):
        self.records: dict = {}
        self.failed: list = []
        self.compile_s = 0.0

    def phase(self, name: str, fn) -> None:
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            rec = fn() or {}
            ok = rec.pop("ok", True)
        except Exception:
            traceback.print_exc()
            rec, ok = {"error": traceback.format_exc(limit=3)}, False
        rec["wall_s"] = time.perf_counter() - t0
        rec["ok"] = ok
        self.records[name] = rec
        if not ok:
            self.failed.append(name)
        log(f"{name}: {'PASS' if ok else 'FAIL'} wall_s={rec['wall_s']:.2f}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _rel_err(out, ref) -> float:
    import jax.numpy as jnp
    out, ref = jnp.asarray(out, jnp.float32), jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) /
                 jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def kernels_phase(cfg, seed: int) -> dict:
    """Each kernel on the served path against its oracle, at the model's
    shapes: a decode batch of 8 tokens and a 128-token prefill chunk."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_prefill import flash_prefill_pallas
    from repro.kernels.grouped_gemm import grouped_gemm_pallas
    from repro.models.moe import sort_by_expert

    e, d, m, k = cfg.n_experts, cfg.d_model, cfg.moe_d_ff, cfg.top_k
    dt = cfg.compute_dtype
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    wi = normal((e, d, 2 * m), d ** -0.5)
    wo = normal((e, m, d), m ** -0.5)
    checks = []

    def check(name, out, oracle):
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = _rel_err(out, want)
        ok = err <= KERNEL_TOL
        checks.append({"kernel": name, "max_err_rel": err, "ok": ok})
        log(f"kernel {name}: max|out-ref|/max|ref|={err:.3e} "
            f"tol={KERNEL_TOL:g} {'ok' if ok else 'FAIL'}")

    for n in (8, PREFILL_CHUNK):
        x = normal((n, d))
        _, topi = jax.lax.top_k(jax.random.normal(next(keys), (n, e)), k)
        sort_idx, _, gs = sort_by_expert(topi, e)
        rows = sort_idx // k
        xs = jnp.take(x, rows, axis=0)
        check(f"grouped_gemm plain wi tokens={n}",
              grouped_gemm_pallas(xs, wi, gs),
              lambda: ref.grouped_gemm_ref(xs, wi, gs))
        check(f"grouped_gemm row_index wi tokens={n}",
              grouped_gemm_pallas(x, wi, gs, row_index=rows),
              lambda: ref.grouped_gemm_fused_ref(x, wi, gs, row_index=rows))
        h = normal((n * k, m))
        check(f"grouped_gemm out_index wo tokens={n}",
              grouped_gemm_pallas(h, wo, gs, out_index=sort_idx,
                                  out_rows=n * k),
              lambda: ref.grouped_gemm_fused_ref(
                  h, wo, gs, out_index=sort_idx, out_rows=n * k))

    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s = LONG_PROMPT
    q, kk, vv = (normal((1, s, h, dh)) for h in (hq, hkv, hkv))
    check(f"flash_prefill whole S={s}", flash_prefill_pallas(q, kk, vv),
          lambda: ref.flash_prefill_ref(q, kk, vv))
    qc = normal((2, PREFILL_CHUNK, hq, dh))
    kc, vc = normal((2, MAX_LEN, hkv, dh)), normal((2, MAX_LEN, hkv, dh))
    off = jnp.asarray([PREFILL_CHUNK, 3 * PREFILL_CHUNK], jnp.int32)
    tv = off + PREFILL_CHUNK
    check(f"flash_prefill chunk C={PREFILL_CHUNK} offsets={off.tolist()}",
          flash_prefill_pallas(qc, kc, vc, q_offset=off, t_valid=tv),
          lambda: ref.flash_prefill_ref(qc, kc, vc, q_offset=off,
                                        t_valid=tv))
    return {"checks": checks, "ok": all(c["ok"] for c in checks)}


def serve_phase(preset: str, seed: int) -> dict:
    """``repro serve-traffic`` end to end at the preset's widths."""
    from repro.api import cli

    path = os.path.join(OUT_DIR, "chip_smoke_serve.json")
    argv = ["serve-traffic", "--profile", PROFILE, "--arch", ARCH,
            "--preset", preset, "--hardware", "TPUv5e", "--seed", str(seed),
            "--max-requests", str(MAX_REQUESTS), "--max-len", str(MAX_LEN),
            "--n-bo", "2", "--mb-slots", "4", "--tick-ms", "0",
            "--policy", "off", "--prefill-chunk", str(PREFILL_CHUNK),
            "--json", path]
    log("repro " + " ".join(argv))
    rc = cli.main(argv)
    with open(path) as fh:
        doc = json.load(fh)
    s = doc["summary"]
    log(f"serve: rc={rc} completed={s['completed']}/{s['arrivals']} "
        f"tokens_out={s['tokens_out']} decode_ticks={s['decode_ticks']} "
        f"prefill_chunks={s['prefill_chunks']} "
        f"bytes_match_all={s['bytes_match_all']} "
        f"dispatch_bytes={s['dispatch_bytes']} "
        f"combine_bytes={s['combine_bytes']} wall_s={s['wall_s']:.2f}")
    ok = (rc == 0 and s["bytes_match_all"] and s["completed"] == MAX_REQUESTS
          == s["arrivals"])
    return {"summary": s, "requests": doc["requests"], "ok": ok}


def _near_tie(logits, token: int) -> float:
    """Reference max minus the reference logit of ``token``, in standard
    deviations of the row."""
    import jax.numpy as jnp
    row = jnp.asarray(logits, jnp.float32)
    return float((jnp.max(row) - row[token]) / jnp.std(row))


def compare_phase(cfg, seed: int, served: list) -> dict:
    """Greedy tokens of the single-program path for the served prompts."""
    import jax
    import numpy as np

    from repro.models.model import make_model
    from repro.serving.engine import DecodeEngine, Request

    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    eng = DecodeEngine(model, params, n_slots=len(served), max_len=MAX_LEN)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                 max_len=MAX_LEN))
    reqs = [Request(rid=r["rid"], prompt=np.asarray(r["prompt"], np.int32),
                    max_new_tokens=len(r["output"])) for r in served]
    for r in reqs:
        eng.submit(r)
    eng.run()
    rows, ok = [], True
    for r, want in zip(served, reqs):
        got, ref = r["output"], want.output
        i = next((j for j, (a, b) in enumerate(zip(got, ref)) if a != b),
                 None)
        row = {"rid": r["rid"], "prompt_len": len(r["prompt"]),
               "tokens": len(got), "agree_prefix": len(got) if i is None
               else i}
        if i is not None:
            # teacher-force the served prefix through the single-program
            # prefill: the reference logits at the first parting position
            toks = np.asarray(list(r["prompt"]) + list(got[:i]), np.int32)
            logits, _ = prefill(params, toks[None])
            row["gap_std"] = _near_tie(logits[0], got[i])
            row["near_tie"] = row["gap_std"] <= NEAR_TIE
            ok &= row["near_tie"]
        rows.append(row)
        log(f"compare rid={row['rid']}: {row['agree_prefix']}/{len(got)} "
            "tokens equal" + ("" if i is None else
                              f", parts at a gap of {row['gap_std']:.3f} std "
                              f"(near-tie tol {NEAR_TIE}) "
                              f"{'ok' if row['near_tie'] else 'FAIL'}"))
    exact = sum(r["agree_prefix"] == r["tokens"] for r in rows)
    log(f"compare: {exact}/{len(rows)} requests token-identical to the "
        f"single-program path; the rest part at near-ties: {ok}")
    return {"requests": rows, "exact": exact, "ok": ok}


def prefill_phase(cfg, seed: int) -> dict:
    """One long prompt in chunks through the AFD runtime vs one
    single-program prefill."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import make_model
    from repro.parallel.afd import AFDRuntime, role_devices

    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, LONG_PROMPT),
                              1, cfg.vocab_size, jnp.int32)
    want, _ = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                 max_len=MAX_LEN))(params,
                                                                   toks)
    rt = AFDRuntime(cfg, params, *role_devices(jax.devices()[:1]))
    caches, pos = rt.init_cache(1, MAX_LEN)
    logits, _, pos = rt.prefill(toks, caches, pos, chunk=PREFILL_CHUNK)
    got = logits[0, -1].astype(jnp.float32)
    want = want[0].astype(jnp.float32)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    gap = _near_tie(want, int(jnp.argmax(got)))
    ok = (rel <= PREFILL_LOGIT_TOL and gap <= NEAR_TIE
          and int(pos[0]) == LONG_PROMPT)
    log(f"prefill {LONG_PROMPT} tokens in {PREFILL_CHUNK}-token chunks: "
        f"logits rel L2 err={rel:.3e} (tol {PREFILL_LOGIT_TOL:g}), "
        f"argmax gap={gap:.3f} std (tol {NEAR_TIE}), "
        f"M2N cycles={rt.stats.dispatches} {'ok' if ok else 'FAIL'}")
    return {"logits_rel_l2": rel, "argmax_gap_std": gap,
            "dispatches": rt.stats.dispatches, "ok": ok}


def hlo_phase(cfg) -> dict:
    """Compile the F role's expert program on the F chips and read its HLO:
    expert weights must stay sharded (no all-gather)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.parallel.afd import make_expert_ffn, role_devices

    _, f_dev = role_devices(jax.devices())
    mesh = Mesh(np.array(f_dev), ("expert",))
    e, d, m, k = cfg.n_experts, cfg.d_model, cfg.moe_d_ff, cfg.top_k
    dt, n = cfg.compute_dtype, 16

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    hlo = make_expert_ffn(cfg, mesh).lower(
        arg((e, d, 2 * m), dt, P("expert")), arg((e, m, d), dt, P("expert")),
        arg((n, d), dt, P()), arg((n, k), jnp.float32, P()),
        arg((n, k), jnp.int32, P())).compile().as_text()
    counts = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
              for op in ("all-gather", "all-reduce", "all-to-all")}
    kernels = hlo.count("tpu_custom_call")
    ok = counts["all-gather"] == 0 and kernels > 0
    log(f"F-role program on {len(f_dev)} chips ({e // len(f_dev)} experts "
        f"each): collectives={counts} pallas_calls={kernels} "
        f"{'ok' if ok else 'FAIL: expert weights all-gathered'}")
    return {"collectives": counts, "pallas_calls": kernels, "ok": ok}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run(preset: str, seed: int, chips: int) -> Smoke:
    import jax

    from repro.launch.cache import use_compile_cache
    from repro.launch.train import preset_config

    cache = use_compile_cache()
    smoke = Smoke()

    def on_event(event, duration, *args, **kwargs):
        if event.startswith("/jax/core/compile/"):
            smoke.compile_s += duration
    jax.monitoring.register_event_duration_secs_listener(on_event)

    cfg = preset_config(ARCH, preset)
    log(f"config: {cfg.name} preset={preset} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"experts={cfg.n_experts} top_k={cfg.top_k} "
        f"moe_d_ff={cfg.moe_d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype} params={cfg.param_count() / 1e9:.3f}e9")
    log(f"compile cache: {cache}")
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    served: list = []

    def serve():
        rec = serve_phase(preset, seed)
        served.extend(rec.pop("requests"))
        return rec

    if chips == 1:
        smoke.phase("kernels", lambda: kernels_phase(cfg, seed))
    else:
        smoke.phase("f_role_hlo", lambda: hlo_phase(cfg))
    smoke.phase("serve", serve)
    smoke.phase("compare", lambda: compare_phase(cfg, seed, served)
                if served else {"ok": False, "error": "nothing served"})
    if chips == 1:
        smoke.phase("prefill", lambda: prefill_phase(cfg, seed))

    wall = time.perf_counter() - t0
    peaks = [dev.memory_stats().get("peak_bytes_in_use")
             if dev.memory_stats() else None for dev in jax.devices()]
    served_tokens = sum(len(r["output"]) for r in served)
    log(f"totals: compile_s={smoke.compile_s:.2f} wall_s={wall:.2f} "
        f"tokens_served={served_tokens} peak_bytes_in_use={peaks} "
        f"failed={smoke.failed}")
    smoke.records["totals"] = {"compile_s": smoke.compile_s, "wall_s": wall,
                               "tokens_served": served_tokens,
                               "peak_bytes_in_use": peaks}
    return smoke


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the AFD path across four chips "
                         "(A on 2, F on 2) and its single-program reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    smoke = run("full", args.seed, args.chips)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as fh:
        json.dump({"device": device, "chips": args.chips,
                   "records": smoke.records}, fh, indent=1, default=str)
    ok = not smoke.failed
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
