"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-moe-1b-a400m --steps 300 --batch 8 --seq 128 \
        --preset 100m --ckpt-dir /tmp/ckpt

Presets:
  smoke — the arch's reduced smoke config (seconds on CPU)
  100m  — a ~100M-parameter member of the same family (the task brief's
          end-to-end driver scale)
  full  — the published config (use under the production mesh on real HW)

Resumes automatically from the newest committed checkpoint in --ckpt-dir;
kill the process mid-run and rerun the same command to exercise the
restart path (bitwise-deterministic thanks to the (seed, step) data
stream).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro import configs
from repro.launch.cache import use_compile_cache
from repro.models.model import make_model
from repro.training import checkpoint as ckpt_mod
from repro.training import data as data_mod
from repro.training import optimizer as opt_mod
from repro.training.train import TrainConfig, make_train_step


def preset_config(arch: str, preset: str):
    if preset == "smoke":
        return configs.get_smoke_config(arch)
    if preset == "full":
        return configs.get_config(arch)
    # ~100M-parameter family member: scale the smoke config up
    cfg = configs.get_config(arch)
    return dataclasses.replace(
        cfg,
        n_layers=min(cfg.n_layers, 8),
        d_model=512,
        n_heads=8 if cfg.n_heads else 0,
        n_kv_heads=min(8, cfg.n_kv_heads) if cfg.n_kv_heads else 0,
        d_head=64 if cfg.n_heads else 0,
        d_ff=2048 if cfg.d_ff else 0,
        moe_d_ff=512 if cfg.is_moe else 0,
        n_experts=min(cfg.n_experts, 8),
        top_k=min(cfg.top_k, 2) if cfg.is_moe else 0,
        shared_d_ff=512 if cfg.n_shared_experts else 0,
        ssm_head_dim=64 if cfg.ssm_state else 0,
        ssm_state=min(cfg.ssm_state, 64) if cfg.ssm_state else 0,
        vocab_size=min(cfg.vocab_size, 32768),
        n_encoder_layers=min(cfg.n_encoder_layers, 4),
        encoder_seq=min(cfg.encoder_seq, 128) if cfg.encoder_seq else 0,
        vision_seq=min(cfg.vision_seq, 32) if cfg.vision_seq else 0,
        dtype="float32", param_dtype="float32",
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="100m",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = preset_config(args.arch, args.preset)
    model = make_model(cfg)
    print(f"arch={cfg.name} preset={args.preset} "
          f"params≈{cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")

    params = model.init(jax.random.PRNGKey(args.seed))
    opt = opt_mod.adamw(lr=args.lr)
    opt_state = opt.init(params)
    dc = data_mod.DataConfig(batch_size=args.batch, seq_len=args.seq,
                             vocab_size=cfg.vocab_size, seed=args.seed)
    step_fn = make_train_step(model, opt, TrainConfig(args.grad_accum),
                              donate=False)

    start = 0
    ck = None
    if args.ckpt_dir:
        ck = ckpt_mod.AsyncCheckpointer(args.ckpt_dir, keep=3)
        restored = ckpt_mod.restore_latest(args.ckpt_dir, params, opt_state)
        if restored is not None:
            start, params, opt_state, _ = restored
            print(f"resumed from step {start}")

    t0 = time.time()
    tokens = 0
    for step in range(start, args.steps):
        batch = data_mod.make_batch(dc, step, cfg)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        tokens += args.batch * args.seq
        if (step + 1) % args.log_every == 0 or step == start:
            dt = time.time() - t0
            print(f"step {step+1:5d}  loss {float(metrics['loss']):.4f}  "
                  f"ce {float(metrics['ce']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}  "
                  f"{tokens/max(dt,1e-9):.0f} tok/s", flush=True)
        if ck and (step + 1) % args.ckpt_every == 0:
            ck.save(step + 1, params, opt_state)
    if ck:
        ck.save(args.steps, params, opt_state)
        ck.wait()
    print(f"done: {args.steps - start} steps in {time.time()-t0:.1f}s; "
          f"entropy floor ≈ {data_mod.entropy_floor(dc):.3f} nats")


if __name__ == "__main__":
    main()
