"""CLI driver smoke tests (launch.train / launch.serve): end-to-end run,
checkpoint resume, and the serving failure drill — via subprocess so each
driver sees a fresh jax."""

import os
import subprocess
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=300, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                         text=True, env=env, timeout=timeout, cwd=ROOT)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_train_driver_runs_and_resumes():
    with tempfile.TemporaryDirectory() as ckpt:
        out1 = _run(["repro.launch.train", "--arch", "qwen1.5-0.5b",
                     "--preset", "smoke", "--steps", "12", "--batch", "4",
                     "--seq", "32", "--ckpt-dir", ckpt, "--ckpt-every", "6",
                     "--log-every", "6"])
        assert "done: 12 steps" in out1
        out2 = _run(["repro.launch.train", "--arch", "qwen1.5-0.5b",
                     "--preset", "smoke", "--steps", "18", "--batch", "4",
                     "--seq", "32", "--ckpt-dir", ckpt, "--log-every", "6"])
        assert "resumed from step 12" in out2
        assert "done: 6 steps" in out2


def test_serve_driver_ep_with_failure_drill():
    # max_new long enough that both slots are mid-generation at tick 2;
    # losing 25% of 2 slots drains ceil(0.5) = 1 (the other survives)
    out = _run(["repro.launch.serve", "--arch", "granite-moe-1b-a400m",
                "--preset", "smoke", "--requests", "4", "--slots", "2",
                "--max-new", "8", "--fail-at", "2"])
    assert "simulated node failure" in out
    assert "requeued=1" in out
    assert "σ̂=" in out


def test_serve_driver_afd_two_role():
    out = _run(["repro.launch.serve", "--arch", "granite-moe-1b-a400m",
                "--preset", "smoke", "--mode", "afd", "--max-new", "3",
                "--slots", "2"],
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "M2N traffic" in out
    assert "AFD: 3 steps" in out


def test_serve_driver_afd_one_device():
    """On one device the A and F roles colocate."""
    out = _run(["repro.launch.serve", "--arch", "granite-moe-1b-a400m",
                "--preset", "smoke", "--mode", "afd", "--max-new", "3",
                "--slots", "2"])
    assert "M2N traffic" in out
    assert "AFD: 3 steps" in out
