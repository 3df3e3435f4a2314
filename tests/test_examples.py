"""The examples/ scripts must RUN — an example that drifts from the
API is worse than none. Each runs in a subprocess at its documented
invocation (CPU), pinned by its final marker."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # the repo must be importable from the script subprocess, and
    # nothing else: the examples document plain `python examples/...`
    # on a clean machine
    env["PYTHONPATH"] = ROOT
    env["SPARKDL_TPU_WORKER_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=ROOT,
    )


def test_train_llama_lora_pjit():
    r = _run("train_llama_lora_pjit.py")
    assert r.returncode == 0, r.stderr[-800:]
    assert "DONE" in r.stdout and "step 4 loss" in r.stdout


def test_serve_continuous_batching():
    r = _run("serve_continuous_batching.py")
    assert r.returncode == 0, r.stderr[-800:]
    assert "DONE" in r.stdout and "acceptance=" in r.stdout


@pytest.mark.gang
def test_horovod_runner_mnist_local_mode():
    r = _run("horovod_runner_mnist.py", "-1")
    assert r.returncode == 0, r.stderr[-800:]
    assert "RESULT:" in r.stdout and "'size': 1" in r.stdout
