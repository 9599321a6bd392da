"""The documented surface, executed: each walkthrough under ``examples/``
runs to its end as a fresh process, and the served path's imports stay
inside the runtime's own packages."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

#: Model-stack packages, removed from the runtime, that the served path
#: must not load again.
LLM_PACKAGES = {f"repro.{p}" for p in ("models", "configs", "launch",
                                       "distributed", "optim", "data")}


def _run(args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("example", ["quickstart.py",
                                     "multi_host_checkpoint.py",
                                     "serve_combining.py"])
def test_example_runs(example):
    proc = _run([f"examples/{example}"], timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_served_path_loads_no_pallas():
    """The kernel package imports nothing of its own, so the runtime
    and its round kernels load neither Pallas nor any model code."""
    proc = _run(["-c", "import sys, repro.api, repro.kernels.vector_rounds;"
                 " print('\\n'.join(sys.modules))"], timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    loaded = proc.stdout.split()
    assert "repro.kernels.vector_rounds" in loaded
    stray = [m for m in loaded
             if m.startswith("jax.experimental.pallas")
             or ".".join(m.split(".")[:2]) in LLM_PACKAGES]
    assert not stray, stray
