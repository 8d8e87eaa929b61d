"""``chip_smoke.py`` is the chip run and nothing else: off a TPU it fails
before it builds anything, and the toy-size CPU rehearsal exists only
behind an explicit argument and never prints a result line."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one device, as the driver runs it
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_without_a_tpu_it_fails_before_building_anything():
    proc = _run(timeout=120)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    # it named the device it found, then stopped: no phase, no result
    assert "platform=cpu" in proc.stdout
    assert "[kernels]" not in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_phase_and_prints_no_result():
    proc = _run("--rehearse-cpu", timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for phase in ("kernels", "head", "train", "serve"):
        assert f"[{phase}] passed" in proc.stdout
    assert '"ok"' not in proc.stdout
