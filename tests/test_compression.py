"""Subprocess wrapper for the 8-device gradient-compression checks."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_compression_collectives_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never a chip
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests/multidev/check_compression.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL OK" in proc.stdout
