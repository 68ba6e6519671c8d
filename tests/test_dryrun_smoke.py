"""Dry-run machinery smoke test: one small cell end-to-end in a
subprocess (forced 512-device CPU mesh, lower+compile+analyze) — proves
the deliverable pipeline under pytest without re-running the full sweep.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json
from repro.launch.dryrun import run_cell
r = run_cell("qwen2_vl_2b", "decode_32k", multi_pod=False, verbose=False)
assert "error" not in r, r.get("traceback", r)
rl = r["roofline"]
assert rl["memory_term_s"] > 0 and rl["dominant"] in (
    "compute", "memory", "collective")
assert r["collectives"]["num_ops"] >= 0
assert r["memory"]["temp_bytes"] is not None
print("DRYRUN_SMOKE_OK", json.dumps(rl["dominant"]))
"""


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never a chip
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DRYRUN_SMOKE_OK" in proc.stdout
