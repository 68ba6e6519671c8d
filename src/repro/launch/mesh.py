"""Mesh construction, one layout per device count.

Functions only: importing this module never touches jax device state.

  LAYOUTS[4]    (2, 2, 1)     ("pod", "data", "model")  one v5e host (2x2)
  LAYOUTS[256]  (16, 16)      ("data", "model")         single pod
  LAYOUTS[512]  (2, 16, 16)   ("pod", "data", "model")  multi pod

Axis roles: see :mod:`repro.parallel.context`.  A device count with no
layout is an error.  The dry-run launcher forces 512 host devices via
XLA_FLAGS before any jax import and picks the 256 or 512 layout per
cell; everything else (serving, training, tests) sees the real device
count.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.context import ParallelContext

LAYOUTS = {
    4: ((2, 2, 1), ("pod", "data", "model")),
    256: ((16, 16), ("data", "model")),
    512: ((2, 16, 16), ("pod", "data", "model")),
}

# Named sharding/schedule variants (pctx overrides) shared by the
# training launcher and the dry run.  "mw" is the paper-faithful default;
# the rest are §Perf hillclimb levers.  moe_microbatch="plan" derives the
# pipeline chunk count G from the planner's overlap-aware dispatch
# decision for the CELL's workload (batch, fabric, modeled expert
# compute) instead of a hard-coded preset.
VARIANTS = {
    "mw": {},                                   # MultiWrite hierarchical EP
    "auto": {"plan_policy": "auto"},            # planner-chosen schemes
    "baseline": {"moe_scheme": "baseline"},     # unicast EP dispatch
    "nosp": {"seq_parallel": False},            # no sequence parallelism
    "selrem": {"remat": "selective"},           # selective remat
    "nofsdp": {"fsdp": False},                  # pure DP (replicated params)
    # hillclimb combos (§Perf):
    "mwopt": {"moe_deferred_tp_reduce": True,   # deferred expert-TP psum
              "moe_microbatch": "plan"},        # + planned pipeline chunks
    "mwdefer": {"moe_deferred_tp_reduce": True},
    "mwmicro": {"moe_microbatch": "plan"},
    "baseopt": {"moe_scheme": "baseline",
                "moe_deferred_tp_reduce": True, "moe_microbatch": "plan"},
}


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_mesh(n_devices: int | None = None):
    """The mesh of ``LAYOUTS[n_devices]`` over the first ``n_devices``
    devices (default: every device this process sees)."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n not in LAYOUTS:
        raise ValueError(f"no mesh layout for {n} device(s); layouts "
                         f"exist for {sorted(LAYOUTS)}")
    shape, axes = LAYOUTS[n]
    return _mesh(shape, axes, devices[:n])


def make_pctx(n_devices: int | None = None, **kw) -> ParallelContext:
    mesh = make_mesh(n_devices)
    return ParallelContext(
        mesh=mesh, pod_axis="pod" if "pod" in mesh.axis_names else None,
        **kw)


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for multi-device CPU tests (device count must match)."""
    return _mesh(shape, axes)
