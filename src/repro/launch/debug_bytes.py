"""Debug utility: attribute HBM-byte estimates to HLO instructions.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=512 PYTHONPATH=src \
    python -m repro.launch.debug_bytes --arch X --shape Y [--multi-pod]

Prints the top-N instructions by multiplicity-weighted traffic — the
profiling view the §Perf loop reads (no real-TPU trace exists here).
"""

from __future__ import annotations

import re
from collections import defaultdict

from repro.launch import hlo_module as H


def attribute_bytes(text: str, top: int = 20, layout=None):
    from repro.launch.hlo_analysis import MeshLayout
    from repro.launch.hlo_module import analyze_module
    if layout is None:
        layout = MeshLayout(("data", "model"), (16, 16))
    rows = []
    cost = analyze_module(text, layout, collect_rows=rows)
    rows.sort(reverse=True)
    print(f"total HBM-byte estimate: {cost.hbm_bytes:.3e}")
    for w, m, op, name, ob, cname in rows[:top]:
        print(f"{w/1e9:9.2f} GB  x{m:6.0f}  {op:18s} out={ob/1e6:9.1f}MB  "
              f"{name[:44]:44s} in {cname[:24]}")
    return rows


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="mw")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()
    from repro.launch.dryrun import input_specs, SHAPES
    from repro.launch.mesh import VARIANTS, make_pctx
    kw = dict(VARIANTS[args.variant])
    if SHAPES[args.shape].kind != "train":
        kw.setdefault("fsdp", False)
    pctx = make_pctx(512 if args.multi_pod else 256, **kw)
    kind, fn, fargs = input_specs(args.arch, args.shape, pctx)
    with pctx.mesh:
        compiled = fn.lower(*fargs).compile()
    attribute_bytes(compiled.as_text(), args.top)


if __name__ == "__main__":
    main()
