import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with ZERO device allocation (inputs are
ShapeDtypeStructs):

  * compiled executable for the production mesh (16x16 single-pod and
    2x16x16 multi-pod) — proving the sharding config is coherent;
  * ``memory_analysis()``  — per-device bytes (fits/doesn't fit);
  * ``cost_analysis()``    — per-device FLOPs / bytes for the roofline;
  * collective wire bytes per mesh axis, parsed from the optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — the paper's bottleneck-link quantity;
  * the three roofline terms (§Roofline) with the TPU v5e constants.

Results are cached as JSON under results/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch kimi_k2_1t \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ARCH_IDS, SHAPES, ShapeSpec, cell_is_skipped, get_config, shapes_for)
from repro.launch.hlo_analysis import MeshLayout


@dataclasses.dataclass
class _CollView:
    bytes_by_axis: dict
    bytes_by_kind: dict
    num_ops: int
from repro.launch.mesh import VARIANTS, make_pctx
from repro.models.api import build_model
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.runtime.trainer import TrainState, make_train_step

# TPU v5e hardware constants (prompt-supplied)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link
DCN_BW = 6.25e9              # bytes/s / chip inter-pod (50 Gbps class)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# optimizer-moment dtype per variant (memory lever for the 1T cell)
VARIANT_OPT_DTYPE = {"mwopt": jnp.bfloat16, "baseopt": jnp.bfloat16}


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs, weak-type-correct, shardable)
# ---------------------------------------------------------------------------

def batch_shapes(cfg, shape: ShapeSpec):
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "decode":
        if cfg.input_mode == "embeddings" and cfg.family != "encdec":
            return {"embeds": jax.ShapeDtypeStruct((b, 1, cfg.d_model),
                                                   jnp.bfloat16)}
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    if cfg.family == "encdec":
        return {"src_embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                   jnp.bfloat16),
                "tgt_tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32)}
    if cfg.input_mode == "embeddings":
        return {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.bfloat16),
                "positions": jax.ShapeDtypeStruct((b, s, 3), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32)}
    out = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
           "labels": jax.ShapeDtypeStruct((b, s), i32)}
    if shape.kind == "prefill":
        out.pop("labels")
    return out


def input_specs(arch: str, shape_name: str, pctx, *, opt_dtype=None):
    """(kind, fn, sharded ShapeDtypeStruct args) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg, pctx)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_sds = jax.eval_shape(model.init, key_sds)
    pspecs = shd.param_specs(params_sds, cfg, pctx)
    params_in = shd.with_sharding(params_sds, pspecs, pctx)
    batch_sds = batch_shapes(cfg, shape)
    bspecs = shd.batch_specs(batch_sds, pctx)
    batch_in = shd.with_sharding(batch_sds, bspecs, pctx)

    if shape.kind == "train":
        opt = adamw(lr=1e-4, opt_dtype=opt_dtype)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        ospecs = shd.param_specs(opt_sds, cfg, pctx)   # elementwise -> same rules
        opt_in = shd.with_sharding(opt_sds, ospecs, pctx)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        state_in = TrainState(params_in, opt_in,
                              shd.with_sharding(
                                  step_sds, jax.sharding.PartitionSpec(),
                                  pctx))
        # pin outputs: new state inherits input shardings (donation works),
        # metrics replicated
        state_out = jax.tree_util.tree_map(lambda s: s.sharding, state_in)
        repl = jax.sharding.NamedSharding(pctx.mesh,
                                          jax.sharding.PartitionSpec())
        metrics_out = {"loss": repl, "grad_norm": repl, "ce": repl,
                       "aux": repl}
        fn = make_train_step(
            model, opt, donate=True,
            jit_kwargs={"out_shardings": (state_out, metrics_out)})
        return "train", fn, (state_in, batch_in)

    # serving cells: params stored bf16 (standard for inference — halves
    # weight HBM and read traffic vs fp32 training master weights)
    params_in = jax.tree_util.tree_map(
        lambda s: (jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                        sharding=s.sharding)
                   if jnp.issubdtype(s.dtype, jnp.floating) else s),
        params_in)
    cache_len = shape.seq_len
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_len))
    cspecs = shd.cache_specs(cache_sds, cfg, pctx)
    cache_in = shd.with_sharding(cache_sds, cspecs, pctx)
    out = serve_out_shardings(cfg, pctx, shape.global_batch, cache_in)
    step = model.prefill if shape.kind == "prefill" else model.decode
    fn = jax.jit(step, donate_argnums=(2,), out_shardings=out)
    return shape.kind, fn, (params_in, batch_in, cache_in)


def serve_out_shardings(cfg, pctx, batch: int, cache_in):
    """``out_shardings`` of a prefill or decode step, whose outputs are
    ``(logits, cache, stats)`` (``models/api.py``) for every family:
    logits split like the batch and the vocabulary where they divide,
    the cache where it came in, and the step's counters (none for a
    dense stack) replicated."""
    logits_spec = jax.sharding.PartitionSpec(
        pctx.dp_axes if batch % (pctx.num_pods * pctx.data_size) == 0
        else None,
        pctx.model_axis if cfg.vocab % pctx.model_size == 0 else None)
    cache_out = jax.tree_util.tree_map(lambda s: s.sharding, cache_in)
    return (jax.sharding.NamedSharding(pctx.mesh, logits_spec), cache_out,
            jax.sharding.NamedSharding(pctx.mesh,
                                       jax.sharding.PartitionSpec()))


# ---------------------------------------------------------------------------
# the dry run
# ---------------------------------------------------------------------------

def model_flops_per_step(arch: str, shape: ShapeSpec) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) — the §Roofline 'useful FLOPs'."""
    from repro.models.api import param_count_shape_only
    cfg = get_config(arch)
    n = param_count_shape_only(cfg)
    if cfg.is_moe:
        per_rank_share = cfg.top_k / cfg.num_experts
        # active = non-expert params + top_k/E of expert params
        expert = (cfg.n_layers - cfg.first_k_dense) * cfg.num_experts * \
            (3 * cfg.d_model * cfg.expert_d_ff)
        n = n - expert + expert * per_rank_share
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * n * tokens


def vmem_elem_counts(arch: str, shape: ShapeSpec, pctx) -> set:
    """Element counts of kernel-resident intermediates for shape-based
    VMEM tagging (see hlo_module.analyze_module): flash score blocks
    [B_loc, H_loc, S, block_k] and SSD/WKV chunk matrices [bh_loc, Q, Q].
    Several sharding variants are emitted; exact-count matching keeps
    collision risk negligible for these large products."""
    cfg = get_config(arch)
    if shape.kind == "decode":
        return set()
    dp = pctx.num_pods * pctx.data_size
    b_loc = max(1, shape.global_batch // dp)
    s = shape.seq_len
    out = set()
    if cfg.family in ("dense", "moe", "encdec") or cfg.shared_attn_every:
        block = min(1024, s)
        for h in {cfg.n_heads, max(1, cfg.n_heads // pctx.model_size)}:
            out.add(b_loc * h * s * block)
    if cfg.family == "hybrid":
        heads = (cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim
        for hl in {heads, max(1, heads // pctx.model_size)}:
            out.add(b_loc * hl * 64 * 64)                 # [bh, Q, Q], Q=64
            out.add(b_loc * hl * 64 * cfg.ssm_state)      # decay/B blocks
    if cfg.family == "rwkv":
        heads = cfg.d_model // cfg.rwkv_head_dim
        for hl in {heads, max(1, heads // pctx.model_size)}:
            out.add(b_loc * hl * 32 * 32)                 # [bh, Q, Q], Q=32
            out.add(b_loc * hl * 32 * cfg.rwkv_head_dim)  # r~/k~ blocks
    return out


# Fabric axis of the planner report grid: every cell additionally carries
# the dispatch+combine decision on each of these registered fabrics
# (--fabric overrides; see core.topology.FABRICS / parse_fabric).
DEFAULT_REPORT_FABRICS = ("2x8", "4x8", "2x8r2")


def planner_cell_report(arch: str, shape: ShapeSpec, pctx,
                        fabrics=DEFAULT_REPORT_FABRICS,
                        calibration=None, budget_s=None) -> dict:
    """Which plan the planner picks for this cell, and the predicted
    delta vs the baseline plan (the quantity the dry-run table reports
    next to the roofline terms).  The cell's collective sites are
    declared as a program and planned JOINTLY — the MoE dispatch/combine
    pair shares one chunk pipeline, so the reported G is the shared G
    the model executes under a bound ExecutionPlan.  ``fabrics`` adds a
    what-if axis: the same cell's per-op decisions on each named fabric.
    ``calibration`` (a telemetry store or path) adds a second what-if
    axis: the same decisions under the store's FITTED hardware model —
    'what would the planner do on the fabric we actually measured'.
    ``budget_s`` declares a latency budget for the cell's phase
    (--phase-budget-us): the contention-aware sweep then reports whether
    any feasible combination met it."""
    from repro.core import planner as pl
    cal_store = None
    if calibration is not None:
        from repro.telemetry import resolve_store
        cal_store = resolve_store(calibration)
    cfg = get_config(arch)
    out = {"policy": pctx.plan_policy}
    n_local = _cell_tokens_per_rank(shape, pctx)
    cell_compute_s = _cell_compute_s(cfg, shape, pctx)
    eplan = None
    if cfg.is_moe:
        eplan = _cell_execution_plan(arch, shape, pctx, budget_s=budget_s)
        role_d = f"{shape.kind}/moe_dispatch"
        out["execution_plan"] = eplan.fingerprint
        out["moe_dispatch"] = eplan.decision(role_d).report()
        out["moe_combine"] = eplan.decision(
            f"{shape.kind}/moe_combine").report()
        joint = eplan.joint.get(role_d)
        out["moe_joint"] = joint.report() if joint else None
        # the microbatch this cell EXECUTES (pctx knob — planner-derived
        # for the "plan" presets; under auto the joint decision's shared
        # G clamped to a divisor of the local token count, exactly as
        # moe_ffn runs it) next to the planner's own pick, so
        # preset/decision drift is visible in the table instead of
        # silently baked in
        planned_g = joint.microbatch if joint else 1
        g_knob = (planned_g if pctx.plan_policy == "auto"
                  else int(pctx.moe_microbatch))
        out["moe_microbatch"] = {
            "executed": max(1, math.gcd(g_knob, n_local)),
            "planned": planned_g,
            "compute_s": cell_compute_s,
        }
    if shape.kind == "train":
        # gradient sync rides in the same cell program (train phase only)
        if eplan is None:
            eplan = _cell_execution_plan(arch, shape, pctx,
                                         budget_s=budget_s)
            out["execution_plan"] = eplan.fingerprint
        gs = eplan.decisions.get("train/grad_sync")
        if gs is not None:
            out["grad_sync"] = gs.report()
    if eplan is not None:
        # contention breakdown + sweep-cost introspection of the cell's
        # phase (solo vs merged shared-link wire, beam/oracle statistics,
        # budget verdict when --phase-budget-us is in play)
        out["phases"] = {ph: dict(rep)
                         for ph, rep in eplan.phase_report.items()}
        out["planner_stats"] = dict(eplan.planner_stats)
    # Reference decision on the paper's §3.1 fixture (8-NPU split-TP full
    # mesh) at this cell's per-chip activation fragment — a what-if the
    # table carries alongside every cell, NOT a collective the traced
    # model necessarily issues (tp_subgroups=1 emits no split-TP gather).
    from repro.core.topology import get_fabric, split_tp_full_mesh
    topo, _ = split_tp_full_mesh(8, tp=4)
    frag = n_local * cfg.d_model * 2
    d = pl.default_planner().choose("allgather", frag, topo)
    out["allgather_ref_8x4"] = {"frag_bytes": frag, **d.report()}
    # Fabric axis: how the decisions move with the physical bottleneck.
    out["fabrics"] = {}
    for fname in fabrics or ():
        ftopo = get_fabric(fname)
        cell = {"allgather": pl.default_planner().choose(
            "allgather", frag, ftopo).report()}
        if cfg.is_moe:
            cell["dispatch"] = pl.default_planner().choose(
                "dispatch", n_local * cfg.d_model * 2, ftopo,
                num_experts=cfg.num_experts, top_k=cfg.top_k,
                token_bytes=cfg.d_model * 2,
                compute_s=cell_compute_s).report()
            cell["combine"] = pl.default_planner().choose(
                "combine", n_local * cfg.d_model * 2, ftopo,
                num_experts=cfg.num_experts, top_k=cfg.top_k,
                token_bytes=cfg.d_model * 2,
                compute_s=cell_compute_s).report()
        # calibration what-if: the same fabric cell under the measured
        # (fitted) hardware model from the --calibration store
        if cal_store is not None:
            from repro.telemetry import calibrated_hw
            hw_cal = calibrated_hw(cal_store, ftopo)
            cal = {"fitted": bool(hw_cal.link_bw),
                   "allgather": pl.default_planner().choose(
                       "allgather", frag, ftopo, hw_cal).report()}
            if cfg.is_moe:
                cal["dispatch"] = pl.default_planner().choose(
                    "dispatch", n_local * cfg.d_model * 2, ftopo, hw_cal,
                    num_experts=cfg.num_experts, top_k=cfg.top_k,
                    token_bytes=cfg.d_model * 2,
                    compute_s=cell_compute_s).report()
                cal["combine"] = pl.default_planner().choose(
                    "combine", n_local * cfg.d_model * 2, ftopo, hw_cal,
                    num_experts=cfg.num_experts, top_k=cfg.top_k,
                    token_bytes=cfg.d_model * 2,
                    compute_s=cell_compute_s).report()
            cell["calibrated"] = cal
        out["fabrics"][fname] = cell
    if cal_store is not None:
        out["calibration_store"] = {"path": cal_store.path,
                                    "records": len(cal_store),
                                    "fabrics": cal_store.fabrics()}
    return out


def _cell_tokens_per_rank(shape: ShapeSpec, pctx) -> int:
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    return max(1, tokens // (pctx.num_pods * pctx.data_size))


def _cell_program(arch: str, shape: ShapeSpec, pctx, budget_s=None):
    """The ONE declared collective program of this cell (phase ==
    shape.kind), shared by the "plan" preset derivation, the auto-policy
    binding and the cell report — so the G a preset executes is always
    derived from the same joint decision the report displays as
    'planned'.  ``budget_s`` caps the phase's contention-aware latency
    (the --phase-budget-us what-if)."""
    from repro.parallel.context import build_collective_program
    cfg = get_config(arch)
    seq = shape.seq_len if shape.kind != "decode" else 1
    return build_collective_program(
        cfg, pctx, "dryrun", {shape.kind: (shape.global_batch, seq)},
        phase_budgets={shape.kind: budget_s} if budget_s else None)


def _cell_execution_plan(arch: str, shape: ShapeSpec, pctx, budget_s=None):
    """Jointly-planned ExecutionPlan of this cell's program (planned
    regardless of policy: the fixed-policy cells still REPORT what the
    planner would bind)."""
    return pctx.plan_collectives(
        _cell_program(arch, shape, pctx, budget_s=budget_s))


def _cell_compute_s(cfg, shape: ShapeSpec, pctx) -> float:
    """Modeled per-rank expert-FFN time of this cell — the overlap
    context the planner's pipelined scoring mode prices chunked
    dispatch/combine against."""
    if not cfg.is_moe:
        return 0.0
    from repro.core.latency_model import moe_overlap_compute_s
    return moe_overlap_compute_s(
        _cell_tokens_per_rank(shape, pctx), cfg.top_k, cfg.d_model,
        cfg.expert_d_ff, tp=pctx.model_size)


def _planned_microbatch(arch: str, shape: ShapeSpec, pctx) -> int:
    """Derive the moe_microbatch preset from the JOINT pipeline decision
    of this cell's program (the 'mwmicro' drift fix, now joint-aware:
    the shared G is the one the dispatch+combine round trip scores best
    at, not the dispatch half's own optimum)."""
    cfg = get_config(arch)
    if not cfg.is_moe:
        return 1
    eplan = _cell_execution_plan(arch, shape, pctx)
    joint = eplan.joint.get(f"{shape.kind}/moe_dispatch")
    g = joint.microbatch if joint else 1
    return max(1, math.gcd(g, _cell_tokens_per_rank(shape, pctx)))


def _cell_pctx(arch: str, shape: ShapeSpec, multi_pod: bool, variant: str):
    pctx_kw = dict(VARIANTS[variant])
    if shape.kind != "train":
        # serving: replicate dense params over data (classic TP serving);
        # MoE experts stay EP-sharded via moe_specs regardless.
        pctx_kw.setdefault("fsdp", False)
    planned_g = pctx_kw.get("moe_microbatch") == "plan"
    if planned_g:
        pctx_kw.pop("moe_microbatch")   # integer presets pass through
    pctx = make_pctx(512 if multi_pod else 256, **pctx_kw)
    if planned_g:
        pctx = dataclasses.replace(
            pctx, moe_microbatch=_planned_microbatch(arch, shape, pctx))
    if pctx.plan_policy == "auto":
        # bind the cell's jointly-planned ExecutionPlan: the traced model
        # resolves its sites by lookup — the dry run exercises the same
        # bound-plan path production launchers use
        program = _cell_program(arch, shape, pctx)
        if program.sites:
            pctx = pctx.bind(pctx.plan_collectives(program))
    return pctx


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             variant: str = "mw", verbose: bool = True,
             fabrics=DEFAULT_REPORT_FABRICS, calibration=None,
             budget_s=None) -> dict:
    skip = cell_is_skipped(arch, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "variant": variant, "skipped": skip}
    shape = SHAPES[shape_name]
    pctx = _cell_pctx(arch, shape, multi_pod, variant)
    t0 = time.monotonic()
    kind, fn, args = input_specs(arch, shape_name, pctx,
                                 opt_dtype=VARIANT_OPT_DTYPE.get(variant))
    with pctx.mesh:
        lowered = fn.lower(*args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    layout = MeshLayout(axes=("pod", "data", "model") if multi_pod
                        else ("data", "model"),
                        sizes=(2, 16, 16) if multi_pod else (16, 16))
    # loop-multiplicity-aware analysis (XLA:CPU cost_analysis counts while
    # bodies once — see launch/hlo_module.py):
    from repro.launch.hlo_module import analyze_module
    mod = analyze_module(hlo, layout,
                         vmem_elem_counts=vmem_elem_counts(
                             arch, shape, pctx))
    coll = _CollView(mod.collective_by_axis, mod.collective_by_kind,
                     mod.collective_ops)
    chips = 512 if multi_pod else 256

    flops_dev = float(mod.flops)
    bytes_dev = float(mod.hbm_bytes)
    xla_flops_dev = float(cost.get("flops", 0.0))     # body-once reference
    # kernel-adjusted memory: intermediates tagged to flash/scan source
    # regions stay in VMEM in the Pallas kernels (boundary q/k/v/o traffic
    # is counted at their producers/consumers); assume the fused kernel
    # eliminates 95% of tagged traffic (flash intermediates are O(S*T) vs
    # O(S*d) boundaries — >99% at 32k, 95% is conservative).
    tagged = sum(mod.hbm_tagged.values())
    bytes_dev_kernel = bytes_dev - 0.95 * tagged
    compute_term = flops_dev / PEAK_FLOPS
    memory_term = bytes_dev / HBM_BW
    ici_bytes = sum(v for k, v in coll.bytes_by_axis.items() if k != "pod")
    pod_bytes = coll.bytes_by_axis.get("pod", 0)
    collective_term = ici_bytes / ICI_BW + pod_bytes / DCN_BW
    collective_term_ici_only = (ici_bytes + pod_bytes) / ICI_BW
    mflops = model_flops_per_step(arch, shape)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "variant": variant, "kind": kind, "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "bytes_per_device_kernel_adj": bytes_dev_kernel,
                 "hbm_tagged": mod.hbm_tagged,
                 "xla_flops_body_once": xla_flops_dev,
                 "loop_trip_counts": mod.loops},
        "collectives": {
            "by_axis": coll.bytes_by_axis,
            "by_kind": coll.bytes_by_kind,
            "num_ops": coll.num_ops,
        },
        "planner": planner_cell_report(arch, shape, pctx, fabrics=fabrics,
                                       calibration=calibration,
                                       budget_s=budget_s),
        "roofline": {
            "compute_term_s": compute_term,
            "memory_term_s": memory_term,
            "memory_term_kernel_adj_s": bytes_dev_kernel / HBM_BW,
            "collective_term_s": collective_term,
            "collective_term_ici_only_s": collective_term_ici_only,
            "dominant": max(
                [("compute", compute_term), ("memory", memory_term),
                 ("collective", collective_term)], key=lambda kv: kv[1])[0],
            "model_flops_global": mflops,
            "useful_flops_ratio": (mflops / (flops_dev * chips)
                                   if flops_dev else None),
        },
    }
    if verbose:
        mm = result["memory"]
        print(f"[{arch} x {shape_name} x "
              f"{'multi' if multi_pod else 'single'} x {variant}] "
              f"kind={kind} compile={t_compile:.0f}s")
        print(f"  memory/device: args={_gb(mm['argument_bytes'])} "
              f"temp={_gb(mm['temp_bytes'])} out={_gb(mm['output_bytes'])}")
        print(f"  flops/device={flops_dev:.3e} bytes/device={bytes_dev:.3e}")
        print(f"  collective bytes by axis: "
              f"{ {k: _gb(v) for k, v in coll.bytes_by_axis.items()} }")
        r = result["roofline"]
        print(f"  roofline: compute={r['compute_term_s']*1e3:.2f}ms "
              f"memory={r['memory_term_s']*1e3:.2f}ms "
              f"collective={r['collective_term_s']*1e3:.2f}ms "
              f"-> dominant={r['dominant']}")
        for op_name, pr in result["planner"].items():
            if isinstance(pr, dict) and "plan" in pr:
                print(f"  planner[{op_name}]: {pr['plan']} "
                      f"predicted={pr['predicted_us']:.1f}us "
                      f"vs baseline={pr['baseline_us']:.1f}us "
                      f"({pr['speedup_pct']:+.1f}%)")
        mb = result["planner"].get("moe_microbatch")
        if mb:
            print(f"  planner[microbatch]: executed={mb['executed']} "
                  f"planned={mb['planned']}")
        for ph, rep in result["planner"].get("phases", {}).items():
            line = (f"  planner[phase {ph}]: {rep['score_s'] * 1e6:.1f}us "
                    f"(contention +{rep['contention_s'] * 1e6:.1f}us)")
            if rep.get("budget_s"):
                line += (f", budget {rep['budget_s'] * 1e6:.0f}us "
                         f"{'ok' if rep.get('budget_ok') else 'VIOLATED'}")
            print(line)
        st = result["planner"].get("planner_stats")
        if st:
            print(f"  planner[search]: {'/'.join(st['search'])}, "
                  f"{st['combos_scored']}/{st['product']} combination(s) "
                  f"scored in {st['planning_wall_s'] * 1e3:.1f}ms")
    return result


def _gb(x):
    if x is None:
        return "?"
    return f"{x/2**30:.2f}GiB"


def cell_path(arch, shape_name, multi_pod, variant):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh = "multi" if multi_pod else "single"
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape_name}__{mesh}__{variant}.json")


def run_and_save(arch, shape_name, multi_pod, variant="mw",
                 force=False, fabrics=DEFAULT_REPORT_FABRICS,
                 calibration=None, budget_s=None) -> dict:
    path = cell_path(arch, shape_name, multi_pod, variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            result = json.load(f)
        # the compiled cell is fabric-independent, but the planner
        # what-if axes are not: refresh them (cheap — no recompile) when
        # the cached cell was computed with a different fabric set, when
        # a calibration store is in play (its fits move with every probe
        # run), or when a phase budget changes the feasibility filter
        cached = set(result.get("planner", {}).get("fabrics", {}))
        if "planner" in result and (cached != set(fabrics or ())
                                    or calibration is not None
                                    or budget_s is not None):
            pctx = _cell_pctx(arch, SHAPES[shape_name], multi_pod, variant)
            result["planner"] = planner_cell_report(
                arch, SHAPES[shape_name], pctx, fabrics=fabrics,
                calibration=calibration, budget_s=budget_s)
            with open(path, "w") as f:
                json.dump(result, f, indent=1)
        return result
    try:
        result = run_cell(arch, shape_name, multi_pod=multi_pod,
                          variant=variant, fabrics=fabrics,
                          calibration=calibration, budget_s=budget_s)
    except Exception as e:  # record failures — they are bugs to fix
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "multi" if multi_pod else "single",
                  "variant": variant, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
        print(f"FAILED [{arch} x {shape_name}]: {e}", file=sys.stderr)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--variant", default="mw", choices=list(VARIANTS))
    ap.add_argument("--fabric", default=",".join(DEFAULT_REPORT_FABRICS),
                    help="comma list of fabrics (registered names or "
                         "parseable specs like 4x8, 2x8r2@12.5) for the "
                         "per-cell planner what-if axis; '' disables")
    ap.add_argument("--calibration", default=None,
                    help="telemetry calibration store (JSONL path): every "
                         "cell's planner section additionally reports the "
                         "decisions under the store's FITTED hardware "
                         "model — the measured-fabric what-if axis")
    ap.add_argument("--phase-budget-us", type=float, default=None,
                    help="latency budget (us) for each cell's phase: the "
                         "contention-aware sweep reports whether any "
                         "feasible plan combination met it")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell")
    ap.add_argument("--force", action="store_true")
    from repro.telemetry.exporter import (add_metrics_args,
                                          finish_exporter_from_args,
                                          start_exporter_from_args)
    add_metrics_args(ap)
    args = ap.parse_args(argv)
    exporter = start_exporter_from_args(args)
    fabrics = tuple(f for f in args.fabric.split(",") if f)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in shapes_for(arch):
                for mp in meshes:
                    cells.append((arch, shape, mp, args.variant))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            cells.append((args.arch, args.shape, mp, args.variant))

    budget_s = (args.phase_budget_us * 1e-6
                if args.phase_budget_us else None)
    failures = 0
    for arch, shape, mp, variant in cells:
        r = run_and_save(arch, shape, mp, variant, force=args.force,
                         fabrics=fabrics, calibration=args.calibration,
                         budget_s=budget_s)
        if "error" in r:
            failures += 1
    print(f"\n{len(cells) - failures}/{len(cells)} cells OK")
    finish_exporter_from_args(args, exporter)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
