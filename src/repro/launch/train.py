"""Training launcher.

Local (real devices, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch mistral_nemo_12b \
      --smoke --steps 50

Production (multi-host TPU; this process shape is what you'd launch per
host — jax.distributed.initialize is invoked when JAX_COORDINATOR is set):
  python -m repro.launch.train --arch kimi_k2_1t --shape train_4k \
      --ckpt-dir gs://...

The mesh is launch/mesh.py's layout for the device count (256 chips:
(16,16); 512: (2,16,16)); parallelism knobs (moe scheme, remat, SP,
FSDP) come from --variant, same names as the dry-run.
"""

from __future__ import annotations

import argparse
import logging
import os

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny batch on local devices")
    ap.add_argument("--variant", default="mw")
    ap.add_argument("--plan-policy", choices=["auto", "fixed"],
                    default=None,
                    help="auto: collective schemes/splits chosen by the "
                         "latency-model planner per payload (§5.2 dynamic "
                         "workflow); fixed: use the --variant knobs "
                         "verbatim.  Default: auto, unless the chosen "
                         "--variant pins an explicit scheme (ablations "
                         "like 'baseline' stay ablations)")
    ap.add_argument("--fabric", default=None,
                    help="fabric the planner scores against instead of the "
                         "mesh-derived shape: a registered name (2x8, 4x8, "
                         "2x8r2, 2x8asym, tpu_2x16) or an inline spec "
                         "'SxP[rR][@INTER[:INTRA]]' in GB/s, e.g. "
                         "'4x8@12.5'.  Changes WHICH dispatch/combine "
                         "plans win; execution stays on the actual mesh")
    ap.add_argument("--calibrate", choices=["off", "startup", "online"],
                    default="off",
                    help="telemetry loop: 'startup' runs a probe sweep + "
                         "fit before step 0 so planner decisions are "
                         "scored under MEASURED link bandwidths; 'online' "
                         "additionally re-probes every --calibrate-every "
                         "steps and re-fits when predicted-vs-measured "
                         "drift exceeds the monitor threshold (decisions "
                         "flip at runtime, no restart)")
    ap.add_argument("--calibrate-every", type=int, default=25,
                    help="online probe cadence in steps")
    ap.add_argument("--calibration-store", default=None,
                    help="calibration JSONL path (default "
                         "results/calibration/calibration.jsonl); "
                         "measurements persist across runs per fabric "
                         "fingerprint")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    from repro.telemetry.exporter import (add_metrics_args,
                                          finish_exporter_from_args,
                                          start_exporter_from_args)
    add_metrics_args(ap)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    exporter = start_exporter_from_args(args)

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()          # multi-host entry

    from repro.configs.base import SHAPES, get_config
    from repro.data.pipeline import DataConfig, SyntheticLM, batch_for_model
    from repro.models.api import build_model
    from repro.optim import adamw, cosine_schedule
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
        pctx = None
        batch, seq = 4, 64
    else:
        import dataclasses

        from repro.launch.mesh import VARIANTS, make_pctx
        variant_kw = VARIANTS[args.variant]
        pctx = make_pctx(**variant_kw)
        plan_policy = args.plan_policy
        if plan_policy is None:
            # planner by default, but a variant that pins a scheme or a
            # policy is an explicit ablation — don't override it
            pins = {"moe_scheme", "plan_policy"} & set(variant_kw)
            plan_policy = pctx.plan_policy if pins else "auto"
        pctx = dataclasses.replace(pctx, plan_policy=plan_policy)
        if args.fabric:
            from repro.core.topology import get_fabric
            pctx = dataclasses.replace(pctx, fabric=get_fabric(args.fabric))
            logging.info("planner fabric: %s", pctx.fabric.name)
        shape = SHAPES[args.shape]
        batch, seq = shape.global_batch, shape.seq_len

    monitor = None
    probe = None
    if args.calibrate != "off":
        import dataclasses

        from repro.core.planner import _ep_topology
        from repro.core.topology import get_fabric
        from repro.telemetry import (GroundTruth, SimProbe,
                                     startup_calibration)
        if pctx is not None:
            topo = _ep_topology(pctx.num_pods, pctx.data_size, pctx.fabric)
        else:
            topo = get_fabric(args.fabric or "2x8")
        # Execution backend: the simulated probe (injectable ground
        # truth) stands in wherever there is no real fabric to time —
        # deployments on a live mesh swap in telemetry.LiveProbe.
        probe = SimProbe(GroundTruth())
        store, monitor, event = startup_calibration(
            topo, args.calibration_store, probe=probe)
        logging.info("calibration startup: %d store records, drift at fit "
                     "%.1f%%, recalibrated=%s", len(store),
                     100 * (event["drift"] if event else 0.0), bool(event))
        if pctx is not None:
            pctx = dataclasses.replace(pctx, calibration=store)

    # Declare the training phase's collective program up-front and bind
    # the jointly-planned ExecutionPlan: the MoE (dispatch, combine) pair
    # is swept as ONE shared chunk pipeline (a smaller dispatch G can win
    # on the combined score) and the split-TP boundary gather rides in
    # the same program.  Built AFTER calibration so the plan is scored
    # under the fitted model; moe_ffn resolves its sites by lookup
    # against the bound plan at trace time.
    eplan = None
    if pctx is not None:
        from repro.parallel.context import build_collective_program
        # itemsize must match the activation dtype built below (site
        # keys embed the payload bucket)
        program = build_collective_program(
            cfg, pctx, "train", {"train": (batch, seq)},
            itemsize=4 if args.smoke else 2)
        if program.sites and pctx.plan_policy == "auto":
            eplan = pctx.plan_collectives(program)
            pctx = pctx.bind(eplan)
            for line in eplan.summary().splitlines():
                logging.info("planner %s", line)
            joint = eplan.joint.get("train/moe_dispatch")
            if joint is not None and joint.microbatch > 1:
                logging.info(
                    "planner pipelined MoE round trip: G=%d shared chunks "
                    "(serial %.1fus -> %.1fus predicted)",
                    joint.microbatch, joint.predicted_serial_s * 1e6,
                    joint.predicted_s * 1e6)
            gs = eplan.decisions.get("train/grad_sync")
            if gs is not None:
                g = gs.shard_map_kwargs.get("microbatch", 1)
                # executed reduction: under plain jit AD inserts the DP
                # mean implicitly, which GSPMD lowers to the flat ring
                # the "ring" plan models; non-ring verdicts need the
                # shard_map planned_psum lowering (core/collectives.py)
                note = ("matches the implicit GSPMD ring this jit step "
                        "executes" if gs.plan == "ring" else
                        "needs the shard_map planned_psum lowering; this "
                        "jit step executes the implicit ring")
                logging.info(
                    "planner gradient sync: %s G=%d (serial %.2fms -> "
                    "%.2fms pipelined; ring baseline %.2fms) — %s",
                    gs.plan, g, gs.predicted_serial_s * 1e3,
                    gs.predicted_s * 1e3, gs.baseline_s * 1e3, note)
        elif pctx.plan_policy == "auto":
            logging.info("planner auto: no collective sites to declare "
                         "for this config (dense, no split-TP gather)")
        else:
            logging.info("planner fixed: moe_scheme=%s moe_combine=%s "
                         "moe_microbatch=%d",
                         pctx.moe_scheme,
                         pctx.moe_combine or pctx.moe_scheme,
                         pctx.moe_microbatch)

    model = build_model(cfg, pctx,
                        dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch, seed=args.seed))
    opt = adamw(lr=cosine_schedule(args.lr, warmup=min(100, args.steps // 10
                                                       or 1),
                                   total=args.steps), weight_decay=0.01)
    tcfg = TrainerConfig(total_steps=args.steps,
                         checkpoint_every=args.ckpt_every,
                         checkpoint_dir=args.ckpt_dir, log_every=10)

    # LIVE overlap-efficiency feedback (ROADMAP debt): pipelined moe_ffn
    # step wall times flow through Planner.note_measurement into the
    # joint decision's log rows, so DriftMonitor's fit_overlap_eff is fed
    # by the real training loop — not just SimProbe/synthetic rows.
    attribution = None
    if monitor is not None and eplan is not None:
        from repro.telemetry import StepAttribution
        joint = next((d for d in eplan.joint.values()
                      if d.microbatch > 1), None)
        if joint is not None:
            from repro.core.planner import default_planner
            attribution = StepAttribution(
                default_planner(), joint,
                n_layers=max(1, cfg.n_layers
                             - getattr(cfg, "first_k_dense", 0)))

    step_hook = None
    if attribution is not None or args.calibrate == "online":
        stale_warned = [False]

        def step_hook(step, row, _every=max(1, args.calibrate_every)):
            if attribution is not None:
                attribution.observe_step(row["wall"])
            if args.calibrate != "online" or step == 0 or step % _every:
                return
            event = monitor.run_cycle(probe)
            if event:
                logging.info(
                    "step %d: drift %.1f%% exceeded %.0f%% — recalibrated "
                    "(%d links refit, overlap_eff=%s, %d program(s) "
                    "replanned); planner cache invalidated",
                    step, 100 * event["drift"],
                    100 * monitor.threshold, event["measured_links"],
                    event.get("overlap_eff"),
                    len(event.get("programs", [])))
                if (pctx is not None and not stale_warned[0]
                        and pctx.bound_plan_stale()):
                    stale_warned[0] = True
                    from repro.telemetry import default_registry
                    default_registry()["repro_plan_stale_total"].inc(
                        program=eplan.program.name,
                        fingerprint=eplan.fingerprint)
                    logging.warning(
                        "step %d: bound ExecutionPlan %s is now STALE — "
                        "the replan under the refit calibration chose "
                        "different decisions; training keeps executing "
                        "the old plan until re-trace (hot re-bind not "
                        "wired yet)", step, eplan.fingerprint)

    trainer = Trainer(model, opt,
                      lambda s: batch_for_model(cfg, data.batch(s)),
                      tcfg, init_rng=jax.random.key(args.seed),
                      step_hook=step_hook)
    hist = trainer.run()
    if hist:
        print(f"final loss {hist[-1]['loss']:.4f} over {len(hist)} steps; "
              f"straggler events: {len(trainer.ledger.events)}")
    if monitor is not None:
        rep = monitor.report()
        print(f"calibration: {rep['recalibrations']} recalibration(s), "
              f"drift {rep['drift_pct']:.1f}%, "
              f"{rep['store_records']} store records")
    if attribution is not None:
        print(f"overlap feedback: {attribution.fed} step timing(s) fed "
              f"into the joint pipeline decision's measurement rows")
    finish_exporter_from_args(args, exporter)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
