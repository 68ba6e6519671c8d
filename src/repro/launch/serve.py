"""Serving launcher: batched generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_7b --smoke \
      --prompts 4 --max-new 32

The device count picks the layout: one device serves without a mesh,
more take ``launch/mesh.py``'s layout for their count (four chips:
pod 2 x data 2, experts over both), and a count with no layout is an
error.  ``build_engine`` is the construction every caller shares
(``chip_smoke.py`` among them).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def serve_continuous(args, cfg, engine, pctx):
    """Drain a seeded Poisson arrival stream through the continuous-
    batching scheduler against the live engine; returns the scheduler
    report."""
    from repro.serving import (AdmissionController, BatchScheduler,
                               PlannerProbe, RequestQueue, TrafficConfig,
                               TrafficGenerator)

    itemsize = 4 if args.smoke else 2
    probe = engine.plan_probe(itemsize)
    if probe is None:
        # pctx-free host (CPU smoke): the admission controller still
        # gets a planner oracle, scored on the requested fabric
        from repro.core.topology import get_fabric
        probe = PlannerProbe(
            get_fabric(args.fabric or "2x8"),
            token_bytes=cfg.d_model * itemsize,
            num_experts=getattr(cfg, "num_experts", 0) or 64,
            top_k=getattr(cfg, "top_k", 0) or 8)
    xover = probe.crossover_batch()
    anchor = int(xover) if xover != float("inf") else max(1, args.prompts)
    tpot_slo_s = (args.tpot_slo_us * 1e-6 if args.tpot_slo_us
                  else probe.decode_step_s(anchor) * 1.15)
    ttft_slo_s = (args.ttft_slo_us * 1e-6 if args.ttft_slo_us else 0.08)
    queue = RequestQueue()
    traffic = TrafficConfig(
        arrival_rate_rps=args.arrival_rate, num_requests=args.requests,
        prompt_lens=(args.prompt_len,), max_news=(args.max_new,),
        vocab=cfg.vocab, seed=args.seed)
    for req in TrafficGenerator(traffic).requests():
        queue.push(req)
    admission = AdmissionController(
        probe, capacity=args.prompts, policy="planner",
        tpot_slo_s=tpot_slo_s, ttft_slo_s=ttft_slo_s)
    sched = BatchScheduler(
        queue=queue, admission=admission, engine=engine, probe=probe,
        binder=engine.plan_binder if pctx is not None else None,
        plan_for_bucket=lambda b: engine.bucket_plan(b, args.prompt_len),
        eos_id=None, seed=args.seed)
    sched.run_until_drained()
    print(f"continuous serving: capacity {args.prompts}, crossover batch "
          f"{anchor if xover != float('inf') else 'none'}, TPOT SLO "
          f"{tpot_slo_s * 1e6:.0f}us, TTFT SLO {ttft_slo_s * 1e3:.0f}ms")
    return sched.report(ttft_slo_s=ttft_slo_s, tpot_slo_s=tpot_slo_s)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-policy", choices=["auto", "fixed"],
                    default="auto",
                    help="auto: MoE dispatch+combine plans per phase from "
                         "the latency-model planner (decode vs prefill "
                         "can differ, Fig 8)")
    ap.add_argument("--moe-scheme", choices=["hierarchical", "baseline"],
                    default="hierarchical",
                    help="fixed policy: MoE dispatch scheme, MultiWrite "
                         "(hierarchical) or unicast (baseline)")
    ap.add_argument("--fabric", default=None,
                    help="fabric the planner scores against: a registered "
                         "name (2x8, 4x8, 2x8r2, 2x8asym) or an inline "
                         "spec 'SxP[rR][@INTER[:INTRA]]' in GB/s")
    ap.add_argument("--calibrate", choices=["off", "startup"],
                    default="off",
                    help="telemetry: probe sweep + fit before serving so "
                         "planner decisions are scored under measured "
                         "link bandwidths; plan_report then carries the "
                         "predicted-vs-measured drift and the last "
                         "re-calibration")
    ap.add_argument("--calibration-store", default=None,
                    help="calibration JSONL path (default "
                         "results/calibration/calibration.jsonl)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching serving tier: seeded open-"
                         "loop Poisson arrivals drain through the "
                         "iteration-level BatchScheduler (finished "
                         "sequences exit / queued requests join between "
                         "decode steps) under planner-informed admission, "
                         "instead of the one-shot batched generate")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous mode: total requests in the arrival "
                         "stream")
    ap.add_argument("--arrival-rate", type=float, default=100.0,
                    help="continuous mode: open-loop Poisson arrival rate "
                         "(requests/s on the scheduler's virtual clock)")
    ap.add_argument("--ttft-slo-us", type=float, default=None,
                    help="continuous mode: time-to-first-token SLO (us) "
                         "for admission pressure + per-request SLO "
                         "classes (default 80000)")
    ap.add_argument("--tpot-slo-us", type=float, default=None,
                    help="continuous mode: time-per-output-token SLO "
                         "(us); admission holds the decode batch at the "
                         "largest size whose planner-predicted step meets "
                         "this (default: 1.15x the predicted step at the "
                         "scheme-crossover batch)")
    ap.add_argument("--decode-slo-us", type=float, default=None,
                    help="decode-phase latency budget (us): the planner "
                         "rejects prefill plan combinations whose shared-"
                         "link traffic would push the decode round trip "
                         "past this cap (contention-aware sweep)")
    ap.add_argument("--seed", type=int, default=0)
    from repro.telemetry.exporter import add_metrics_args
    add_metrics_args(ap)
    return ap.parse_args(argv)


def serving_config(args):
    """The model config ``args`` names (``--smoke``: a reduced one)."""
    from repro.configs.base import get_config
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced(n_layers=4, d_model=128, n_heads=4, d_ff=256,
                          vocab=2048)
    return cfg


def init_params(model, pctx, seed: int, dtype):
    """Seeded parameters in ``dtype``, created already sharded by
    ``param_specs`` on the context's mesh: no array lands whole on one
    device first."""
    def init(key):
        return jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            model.init(key))

    key = jax.random.key(seed)
    if pctx is None:
        return jax.jit(init)(key)
    from repro.parallel import sharding as shd
    specs = shd.param_specs(jax.eval_shape(init, key), model.cfg, pctx)
    return jax.jit(init, out_shardings=shd.named(specs, pctx))(key)


def build_engine(args, cfg, params=None, dtype=None):
    """The serving stack ``args`` describes, on every device this process
    sees: the parallel context (one device serves without a mesh; more
    take the ``launch/mesh.py`` layout for their count), the bound
    collective plan, the model, its parameters (``params``, or seeded
    ones) and the :class:`ServeEngine` over them.  ``dtype``: the
    activation dtype (default f32 under ``--smoke``, else bf16)."""
    import dataclasses

    from repro.models.api import build_model
    from repro.runtime.server import ServeConfig, ServeEngine

    if dtype is None:
        dtype = jnp.float32 if args.smoke else jnp.bfloat16
    pctx = None
    if len(jax.devices()) > 1:
        from repro.launch.mesh import make_pctx
        pctx = make_pctx(fsdp=False, plan_policy=args.plan_policy,
                         moe_scheme=args.moe_scheme)
    # resolve --fabric into the context BEFORE telemetry: probe records
    # and trace-time planner lookups must share ONE fabric fingerprint
    # (a monitor keyed to the mesh-derived topology would store records
    # the --fabric decisions never find)
    if pctx is not None and args.fabric:
        from repro.core.topology import get_fabric
        pctx = dataclasses.replace(pctx, fabric=get_fabric(args.fabric))
    monitor = None
    store = None
    if args.calibrate != "off":
        from repro.core.planner import _ep_topology
        from repro.core.topology import get_fabric
        from repro.telemetry import startup_calibration
        if pctx is not None:
            topo = _ep_topology(pctx.num_pods, pctx.data_size, pctx.fabric)
        else:
            topo = get_fabric(args.fabric or "2x8")
        # simulated probe (the default) stands in when there is no
        # fabric to time (CPU smoke); live deployments pass a LiveProbe
        store, monitor, event = startup_calibration(
            topo, args.calibration_store)
        print(f"calibration: {len(store)} records, "
              f"recalibrated={bool(event)}"
              + (f", drift at fit {100 * event['drift']:.1f}%"
                 if event else ""))
    # Declare both serving phases' collective sites as ONE program and
    # bind the jointly-planned ExecutionPlan BEFORE building the model:
    # the jitted prefill/decode traces then resolve their MoE round trips
    # by site lookup (prefill and decode sites differ by payload, so one
    # bound plan serves both phases).
    if pctx is not None and pctx.plan_policy == "auto":
        from repro.parallel.context import build_collective_program
        # itemsize must match the activation dtype build_model uses
        # below (site keys embed the payload bucket)
        budgets = ({"decode": args.decode_slo_us * 1e-6}
                   if args.decode_slo_us else None)
        program = build_collective_program(
            cfg, pctx, "serve", {"prefill": (args.prompts, args.prompt_len),
                                 "decode": (args.prompts, 1)},
            itemsize=jnp.dtype(dtype).itemsize, phase_budgets=budgets)
        if program.sites:
            eplan = pctx.plan_collectives(program)
            pctx = pctx.bind(eplan)
            print(eplan.summary())
            dec = eplan.phase_report.get("decode", {})
            if dec.get("budget_s"):
                verdict = ("met" if dec.get("budget_ok")
                           else "VIOLATED (no feasible combination; "
                                "best-effort plan bound)")
                print(f"decode SLO {dec['budget_s'] * 1e6:.0f}us: "
                      f"{verdict} — contended decode "
                      f"{dec.get('contended_score_s', 0.0) * 1e6:.1f}us")
    model = build_model(cfg, pctx, dtype=dtype)
    if params is None:
        # weights are held in the dtype every matmul casts them to
        params = init_params(model, pctx, args.seed, dtype)
    return ServeEngine(model, params,
                       ServeConfig(max_new_tokens=args.max_new,
                                   temperature=args.temperature),
                       pctx=pctx, calibration=store, monitor=monitor)


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    from repro.telemetry.exporter import (finish_exporter_from_args,
                                          start_exporter_from_args)
    args = parse_args(argv)
    enable_compile_cache()
    exporter = start_exporter_from_args(args)
    cfg = serving_config(args)
    engine = build_engine(args, cfg)
    pctx = engine.pctx
    if args.continuous:
        rep = serve_continuous(args, cfg, engine, pctx)
        print(f"served {rep['completed']}/{args.requests} request(s) in "
              f"{rep['iterations']} iteration(s), horizon "
              f"{rep['horizon_s'] * 1e3:.0f}ms, max in-flight "
              f"{rep['max_in_flight']}")
        print(f"TTFT p50/p99 {rep['ttft_p50_s'] * 1e3:.1f}/"
              f"{rep['ttft_p99_s'] * 1e3:.1f}ms, TPOT p50/p99 "
              f"{rep['tpot_p50_s'] * 1e6:.0f}/{rep['tpot_p99_s'] * 1e6:.0f}"
              f"us, queue-wait p99 {rep['queue_wait_p99_s'] * 1e3:.1f}ms")
        print(f"admission: holds={rep['admission_holds']} "
              f"rejects={sum(rep['admission_rejects'].values())}; "
              f"plan prefetches={rep['prefetch_rebinds']} "
              f"swaps={rep.get('plan_swaps', 0)} "
              f"cold retraces={rep.get('cold_retraces', 0)}; SLO-good "
              f"{rep['slo_good']}/{rep['completed']} "
              f"(goodput {rep['goodput_rps']:.1f}/s)")
        finish_exporter_from_args(args, exporter)
        return 0
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab, size=(args.prompts, args.prompt_len)).astype(np.int32)
    out = engine.generate(prompts, seed=args.seed)
    print(f"generated {out.shape}; "
          f"prefill {engine.stats['prefill_s']*1e3:.0f}ms, "
          f"decode {engine.stats['decode_s']*1e3:.0f}ms")
    for phase, per_op in engine.stats.get("plans", {}).items():
        if phase == "execution_plan":
            print(f"execution plan fingerprint: {per_op}")
            continue
        if phase == "stale":
            print(f"bound plan stale: {per_op}")
            continue
        if phase == "planner":
            print(f"planner: {'/'.join(per_op['search'])} search, "
                  f"{per_op['combos_scored']}/{per_op['product']} "
                  f"combination(s) scored across {per_op['phases']} "
                  f"phase(s) in {per_op['planning_wall_s'] * 1e3:.1f}ms")
            continue
        if phase == "phases":
            for ph, rep in per_op.items():
                line = (f"phase[{ph}]: {rep['score_s'] * 1e6:.1f}us "
                        f"(contention +{rep['contention_s'] * 1e6:.1f}us)")
                if rep.get("budget_s"):
                    line += (f", budget {rep['budget_s'] * 1e6:.0f}us "
                             f"{'ok' if rep.get('budget_ok') else 'VIOLATED'}")
                print(line)
            continue
        if phase == "calibration":
            last = per_op.get("last_recalibration")
            print(f"calibration: drift {per_op['drift_pct']:.1f}% over "
                  f"{per_op['observations']} probe(s), "
                  f"{per_op['recalibrations']} recalibration(s)"
                  + (f", last refit {last['measured_links']} links"
                     if last else ""))
            continue
        for op, rep in per_op.items():
            if not rep:
                continue
            print(f"planner[{phase}/{op}]: {rep['plan']} "
                  f"predicted={rep['predicted_us']:.1f}us "
                  f"vs baseline={rep['baseline_us']:.1f}us "
                  f"({rep['speedup_pct']:+.1f}%)")
    print(out[:, :16])
    finish_exporter_from_args(args, exporter)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
