"""8-device equality checks for the shard_map MultiWrite collectives.

Run as a subprocess by tests/test_collectives.py (so the forced device
count never leaks into the main test process):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tests/multidev/check_collectives.py

Prints one line per check; exits nonzero on any failure.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from repro.core import collectives as cl  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402


def check(name, ok):
    print(f"{'PASS' if ok else 'FAIL'} {name}")
    if not ok:
        raise SystemExit(1)


# ===========================================================================
# multiwrite_allgather == reference (paper §5.2 equivalence)
# ===========================================================================

def run_allgather_checks():
    mesh = jax.make_mesh((8,), ("x",))
    rng = np.random.default_rng(0)
    for rows, feat in ((16, 32), (8, 5), (64, 128)):
        x = jnp.asarray(rng.normal(size=(8 * rows, feat)).astype(np.float32))
        for mode, split in [("paired", 0.5), ("paired", 0.25),
                            ("paired", 0.75), ("full", 0.5), ("full", 0.375)]:
            ref_fn = jax.jit(shard_map(
                functools.partial(cl.allgather_reference, axis_name="x"),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                check_vma=False))
            mw_fn = jax.jit(shard_map(
                functools.partial(cl.multiwrite_allgather, axis_name="x",
                                  split=split, mode=mode),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                check_vma=False))
            ref = np.asarray(ref_fn(x))
            got = np.asarray(mw_fn(x))
            ok = np.array_equal(ref, got)
            check(f"allgather mode={mode} split={split} shape=({rows},{feat})",
                  ok)
    # planner-driven path: scheme + split come from Planner.choose at
    # trace time (no hard-coded mode=/split=), result must stay bit-exact.
    # DEFAULT hw + tiny fragment -> the baseline branch; IDEAL hw -> the
    # planner picks multiwrite at ANY size, exercising the mw branch too.
    from repro.core import latency_model as lm
    from repro.core.planner import default_planner
    from repro.core.topology import split_tp_full_mesh
    x = jnp.asarray(rng.normal(size=(8 * 16, 32)).astype(np.float32))
    ref_fn = jax.jit(shard_map(
        functools.partial(cl.allgather_reference, axis_name="x"),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    topo, _ = split_tp_full_mesh(8, tp=4)
    for hw, want_mw in ((None, False), (lm.IDEAL, True)):
        planned_fn = jax.jit(shard_map(
            functools.partial(cl.planned_allgather, axis_name="x", hw=hw),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
        ok = np.array_equal(np.asarray(ref_fn(x)), np.asarray(planned_fn(x)))
        d = default_planner().choose("allgather", x.nbytes // 8, topo, hw,
                                     executable_only=True)
        branch_ok = d.plan.startswith("multiwrite") == want_mw
        check(f"planned_allgather hw={'IDEAL' if hw else 'DEFAULT'} "
              f"(plan={d.plan}) == reference", ok and branch_ok)


# ===========================================================================
# MoE dispatch/combine == dense reference
# ===========================================================================

def moe_reference(tokens, ids, gates, num_experts):
    """Dense oracle: out[t] = sum_k gate * scale(e_k) * token."""
    scale = (np.arange(num_experts) + 1.0) * 0.01
    out = np.zeros_like(tokens, dtype=np.float64)
    for t in range(tokens.shape[0]):
        for kk in range(ids.shape[1]):
            out[t] += gates[t, kk] * scale[ids[t, kk]] * tokens[t]
    return out.astype(np.float32)


def run_dispatch_checks(scheme):
    pods, eps = 2, 4
    mesh = jax.make_mesh((pods, eps), ("pod", "ep"))
    num_experts, k, n_per_chip, h = 16, 4, 24, 8
    epmesh = cl.EPMesh(pod_axis="pod", ep_axis="ep", num_pods=pods,
                       ep_per_pod=eps)
    cfg = cl.DispatchConfig(num_experts=num_experts, top_k=k,
                            pod_capacity=1.0, ep_capacity=1.0,
                            expert_capacity=1.0)
    per_rank = num_experts // (pods * eps)
    n_total = n_per_chip * pods * eps
    rng = np.random.default_rng(7)
    tokens = rng.normal(size=(n_total, h)).astype(np.float32)
    logits = rng.normal(size=(n_total, num_experts)).astype(np.float32)
    gates_np, ids_np = jax.jit(
        functools.partial(cl.route_topk, k=k))(jnp.asarray(logits))
    gates_np, ids_np = np.asarray(gates_np), np.asarray(ids_np)
    ref = moe_reference(tokens, ids_np, gates_np, num_experts)

    def step(tok, ids, gates):
        scale = (jnp.arange(num_experts, dtype=jnp.float32) + 1.0) * 0.01
        my_pod = jax.lax.axis_index("pod")
        my_ep = jax.lax.axis_index("ep")
        my_rank = my_pod * eps + my_ep
        if scheme in ("hierarchical", "hierarchical_unicast_combine"):
            exp_tok, exp_gate, state = cl.hierarchical_dispatch(
                tok, ids, gates, cfg, epmesh)
            local_scale = scale[my_rank * per_rank
                                + jnp.arange(per_rank)][:, None, None]
            combine = (cl.hierarchical_combine_unicast
                       if scheme == "hierarchical_unicast_combine"
                       else cl.hierarchical_combine)
            out = combine(exp_tok * local_scale, exp_gate, state)
        else:
            exp_tok, exp_gate, state = cl.baseline_dispatch(
                tok, ids, gates, cfg, epmesh)
            local_scale = scale[my_rank * per_rank
                                + jnp.arange(per_rank)][:, None, None]
            out = cl.baseline_combine(exp_tok * local_scale, exp_gate, state)
        return out

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(("pod", "ep")), P(("pod", "ep")), P(("pod", "ep"))),
        out_specs=P(("pod", "ep")), check_vma=False))
    got = np.asarray(fn(jnp.asarray(tokens), jnp.asarray(ids_np),
                        jnp.asarray(gates_np)))
    err = np.max(np.abs(got - ref))
    check(f"moe {scheme} dispatch+combine == dense reference (err={err:.2e})",
          err < 1e-4)


# ===========================================================================
# capacity-drop invariants
# ===========================================================================

def run_capacity_checks():
    """With a tight expert capacity, delivered outputs are a masked subset:
    dropped (token, expert) contributions vanish, everything else exact."""
    mesh = jax.make_mesh((2, 4), ("pod", "ep"))
    num_experts, k, n_per_chip, h = 16, 2, 16, 4
    epmesh = cl.EPMesh("pod", "ep", 2, 4)
    cfg = cl.DispatchConfig(num_experts, k, pod_capacity=1.0,
                            ep_capacity=1.0, expert_capacity=0.25)
    per_rank = 2
    rng = np.random.default_rng(3)
    n_total = n_per_chip * 8
    tokens = rng.normal(size=(n_total, h)).astype(np.float32)
    logits = rng.normal(size=(n_total, num_experts)).astype(np.float32)
    gates, ids = cl.route_topk(jnp.asarray(logits), k)

    def step(tok, ids_, gates_):
        my_rank = jax.lax.axis_index("pod") * 4 + jax.lax.axis_index("ep")
        exp_tok, exp_gate, state = cl.hierarchical_dispatch(
            tok, ids_, gates_, cfg, epmesh)
        return cl.hierarchical_combine(exp_tok, exp_gate, state)

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(("pod", "ep")),) * 3,
        out_specs=jax.sharding.PartitionSpec(("pod", "ep")),
        check_vma=False))
    got = np.asarray(fn(jnp.asarray(tokens), ids, gates))
    # identity experts -> out[t] = (sum of surviving gates) * token[t];
    # surviving-gate sum in [0, 1]:
    tok_norm = np.sum(tokens * tokens, axis=1)
    coef = np.sum(got * tokens, axis=1) / np.maximum(tok_norm, 1e-9)
    ok = np.all(coef < 1.0 + 1e-4) and np.all(coef > -1e-4)
    resid = got - coef[:, None] * tokens
    ok = ok and float(np.max(np.abs(resid))) < 1e-4
    check("moe capacity drop keeps outputs a gated subset", ok)


# ===========================================================================
# layers.split_tp_allgather (tp_subgroups path through the planner)
# ===========================================================================

def run_split_tp_layer_checks():
    import dataclasses

    from repro.models import layers as L
    from repro.parallel.context import ParallelContext

    mesh = jax.make_mesh((8,), ("x",))
    pctx = ParallelContext(mesh=mesh, pod_axis=None, data_axis="x",
                           model_axis="x", tp_subgroups=2)
    rng = np.random.default_rng(4)
    for rows, feat in ((16, 32), (8, 5)):
        x = jnp.asarray(rng.normal(size=(8 * rows, feat)).astype(np.float32))
        ref_fn = jax.jit(shard_map(
            functools.partial(cl.allgather_reference, axis_name="x",
                              num_domains=2),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
        ref = np.asarray(ref_fn(x))
        for policy in ("fixed", "auto"):
            p = dataclasses.replace(pctx, plan_policy=policy)
            fn = jax.jit(shard_map(
                functools.partial(L.split_tp_allgather, pctx=p),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                check_vma=False))
            got = np.asarray(fn(x))
            check(f"layers.split_tp_allgather policy={policy} "
                  f"shape=({rows},{feat}) == reference",
                  np.array_equal(ref, got))


# ===========================================================================
# pipelined moe_ffn (microbatch G > 1, double-buffered) == serial G == 1
# ===========================================================================

def run_moe_pipeline_checks():
    import dataclasses
    import types

    from repro.models.moe import init_moe, moe_ffn
    from repro.parallel.context import ParallelContext

    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = types.SimpleNamespace(num_experts=8, top_k=2, act="silu",
                                moe_capacity=4.0)
    d_model, f = 16, 32
    params = init_moe(jax.random.key(0), d_model, f, cfg.num_experts)
    rng = np.random.default_rng(5)
    # b*s = 64 -> n_local = 16 per (pod, data) rank, divisible by G = 4
    x = jnp.asarray(rng.normal(size=(4, 16, d_model)).astype(np.float32))
    base = ParallelContext(mesh=mesh, pod_axis="pod", data_axis="data",
                           model_axis="model", plan_policy="fixed")
    # both dispatch schemes x both combine schemes (baseline dispatch has
    # no relay to reduce at, so its return path is always unicast)
    combos = [("hierarchical", "hierarchical"),
              ("hierarchical", "baseline"),
              ("baseline", "baseline")]
    for scheme, combine in combos:
        outs, auxs, drops = {}, {}, {}
        for g in (1, 4):
            pctx = dataclasses.replace(base, moe_scheme=scheme,
                                       moe_combine=combine,
                                       moe_microbatch=g)
            with mesh:
                out, aux, dropped = jax.jit(
                    lambda xx, p=pctx: moe_ffn(params, xx, cfg, p))(x)
            outs[g], auxs[g] = np.asarray(out), float(aux)
            drops[g] = int(np.sum(dropped))
        ok = np.array_equal(outs[1], outs[4])
        err = float(np.max(np.abs(outs[1] - outs[4])))
        check(f"moe_ffn pipelined G=4 bit-exact vs G=1 "
              f"(dispatch={scheme}, combine={combine}, err={err:.1e})", ok)
        check(f"moe_ffn pipelined aux finite "
              f"(dispatch={scheme}, combine={combine})",
              np.isfinite(auxs[4]) and np.isfinite(auxs[1]))
        check(f"moe_ffn drops nothing at capacity 4.0, G=1 and G=4 "
              f"(dispatch={scheme}, combine={combine}, dropped={drops})",
              drops == {1: 0, 4: 0})


# ===========================================================================
# moe_ffn under a bound ExecutionPlan == the legacy knob/resolve path
# ===========================================================================

def run_execution_plan_checks():
    import dataclasses
    import types

    from repro.core import plan as plan_ir
    from repro.core.latency_model import moe_overlap_compute_s
    from repro.models.moe import init_moe, moe_ffn
    from repro.parallel.context import ParallelContext

    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = types.SimpleNamespace(num_experts=8, top_k=2, act="silu",
                                moe_capacity=4.0)
    d_model, f = 16, 32
    params = init_moe(jax.random.key(0), d_model, f, cfg.num_experts)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 16, d_model)).astype(np.float32))
    base = ParallelContext(mesh=mesh, pod_axis="pod", data_axis="data",
                           model_axis="model", plan_policy="fixed")
    # the EXACT workload moe_ffn derives at trace time (fp32 tokens)
    n_local = (4 * 16) // (2 * 2)
    token_bytes = d_model * 4
    compute_s = moe_overlap_compute_s(n_local, cfg.top_k, d_model, f, tp=2)

    def run(pctx):
        with mesh:
            out, aux, _ = jax.jit(
                lambda xx, p=pctx: moe_ffn(params, xx, cfg, p))(x)
        return np.asarray(out), float(aux)

    combos = [("hierarchical", "hierarchical", 4),
              ("hierarchical", "baseline", 4),
              ("baseline", "baseline", 2)]
    for scheme, combine, g in combos:
        legacy = dataclasses.replace(base, moe_scheme=scheme,
                                     moe_combine=combine,
                                     moe_microbatch=g)
        sites = legacy.moe_sites("train", num_experts=cfg.num_experts,
                                 top_k=cfg.top_k, tokens_per_rank=n_local,
                                 token_bytes=token_bytes,
                                 compute_s=compute_s)
        program = plan_ir.CollectiveProgram("train", sites)
        pinned = plan_ir.pinned_execution_plan(
            program, {"train/moe_dispatch": {"moe_scheme": scheme,
                                             "moe_combine": combine,
                                             "microbatch": g}})
        # bound context declares CONTRASTING knobs: only the plan lookup
        # can produce the pinned configuration
        bound = dataclasses.replace(base, moe_scheme="baseline",
                                    moe_microbatch=1).bind(pinned)
        got = bound.moe_pipeline_kwargs(cfg.num_experts, cfg.top_k,
                                        tokens_per_rank=n_local,
                                        token_bytes=token_bytes,
                                        compute_s=compute_s)
        check(f"bound-plan lookup hit (dispatch={scheme}, combine={combine}"
              f", G={g})",
              got == {"moe_scheme": scheme, "moe_combine": combine,
                      "microbatch": g})
        out_legacy, aux_legacy = run(legacy)
        out_bound, aux_bound = run(bound)
        ok = np.array_equal(out_legacy, out_bound)
        err = float(np.max(np.abs(out_legacy - out_bound)))
        check(f"moe_ffn bound ExecutionPlan bit-exact vs legacy knobs "
              f"(dispatch={scheme}, combine={combine}, G={g}, "
              f"err={err:.1e})", ok)
        check(f"moe_ffn bound aux matches (dispatch={scheme}, "
              f"combine={combine})", aux_legacy == aux_bound)

    # a genuinely PLANNED bind agrees bit-exactly with the ad-hoc auto
    # path (same joint decisions, different resolution mechanism)
    auto = dataclasses.replace(base, plan_policy="auto")
    program = plan_ir.CollectiveProgram(
        "train", auto.moe_sites("train", num_experts=cfg.num_experts,
                                top_k=cfg.top_k, tokens_per_rank=n_local,
                                token_bytes=token_bytes,
                                compute_s=compute_s))
    eplan = auto.plan_collectives(program)
    out_bound, _ = run(auto.bind(eplan))
    out_auto, _ = run(auto)
    check("moe_ffn planned bind bit-exact vs ad-hoc auto "
          f"[{eplan.fingerprint}]", np.array_equal(out_bound, out_auto))


# ===========================================================================
# telemetry LiveProbe: every executable plan's lowering times on the mesh
# ===========================================================================

def run_live_probe_checks():
    from repro.core import plan as plan_ir
    from repro.core.topology import two_server_cluster
    from repro.telemetry import LiveProbe, probe_sweep

    mesh = jax.make_mesh((2, 4), ("pod", "ep"))
    probe = LiveProbe(mesh, axis_name="ep", ep_axis="ep", pod_axis="pod",
                      repeats=1, warmup=1)
    topo = two_server_cluster(npus_per_server=4, num_servers=2)
    records = probe_sweep(topo, probe,
                          payloads={"allgather": (1 << 16,),
                                    "dispatch": (32 * 512,),
                                    "combine": (32 * 512,)},
                          token_bytes=512, num_experts=16, top_k=4)
    by_op = {}
    for r in records:
        by_op.setdefault(r["op"], []).append(r)
        check(f"live probe {r['op']}/{r['plan']} timed "
              f"({r['measured_s']*1e3:.1f}ms, source={r['source']})",
              np.isfinite(r["measured_s"]) and r["measured_s"] > 0
              and r["source"] == "live")
    executable = {op: len([p for p in plan_ir.plans_for(
        op, executable_only=True)]) for op in ("allgather", "dispatch",
                                               "combine")}
    for op, n in executable.items():
        check(f"live probe covered all {n} executable {op} plans",
              len(by_op.get(op, [])) == n)

    # directed p2p rail microbenchmark on the live mesh (per ordered
    # server pair — the probe that fits never-bottlenecking directions)
    from repro.telemetry import probe_link_directions
    drecords = probe_link_directions(topo, probe, payloads=(1 << 16,))
    roles = sorted(r["bottleneck_role"] for r in drecords)
    check(f"live directed probes cover both rail directions ({roles})",
          roles == ["inter:0>1", "inter:1>0"]
          and all(r["measured_s"] > 0 for r in drecords))


# ===========================================================================
# transformer block: SP gather routed through split_tp_allgather
# (tp_subgroups > 1) must not change the forward pass
# ===========================================================================

def run_split_tp_block_checks():
    import dataclasses

    from repro.configs.base import get_config
    from repro.models.api import build_model
    from repro.parallel.context import ParallelContext

    cfg = get_config("mistral_nemo_12b").reduced(
        n_layers=2, d_model=64, n_heads=8, n_kv_heads=8, d_ff=128,
        vocab=256)
    mesh = make_test_mesh((1, 8), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, pod_axis=None, data_axis="data",
                           model_axis="model", fsdp=False, remat="none",
                           seq_parallel=True)
    rng = np.random.default_rng(11)
    batch = {"tokens": jnp.asarray(rng.integers(0, 256, (2, 64)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 256, (2, 64)),
                                   jnp.int32)}
    outs = {}
    for nd in (1, 2, 4):
        p = dataclasses.replace(pctx, tp_subgroups=nd)
        model = build_model(cfg, p, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        with mesh:
            loss, metrics = jax.jit(model.loss)(params, batch)
        outs[nd] = float(loss)
    # nd=2/4 route every block's SP boundary gather through
    # layers.split_tp_allgather (hierarchical: intra-domain multiwrite
    # gather + one cross-domain gather); nd=1 is the implicit GSPMD path.
    for nd in (2, 4):
        ok = np.isfinite(outs[nd]) and abs(
            outs[nd] - outs[1]) <= 1e-4 * max(1.0, abs(outs[1]))
        check(f"transformer block split-TP gather tp_subgroups={nd} "
              f"matches tp_subgroups=1 (loss {outs[nd]:.6f} vs "
              f"{outs[1]:.6f})", ok)


if __name__ == "__main__":
    assert len(jax.devices()) == 8, jax.devices()
    run_allgather_checks()
    run_dispatch_checks("hierarchical")
    run_dispatch_checks("hierarchical_unicast_combine")
    run_dispatch_checks("baseline")
    run_capacity_checks()
    run_moe_pipeline_checks()
    run_execution_plan_checks()
    run_split_tp_layer_checks()
    run_split_tp_block_checks()
    run_live_probe_checks()
    print("ALL OK")
