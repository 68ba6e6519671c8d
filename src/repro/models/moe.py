"""MoE FFN layer built on the MultiWrite hierarchical dispatch.

Token path per layer (DeepSeek-style EP):

  router -> top-k -> hierarchical_dispatch (stage-1 ONE copy per
  (token, remote pod) over DCN, stage-2 relay replication intra-pod)
  -> per-expert gated FFN (TP over the model axis inside each expert)
  -> hierarchical_combine (relay-side partial reduction on the way back)

Scheme selection: the dispatch scheme, the combine (return-path) scheme
and the pipeline chunk count G are ONE jointly-planned decision
(``pctx.moe_pipeline_kwargs``) — resolved by declared-site lookup
against a bound :class:`~repro.core.plan.ExecutionPlan`, or through the
same ``Planner.plan_program`` joint sweep ad hoc under
``plan_policy="auto"`` (payload size + topology decide — the §5.2
dynamic workflow, Fig 8's batch-dependent winner, with both halves of
the round trip scored as one shared chunk pipeline); under "fixed",
``pctx.moe_scheme``/``pctx.moe_combine`` select hierarchical
(MultiWrite) vs baseline (unicast) verbatim — the paper's comparison
pair, selectable per run for the §Perf ablation.  A hierarchical
dispatch may return via relay-reduced partials (hierarchical_combine)
or individual partials (hierarchical_combine_unicast), whichever the
joint ledger scores faster on the active fabric; G > 1 runs the
double-buffered pipeline below — dispatch of chunk k+1 overlaps expert
FFN of chunk k and combine of chunk k-1, bit-exact vs the G == 1 trace.

EP placement: EP spans (pod, data) when the arch has enough experts
(kimi-k2: 384 experts over 32 EP ranks — the paper's large-EP regime);
otherwise EP = the data axis and pod stays pure DP (dbrx: 16 experts).
Without a mesh (pctx=None) the dispatch degenerates to local packing —
the same code path, zero collectives.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import collectives as cl
from repro.models import layers as L
from jax import shard_map
from repro.parallel.context import ParallelContext


def init_moe(key, d: int, f: int, num_experts: int, ep_ranks: int = 1):
    """Router + stacked expert weights [E, ...] (gated FFN)."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    sc_d, sc_f = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "router": L.truncated_normal(kr, (d, num_experts), sc_d),
        "w1": L.truncated_normal(k1, (num_experts, d, f), sc_d),
        "w3": L.truncated_normal(k3, (num_experts, d, f), sc_d),
        "w2": L.truncated_normal(k2, (num_experts, f, d), sc_f),
    }


def moe_specs(pctx: ParallelContext, num_experts: int, fsdp: bool):
    """Experts sharded over the EP axes; expert hidden over model (TP)."""
    use_pod, _ = pctx.ep_ranks(num_experts)
    ep = (("pod", "data") if use_pod and pctx.pod_axis
          else (pctx.data_axis,))
    return {
        "router": P(None, None),
        "w1": P(ep, None, pctx.model_axis),
        "w3": P(ep, None, pctx.model_axis),
        "w2": P(ep, pctx.model_axis, None),
    }


def _expert_ffn(w1, w3, w2, x, act_name: str, model_axis: str | None):
    """Per-expert gated FFN on packed buffers x: [E_l, C, D].
    w*: [E_l, D, F_shard] — row-parallel over model_axis (psum inside)."""
    act = L.activation(act_name)
    dt = x.dtype
    h = act(jnp.einsum("ecd,edf->ecf", x, w1.astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", x, w3.astype(dt))
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(dt))
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out


def balanced_capacities(n_tokens: int, k: int, p: int, d: int,
                        per_rank: int, cf: float) -> cl.DispatchConfig:
    """Capacity factors sized from *balanced-routing expectations* (the
    paper evaluates with load balancing on, §6.1), with headroom ``cf``:

      stage-1 slots/pod     ~ N * min(1, k/p)
      stage-2 slots/ep rank ~ (arrivals p*Cp) * min(1, (k/p)/d)
      expert slots          ~ N*k/per_rank  (total (token,expert) pairs)
    """
    pod_cap = min(1.0, k / p) * cf
    cp = max(1, int(round(n_tokens * pod_cap)))
    ep_cap = min(1.0, (k / p) / d) * cf
    cd = max(1, int(round(p * cp * ep_cap)))
    ce_target = max(1, int(round(n_tokens * k / per_rank * cf)))
    exp_cap = ce_target / (d * cd)
    return cl.DispatchConfig(num_experts=per_rank * p * d, top_k=k,
                             pod_capacity=pod_cap, ep_capacity=ep_cap,
                             expert_capacity=exp_cap)


def unicast_capacities(dcfg: cl.DispatchConfig, n_tokens: int, k: int,
                       ranks: int, per_rank: int,
                       cf: float) -> cl.DispatchConfig:
    """Rebase a :func:`balanced_capacities` config for the UNICAST
    (per-destination-RANK) packing of ``baseline_dispatch``: fair
    capacity is the balanced per-rank expectation (k/R), and
    ``expert_capacity`` — a fraction of the incoming buffer — must be
    renormalized from the hierarchical D*Cd buffer to the unicast R*Cr
    one, or small decode batches round the expert buffer down to zero
    slots.  Kept next to its hierarchical twin so the two sizing rules
    (which both anticipate the callee's ``max(1, round(...))``) evolve
    together."""
    rank_cap = min(1.0, k / ranks) * cf
    cr = max(1, int(round(n_tokens * rank_cap)))
    ce_target = max(1, int(round(n_tokens * k / per_rank * cf)))
    return dataclasses.replace(dcfg, pod_capacity=rank_cap,
                               expert_capacity=ce_target / (ranks * cr))


def load_balance_loss(logits, ids, num_experts: int):
    """Switch-style aux loss: E * sum_i f_i * P_i (local estimate)."""
    probs = jax.nn.softmax(logits, axis=-1)                  # [N, E]
    onehot = jnp.any(ids[..., None] == jnp.arange(num_experts), axis=1)
    f = jnp.mean(onehot.astype(jnp.float32), axis=0)         # fraction routed
    p_mean = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p_mean)


def moe_ffn(params, x, cfg, pctx: ParallelContext | None,
            capacity_factor: float | None = None):
    """x: [B, S, D] -> ([B, S, D], aux_loss).  params from init_moe."""
    b, s, d = x.shape
    dt = x.dtype
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity
    tokens_in = x.reshape(b * s, d)

    if pctx is None:
        epmesh = cl.EPMesh(pod_axis=None, ep_axis="_none", num_pods=1,
                           ep_per_pod=1)
        dcfg = balanced_capacities(b * s, cfg.top_k, 1, 1, cfg.num_experts,
                                   capacity_factor)
        out, aux = _moe_local(params, tokens_in, cfg, dcfg, epmesh)
        return out.reshape(b, s, d).astype(dt), aux

    use_pod, _ = pctx.ep_ranks(cfg.num_experts)
    p = pctx.num_pods if use_pod else 1
    dd = pctx.data_size
    epmesh = cl.EPMesh(
        pod_axis=pctx.pod_axis if use_pod else None,
        ep_axis=pctx.data_axis, num_pods=p, ep_per_pod=dd)
    per_rank = cfg.num_experts // (p * dd)
    ep_spec = ((pctx.pod_axis, pctx.data_axis) if use_pod
               else (pctx.data_axis,))
    dp_spec = pctx.dp_axes
    n_local = (b * s) // (pctx.num_pods * pctx.data_size)
    dcfg = balanced_capacities(n_local, cfg.top_k, p, dd, per_rank,
                               capacity_factor)
    # The WHOLE round trip — dispatch scheme, return-path scheme and the
    # shared pipeline chunk count G — is one jointly-planned decision:
    # a bound ExecutionPlan resolves it by declared-site lookup; under
    # plan_policy="auto" without a bound plan the same joint sweep runs
    # ad hoc (§5.2 dynamic workflow — decode traces pick the unicast
    # pair at small batch, prefill/train cross to MultiWrite, and the
    # shared-pipeline scorer picks the G where overlapping
    # dispatch/compute/combine chunks beats BOTH halves' per-chunk
    # launch alphas); the declared moe_scheme/moe_combine/moe_microbatch
    # knobs apply under "fixed".
    from repro.core.latency_model import moe_overlap_compute_s
    compute_s = moe_overlap_compute_s(n_local, cfg.top_k, d,
                                      params["w1"].shape[-1],
                                      tp=pctx.model_size)
    pipe_kw = pctx.moe_pipeline_kwargs(
        cfg.num_experts, cfg.top_k, tokens_per_rank=n_local,
        token_bytes=d * x.dtype.itemsize, compute_s=compute_s)
    # the chosen G must divide the local token count; gcd clamps it to
    # the largest divisor <= G (pow-2 grids always divide pow-2 batches).
    # A clamp re-resolves the configuration AT the executed G: the
    # scheme pair is taken from the joint sweep's candidates at the
    # depth the pipeline actually runs, not one it never honors.
    microbatch = math.gcd(max(1, int(pipe_kw["microbatch"])),
                          n_local) or 1
    if microbatch != int(pipe_kw["microbatch"]):
        pipe_kw = pctx.moe_pipeline_kwargs(
            cfg.num_experts, cfg.top_k, tokens_per_rank=n_local,
            token_bytes=d * x.dtype.itemsize, compute_s=compute_s,
            microbatch=microbatch)
    scheme = pipe_kw["moe_scheme"]
    combine_scheme = pipe_kw["moe_combine"]
    if scheme == "baseline":
        dcfg = unicast_capacities(dcfg, n_local, cfg.top_k, p * dd,
                                  per_rank, capacity_factor)

    # deferred TP reduction: the combine tree is LINEAR in the expert
    # outputs, so the row-parallel psum commutes through it — emit partial
    # (F-shard) contributions from the experts and reduce ONCE on the
    # final [N, D] result instead of per-layer [E_l, Ce, D] buffers.
    expert_axis = (None if pctx.moe_deferred_tp_reduce
                   else pctx.model_axis)

    # The chunk pipeline is split at the dispatch/compute boundary:
    # ``dispatch_chunk`` routes + issues the dispatch collectives,
    # ``finish_chunk`` runs the expert FFN and the combine.  The three
    # stages run under the named scopes ``moe.dispatch``,
    # ``moe.expert_ffn`` and ``moe.combine``, which the compiled ops'
    # ``op_name`` and a profiler trace carry, so that each planned
    # site's device time can be found by name.  The
    # pipelined ``inner`` below issues the NEXT chunk's dispatch before
    # finishing the previous one, so the dispatch collectives of chunk
    # k+1 have no data dependency on the FFN/combine of chunk k and the
    # compiler overlaps them (double buffering: one chunk in flight).

    def dispatch_chunk(tok, router, w1, w3, w2):
        logits = tok.astype(jnp.float32) @ router
        gates, ids = cl.route_topk(logits, cfg.top_k)
        aux = load_balance_loss(logits, ids, cfg.num_experts)
        aux = jax.lax.pmean(aux, dp_spec)
        dispatch_fn = (cl.hierarchical_dispatch if scheme == "hierarchical"
                       else cl.baseline_dispatch)
        with jax.named_scope("moe.dispatch"):
            exp_tok, exp_gate, st = dispatch_fn(tok, ids, gates, dcfg,
                                                epmesh)
        return (exp_tok, exp_gate, st), aux

    def finish_chunk(pack, w1, w3, w2, out_dtype):
        exp_tok, exp_gate, st = pack
        with jax.named_scope("moe.expert_ffn"):
            exp_out = _expert_ffn(w1, w3, w2, exp_tok, cfg.act, expert_axis)
        if scheme == "hierarchical":
            combine_fn = (cl.hierarchical_combine
                          if combine_scheme == "hierarchical"
                          else cl.hierarchical_combine_unicast)
        else:
            combine_fn = cl.baseline_combine
        with jax.named_scope("moe.combine"):
            out = combine_fn(exp_out, exp_gate, st)
            if pctx.moe_deferred_tp_reduce:
                out = jax.lax.psum(out, pctx.model_axis)
        return out.astype(out_dtype)

    def one_chunk(tok, router, w1, w3, w2):
        pack, aux = dispatch_chunk(tok, router, w1, w3, w2)
        return finish_chunk(pack, w1, w3, w2, tok.dtype), aux

    def inner(tok, router, w1, w3, w2):
        g = microbatch
        if g <= 1:
            return one_chunk(tok, router, w1, w3, w2)
        n_loc, h = tok.shape
        assert n_loc % g == 0, (n_loc, g)
        chunks = tok.reshape(g, n_loc // g, h)
        # software-pipelined chunk loop (double-buffered): the scan body
        # dispatches chunk k+1 FIRST, then finishes chunk k — per-chunk
        # results are identical to the serial loop (bit-exact), only the
        # issue order changes, which is what lets the dispatch traffic
        # hide behind the previous chunk's expert FFN + combine.
        pack0, aux0 = dispatch_chunk(chunks[0], router, w1, w3, w2)

        def body(carry, tok_next):
            pack_next, aux_next = dispatch_chunk(tok_next, router,
                                                 w1, w3, w2)
            out_prev = finish_chunk(carry, w1, w3, w2, tok.dtype)
            return pack_next, (out_prev, aux_next)

        pack_last, (outs, auxs) = jax.lax.scan(body, pack0, chunks[1:])
        out_last = finish_chunk(pack_last, w1, w3, w2, tok.dtype)
        out = jnp.concatenate([outs.reshape(n_loc - n_loc // g, h),
                               out_last], axis=0)
        return out, (aux0 + jnp.sum(auxs)) / g

    out, aux = shard_map(
        inner, mesh=pctx.mesh,
        in_specs=(P(dp_spec, None),            # tokens split over DP ranks
                  P(None, None),               # router replicated
                  P(ep_spec, None, pctx.model_axis),
                  P(ep_spec, None, pctx.model_axis),
                  P(ep_spec, pctx.model_axis, None)),
        out_specs=(P(dp_spec, None), P()),
        check_vma=False,
    )(tokens_in, params["router"].astype(jnp.float32),
      params["w1"], params["w3"], params["w2"])
    return out.reshape(b, s, d).astype(dt), aux


def _moe_local(params, tokens, cfg, dcfg, epmesh):
    """Single-device path (smoke tests): same dispatch code, no axes."""
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates, ids = cl.route_topk(logits, cfg.top_k)
    aux = load_balance_loss(logits, ids, cfg.num_experts)
    exp_tok, exp_gate, st = cl.hierarchical_dispatch(
        tokens, ids, gates, dcfg, epmesh)
    exp_out = _expert_ffn(params["w1"], params["w3"], params["w2"],
                          exp_tok, cfg.act, None)
    return cl.hierarchical_combine(exp_out, exp_gate, st), aux
