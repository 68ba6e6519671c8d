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
                        per_rank: int, cf: float,
                        unicast: bool = False) -> cl.DispatchConfig:
    """Capacity factors sized from *balanced-routing expectations* (the
    paper evaluates with load balancing on, §6.1), with headroom ``cf``:

      stage-1 slots/pod     ~ N * min(1, k/p)
      stage-2 slots/ep rank ~ (arrivals p*Cp) * min(1, (k/p)/d)
      expert slots          ~ N*k/per_rank  (total (token,expert) pairs)

    ``unicast`` sizes the per-destination-RANK packing of
    ``baseline_dispatch`` instead: the balanced per-rank expectation
    (k/R over R = p*d ranks), and ``expert_capacity`` — a share of the
    incoming buffer — renormalized from the hierarchical D*Cd buffer to
    the unicast R*Cr one, or small decode batches round the expert
    buffer down to zero slots.  The factors may pass 1, and
    :func:`repro.core.collectives.capped_capacities` caps each stage at
    the rows that can arrive before a dispatch runs them."""
    ce_target = max(1, int(round(n_tokens * k / per_rank * cf)))
    if unicast:
        rank_cap = min(1.0, k / (p * d)) * cf
        cr = max(1, int(round(n_tokens * rank_cap)))
        return cl.DispatchConfig(num_experts=per_rank * p * d, top_k=k,
                                 pod_capacity=rank_cap,
                                 expert_capacity=ce_target / (p * d * cr))
    pod_cap = min(1.0, k / p) * cf
    cp = max(1, int(round(n_tokens * pod_cap)))
    ep_cap = min(1.0, (k / p) / d) * cf
    cd = max(1, int(round(p * cp * ep_cap)))
    exp_cap = ce_target / (d * cd)
    return cl.DispatchConfig(num_experts=per_rank * p * d, top_k=k,
                             pod_capacity=pod_cap, ep_capacity=ep_cap,
                             expert_capacity=exp_cap)


def load_balance_loss(logits, ids, num_experts: int):
    """Switch-style aux loss: E * sum_i f_i * P_i (local estimate)."""
    probs = jax.nn.softmax(logits, axis=-1)                  # [N, E]
    onehot = jnp.any(ids[..., None] == jnp.arange(num_experts), axis=1)
    f = jnp.mean(onehot.astype(jnp.float32), axis=0)         # fraction routed
    p_mean = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p_mean)


@dataclasses.dataclass(frozen=True)
class MoEPlan:
    """What one MoE layer runs for a token count, decided on static
    shapes: the dispatch and return-path schemes, the pipeline chunk
    count G, the rows padded on so that the data-parallel ranks split
    the tokens evenly (0 where they divide), the dispatch capacities of
    one chunk and the expert-parallel mesh they run on."""
    dispatch: str            # "hierarchical" (MultiWrite) or "baseline"
    combine: str             # "hierarchical" (relay-reduced) or "baseline"
    g: int
    pad_rows: int
    n_local: int             # token rows per rank, padding included
    dcfg: cl.DispatchConfig
    mesh: cl.EPMesh

    @property
    def slots(self) -> tuple[int, ...]:
        """Slots each dispatch stage allocates per chunk a chip: (Cp,
        Cd, Ce) MultiWrite, (Cr, Ce) unicast."""
        return cl.dispatch_slots(self.dcfg, self.mesh, self.n_local // self.g,
                                 unicast=self.dispatch == "baseline")

    def attrs(self) -> dict:
        """The plan as span attributes (``engine.prefill`` /
        ``engine.decode``)."""
        return {"moe_dispatch": self.dispatch, "moe_combine": self.combine,
                "moe_g": self.g, "ep_pad_rows": self.pad_rows,
                "moe_slots": "/".join(map(str, self.slots))}


def _ep_mesh(cfg, pctx: ParallelContext | None) -> cl.EPMesh:
    """The expert-parallel slice of the mesh the experts are placed on:
    (pod, data) where the arch has enough experts, else data alone."""
    if pctx is None:
        return cl.EPMesh(pod_axis=None, ep_axis="_none", num_pods=1,
                         ep_per_pod=1)
    use_pod, _ = pctx.ep_ranks(cfg.num_experts)
    return cl.EPMesh(pod_axis=pctx.pod_axis if use_pod else None,
                     ep_axis=pctx.data_axis,
                     num_pods=pctx.num_pods if use_pod else 1,
                     ep_per_pod=pctx.data_size)


def plan_moe(cfg, pctx: ParallelContext | None, n_tokens: int, d: int,
             f: int, itemsize: int) -> MoEPlan:
    """The plan :func:`moe_ffn` runs for ``n_tokens`` rows of width ``d``
    (activation ``itemsize`` bytes) through experts of hidden width
    ``f``, at the headroom ``cfg.moe_capacity``.

    The WHOLE round trip — dispatch scheme, return-path scheme and the
    shared pipeline chunk count G — is one jointly-planned decision: a
    bound ExecutionPlan resolves it by declared-site lookup; under
    plan_policy="auto" without a bound plan the same joint sweep runs ad
    hoc (§5.2 dynamic workflow — decode traces pick the unicast pair at
    small batch, prefill/train cross to MultiWrite, and the
    shared-pipeline scorer picks the G where overlapping
    dispatch/compute/combine chunks beats BOTH halves' per-chunk launch
    alphas); the declared moe_scheme/moe_combine/moe_microbatch knobs
    apply under "fixed".

    The capacities are sized for the rows per rank, which padding does
    not raise past the real rows of the fullest rank (padded rows take
    no slot), and the slots are those of one chunk of them."""
    epm = _ep_mesh(cfg, pctx)
    if pctx is None:
        return _chunk_capacities(cfg, epm, "hierarchical", "hierarchical",
                                 1, 0, n_tokens)
    ranks = pctx.num_pods * pctx.data_size
    pad = -n_tokens % ranks
    n_local = (n_tokens + pad) // ranks
    from repro.core.latency_model import moe_overlap_compute_s
    compute_s = moe_overlap_compute_s(n_local, cfg.top_k, d, f,
                                      tp=pctx.model_size)
    kw = dict(tokens_per_rank=n_local, token_bytes=d * itemsize,
              compute_s=compute_s)
    pipe_kw = pctx.moe_pipeline_kwargs(cfg.num_experts, cfg.top_k, **kw)
    # the chosen G must divide the local token count; gcd clamps it to
    # the largest divisor <= G (pow-2 grids always divide pow-2 batches).
    # A clamp re-resolves the configuration AT the executed G: the
    # scheme pair is taken from the joint sweep's candidates at the
    # depth the pipeline actually runs, not one it never honors.
    microbatch = math.gcd(max(1, int(pipe_kw["microbatch"])), n_local) or 1
    if microbatch != int(pipe_kw["microbatch"]):
        pipe_kw = pctx.moe_pipeline_kwargs(cfg.num_experts, cfg.top_k,
                                           microbatch=microbatch, **kw)
    return _chunk_capacities(cfg, epm, pipe_kw["moe_scheme"],
                             pipe_kw["moe_combine"], microbatch, pad, n_local)


def _chunk_capacities(cfg, epm: cl.EPMesh, dispatch: str, combine: str,
                      g: int, pad: int, n_local: int) -> MoEPlan:
    """The plan with its capacities: the balanced load of ``n_local``
    rows a rank at headroom ``cfg.moe_capacity``, capped at what can
    arrive in one chunk of ``n_local // g`` of them."""
    unicast = dispatch == "baseline"
    headroom = balanced_capacities(
        n_local, cfg.top_k, epm.num_pods, epm.ep_per_pod,
        cfg.num_experts // epm.num_ranks, cfg.moe_capacity, unicast)
    return MoEPlan(dispatch, combine, g, pad, n_local,
                   cl.capped_capacities(headroom, epm, n_local // g, unicast),
                   epm)


def _placed(state) -> jax.Array:
    """(token, expert) pairs that reached an expert slot on this rank."""
    return jnp.sum(state.map_exp >= 0, dtype=jnp.int32)


def moe_ffn(params, x, cfg, pctx: ParallelContext | None):
    """x: [B, S, D] -> ([B, S, D], aux_loss, dropped).  params from
    init_moe.  ``dropped`` [R] int32, one count per data-parallel rank
    (R = 1 without a mesh), adds up to the (token, expert) pairs of the
    real rows that found no slot at any stage of the dispatch; it is
    summed where it is read, so that counting adds no collective."""
    b, s, d = x.shape
    dt = x.dtype
    n = b * s
    tokens_in = x.reshape(n, d)
    plan = plan_moe(cfg, pctx, n, d, params["w1"].shape[-1],
                    x.dtype.itemsize)
    dcfg, epmesh = plan.dcfg, plan.mesh

    if pctx is None:
        out, aux, dropped = _moe_local(params, tokens_in, cfg, dcfg, epmesh)
        return out.reshape(b, s, d).astype(dt), aux, dropped

    ep_spec = ((epmesh.pod_axis, pctx.data_axis) if epmesh.pod_axis
               else (pctx.data_axis,))
    dp_spec = pctx.dp_axes
    n_local = plan.n_local
    # rows that do not divide over the data-parallel ranks are padded to
    # the next multiple and masked out of the dispatch; where they divide
    # (decided here, on static shapes) the program has no pad, mask or
    # slice at all
    if plan.pad_rows:
        tokens_in = jnp.pad(tokens_in, ((0, plan.pad_rows), (0, 0)))

    def real_rows(k, rows):
        """[rows] bool: which rows of this rank's chunk ``k`` are real
        (padding sits past row ``n`` of the whole batch); None where
        nothing is padded."""
        if not plan.pad_rows:
            return None
        first = jax.lax.axis_index(dp_spec) * n_local + k * rows
        return first + jnp.arange(rows) < n

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
    # Each chunk also counts the (token, expert) pairs its real rows
    # ask for less those that reached an expert slot on this rank;
    # summed over the ranks, that is what the dispatch dropped.

    def dispatch_chunk(tok, k, router, w1, w3, w2):
        logits = tok.astype(jnp.float32) @ router
        gates, ids = cl.route_topk(logits, cfg.top_k)
        aux = load_balance_loss(logits, ids, cfg.num_experts)
        aux = jax.lax.pmean(aux, dp_spec)
        dispatch_fn = (cl.hierarchical_dispatch
                       if plan.dispatch == "hierarchical"
                       else cl.baseline_dispatch)
        valid = real_rows(k, tok.shape[0])
        with jax.named_scope("moe.dispatch"):
            exp_tok, exp_gate, st = dispatch_fn(tok, ids, gates, dcfg,
                                                epmesh, valid)
        rows = (tok.shape[0] if valid is None
                else jnp.sum(valid, dtype=jnp.int32))
        dropped = rows * cfg.top_k - _placed(st)
        return (exp_tok, exp_gate, st), aux, dropped

    def finish_chunk(pack, w1, w3, w2, out_dtype):
        exp_tok, exp_gate, st = pack
        with jax.named_scope("moe.expert_ffn"):
            exp_out = _expert_ffn(w1, w3, w2, exp_tok, cfg.act, expert_axis)
        if plan.dispatch == "hierarchical":
            combine_fn = (cl.hierarchical_combine
                          if plan.combine == "hierarchical"
                          else cl.hierarchical_combine_unicast)
        else:
            combine_fn = cl.baseline_combine
        with jax.named_scope("moe.combine"):
            out = combine_fn(exp_out, exp_gate, st)
            if pctx.moe_deferred_tp_reduce:
                out = jax.lax.psum(out, pctx.model_axis)
        return out.astype(out_dtype)

    def inner(tok, router, w1, w3, w2):
        g = plan.g
        if g <= 1:
            pack, aux, dropped = dispatch_chunk(tok, 0, router, w1, w3, w2)
            out = finish_chunk(pack, w1, w3, w2, tok.dtype)
            return out, aux, dropped[None]
        n_loc, h = tok.shape
        assert n_loc % g == 0, (n_loc, g)
        chunks = tok.reshape(g, n_loc // g, h)
        # software-pipelined chunk loop (double-buffered): the scan body
        # dispatches chunk k+1 FIRST, then finishes chunk k — per-chunk
        # results are identical to the serial loop (bit-exact), only the
        # issue order changes, which is what lets the dispatch traffic
        # hide behind the previous chunk's expert FFN + combine.  The
        # chunk index rides along only where rows are masked.
        pack0, aux0, drop0 = dispatch_chunk(chunks[0], 0, router,
                                            w1, w3, w2)

        def body(carry, xs):
            tok_next, k = xs
            pack_next, aux_next, drop_next = dispatch_chunk(
                tok_next, k, router, w1, w3, w2)
            out_prev = finish_chunk(carry, w1, w3, w2, tok.dtype)
            return pack_next, (out_prev, aux_next, drop_next)

        ks = jnp.arange(1, g) if plan.pad_rows else None
        pack_last, (outs, auxs, drops) = jax.lax.scan(
            body, pack0, (chunks[1:], ks))
        out_last = finish_chunk(pack_last, w1, w3, w2, tok.dtype)
        out = jnp.concatenate([outs.reshape(n_loc - n_loc // g, h),
                               out_last], axis=0)
        dropped = drop0 + jnp.sum(drops)
        return out, (aux0 + jnp.sum(auxs)) / g, dropped[None]

    out, aux, dropped = shard_map(
        inner, mesh=pctx.mesh,
        in_specs=(P(dp_spec, None),            # tokens split over DP ranks
                  P(None, None),               # router replicated
                  P(ep_spec, None, pctx.model_axis),
                  P(ep_spec, None, pctx.model_axis),
                  P(ep_spec, pctx.model_axis, None)),
        out_specs=(P(dp_spec, None), P(), P(dp_spec)),
        check_vma=False,
    )(tokens_in, params["router"].astype(jnp.float32),
      params["w1"], params["w3"], params["w2"])
    if plan.pad_rows:
        out = out[:n]
    return out.reshape(b, s, d).astype(dt), aux, dropped


def _moe_local(params, tokens, cfg, dcfg, epmesh):
    """Single-device path (smoke tests): same dispatch code, no axes."""
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates, ids = cl.route_topk(logits, cfg.top_k)
    aux = load_balance_loss(logits, ids, cfg.num_experts)
    exp_tok, exp_gate, st = cl.hierarchical_dispatch(
        tokens, ids, gates, dcfg, epmesh)
    exp_out = _expert_ffn(params["w1"], params["w3"], params["w2"],
                          exp_tok, cfg.act, None)
    dropped = tokens.shape[0] * cfg.top_k - _placed(st)
    return cl.hierarchical_combine(exp_out, exp_gate, st), aux, dropped[None]
