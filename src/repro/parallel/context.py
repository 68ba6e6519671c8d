"""Parallelism context: which mesh axes exist and how layers shard.

A :class:`ParallelContext` travels with a model instance.  ``pctx=None``
means single-device (smoke tests); all sharding helpers become no-ops and
the MoE path degenerates to local dispatch.

Axis roles on the production mesh (launch/mesh.py):

  pod    slow inter-pod axis (DCN) — DP, and the outer level of the
         MultiWrite hierarchical EP dispatch.
  data   fast intra-pod axis — DP/FSDP, and EP for MoE layers.
  model  fast intra-pod axis — TP (Megatron col/row), sequence/KV-length
         sharding for decode, optionally subdivided into split-TP domains
         for the §3.1 multiwrite AllGather scenario.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Mesh
    pod_axis: Optional[str] = None    # None on a single-pod mesh
    data_axis: str = "data"
    model_axis: str = "model"
    fsdp: bool = True                 # shard weights over data (ZeRO-3-ish)
    plan_policy: str = "fixed"        # "auto": collective schemes/knobs come
    #   from core.planner.Planner at trace time (the §5.2 dynamic workflow —
    #   scheme choice emerges from payload size + topology + calibration);
    #   "fixed": the explicit knobs below are used verbatim.
    moe_scheme: str = "hierarchical"  # hierarchical (MultiWrite) | baseline
    #                                   (plan_policy="fixed" only)
    moe_combine: Optional[str] = None  # return-path scheme under "fixed":
    #   "hierarchical" (relay-reduced) | "baseline" (unicast return) |
    #   None = follow moe_scheme.  Under "auto" the combine planner op
    #   decides, independently of dispatch.
    fabric: Optional[object] = None   # explicit core.topology.Topology the
    #   planner scores against (--fabric CLI); None = derived from the mesh
    #   shape (pod == server).  Only changes WHICH plan wins — execution
    #   stays on the actual mesh.
    calibration: Optional[object] = None  # telemetry CalibrationStore (or
    #   path) of measured collective timings: planner decisions are scored
    #   under the store's FITTED hardware model for the active fabric
    #   instead of datasheet constants (--calibrate CLI surface).
    moe_skew: float = 0.0             # hot-expert routing skew the planner
    #   prices dispatch/combine under (0 = balanced routing, paper §6.1)
    tp_subgroups: int = 1             # §3.1 split-TP domains on model axis
    remat: str = "full"               # none | selective | full
    seq_shard_decode: bool = True     # shard decode KV length over model
    seq_parallel: bool = True         # Megatron-SP: residual stream's seq
    #                                   dim sharded over model between blocks
    # --- MoE perf levers (§Perf hillclimb; defaults = paper-faithful) -----
    moe_deferred_tp_reduce: bool = False  # move the expert row-parallel
    #   psum ([E_l, Ce, D] per layer) through the LINEAR combine tree to a
    #   single [N, D] psum at the end — ~Ce*E_l/N x fewer model-axis bytes
    moe_microbatch: int = 1           # split dispatch into G chunks,
    #   double-buffered: dispatch of chunk k+1 overlaps expert FFN of
    #   chunk k and combine of chunk k-1 — a latency lever AND a memory
    #   lever (peak dispatch buffers ~2/G of the unchunked size: the
    #   pipeline keeps TWO chunks in flight, vs 1/G for the old serial
    #   chunk loop).  Under plan_policy="auto" the planner's microbatch
    #   knob overrides this — the pipelined scoring mode picks the G
    #   where the overlap win beats the per-chunk alpha.
    execution_plan: Optional[object] = None  # bound
    #   core.plan.ExecutionPlan: the jointly-planned, fingerprinted
    #   verdict for this workload's declared collective program.  Trace-
    #   time consumers (moe_ffn, split-TP gathers) resolve their site by
    #   key lookup against it; sites the program didn't declare fall back
    #   to plan_policy.  Install via ``pctx.bind(plan)``.

    # -- derived -------------------------------------------------------------
    @property
    def dp_axes(self):
        return ((self.pod_axis, self.data_axis) if self.pod_axis
                else (self.data_axis,))

    @property
    def num_pods(self) -> int:
        return self.mesh.shape[self.pod_axis] if self.pod_axis else 1

    @property
    def data_size(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    def ep_ranks(self, num_experts: int) -> tuple[bool, int]:
        """(use_pod_axis, total EP ranks) for an MoE layer: EP spans the pod
        axis only when there are enough experts (the paper's large-EP
        regime); otherwise EP = data axis and pod stays pure DP."""
        if self.pod_axis and num_experts >= self.num_pods * self.data_size:
            return True, self.num_pods * self.data_size
        return False, self.data_size

    # -- planner consumption -------------------------------------------------
    def _plan_topo_hw(self, num_experts: int):
        """(topology, hardware model) the EP planner ops score against:
        the explicit ``fabric`` (or the mesh-derived shape), and — when a
        ``calibration`` store is wired — the store's fitted model for
        that fabric instead of the datasheet DEFAULT."""
        from repro.core.planner import _ep_topology
        use_pod, _ = self.ep_ranks(num_experts)
        topo = _ep_topology(self.num_pods if use_pod else 1,
                            self.data_size, self.fabric)
        hw = None
        if self.calibration is not None:
            from repro.telemetry import calibrated_hw, resolve_store
            hw = calibrated_hw(resolve_store(self.calibration), topo)
        return topo, hw

    # -- declarative collective programs (the bindable planning surface) -----
    def bind(self, plan) -> "ParallelContext":
        """Install a jointly-planned :class:`~repro.core.plan.ExecutionPlan`
        (returns the bound context — the dataclass is frozen).  The plan
        must have been planned for THIS context's fabric: binding a plan
        fingerprinted on a different topology is a deployment bug caught
        here rather than silently mis-executed."""
        if (plan is not None and self.fabric is not None
                and plan.topo_fingerprint != ("pinned",)):
            from repro.core.topology import same_fabric_fingerprint
            fp = self.fabric.fingerprint()
            # failure/recalibration variants of the serving fabric are
            # legitimate bind targets (failover re-binds a plan computed
            # on the surviving-capacity graph); FOREIGN fabrics are not
            if not same_fabric_fingerprint(plan.topo_fingerprint, fp):
                raise ValueError(
                    f"ExecutionPlan {plan.fingerprint} was planned on "
                    f"{plan.topo_fingerprint[0]!r}, but this context's "
                    f"fabric is {fp[0]!r} — replan the program for the "
                    f"active fabric before binding")
        if plan is not None:
            # lazy: repro.telemetry transitively imports the planner this
            # module feeds, so the metrics plane resolves at call time
            from repro.telemetry import metrics as _m
            _m.default_registry()["repro_plan_bind_total"].inc(
                program=plan.program.name, fingerprint=plan.fingerprint)
        return dataclasses.replace(self, execution_plan=plan)

    def moe_sites(self, phase: str, *, num_experts: int, top_k: int,
                  tokens_per_rank: int, token_bytes: int,
                  compute_s: float = 0.0) -> tuple:
        """This context's coupled MoE (dispatch, combine) site pair for
        one phase — skew comes from the declared ``moe_skew`` knob, so a
        program built here prices exactly what the trace-time lookup
        will ask for."""
        from repro.core import plan as plan_ir
        return plan_ir.moe_sites(
            phase, num_experts=num_experts, top_k=top_k,
            tokens_per_rank=tokens_per_rank, token_bytes=token_bytes,
            skew=self.moe_skew, compute_s=compute_s)

    def split_tp_gather_site(self, phase: str, *, global_batch: int,
                             seq_len: int, d_model: int, itemsize: int = 2):
        """The §3.1 split-TP AllGather site this context's transformer
        blocks will issue for one phase (the SP -> TP boundary gather of
        ``_split_tp_seq_gather``), or ``None`` when the geometry emits no
        split-TP gather — mirrors the trace-time guards exactly."""
        m, nd = self.model_size, self.tp_subgroups
        dp = self.num_pods * self.data_size
        if (nd != 2 or not self.seq_parallel or m % nd or seq_len % m
                or global_batch % dp):
            return None
        from repro.core import plan as plan_ir
        from repro.core.topology import split_tp_full_mesh
        frag = (global_batch // dp) * (seq_len // m) * d_model * itemsize
        topo, _ = split_tp_full_mesh(m, tp=m // nd)
        return plan_ir.allgather_site(phase, frag_bytes=frag,
                                      num_domains=nd, topo=topo)

    def grad_sync_site(self, phase: str, *, num_params: int,
                       tokens_per_rank: int):
        """The per-step gradient AllReduce site of one training phase,
        or ``None`` when there are no data-parallel replicas to sync.

        Payload: fp32 gradients of the TP-sharded parameters.  Overlap
        context: the modeled backward-pass time — gradient buckets
        become ready back-to-front during backprop, so a chunked sync
        (microbatch > 1) hides earlier chunks' wire time behind later
        layers' backward compute.  Fabric: the full DP span — gradient
        sync always crosses the pod axis (unlike EP, which stays
        intra-pod for small expert counts)."""
        dp = self.num_pods * self.data_size
        if dp <= 1:
            return None
        from repro.core import plan as plan_ir
        from repro.core.latency_model import backward_compute_s
        from repro.core.planner import _ep_topology
        payload = float(num_params) * 4.0 / max(1, self.model_size)
        compute = backward_compute_s(num_params, tokens_per_rank,
                                     tp=self.model_size)
        topo = _ep_topology(self.num_pods, self.data_size, self.fabric)
        return plan_ir.grad_sync_site(phase, payload_bytes=payload,
                                      compute_s=compute, topo=topo)

    def plan_collectives(self, program):
        """Jointly plan a declared program on this context's fabric and
        calibration: the launch-surface entry point
        (``pctx = pctx.bind(pctx.plan_collectives(program))``)."""
        from repro.core.planner import default_planner
        num_experts = max((dict(s.scenario_kw).get("num_experts", 0)
                           for s in program.sites), default=0)
        topo, hw = self._plan_topo_hw(num_experts)
        return default_planner().plan_program(program, topo, hw)

    def bound_plan_stale(self, planner=None) -> Optional[bool]:
        """Whether the bound ExecutionPlan has been superseded by a
        replan of its program under newer calibration (True), is still
        current (False), or cannot be judged (None: nothing bound, a
        pinned plan, or a program the planner never saw).  The minimal
        observable slice of hot re-binding: until plans swap in-place,
        drift at least becomes VISIBLE at every launch surface."""
        if self.execution_plan is None:
            return None
        if planner is None:
            from repro.core.planner import default_planner
            planner = default_planner()
        return planner.plan_is_stale(self.execution_plan)

    # -- trace-time site resolution ------------------------------------------
    def moe_pipeline_kwargs(self, num_experts: int, top_k: int,
                            tokens_per_rank: int, token_bytes: int,
                            compute_s: float = 0.0,
                            microbatch: Optional[int] = None) -> dict:
        """The full MoE round-trip configuration one layer executes:
        ``{"moe_scheme", "moe_combine", "microbatch"}`` — dispatch
        scheme, return-path scheme and the SHARED pipeline chunk count,
        decided together.

        Resolution order: (1) a bound :class:`ExecutionPlan` whose
        declared dispatch site matches this workload (pure lookup, the
        production path); (2) under ``plan_policy="auto"``, an ad-hoc
        single-phase program through ``Planner.plan_program`` (same
        joint sweep, LRU-cached — undeclared workloads still plan
        jointly); (3) the declared fixed knobs.  The executable-pairing
        constraint (a unicast dispatch leaves no relay state, so its
        return path is unicast) holds on every path.

        ``microbatch`` constrains the result to the chunk count the
        layer actually RUNS: when moe_ffn's divisibility clamp moves G
        off the planned value, it re-resolves here and gets the best
        joint candidate AT the executed G (a scheme pair that only won
        at the planned depth is never executed at one the sweep scored
        worse)."""
        payload = float(tokens_per_rank) * token_bytes
        scen = dict(num_experts=num_experts, top_k=top_k,
                    token_bytes=token_bytes)
        decision = None
        if self.execution_plan is not None:
            role = self.execution_plan.find_role(
                "dispatch", payload, skew=self.moe_skew,
                compute_s=compute_s, **scen)
            if role is not None:
                anchor = self.execution_plan.group_of.get(role)
                decision = (self.execution_plan.joint.get(anchor)
                            if anchor is not None else None)
                if decision is None:
                    kw = self.execution_plan.site_kwargs(role)
                    return self._norm_moe_kwargs(
                        self._kwargs_at_g(None, kw, microbatch))
        if decision is None:
            if self.plan_policy != "auto":
                return self._norm_moe_kwargs(self._kwargs_at_g(
                    None, {"moe_scheme": self.moe_scheme,
                           "moe_combine": self.moe_combine,
                           "microbatch": max(1, int(self.moe_microbatch))},
                    microbatch))
            from repro.core import plan as plan_ir
            sites = self.moe_sites(
                "auto", num_experts=num_experts, top_k=top_k,
                tokens_per_rank=tokens_per_rank, token_bytes=token_bytes,
                compute_s=compute_s)
            eplan = self.plan_collectives(
                plan_ir.CollectiveProgram("moe/auto", sites))
            decision = eplan.joint.get(sites[0].role)
            if decision is None:
                return self._norm_moe_kwargs(self._kwargs_at_g(
                    None, eplan.site_kwargs(sites[0].role), microbatch))
        return self._norm_moe_kwargs(self._kwargs_at_g(
            decision, dict(decision.shard_map_kwargs), microbatch))

    @staticmethod
    def _kwargs_at_g(decision, kwargs: dict,
                     microbatch: Optional[int]) -> dict:
        """Constrain a resolved configuration to an executed chunk count:
        the best JOINT candidate at that G when the decision carries a
        candidate sweep, else the same kwargs with G overridden."""
        if microbatch is None or \
                int(microbatch) == int(kwargs.get("microbatch", 1)):
            return kwargs
        g = max(1, int(microbatch))
        for name, kn, _ in sorted(
                getattr(decision, "candidates", None) or (),
                key=lambda c: c[2]):
            if dict(kn).get("microbatch", 1) != g or "+" not in name:
                continue
            from repro.core import plan as plan_ir
            d_name, _, c_name = name.partition("+")
            kw = plan_ir.get_plan("dispatch", d_name).shard_map_kwargs(
                microbatch=g)
            kw.update(plan_ir.get_plan("combine", c_name).shard_map_kwargs(
                microbatch=g))
            return kw
        return {**kwargs, "microbatch": g}

    @staticmethod
    def _norm_moe_kwargs(kw: dict) -> dict:
        """Normalize a resolved MoE configuration: the combine defaults
        to following the dispatch scheme, and the baseline (unicast)
        dispatch forces the unicast return path (no relay state exists
        for a relay-reduced combine)."""
        scheme = kw.get("moe_scheme", "hierarchical")
        combine = kw.get("moe_combine") or scheme
        if scheme == "baseline":
            combine = "baseline"
        return {"moe_scheme": scheme, "moe_combine": combine,
                "microbatch": max(1, int(kw.get("microbatch", 1)))}

    def allgather_plan(self, frag_bytes: float, num_domains: int = 2):
        """Decision for the §3.1 split-TP AllGather at one traced
        fragment size: bound-plan lookup first, then the planner under
        "auto", ``None`` under "fixed" (the call site keeps the
        paper-faithful analytic knobs)."""
        if self.execution_plan is not None:
            role = self.execution_plan.find_role(
                "allgather", frag_bytes, num_domains=num_domains)
            if role is not None:
                return self.execution_plan.decision(role)
        if self.plan_policy != "auto":
            return None
        from repro.core.planner import default_planner
        from repro.core.topology import split_tp_full_mesh
        n = self.model_size
        topo, _ = split_tp_full_mesh(n, tp=max(1, n // num_domains))
        return default_planner().choose(
            "allgather", float(frag_bytes), topo, executable_only=True,
            num_domains=num_domains)


def build_collective_program(cfg, pctx: ParallelContext, name: str,
                             phases: dict, *, itemsize: int = 2,
                             phase_budgets: Optional[dict] = None):
    """The declared collective program of one launch surface.

    ``phases`` maps a phase name ("train" | "prefill" | "decode") to its
    ``(global_batch, seq_len)`` workload (``seq_len == 1`` for decode).
    Per phase this declares the coupled MoE (dispatch, combine) pair
    (MoE archs) and the split-TP boundary gather (when the context's
    geometry emits one) — exactly the sites the traced model will look
    up, derived from the same shard math the trace uses.  ``itemsize``
    must match the activation dtype the model will TRACE with (bf16
    default; pass 4 for fp32 smoke runs) — site keys embed the payload
    bucket, so a dtype mismatch makes every lookup miss and fall back
    to ad-hoc planning at the wrong payload.

    ``phase_budgets`` (phase name -> seconds) declares per-phase latency
    caps — a decode SLO here constrains the OTHER phases' plans during
    the planner's contention-aware sweep (``--decode-slo-us`` on the
    serve CLI)."""
    from repro.core import plan as plan_ir
    from repro.core.latency_model import moe_overlap_compute_s
    sites = []
    for phase, (global_batch, seq_len) in phases.items():
        if getattr(cfg, "is_moe", False):
            dp = pctx.num_pods * pctx.data_size
            n_rank = max(1, (global_batch * seq_len) // dp)
            d_ff = getattr(cfg, "expert_d_ff", cfg.d_model)
            compute_s = moe_overlap_compute_s(
                n_rank, cfg.top_k, cfg.d_model, d_ff, tp=pctx.model_size)
            sites.extend(pctx.moe_sites(
                phase, num_experts=cfg.num_experts, top_k=cfg.top_k,
                tokens_per_rank=n_rank,
                token_bytes=cfg.d_model * itemsize,
                compute_s=compute_s))
        if seq_len > 1:
            ag = pctx.split_tp_gather_site(
                phase, global_batch=global_batch, seq_len=seq_len,
                d_model=cfg.d_model, itemsize=itemsize)
            if ag is not None:
                sites.append(ag)
        if phase == "train":
            # every optimizer step ends in a gradient AllReduce over the
            # DP replicas — declare it so the planner sweeps its scheme
            # and chunking jointly with the phase's other collectives
            from repro.models.api import param_count_shape_only
            dp = pctx.num_pods * pctx.data_size
            n_rank = max(1, (global_batch * seq_len) // dp)
            gs = pctx.grad_sync_site(
                phase, num_params=param_count_shape_only(cfg),
                tokens_per_rank=n_rank)
            if gs is not None:
                sites.append(gs)
    return plan_ir.CollectiveProgram(name, tuple(sites),
                                     phase_budgets=dict(phase_budgets or {}))


class PlanBinder:
    """Double-buffered :class:`~repro.core.plan.ExecutionPlan` binding
    with a traced-lowering cache keyed on plan fingerprint — the hot
    re-bind mechanic that turns plan churn into a runtime non-event
    (ROADMAP: millions-of-users path).

    ``trace_fn(plan)`` builds the traced/lowered artifact that executes
    under ``plan`` (e.g. jitted prefill/decode closures over the bound
    context).  The binder keeps two buffers:

    - the **active** (plan, artifact) pair the step loop executes;
    - a **pending** plan staged by :meth:`stage` — its artifact is built
      (or found in the cache) at stage time, OFF the step path.

    :meth:`swap_if_pending` is called at step boundaries and is a pure
    pointer swap when the staged lowering is cached (zero cold retraces,
    asserted in tests/test_failover.py).  A swap whose artifact is
    missing — evicted, or staged around the cache — builds it AT the
    swap point and counts it as a cold retrace, so regressions are
    observable rather than silent.  Re-binding to a previously-seen
    fingerprint (recovery flipping back to the pre-failure plan) is a
    cache hit: no retrace at all.
    """

    def __init__(self, trace_fn, plan=None, *, cache_size: int = 8) -> None:
        import collections
        self._trace_fn = trace_fn
        self._cache: "collections.OrderedDict" = collections.OrderedDict()
        self.cache_size = max(1, int(cache_size))
        self.swaps = 0
        self.cold_retraces = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._pending = None          # staged plan awaiting a boundary
        self._active = (None, None)   # (plan, artifact)
        if plan is not None or trace_fn is not None:
            # the initial bind traces at construction (startup, not a
            # swap): the step loop starts with a warm active buffer
            self._active = (plan, self._build(plan))

    @staticmethod
    def _key(plan):
        return plan.fingerprint if plan is not None else None

    @staticmethod
    def _program(plan) -> str:
        return plan.program.name if plan is not None else "none"

    def _metrics(self):
        from repro.telemetry import metrics as _m
        return _m.default_registry()

    def _build(self, plan):
        """Artifact for ``plan`` through the fingerprint-keyed cache."""
        key = self._key(plan)
        if key in self._cache:
            self._cache.move_to_end(key)
            self.cache_hits += 1
            self._metrics()["repro_lowering_cache_hits_total"].inc(
                program=self._program(plan))
            return self._cache[key]
        self.cache_misses += 1
        self._metrics()["repro_lowering_cache_misses_total"].inc(
            program=self._program(plan))
        art = self._trace_fn(plan)
        self._cache[key] = art
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)
        return art

    @property
    def plan(self):
        return self._active[0]

    @property
    def artifact(self):
        return self._active[1]

    @property
    def pending(self) -> bool:
        return self._pending is not None

    def stage(self, plan) -> bool:
        """Stage ``plan`` for the next step boundary, building its
        lowering NOW (double-buffered: the active plan keeps serving
        while the replacement traces).  Returns False when ``plan`` is
        already active with nothing pending — there is nothing to swap."""
        if self._pending is None and self._key(plan) == \
                self._key(self._active[0]):
            return False
        self._build(plan)
        self._pending = plan
        return True

    def swap_if_pending(self) -> bool:
        """Make the staged plan active (call between steps).  A pure
        pointer swap when the staged lowering is cached; a cache miss
        here IS the cold retrace the double-buffering exists to avoid,
        and is counted as such."""
        if self._pending is None:
            return False
        plan = self._pending
        self._pending = None
        key = self._key(plan)
        if key in self._cache:
            self._cache.move_to_end(key)
            art = self._cache[key]
        else:
            self.cold_retraces += 1
            self._metrics()["repro_rebind_cold_retrace_total"].inc(
                program=self._program(plan))
            art = self._build(plan)
        self._active = (plan, art)
        self.swaps += 1
        self._metrics()["repro_plan_rebind_total"].inc(
            program=self._program(plan),
            fingerprint=(plan.fingerprint if plan is not None else "none"))
        return True


def shard(x, pctx: Optional[ParallelContext], *spec):
    """with_sharding_constraint that no-ops without a context."""
    if pctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pctx.mesh, P(*spec)))


def shard_residual(x, pctx: Optional[ParallelContext]):
    """Between-block constraint on the residual stream [B, S, D]:
    SP shards S over model (memory / L x smaller scan-bwd carry stack)."""
    if pctx is None:
        return x
    if pctx.seq_parallel and x.shape[1] % pctx.model_size == 0:
        return shard(x, pctx, pctx.dp_axes, pctx.model_axis, None)
    return shard(x, pctx, pctx.dp_axes, None, None)
