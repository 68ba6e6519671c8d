"""Batched serving engine: prefill + KV-cache decode.

Production shape: requests are padded into fixed batch slots, prefilled
once, then decoded step-by-step with the jitted decode function (cache
donated each step).  Greedy or temperature sampling.  Per-slot stop
handling; slots keep decoding until all hit max_new or EOS (static-shape
friendly — finished slots are masked, not removed).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import spans


def _metrics():
    """The process-wide metrics registry, looked up where an event
    happens."""
    from repro.telemetry import metrics as _m
    return _m.default_registry()


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 = greedy
    eos_id: Optional[int] = None
    cache_dtype: object = jnp.bfloat16


@dataclasses.dataclass
class _ServeLowering:
    """Traced-lowering artifact of one ExecutionPlan: the jitted
    prefill/decode pair whose MoE layers consumed exactly that plan's
    decisions at trace time, plus the bound context they read them
    from, the jitted cache builder with the cache shapes it has
    compiled (``(batch, max_len, dtype)``), and the MoE plan its layers
    run per token count, as far as the spans have asked for it."""
    pctx: object
    model: object
    prefill: Callable
    decode: Callable
    init_cache: Callable
    cache_shapes: set = dataclasses.field(default_factory=set)
    moe_plans: dict = dataclasses.field(default_factory=dict)


def _moe_attrs(lowering: _ServeLowering, n_tokens: int, stats) -> dict:
    """Span attributes of an MoE launch of ``n_tokens`` token rows: the
    (token, expert) pairs its dispatch dropped (the per-rank counts of
    the step's ``stats``, read after ``engine.wait``, when the device
    has finished them) and the plan its layers ran."""
    plan = lowering.moe_plans.get(n_tokens)
    if plan is None:
        plan = lowering.moe_plans[n_tokens] = lowering.model.moe_plan(
            n_tokens)
    return {"moe_dropped": int(np.asarray(stats["moe_dropped"]).sum()),
            **plan.attrs()}


@dataclasses.dataclass
class CohortState:
    """In-flight decode state of one cohort (one prefill's worth of
    requests, position-aligned): the KV/recurrent cache, the last
    logits, and the sampling key."""
    cache: object
    logits: object
    key: object
    batch: int
    cohort: int = 0               # id, in the order the engine started it


class ServeEngine:
    def __init__(self, model, params, cfg: ServeConfig = ServeConfig(),
                 pctx=None, fabric=None, calibration=None, monitor=None,
                 model_builder=None):
        """``fabric``: optional fabric spec/name (see
        ``core.topology.get_fabric``) the planner scores against instead
        of the mesh-derived shape — the serving side of ``--fabric``.
        ``calibration``: optional telemetry CalibrationStore (or path):
        planner decisions are scored under the store's fitted hardware
        model.  ``monitor``: optional telemetry DriftMonitor whose
        predicted-vs-measured state ``plan_report`` surfaces.
        ``model_builder``: optional ``pctx -> Model`` rebuilding the
        model functions against a re-bound context (defaults to
        ``models.api.build_model`` on the same config) — what
        :meth:`rebind` traces when a replan swaps in."""
        self.model = model
        self.params = params
        self.cfg = cfg
        if pctx is not None and (fabric is not None
                                 or calibration is not None):
            import dataclasses as _dc

            from repro.core.topology import get_fabric
            repl = {}
            if fabric is not None:
                repl["fabric"] = (get_fabric(fabric)
                                  if isinstance(fabric, str) else fabric)
            if calibration is not None:
                repl["calibration"] = calibration
            pctx = _dc.replace(pctx, **repl)
        self.pctx = pctx
        self.monitor = monitor
        self._model_builder = model_builder
        from repro.parallel.context import PlanBinder
        initial = pctx.execution_plan if pctx is not None else None
        self._binder = PlanBinder(self._trace_plan, plan=initial)
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "tokens": 0}
        self._cohort_ids = itertools.count()
        self._stale_warned = False
        # (batch, prompt_len)-keyed memos: per-step scheduler queries
        # (plan_report, admission probes) must never re-derive the
        # program or re-plan — the planner LRU stays warm and these
        # stay O(1) on the hot path
        self._programs: dict = {}
        self._plan_cache: dict = {}
        self._probe = None

    # -- hot plan re-bind -----------------------------------------------------
    def _trace_plan(self, plan) -> _ServeLowering:
        """PlanBinder trace_fn: (re)build + jit the phase functions under
        ``plan``.  The initial bind reuses the already-constructed model
        (serve.py binds the plan before building it, so its closures
        consumed exactly this plan); a re-bind constructs fresh model
        closures over the newly-bound context so the next trace reads
        the new decisions."""
        base_plan = self.pctx.execution_plan if self.pctx is not None \
            else None
        if plan is base_plan or self.pctx is None:
            pctx, model = self.pctx, self.model
        else:
            pctx = self.pctx.bind(plan)
            if self._model_builder is not None:
                model = self._model_builder(pctx)
            else:
                from repro.models.api import build_model
                model = build_model(self.model.cfg, pctx)
        # the cache is built by one compiled program per cohort shape:
        # eager, every buffer of it is a dispatch of its own
        return _ServeLowering(
            pctx=pctx, model=model, prefill=jax.jit(model.prefill),
            decode=jax.jit(model.decode, donate_argnums=(2,)),
            init_cache=jax.jit(model.init_cache, static_argnums=(0, 1, 2)))

    def rebind(self, plan) -> bool:
        """Stage ``plan`` (e.g. a failover replan from the drift
        monitor) for hot re-bind: its lowering is built NOW, off the
        request path, and swapped in atomically at the next
        :meth:`generate` entry.  Returns True when a swap is pending."""
        self.invalidate_plan_cache()
        return self._binder.stage(plan)

    @property
    def plan_binder(self):
        return self._binder

    @property
    def _prefill(self):
        return self._binder.artifact.prefill

    @property
    def _decode(self):
        return self._binder.artifact.decode

    def serving_program(self, batch: int, prompt_len: int):
        """The declared collective program of this serving shape: both
        phases' coupled MoE (dispatch, combine) pairs — prefill at
        batch*prompt_len tokens, decode at batch tokens — plus the
        split-TP boundary gather when the context emits one.  This is
        what gets jointly planned and bound; decode typically stays on
        the unicast pair (small payload, Fig 8) while prefill crosses to
        MultiWrite with a shared microbatch G > 1 (decode has no compute
        to hide chunks behind).  Sites assume bf16 activations (the
        production serving dtype; fp32 smoke launchers bind their own
        program with the right itemsize before building the model).

        Memoized on ``(batch, prompt_len)``: per-step scheduler queries
        reuse the declared program instead of re-deriving its sites."""
        key = (int(batch), int(prompt_len))
        program = self._programs.get(key)
        if program is None:
            from repro.parallel.context import build_collective_program
            program = build_collective_program(
                self.model.cfg, self.pctx, "serve",
                {"prefill": (batch, prompt_len), "decode": (batch, 1)})
            self._programs[key] = program
        return program

    def invalidate_plan_cache(self) -> None:
        """Drop memoized fresh plans (a recalibration or re-bind may
        have changed what planning would choose; the declared programs
        themselves are shape-only and stay)."""
        self._plan_cache.clear()

    def _fresh_plan(self, batch: int, prompt_len: int):
        """Fresh jointly-planned ExecutionPlan for this exact serving
        shape, memoized on ``(batch, prompt_len)`` — repeated per-step
        queries hit this dict (and underneath it the planner LRU), not
        a re-plan."""
        key = (int(batch), int(prompt_len))
        if key in self._plan_cache:
            return self._plan_cache[key]
        program = self.serving_program(batch, prompt_len)
        plan = None
        if program.sites and self.pctx.plan_policy == "auto":
            plan = self.pctx.plan_collectives(program)
        self._plan_cache[key] = plan
        return plan

    def execution_plan(self, batch: int, prompt_len: int):
        """The jointly-planned ExecutionPlan for this serving shape: the
        context's bound plan when one covers these phases (serve.py
        binds before building the model, so the traces consumed exactly
        this), else a fresh ``plan_program`` on the context's fabric and
        calibration."""
        if self.pctx is None:
            return None
        # the binder's ACTIVE plan (post-swap) supersedes the context's
        # construction-time binding once a hot re-bind has landed
        bound = self._binder.plan or self.pctx.execution_plan
        if bound is not None:
            return bound
        return self._fresh_plan(batch, prompt_len)

    # -- batch-bucket plan prefetch (the serving tier's admission seam) ------
    def bucket_plan(self, batch: int, prompt_len: int):
        """ExecutionPlan for the BUCKETED serving shape — what the
        admission controller stages ahead of growing the decode batch
        across a bucket boundary.  None when the context cannot plan
        (no context, pinned policy, or no collective sites)."""
        if self.pctx is None or self.pctx.plan_policy != "auto":
            return None
        from repro.core.plan import batch_bucket
        return self._fresh_plan(batch_bucket(max(1, batch)), prompt_len)

    def plan_probe(self, itemsize: int = 2):
        """PlannerProbe over this engine's fabric/calibration — the
        admission controller's latency oracle.  ``itemsize`` must match
        the traced activation dtype (2 = bf16 production, 4 = fp32
        smoke).  None when the engine has no parallel context."""
        if self._probe is not None:
            return self._probe
        if self.pctx is None:
            return None
        from repro.serving.admission import PlannerProbe
        cfg = self.model.cfg
        topo, hw = self.pctx._plan_topo_hw(
            getattr(cfg, "num_experts", 0) or 0)
        self._probe = PlannerProbe(
            topo, token_bytes=cfg.d_model * itemsize,
            num_experts=getattr(cfg, "num_experts", 0) or 64,
            top_k=getattr(cfg, "top_k", 0) or 8, hw=hw,
            d_model=cfg.d_model, tp=self.pctx.model_size)
        return self._probe

    def plan_report(self, batch: int, prompt_len: int) -> dict:
        """Per-phase view of the jointly-planned serving program: each
        phase's dispatch and combine site decisions plus the JOINT
        pipeline verdict (shared microbatch G, combined predicted
        latency) — the decisions the jitted MoE layers consume at trace
        time, resolved against the same bound ExecutionPlan."""
        out = {}
        if self.monitor is not None:
            # predicted-vs-measured error + last re-calibration, from the
            # telemetry drift monitor (the serving face of the loop)
            out["calibration"] = self.monitor.report()
        eplan = self.execution_plan(batch, prompt_len)
        if eplan is None:
            return out
        out["execution_plan"] = eplan.fingerprint
        if self.pctx is not None and self.pctx.execution_plan is eplan:
            # a replan (drift recalibration) may have superseded the
            # bound plan's fingerprint; the traces still execute the OLD
            # plan until a re-bind — surface that instead of hiding it
            stale = self.pctx.bound_plan_stale()
            if stale is not None:
                out["stale"] = stale
                if stale:
                    # hot re-bind instead of the old warn-and-limp flow:
                    # when the drift monitor retargeted this program
                    # (failover/failback), stage its replacement plan —
                    # the swap lands at the next generate() boundary
                    staged = None
                    if self.monitor is not None:
                        staged = self.monitor.staged_plan(
                            eplan.program.name)
                    if staged is not None:
                        out["restaged"] = self.rebind(staged)
                    elif not self._stale_warned:
                        self._stale_warned = True
                        _metrics()["repro_plan_stale_total"].inc(
                            program=eplan.program.name,
                            fingerprint=eplan.fingerprint)
                        print(f"WARNING: bound ExecutionPlan "
                              f"{eplan.fingerprint} is stale — a replan "
                              f"chose different decisions for this "
                              f"program; serving continues on the old "
                              f"plan until re-bind/re-trace")
        if eplan.phase_report:
            out["phases"] = {ph: dict(rep)
                             for ph, rep in eplan.phase_report.items()}
            # phase-budget SLO verdicts, scrape-visible: 1/0 for budgeted
            # phases, plus every phase's predicted (contended) score
            reg = _metrics()
            for ph, rep in eplan.phase_report.items():
                score = rep.get("contended_score_s", rep.get("score_s"))
                if score is not None:
                    reg["repro_phase_predicted_seconds"].set(
                        score, phase=ph, fingerprint=eplan.fingerprint)
                if rep.get("budget_s") is not None:
                    reg["repro_phase_budget_ok"].set(
                        1.0 if rep.get("budget_ok") else 0.0,
                        phase=ph, fingerprint=eplan.fingerprint)
        if eplan.planner_stats:
            out["planner"] = dict(eplan.planner_stats)
        for site in eplan.program.sites:
            phase, _, kind = site.role.partition("/")
            if kind == "moe_dispatch":
                cell = out.setdefault(phase, {})
                cell["dispatch"] = eplan.decision(site.role).report()
                joint = eplan.joint.get(site.role)
                if joint is not None:
                    cell["joint"] = joint.report()
            elif kind == "moe_combine":
                out.setdefault(phase, {})["combine"] = \
                    eplan.decision(site.role).report()
            elif kind == "split_tp_gather":
                out.setdefault(phase, {})["split_tp_gather"] = \
                    eplan.decision(site.role).report()
        return out

    # -- the step-level cohort API (what the BatchScheduler drives) ----------
    def start_cohort(self, prompts: np.ndarray,
                     max_new: Optional[int] = None,
                     seed: int = 0):
        """Prefill one cohort of requests ([b, s] int32, already padded
        to one shared prompt_len) and sample its first tokens.  Returns
        ``(state, tokens, wall_s)`` — feed ``tokens`` back through
        :meth:`step_cohort` for each subsequent decode round."""
        cfg = self.model.cfg
        b, s = prompts.shape
        max_new = max_new or self.cfg.max_new_tokens
        lowering = self._binder.artifact
        cohort = next(self._cohort_ids)
        t0 = time.monotonic()
        with spans.span("engine.prefill", batch=b, prompt_len=s,
                        cohort=cohort) as sp:
            shape = (b, int(s + max_new), self.cfg.cache_dtype)
            # first: this lowering's builder compiles for the shape now
            with spans.span("engine.cache_init",
                            first=shape not in lowering.cache_shapes):
                cache = lowering.init_cache(*shape)
                lowering.cache_shapes.add(shape)
            with spans.span("engine.embed"):
                from repro.data.pipeline import batch_for_model
                batch = batch_for_model(
                    cfg, {"tokens": prompts, "labels": prompts})
                batch.pop("labels", None)
            with spans.span("engine.launch"):
                logits, cache, stats = lowering.prefill(
                    self.params, batch, cache)
                state = CohortState(cache=cache, logits=logits,
                                    key=jax.random.key(seed), batch=b,
                                    cohort=cohort)
                sampled = self._sample(state)
            with spans.span("engine.wait"):
                tokens = np.asarray(sampled, np.int32)
            if stats and spans.recording():
                sp.set(**_moe_attrs(lowering, b * s, stats))
        return state, tokens, time.monotonic() - t0

    def step_cohort(self, state: "CohortState", tokens: np.ndarray):
        """One decode round: consume the cohort's last sampled tokens,
        sample the next.  Returns ``(state, tokens, wall_s)``."""
        t0 = time.monotonic()
        lowering = self._binder.artifact
        with spans.span("engine.decode", batch=state.batch,
                        cohort=state.cohort) as sp:
            with spans.span("engine.embed"):
                dec_in = self._decode_batch(
                    np.asarray(tokens, np.int32)[:, None])
            with spans.span("engine.launch"):
                state.logits, state.cache, stats = lowering.decode(
                    self.params, dec_in, state.cache)
                sampled = self._sample(state)
            with spans.span("engine.wait"):
                tokens = np.asarray(sampled, np.int32)
            if stats and spans.recording():
                sp.set(**_moe_attrs(lowering, state.batch, stats))
        return state, tokens, time.monotonic() - t0

    def _sample(self, state: "CohortState"):
        """Dispatch the sampling of the next tokens; the device array it
        returns is read on the host by the caller."""
        if self.cfg.temperature > 0:
            state.key, sub = jax.random.split(state.key)
            return jax.random.categorical(
                sub, jnp.asarray(state.logits) / self.cfg.temperature,
                axis=-1)
        return jnp.argmax(state.logits, axis=-1)

    def generate(self, prompts: np.ndarray, max_new: Optional[int] = None,
                 seed: int = 0) -> np.ndarray:
        """prompts: [B, S] int32 (already padded).  Returns [B, max_new].

        Thin client of the continuous-batching scheduler: the whole
        batch arrives at t=0 and drains as one cohort through the same
        :meth:`start_cohort`/:meth:`step_cohort` loop the serving tier
        interleaves — one code path, bit-exact either way under greedy
        decoding (rows are numerically independent)."""
        b, s = prompts.shape
        max_new = max_new or self.cfg.max_new_tokens
        # step boundary: a staged re-bind (failover replan) lands here —
        # pointer swap onto the pre-traced lowering, never mid-decode
        self._binder.swap_if_pending()
        plans = self.plan_report(b, s)
        if plans:
            self.stats["plans"] = plans
        from repro.serving.admission import AdmissionController
        from repro.serving.queue import Request, RequestQueue
        from repro.serving.scheduler import BatchScheduler
        queue = RequestQueue()
        for i in range(b):
            queue.push(Request(rid=i, arrival_s=0.0,
                               prompt=np.asarray(prompts[i], np.int32),
                               max_new=max_new))
        sched = BatchScheduler(
            queue=queue,
            admission=AdmissionController(capacity=b, policy="greedy"),
            engine=self, eos_id=self.cfg.eos_id, seed=seed)
        sched.run_until_drained()
        out = np.zeros((b, max_new), np.int32)
        never_eos = 0
        for req in sched.completed:
            toks = req.tokens[:max_new]
            out[req.rid, :len(toks)] = toks
            never_eos += 0 if req.eos else 1
        self.stats["prefill_s"] += sched.wall["prefill_s"]
        self.stats["decode_s"] += sched.wall["decode_s"]
        reg = _metrics()
        reg["repro_step_wall_seconds"].observe(
            sched.wall["prefill_s"], phase="prefill")
        reg["repro_step_wall_seconds"].observe(
            sched.wall["decode_s"], phase="decode")
        self.stats["tokens"] += never_eos * max_new
        return out

    def _decode_batch(self, tokens: np.ndarray):
        cfg = self.model.cfg
        if cfg.input_mode == "embeddings" and cfg.family != "encdec":
            # stub frontend: decode feeds token embeddings through the table
            # is not available; hash-embed like the pipeline stub.
            from repro.data.pipeline import _stub_embed
            return {"embeds": jnp.asarray(_stub_embed(tokens, cfg.d_model))}
        return {"tokens": jnp.asarray(tokens)}
