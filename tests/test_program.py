"""Declarative collective programs + jointly-planned ExecutionPlans
(ISSUE 5).

What is pinned here:

  * the program IR: site keys, role uniqueness, coupling validation,
    cache-key stability;
  * ``Planner.plan_program``: uncoupled sites match ``choose``; the
    coupled MoE (dispatch, combine) product sweep respects the
    executable-pairing constraint and shares ONE microbatch G;
  * the ISSUE acceptance point: the jointly-planned (dispatch G,
    combine G) pair DIFFERS from the PR-4 dispatch-first choice at some
    operating point and strictly beats it on the combined
    shared-pipeline score;
  * ExecutionPlan identity (fingerprints) and binding
    (``ParallelContext.bind`` -> trace-time lookup, miss fallback,
    fabric mismatch guard);
  * whole-program replanning after a re-calibration
    (``Planner.replan_programs`` / ``DriftMonitor.recalibrate``);
  * ``StepAttribution``: live step wall times reach
    ``fit_overlap_eff`` through ``Planner.note_measurement``;
  * the directed linkprobe: never-bottlenecking rail directions get
    fitted instead of staying nominal.
"""

import dataclasses

import pytest

from repro.core import latency_model as lm
from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core.topology import get_fabric, two_server_cluster

TOKEN = lm.TOKEN_BYTES


def compute_ctx(batch, top_k=8, d_model=7168, f_shard=2048):
    return lm.expert_compute_time_s(batch, top_k, d_model, f_shard)


def moe_program(batch, *, phase="train", token_bytes=TOKEN,
                compute_s=None, skew=0.0):
    if compute_s is None:
        compute_s = compute_ctx(batch)
    sites = plan_ir.moe_sites(phase, num_experts=64, top_k=8,
                              tokens_per_rank=batch,
                              token_bytes=token_bytes,
                              compute_s=compute_s, skew=skew)
    return plan_ir.CollectiveProgram(phase, sites)


# ---------------------------------------------------------------------------
# the program IR
# ---------------------------------------------------------------------------

class TestProgramIR:
    def test_site_key_matches_trace_side_construction(self):
        d, c = plan_ir.moe_sites("train", num_experts=64, top_k=8,
                                 tokens_per_rank=512, token_bytes=TOKEN,
                                 compute_s=1e-3, skew=0.5)
        assert d.key() == plan_ir.site_key(
            "dispatch", 512 * TOKEN, skew=0.5, compute_s=1e-3,
            num_experts=64, top_k=8, token_bytes=TOKEN)
        assert c.coupled_with == d.role
        # nearby payloads/compute share the bucketed key
        assert d.key() == plan_ir.site_key(
            "dispatch", 512 * TOKEN - 7, skew=0.5, compute_s=1.01e-3,
            num_experts=64, top_k=8, token_bytes=TOKEN)

    def test_duplicate_roles_rejected(self):
        s = plan_ir.allgather_site("p", frag_bytes=1024)
        with pytest.raises(ValueError, match="duplicate site roles"):
            plan_ir.CollectiveProgram("p", (s, s))

    def test_dangling_coupling_rejected(self):
        s = plan_ir.CollectiveSite(op="combine", role="c",
                                   payload_bytes=1.0, coupled_with="ghost")
        with pytest.raises(ValueError, match="unknown role"):
            plan_ir.CollectiveProgram("p", (s,))

    def test_coupling_chain_rejected(self):
        a = plan_ir.CollectiveSite(op="dispatch", role="a",
                                   payload_bytes=1.0, coupled_with="b")
        b = plan_ir.CollectiveSite(op="combine", role="b",
                                   payload_bytes=1.0, coupled_with="c")
        c = plan_ir.CollectiveSite(op="dispatch", role="c",
                                   payload_bytes=1.0)
        with pytest.raises(ValueError, match="chain"):
            plan_ir.CollectiveProgram("p", (a, b, c))

    def test_groups_partition(self):
        prog = moe_program(256)
        ag = plan_ir.allgather_site("train", frag_bytes=1 << 20)
        prog2 = plan_ir.CollectiveProgram("p", (*prog.sites, ag))
        groups = prog2.groups()
        assert [len(g) for g in groups] == [2, 1]
        assert groups[0][0].op == "dispatch"

    def test_cache_key_stable_and_workload_sensitive(self):
        assert moe_program(256).cache_key() == moe_program(256).cache_key()
        assert moe_program(256).cache_key() != moe_program(512).cache_key()


# ---------------------------------------------------------------------------
# plan_program: joint sweep
# ---------------------------------------------------------------------------

class TestPlanProgram:
    @pytest.fixture()
    def planner(self):
        return pl.Planner()

    def test_single_site_matches_choose(self, planner):
        topo, _ = __import__(
            "repro.core.topology", fromlist=["split_tp_full_mesh"]
        ).split_tp_full_mesh(8, tp=4)
        site = plan_ir.allgather_site("t", frag_bytes=4 << 20)
        prog = plan_ir.CollectiveProgram("t", (site,))
        eplan = planner.plan_program(prog, topo)
        direct = planner.choose("allgather", 4 << 20, topo,
                                executable_only=True, num_domains=2)
        got = eplan.decision("t/split_tp_gather")
        assert (got.plan, got.knobs) == (direct.plan, direct.knobs)

    def test_joint_sweep_shares_one_microbatch(self, planner):
        topo = two_server_cluster()
        eplan = planner.plan_program(moe_program(1024), topo)
        joint = eplan.joint["train/moe_dispatch"]
        kw = eplan.site_kwargs("train/moe_dispatch")
        assert kw["microbatch"] == joint.microbatch
        assert kw == eplan.site_kwargs("train/moe_combine")
        # every joint candidate shares its G across both halves by
        # construction; the pairing constraint holds: no candidate pairs
        # a unicast dispatch with a relay-reduced combine
        for name, _, _ in joint.candidates:
            d_name, c_name = name.split("+")
            if d_name == "unicast":
                assert c_name == "unicast"

    def test_joint_beats_dispatch_first(self, planner):
        """ISSUE acceptance: the jointly-planned (dispatch G, combine G)
        pair differs from the PR-4 dispatch-first choice at some
        fabric/batch point and strictly beats it on the combined
        modeled score."""
        topo = two_server_cluster()
        hw = planner.hw
        differed = []
        for batch in (128, 256, 512, 1024, 2048):
            compute_s = compute_ctx(batch)
            eplan = planner.plan_program(
                moe_program(batch, compute_s=compute_s), topo)
            joint = eplan.joint["train/moe_dispatch"]
            # PR-4 path: dispatch sweeps alone, combine compared at the
            # EXECUTED dispatch G
            d = planner.choose("dispatch", batch * TOKEN, topo,
                               token_bytes=TOKEN, compute_s=compute_s)
            g = d.microbatch
            c_at_g = min(
                (t, name) for name, kn, t in planner.choose(
                    "combine", batch * TOKEN, topo, token_bytes=TOKEN,
                    compute_s=compute_s).candidates
                if dict(kn).get("microbatch", 1) == g)
            c_name = c_at_g[1]
            if d.plan == "unicast":
                c_name = "unicast"          # executable pairing
            # combined score of the dispatch-first configuration under
            # the SAME shared-pipeline model
            scen_kw = dict(num_experts=64, top_k=8, token_bytes=TOKEN,
                           skew=0.0, compute_s=compute_s)
            d_scen = pl.Planner._scenario("dispatch", topo, scen_kw)
            c_scen = pl.Planner._scenario("combine", topo, scen_kw)
            bucket = pl.bucket_payload(batch * TOKEN)
            ld = plan_ir.get_plan("dispatch", d.plan).simulate(
                d_scen, bucket, microbatch=g)
            lc = plan_ir.get_plan("combine", c_name).simulate(
                c_scen, bucket, microbatch=g)
            first_t = lm.score_pipeline((ld, lc), hw)
            pair_first = (
                "hierarchical" if d.plan == "multiwrite" else "baseline", g,
                "hierarchical" if c_name == "multiwrite" else "baseline", g)
            pair_joint = (joint.shard_map_kwargs["moe_scheme"],
                          joint.microbatch,
                          joint.shard_map_kwargs["moe_combine"],
                          joint.microbatch)
            # the joint sweep optimizes over a superset that includes
            # the dispatch-first configuration
            assert joint.predicted_s <= first_t + 1e-12, (batch,)
            if pair_joint != pair_first:
                differed.append((batch, pair_first, pair_joint))
                assert joint.predicted_s < first_t, (batch,)
        assert differed, ("joint sweep never changed a decision vs the "
                          "dispatch-first path over the sweep")

    def test_program_cache_and_fingerprints(self, planner):
        topo = two_server_cluster()
        prog = moe_program(512)
        a = planner.plan_program(prog, topo)
        b = planner.plan_program(prog, topo)
        assert a is b                      # LRU hit
        assert a.fingerprint == b.fingerprint
        # deterministic across planners, not just a cache hit
        assert pl.Planner().plan_program(prog, topo).fingerprint == \
            a.fingerprint
        degraded = planner.hw.recalibrated(
            {"links": {k: ln.bw / 4 for k, ln in topo.links.items()
                       if topo.server_of(k[0]) != topo.server_of(k[1])}})
        c = planner.plan_program(prog, topo, degraded)
        assert c.hw_fingerprint != a.hw_fingerprint

    def test_replan_programs_after_recalibration(self, planner):
        topo = two_server_cluster()
        prog = moe_program(64)             # small batch: unicast pair
        before = planner.plan_program(prog, topo)
        degraded = planner.hw.recalibrated(
            {"links": {k: ln.bw / 8 for k, ln in topo.links.items()
                       if topo.server_of(k[0]) != topo.server_of(k[1])}})
        planner.refresh_hardware(degraded)
        events = planner.replan_programs()
        ev = next(e for e in events if e["program"] == "train")
        assert ev["changed"]
        assert ev["plan"].fingerprint != before.fingerprint
        # the degradation flips the small-batch pair off unicast
        assert before.site_kwargs("train/moe_dispatch")["moe_scheme"] == \
            "baseline"
        assert ev["plan"].site_kwargs(
            "train/moe_dispatch")["moe_scheme"] == "hierarchical"


# ---------------------------------------------------------------------------
# binding into the ParallelContext
# ---------------------------------------------------------------------------

def _mesh_pctx(**kw):
    import jax

    from repro.launch.mesh import make_test_mesh
    from repro.parallel.context import ParallelContext
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = make_test_mesh(shape=(1,), axes=("model",))
    return ParallelContext(mesh=mesh, pod_axis=None, data_axis="model",
                           model_axis="model", **kw)


class TestBinding:
    def test_bound_lookup_serves_joint_kwargs(self):
        pctx = _mesh_pctx(plan_policy="auto", fabric=two_server_cluster())
        cs = compute_ctx(1024)
        sites = pctx.moe_sites("train", num_experts=64, top_k=8,
                               tokens_per_rank=1024, token_bytes=TOKEN,
                               compute_s=cs)
        eplan = pctx.plan_collectives(
            plan_ir.CollectiveProgram("train", sites))
        bound = pctx.bind(eplan)
        got = bound.moe_pipeline_kwargs(64, 8, tokens_per_rank=1024,
                                        token_bytes=TOKEN, compute_s=cs)
        want = eplan.site_kwargs("train/moe_dispatch")
        assert got == bound._norm_moe_kwargs(want)

    def test_bound_miss_falls_back_to_policy(self):
        pctx = _mesh_pctx(plan_policy="fixed", moe_scheme="baseline",
                          moe_microbatch=2)
        prog = plan_ir.CollectiveProgram(
            "train", pctx.moe_sites("train", num_experts=64, top_k=8,
                                    tokens_per_rank=4096,
                                    token_bytes=TOKEN))
        bound = pctx.bind(plan_ir.pinned_execution_plan(
            prog, {"train/moe_dispatch": {"moe_scheme": "hierarchical",
                                          "moe_combine": "hierarchical",
                                          "microbatch": 8}}))
        # a workload the program never declared: declared knobs win
        got = bound.moe_pipeline_kwargs(64, 8, tokens_per_rank=32,
                                        token_bytes=TOKEN)
        assert got == {"moe_scheme": "baseline", "moe_combine": "baseline",
                       "microbatch": 2}
        # the declared workload resolves from the pinned plan
        hit = bound.moe_pipeline_kwargs(64, 8, tokens_per_rank=4096,
                                        token_bytes=TOKEN)
        assert hit == {"moe_scheme": "hierarchical",
                       "moe_combine": "hierarchical", "microbatch": 8}

    def test_bind_rejects_foreign_fabric(self):
        pctx_a = _mesh_pctx(plan_policy="auto",
                            fabric=two_server_cluster())
        pctx_b = _mesh_pctx(plan_policy="auto", fabric=get_fabric("4x8"))
        eplan = pctx_a.plan_collectives(moe_program(256))
        with pytest.raises(ValueError, match="replan the program"):
            pctx_b.bind(eplan)

    def test_executed_g_constraint_reresolves_schemes(self):
        """When moe_ffn's divisibility clamp moves G off the planned
        value, the configuration is re-resolved AT the executed G: the
        returned pair is the best joint candidate at that depth, not
        the planned-G pair run at a depth the sweep scored worse."""
        pctx = _mesh_pctx(plan_policy="auto", fabric=two_server_cluster())
        batch, cs = 2048, compute_ctx(2048)
        kw = dict(num_experts=64, top_k=8, tokens_per_rank=batch,
                  token_bytes=TOKEN, compute_s=cs)
        free = pctx.moe_pipeline_kwargs(**kw)
        assert free["microbatch"] > 1
        sites = pctx.moe_sites("auto", **kw)
        joint = pctx.plan_collectives(
            plan_ir.CollectiveProgram("moe/auto", sites)).joint[
                sites[0].role]
        for g in (1, 2):
            got = pctx.moe_pipeline_kwargs(**kw, microbatch=g)
            assert got["microbatch"] == g
            best_t, best_name = min(
                (t, name) for name, kn, t in joint.candidates
                if dict(kn).get("microbatch", 1) == g)
            d_name, _, c_name = best_name.partition("+")
            assert got["moe_scheme"] == (
                "hierarchical" if d_name == "multiwrite" else "baseline")
            assert got["moe_combine"] == (
                "hierarchical" if c_name == "multiwrite" else "baseline")

    def test_allgather_site_binding(self):
        from repro.core.topology import split_tp_full_mesh
        pctx = _mesh_pctx(plan_policy="auto")
        topo, _ = split_tp_full_mesh(8, tp=4)
        site = plan_ir.allgather_site("train", frag_bytes=8 << 20,
                                      num_domains=2, topo=topo)
        eplan = pctx.plan_collectives(
            plan_ir.CollectiveProgram("train", (site,)))
        bound = pctx.bind(eplan)
        d = bound.allgather_plan(8 << 20, num_domains=2)
        assert (d.plan, d.knobs) == \
            (eplan.decision("train/split_tp_gather").plan,
             eplan.decision("train/split_tp_gather").knobs)


# ---------------------------------------------------------------------------
# live step-time attribution -> overlap-efficiency fit
# ---------------------------------------------------------------------------

class TestStepAttribution:
    def _joint(self, planner, batch=2048):
        topo = two_server_cluster()
        eplan = planner.plan_program(moe_program(batch), topo)
        joint = eplan.joint["train/moe_dispatch"]
        assert joint.microbatch > 1
        return joint

    def test_explicit_overhead_recovers_true_eta(self):
        from repro.telemetry import StepAttribution, fit_overlap_eff
        planner = pl.Planner()
        joint = self._joint(planner)
        true_eta = 0.55
        t_true = (joint.predicted_serial_s
                  - true_eta * (joint.predicted_serial_s
                                - joint.predicted_ideal_s))
        layers, overhead = 4, 3e-3
        att = StepAttribution(planner, joint, n_layers=layers,
                              overhead_s=overhead, warmup=2)
        for _ in range(8):
            att.observe_step(overhead + layers * t_true)
        assert att.fed == 6                # warmup steps excluded
        eta = fit_overlap_eff(planner.decision_log)
        assert eta is not None
        assert abs(eta - true_eta) < 0.05

    def test_min_anchored_mode_feeds_rows(self):
        from repro.telemetry import StepAttribution
        planner = pl.Planner()
        joint = self._joint(planner)
        att = StepAttribution(planner, joint, n_layers=2, warmup=1)
        rows = [att.observe_step(1e-2 + 1e-4 * i) for i in range(5)]
        assert rows[0] is None
        fed = [r for r in rows if r is not None]
        assert fed and all(r["measured_s"] > 0 for r in fed)

    def test_trainer_step_hook_reaches_decision_log(self):
        """End-to-end: a Trainer step_hook wired like train.py's feeds
        wall times into the planner's joint decision rows."""
        from repro.telemetry import StepAttribution
        planner = pl.Planner()
        joint = self._joint(planner)
        att = StepAttribution(planner, joint, n_layers=1,
                              overhead_s=0.0, warmup=0)

        def step_hook(step, row):
            att.observe_step(row["wall"])

        for step in range(3):
            step_hook(step, {"wall": joint.predicted_s})
        measured = [r for r in planner.decision_log
                    if r.get("measured_s") is not None
                    and r["op"] == "dispatch+combine"]
        assert len(measured) == 3


# ---------------------------------------------------------------------------
# directed linkprobe: never-bottlenecking directions get fitted
# ---------------------------------------------------------------------------

class TestDirectionProbes:
    def test_forward_rails_fitted_on_asymmetric_fabric(self):
        from repro.core.planner import Planner
        from repro.telemetry import (CalibrationStore, DriftMonitor,
                                     GroundTruth, SimProbe,
                                     fit_measurements, topo_key)
        topo = get_fabric("2x8asym")
        truth = GroundTruth().degraded(topo, 2.0, "inter")
        store = CalibrationStore(":memory:")
        monitor = DriftMonitor(Planner(), store, topo)
        monitor.run_cycle(SimProbe(truth))
        recs = list(store.latest_by_key(fabric=topo_key(topo)).values())
        measurements, fits = fit_measurements(recs, topo)
        fwd = {k: v for k, v in measurements.get("links", {}).items()
               if topo.server_of(k[0]) == 0 and topo.server_of(k[1]) == 1}
        rev = {k: v for k, v in measurements.get("links", {}).items()
               if topo.server_of(k[0]) == 1 and topo.server_of(k[1]) == 0}
        # forward rails (nominal 25, truly 12.5 after 2x degradation)
        # were previously UNFITTABLE: no collective ever bottlenecks
        # there.  The directed probes pin them.
        assert fwd and all(abs(v - 12.5e9) < 1.5e9 for v in fwd.values())
        assert rev and all(abs(v - 6.25e9) < 1e9 for v in rev.values())
        assert fits["inter:0>1"].trusted and fits["inter:1>0"].trusted

    def test_direction_records_are_per_direction_in_store(self):
        from repro.telemetry import (CalibrationStore, GroundTruth,
                                     SimProbe, probe_link_directions)
        topo = two_server_cluster()
        recs = probe_link_directions(topo, SimProbe(GroundTruth()))
        store = CalibrationStore(":memory:")
        store.extend(recs)
        latest = store.latest_by_key()
        roles = {r["bottleneck_role"] for r in latest.values()}
        assert roles == {"inter:0>1", "inter:1>0"}
        assert len(latest) == len(recs)    # directions never supersede
        #   each other (only re-probes of the SAME direction do)
