"""Contention-aware whole-program planning (ISSUE 7).

What is pinned here:

  * ``merge_ledgers``: exact per-link sums across concurrent sites, one
    merged ledger per fabric (disjoint fabrics never add), empty
    ledgers skipped, first-seen fabric order preserved;
  * ``score_phase`` / ``phase_breakdown``: the t_phase = max own score
    + shared-link excess model, zero contention on disjoint fabrics,
    background traffic only ever raises the score;
  * ``Planner._search_phase``: joint search never loses to the greedy
    per-site assignment, contention genuinely flips decisions on shared
    fabrics, beam equals the exhaustive oracle on small programs, the
    wide tpu_2x16 program trips ``auto`` into beam under the
    enumeration budget;
  * phase budgets: validation, the feasibility constraint (a budgeted
    phase rejects other phases' combinations whose background traffic
    busts its cap), and the ``budget_violated`` best-effort fallback;
  * staleness surfacing: ``Planner.plan_is_stale``,
    ``ParallelContext.bound_plan_stale`` and the one-shot
    ``ServeEngine.plan_report`` warning;
  * planner introspection: phase/search stats on
    ``ExecutionPlan.report()`` and the op="program" decision_log row.
"""

import dataclasses

import pytest

from repro.core import latency_model as lm
from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core.topology import (get_fabric, split_tp_full_mesh,
                                 two_server_cluster)

TOKEN = lm.TOKEN_BYTES
TP, SEQ = 8, 2048


def compute_ctx(batch, top_k=8, d_model=7168, f_shard=2048):
    return lm.expert_compute_time_s(batch, top_k, d_model, f_shard)


def train_program(batch, n_params, extra=()):
    """MoE (dispatch, combine) pair + gradient sync in ONE phase — the
    canonical contended program of the flip sweep."""
    d, c = plan_ir.moe_sites("train", num_experts=64, top_k=8,
                             tokens_per_rank=batch, token_bytes=TOKEN,
                             compute_s=compute_ctx(batch))
    gs = plan_ir.grad_sync_site(
        "train", payload_bytes=n_params * 4 / TP,
        compute_s=lm.backward_compute_s(n_params, SEQ, tp=TP))
    return plan_ir.CollectiveProgram("train", (d, c, gs) + tuple(extra))


def serve_program(budget=None, *, decode_batch=64, prefill_batch=4096):
    dec = plan_ir.moe_sites("decode", num_experts=64, top_k=8,
                            tokens_per_rank=decode_batch,
                            token_bytes=TOKEN)
    pre = plan_ir.moe_sites("prefill", num_experts=64, top_k=8,
                            tokens_per_rank=prefill_batch,
                            token_bytes=TOKEN,
                            compute_s=compute_ctx(prefill_batch))
    return plan_ir.CollectiveProgram(
        "serve", (*dec, *pre),
        phase_budgets={} if budget is None else {"decode": budget})


def greedy_phase(planner, program, topo, phase="train"):
    """Independent per-site planning re-scored under the phase model:
    every group's own contention-free best."""
    groups = program.phases()[phase]
    bundles = [planner._group_candidates(g, topo, planner.hw, True)
               for g in groups]
    entries = [(b["cands"][0]["score_s"], b["cands"][0]["ledgers"])
               for b in bundles]
    return lm.score_phase(entries, planner.hw)


def demand_ledger(topo, nbytes, link=None):
    """Minimal pure-demand ledger: ``nbytes`` on one directed link."""
    link = link or next(iter(topo.links))
    return plan_ir.Ledger(topo=topo, link_bytes={link: float(nbytes)},
                          relay_bytes={}, flow_counts={link: 1})


# ---------------------------------------------------------------------------
# merge_ledgers / score_phase
# ---------------------------------------------------------------------------

class TestMergeLedgers:
    def test_merged_is_per_link_sum(self):
        topo = get_fabric("2x8")
        scen = plan_ir.default_scenarios(topo)
        ledgers = [
            plan_ir.get_plan("dispatch", "multiwrite").simulate(
                scen["dispatch"], 1 << 20),
            plan_ir.get_plan("allreduce", "ring").simulate(
                scen["allreduce"], 1 << 22),
            plan_ir.get_plan("allreduce", "hierarchical").simulate(
                scen["allreduce"], 1 << 18),
        ]
        merged = lm.merge_ledgers(ledgers)
        assert len(merged) == 1            # one fabric -> one phase ledger
        m = merged[0]
        for field in ("link_bytes", "relay_bytes", "flow_counts"):
            want: dict = {}
            for led in ledgers:
                for k, v in getattr(led, field).items():
                    want[k] = want.get(k, 0) + v
            got = getattr(m, field)
            assert set(got) == set(want)
            for k in want:
                assert got[k] == pytest.approx(want[k])

    def test_disjoint_fabrics_never_add(self):
        ep = two_server_cluster()
        tp_mesh, _ = split_tp_full_mesh(8, tp=4)
        a = demand_ledger(ep, 1 << 20)
        b = demand_ledger(tp_mesh, 1 << 24)
        merged = lm.merge_ledgers([a, b])
        assert len(merged) == 2            # per-fabric, first-seen order
        assert merged[0].topo is ep and merged[1].topo is tp_mesh
        # the phase floor is the max over fabrics, not their sum
        assert lm.phase_wire_s([a, b]) == pytest.approx(
            max(lm.ledger_wire_s(a), lm.ledger_wire_s(b)))

    def test_empty_ledgers_skipped(self):
        topo = two_server_cluster()
        a = demand_ledger(topo, 4096)
        empty = plan_ir.Ledger(topo=topo, link_bytes={}, relay_bytes={},
                               flow_counts={})
        merged = lm.merge_ledgers([empty, a, empty])
        assert len(merged) == 1
        assert merged[0].link_bytes == a.link_bytes
        assert lm.merge_ledgers([empty]) == ()

    def test_merged_ledger_is_pure_demand(self):
        """Merging strips schedule context: one stage, no overlap, no
        compute — score with ledger_wire_s, never score_ledger."""
        topo = two_server_cluster()
        led = dataclasses.replace(demand_ledger(topo, 1 << 20),
                                  stages=8, overlap=True, compute_s=1.0)
        (m,) = lm.merge_ledgers([led, demand_ledger(topo, 1 << 20)])
        assert m.stages == 1 and not m.overlap and m.compute_s == 0.0


class TestScorePhase:
    def test_disjoint_fabric_groups_zero_contention(self):
        ep = two_server_cluster()
        tp_mesh, _ = split_tp_full_mesh(8, tp=4)
        entries = [(5e-4, (demand_ledger(ep, 1 << 24),)),
                   (3e-4, (demand_ledger(tp_mesh, 1 << 24),))]
        rep = lm.phase_breakdown(entries)
        assert rep["contention_s"] == 0.0
        assert rep["score_s"] == pytest.approx(5e-4)   # slowest group

    def test_shared_link_excess_charged_on_top(self):
        topo = two_server_cluster()
        link = next(iter(topo.links))
        a = demand_ledger(topo, 1 << 26, link)
        b = demand_ledger(topo, 1 << 26, link)
        sa, sb = lm.ledger_wire_s(a), lm.ledger_wire_s(b)
        entries = [(sa, (a,)), (sb, (b,))]
        rep = lm.phase_breakdown(entries)
        # both groups on ONE link: merged wire is the sum, the excess
        # over the larger own wire is pure contention
        assert rep["phase_wire_s"] == pytest.approx(sa + sb)
        assert rep["contention_s"] == pytest.approx(min(sa, sb))
        assert rep["score_s"] == pytest.approx(
            rep["solo_s"] + rep["contention_s"])
        assert lm.score_phase(entries) == pytest.approx(rep["score_s"])

    def test_background_only_raises_the_score(self):
        topo = two_server_cluster()
        link = next(iter(topo.links))
        entries = [(1e-4, (demand_ledger(topo, 1 << 22, link),))]
        base = lm.score_phase(entries)
        bg = [demand_ledger(topo, 1 << 26, link)]
        assert lm.score_phase(entries, background=bg) > base
        # background on a foreign fabric is invisible
        other, _ = split_tp_full_mesh(8, tp=4)
        assert lm.score_phase(
            entries, background=[demand_ledger(other, 1 << 28)]
        ) == pytest.approx(base)


# ---------------------------------------------------------------------------
# phase search: joint vs greedy, beam vs oracle
# ---------------------------------------------------------------------------

class TestPhaseSearch:
    def test_contention_flips_the_grad_sync_scheme(self):
        """The tentpole behavior: planned independently, grad sync picks
        the relay-heavy multiwrite reduce; planned jointly with the MoE
        round trip contending for the same rails, the planner moves it
        off the shared bottleneck and strictly wins on the contended
        score."""
        topo = get_fabric("2x8")
        planner = pl.Planner()
        program = train_program(1024, 100_000_000)
        greedy_s = greedy_phase(planner, program, topo)
        eplan = planner.plan_program(program, topo)
        joint_s = eplan.phase_report["train"]["score_s"]
        gs = eplan.decisions["train/grad_sync"]
        assert gs.plan == "hierarchical"   # independent best: multiwrite
        assert joint_s < greedy_s

    def test_joint_never_loses_to_greedy(self):
        topo = get_fabric("tpu_2x16")
        planner = pl.Planner()
        for batch, n_params in ((64, 10**7), (1024, 10**8),
                                (4096, 12 * 10**9)):
            program = train_program(batch, n_params)
            greedy_s = greedy_phase(planner, program, topo)
            eplan = planner.plan_program(program, topo)
            assert (eplan.phase_report["train"]["score_s"]
                    <= greedy_s + 1e-12), (batch, n_params)

    def test_beam_matches_oracle_on_small_programs(self):
        program = train_program(1024, 100_000_000)
        for fname in ("mesh8", "2x8"):
            topo = get_fabric(fname)
            b = pl.Planner(search="beam").plan_program(program, topo)
            o = pl.Planner(search="exhaustive").plan_program(program, topo)
            assert (b.phase_report["train"]["score_s"]
                    == pytest.approx(o.phase_report["train"]["score_s"],
                                     rel=1e-9)), fname
            assert b.planner_stats["search"] == ["beam"]
            assert o.planner_stats["search"] == ["exhaustive"]

    def test_wide_program_trips_auto_into_beam(self):
        """The >=3-group tpu_2x16 program: the candidate product exceeds
        EXHAUSTIVE_LIMIT, auto resolves to beam, and beam enumerates
        under 10% of the product."""
        topo = get_fabric("tpu_2x16")
        program = train_program(
            2048, 12_000_000_000,
            extra=(plan_ir.allgather_site("train", frag_bytes=8 << 20),))
        eplan = pl.Planner().plan_program(program, topo)
        stats = eplan.planner_stats
        assert stats["product"] > pl.Planner.EXHAUSTIVE_LIMIT
        assert stats["search"] == ["beam"]
        assert stats["combos_scored"] < 0.10 * stats["product"]
        assert stats["combos_pruned"] == (stats["product"]
                                          - stats["combos_scored"])
        # and lands within 2% of the forced-exhaustive oracle
        oracle = pl.Planner(search="exhaustive").plan_program(program, topo)
        beam_s = eplan.phase_report["train"]["score_s"]
        oracle_s = oracle.phase_report["train"]["score_s"]
        assert beam_s <= oracle_s * 1.02

    def test_zero_contention_reproduces_independent_planning(self):
        """Groups on disjoint fabrics cannot contend: the joint search
        must bind exactly what per-site planning binds (the backward-
        compatibility face of the tie-break)."""
        ep = two_server_cluster()
        tp_mesh, _ = split_tp_full_mesh(8, tp=4)
        d, c = plan_ir.moe_sites("train", num_experts=64, top_k=8,
                                 tokens_per_rank=1024, token_bytes=TOKEN,
                                 compute_s=compute_ctx(1024))
        ag = plan_ir.allgather_site("train", frag_bytes=4 << 20,
                                    topo=tp_mesh)
        planner = pl.Planner()
        eplan = planner.plan_program(
            plan_ir.CollectiveProgram("train", (d, c, ag)), ep)
        solo = planner.plan_program(
            plan_ir.CollectiveProgram("train", (d, c)), ep)
        got = eplan.decisions["train/moe_dispatch"]
        want = solo.decisions["train/moe_dispatch"]
        assert (got.plan, got.knobs) == (want.plan, want.knobs)
        direct = planner.choose("allgather", 4 << 20, tp_mesh,
                                executable_only=True, num_domains=2)
        ag_dec = eplan.decisions["train/split_tp_gather"]
        assert (ag_dec.plan, ag_dec.knobs) == (direct.plan, direct.knobs)
        assert eplan.phase_report["train"]["contention_s"] == 0.0


# ---------------------------------------------------------------------------
# _search_phase mechanics (synthetic candidates)
# ---------------------------------------------------------------------------

class TestSearchMechanics:
    def _bundle(self, cands):
        return {"cands": [{"score_s": s, "ledgers": (led,), "row": None}
                          for s, led in cands]}

    def test_contended_combo_loses_to_frugal_one(self):
        """Two groups flooding one link: the all-own-best combo pays the
        shared-link excess, a slightly slower frugal candidate wins the
        phase — and the greedy combo is provably scored too."""
        topo = two_server_cluster()
        link = next(iter(topo.links))
        big = demand_ledger(topo, 1 << 28, link)
        tiny = demand_ledger(topo, 1 << 10, link)
        wire_big = lm.ledger_wire_s(big)
        planner = pl.Planner()
        bundles = [
            self._bundle([(wire_big, big),
                          (wire_big * 1.05, tiny)]),   # frugal, 5% slower
            self._bundle([(wire_big, big)]),
        ]
        combo, stats = planner._search_phase(bundles, planner.hw)
        assert combo == (1, 0)             # not the greedy (0, 0)
        assert stats["search"] == "exhaustive"
        assert stats["combos_scored"] == 2

    def test_budget_rejects_hostile_background_combos(self):
        """An already-planned budgeted phase constrains this one: the
        own-best combo whose background traffic busts the cap is
        rejected in favor of a feasible runner-up."""
        topo = two_server_cluster()
        link = next(iter(topo.links))
        victim = [(1e-5, (demand_ledger(topo, 1 << 12, link),))]
        big = demand_ledger(topo, 1 << 28, link)
        tiny = demand_ledger(topo, 1 << 10, link)
        planner = pl.Planner()
        bundles = [self._bundle([(1e-4, big), (2e-4, tiny)])]
        cap = 1e-3                         # busts under big, holds under tiny
        assert lm.score_phase(victim, planner.hw,
                              background=[big]) > cap
        assert lm.score_phase(victim, planner.hw,
                              background=[tiny]) < cap
        combo, stats = planner._search_phase(
            bundles, planner.hw, constraints=[(victim, cap)])
        assert combo == (1,)
        assert not stats["budget_violated"]
        # nothing feasible: best-effort falls back to the own best
        combo, stats = planner._search_phase(
            bundles, planner.hw, constraints=[(victim, 1e-9)])
        assert combo == (0,)
        assert stats["budget_violated"]

    def test_own_budget_caps_the_phase_score(self):
        topo = two_server_cluster()
        link = next(iter(topo.links))
        led = demand_ledger(topo, 1 << 12, link)
        planner = pl.Planner()
        bundles = [self._bundle([(1e-4, led), (2e-4, led)])]
        combo, stats = planner._search_phase(bundles, planner.hw,
                                             budget=1.5e-4)
        assert combo == (0,) and not stats["budget_violated"]
        combo, stats = planner._search_phase(bundles, planner.hw,
                                             budget=1e-9)
        assert combo == (0,) and stats["budget_violated"]


# ---------------------------------------------------------------------------
# phase budgets end-to-end
# ---------------------------------------------------------------------------

class TestPhaseBudgets:
    def test_unknown_phase_budget_rejected(self):
        with pytest.raises(ValueError, match="unknown phase"):
            train_program(64, 10**7).__class__(
                "p", train_program(64, 10**7).sites,
                phase_budgets={"decode": 1e-3})

    def test_nonpositive_budget_rejected(self):
        with pytest.raises(ValueError, match="positive"):
            serve_program(0.0, decode_batch=64)
        with pytest.raises(ValueError, match="positive"):
            serve_program(-1e-3)

    def test_generous_budget_is_met(self):
        topo = two_server_cluster()
        eplan = pl.Planner().plan_program(serve_program(0.5), topo)
        rep = eplan.phase_report["decode"]
        assert rep["budget_s"] == 0.5
        assert rep["budget_ok"]
        assert rep["contended_score_s"] <= 0.5
        # the contended verdict includes the OTHER phase's traffic
        assert rep["contended_score_s"] >= rep["score_s"]
        assert not eplan.planner_stats["budget_violated"]

    def test_infeasible_budget_binds_best_effort(self):
        """No prefill combination can keep a 1ms decode SLO on 2x8: the
        planner flags the violation and still binds the unconstrained
        best rather than refusing to plan."""
        topo = two_server_cluster()
        planner = pl.Planner()
        tight = planner.plan_program(serve_program(1e-3), topo)
        free = planner.plan_program(serve_program(), topo)
        rep = tight.phase_report["decode"]
        assert not rep["budget_ok"]
        assert tight.planner_stats["budget_violated"]
        for role in ("prefill/moe_dispatch", "prefill/moe_combine"):
            assert (tight.decisions[role].plan
                    == free.decisions[role].plan)

    def test_budget_changes_the_cache_key(self):
        a = serve_program().cache_key()
        b = serve_program(1e-3).cache_key()
        assert a != b
        assert serve_program(1e-3).cache_key() == b


# ---------------------------------------------------------------------------
# staleness surfacing
# ---------------------------------------------------------------------------

def _alpha_bloated(hw):
    """A recalibration that flips microbatch decisions everywhere: a
    200x operator-startup alpha makes chunking unaffordable."""
    return dataclasses.replace(hw, alpha_base=hw.alpha_base * 200)


class TestStaleness:
    def test_plan_is_stale_lifecycle(self):
        topo = two_server_cluster()
        planner = pl.Planner()
        program = train_program(1024, 100_000_000)
        e1 = planner.plan_program(program, topo)
        assert planner.plan_is_stale(e1) is False
        planner.refresh_hardware(_alpha_bloated(planner.hw))
        events = planner.replan_programs()
        ev = next(e for e in events if e["program"] == "train")
        assert ev["changed"]
        assert planner.plan_is_stale(e1) is True
        assert planner.plan_is_stale(ev["plan"]) is False

    def test_foreign_plan_is_unjudgeable(self):
        topo = two_server_cluster()
        e1 = pl.Planner().plan_program(train_program(64, 10**7), topo)
        assert pl.Planner().plan_is_stale(e1) is None
        pinned = plan_ir.pinned_execution_plan(
            serve_program(), {
                role: {"moe_scheme": "baseline",
                       "moe_combine": "baseline", "microbatch": 1}
                for role in ("decode/moe_dispatch", "decode/moe_combine",
                             "prefill/moe_dispatch",
                             "prefill/moe_combine")})
        assert pl.Planner().plan_is_stale(pinned) is None

    def test_bound_plan_stale_and_serve_warning(self, capsys):
        """The launch-surface face: a drift recalibration replans the
        bound program; ``bound_plan_stale`` flips, ``plan_report``
        carries ``stale`` and warns exactly once."""
        import jax

        from repro.core.planner import default_planner
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.context import ParallelContext
        from repro.runtime.server import ServeEngine
        if len(jax.devices()) < 1:
            pytest.skip("no devices")
        mesh = make_test_mesh(shape=(1,), axes=("model",))
        pctx = ParallelContext(mesh=mesh, pod_axis=None,
                               data_axis="model", model_axis="model",
                               plan_policy="auto",
                               fabric=two_server_cluster())
        assert pctx.bound_plan_stale() is None      # nothing bound
        d, c = pctx.moe_sites("prefill", num_experts=64, top_k=8,
                              tokens_per_rank=4096, token_bytes=TOKEN,
                              compute_s=compute_ctx(4096))
        program = plan_ir.CollectiveProgram("serve", (d, c))
        eplan = pctx.plan_collectives(program)
        pctx = pctx.bind(eplan)
        assert pctx.bound_plan_stale() is False

        class _Stub:
            prefill = staticmethod(lambda *a: None)
            decode = staticmethod(lambda *a: None)
            init_cache = staticmethod(lambda *a: None)

        engine = ServeEngine(_Stub(), None, pctx=pctx)
        dp = default_planner()
        hw0 = dp.hw
        try:
            dp.refresh_hardware(_alpha_bloated(hw0))
            events = dp.replan_programs()
            assert any(e["program"] == "serve" and e["changed"]
                       for e in events)
            assert pctx.bound_plan_stale() is True
            capsys.readouterr()
            rep = engine.plan_report(4096, 1)
            assert rep["stale"] is True
            assert "stale" in capsys.readouterr().out
            rep = engine.plan_report(4096, 1)      # one-shot warning
            assert rep["stale"] is True
            assert "stale" not in capsys.readouterr().out
        finally:
            dp.refresh_hardware(hw0)
            dp.replan_programs()
        assert pctx.bound_plan_stale() is False


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------

class TestIntrospection:
    def test_execution_plan_report_carries_search_stats(self):
        topo = get_fabric("2x8")
        planner = pl.Planner()
        eplan = planner.plan_program(train_program(1024, 10**8), topo)
        out = eplan.report()
        assert "phases" in out and "planner" in out
        stats = out["planner"]
        for key in ("search", "phases", "candidates", "product",
                    "combos_scored", "combos_pruned", "beam_width",
                    "planning_wall_s", "budget_violated"):
            assert key in stats, key
        assert stats["planning_wall_s"] > 0
        rep = out["phases"]["train"]
        assert rep["search"]["product"] == rep["search"]["combos_scored"]
        assert rep["score_s"] == pytest.approx(
            rep["solo_s"] + rep["contention_s"])

    def test_summary_surfaces_contention(self):
        topo = get_fabric("2x8")
        eplan = pl.Planner().plan_program(train_program(1024, 10**8),
                                          topo)
        assert eplan.phase_report["train"]["contention_s"] > 0
        assert "contention" in eplan.summary()

    def test_program_decision_log_row(self):
        planner = pl.Planner()
        eplan = planner.plan_program(train_program(256, 10**7),
                                     get_fabric("2x8"))
        row = next(r for r in reversed(planner.decision_log)
                   if r["op"] == "program")
        assert row["plan"] == "train"
        assert row["planner"]["combos_scored"] >= 1
        # never mistakable for a measurable op row (fit_overlap_eff
        # filters on predicted_serial_s > 0)
        assert row["predicted_serial_s"] == 0.0
        assert row["predicted_s"] == pytest.approx(
            sum(rep["score_s"]
                for rep in eplan.phase_report.values()))
