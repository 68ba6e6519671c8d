"""Fault-tolerant planning: failure model, feasibility masking, the
degradation ladder, detection, and hot plan re-bind.

Pins the ISSUE-9 acceptance surface:
  * ``FailureState`` composes onto a topology (``with_failures``),
    changes the fingerprint, and routes around dead links/relays;
  * planner candidates whose ledgers charge a dead link (or whose
    forwarding engine sits on a dead relay) are masked as infeasible —
    multiwrite degrades down the ladder instead of scoring garbage, and
    a fully partitioned fabric raises the typed ``NoFeasiblePlanError``;
  * ``PlanBinder`` double-buffers plan swaps with a fingerprint-keyed
    traced-lowering cache (zero cold retraces at swap time);
  * probe hardening (bounded retry, timeouts counted not fatal) and the
    ``FailureDetector`` strike/revive hysteresis;
  * the ``DriftMonitor`` failover arc: detection retargets registered
    programs, staleness surfaces, recovery flips back.
"""

import dataclasses

import pytest

from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core import schedules  # noqa: F401 — registers plans
from repro.core.topology import (FailureState, NO_FAILURES, get_fabric,
                                 same_fabric_fingerprint)
from repro.parallel.context import PlanBinder
from repro.telemetry import (CalibrationStore, DriftMonitor,
                             FailureDetector, GroundTruth, ProbePolicy,
                             ProbeTimeout, SimProbe,
                             attributed_bottleneck, default_registry,
                             measure_safely, rail_probe_ledger,
                             reset_default_registry)

TOKEN_BYTES = 7168
BIG = 8 << 20     # payload where multiwrite wins on a healthy 2x8


@pytest.fixture(autouse=True)
def _fresh_metrics():
    reset_default_registry()
    yield
    reset_default_registry()


def moe_program(name="ft_serve"):
    return plan_ir.CollectiveProgram(
        name=name,
        sites=plan_ir.moe_sites("prefill", num_experts=64, top_k=8,
                                tokens_per_rank=64,
                                token_bytes=TOKEN_BYTES))


# ---------------------------------------------------------------------------
# failure model (core/topology)
# ---------------------------------------------------------------------------

class TestFailureState:
    def test_empty_state_is_falsy_and_identity(self):
        topo = get_fabric("2x8")
        assert not NO_FAILURES
        assert topo.with_failures(NO_FAILURES) is topo

    def test_fingerprint_changes_under_failures(self):
        topo = get_fabric("2x8")
        fs = FailureState(dead_links={(0, 8)})
        failed = topo.with_failures(fs)
        assert failed.fingerprint() != topo.fingerprint()
        # healthy fingerprints never gain a failure element: recovery
        # flips back to the ORIGINAL identity (cache keys line up)
        assert topo.fingerprint() == get_fabric("2x8").fingerprint()

    def test_same_fabric_fingerprint_spans_failure_variants(self):
        topo = get_fabric("2x8")
        failed = topo.with_failures(FailureState(dead_links={(0, 8)}))
        assert same_fabric_fingerprint(topo.fingerprint(),
                                       failed.fingerprint())
        other = get_fabric("4x8")
        assert not same_fabric_fingerprint(topo.fingerprint(),
                                           other.fingerprint())

    def test_dead_link_routes_around(self):
        topo = get_fabric("2x8")
        failed = topo.with_failures(FailureState(dead_links={(0, 8)}))
        assert (0, 8) not in failed.links
        path = failed.path(0, 8)
        assert path[0] == 0 and path[-1] == 8
        assert (0, 8) not in zip(path, path[1:])

    def test_dead_relay_not_transited(self):
        topo = get_fabric("2x8")
        # node 1 (the healthy detour's first hop for 0->8 with rail
        # (0,8) dead) refuses to forward: the route must avoid it
        fs = FailureState(dead_links={(0, 8)}, dead_relays={1, 9})
        failed = topo.with_failures(fs)
        path = failed.path(0, 8)
        assert 1 not in path[1:-1] and 9 not in path[1:-1]

    def test_degraded_factor_multiplies_and_composes(self):
        topo = get_fabric("2x8")
        half = topo.with_failures(
            FailureState(degraded_links={(0, 8): 0.5}))
        assert half.link(0, 8).bw == pytest.approx(
            topo.link(0, 8).bw * 0.5)
        quarter = half.with_failures(
            FailureState(degraded_links={(0, 8): 0.5}))
        assert quarter.link(0, 8).bw == pytest.approx(
            topo.link(0, 8).bw * 0.25)
        # the merged identity carries the COMPOSED factor
        assert dict(quarter.failures.degraded_links)[(0, 8)] \
            == pytest.approx(0.25)

    def test_lost_npu_loses_every_link(self):
        topo = get_fabric("2x8")
        failed = topo.with_failures(FailureState(lost_npus={0}))
        assert not [k for k in failed.links if 0 in k]
        # node count is preserved (ClusterMeta invariant): the NPU is
        # lost, not renumbered
        assert failed.num_nodes == topo.num_nodes

    def test_factor_validation(self):
        with pytest.raises(ValueError):
            FailureState(degraded_links={(0, 1): 0.0})


# ---------------------------------------------------------------------------
# planner feasibility masking + the degradation ladder
# ---------------------------------------------------------------------------

class TestFeasibilityMasking:
    def test_ledger_infeasible_checks(self):
        topo = get_fabric("2x8")
        led = plan_ir.Ledger(topo=topo, link_bytes={(0, 8): 1.0},
                             relay_bytes={}, flow_counts={(0, 8): 1})
        assert pl.ledger_infeasible(led, NO_FAILURES) is None
        assert "dead link" in pl.ledger_infeasible(
            led, FailureState(dead_links={(0, 8)}))

    def test_dead_rail_reroutes_not_raises(self):
        topo = get_fabric("2x8")
        failed = topo.with_failures(FailureState(dead_links={(0, 8),
                                                             (8, 0)}))
        planner = pl.Planner()
        eplan = planner.plan_program(moe_program(), failed)
        truth_fs = failed.failures
        for role, led in pl.plan_site_ledgers(eplan, failed).items():
            assert pl.ledger_infeasible(led, truth_fs) is None, role
        # losing a rail never makes an op predict faster than healthy
        for op in ("dispatch", "allreduce", "reduce_scatter"):
            healthy = planner.choose(op, 8 << 20, topo,
                                     executable_only=True)
            hurt = planner.choose(op, 8 << 20, failed,
                                  executable_only=True)
            assert hurt.predicted_s >= healthy.predicted_s * (1 - 1e-9), op

    def test_relay_ladder_multiwrite_to_unicast(self):
        topo = get_fabric("2x8")
        planner = pl.Planner()
        healthy = planner.choose("combine", BIG, topo,
                                 executable_only=True)
        assert healthy.plan == "multiwrite"
        # the sending server's forwarding engines dead: multiwrite's
        # ledger charges a dead relay engine and masks, plain unicast
        # (relay_bytes but no engine dependence) survives
        failed = topo.with_failures(
            FailureState(dead_relays=set(range(8))))
        degraded = planner.choose("combine", BIG, failed,
                                  executable_only=True)
        assert degraded.plan == "unicast"
        reg = default_registry()
        assert reg["repro_plan_infeasible_total"].value(
            op="combine", fabric=failed.name) >= 1

    def test_relay_ladder_allreduce_to_hierarchical(self):
        topo = get_fabric("2x8")
        planner = pl.Planner()
        assert planner.choose("allreduce", BIG, topo,
                              executable_only=True).plan == "multiwrite"
        failed = topo.with_failures(
            FailureState(dead_relays=set(range(8))))
        degraded = planner.choose("allreduce", BIG, failed,
                                  executable_only=True)
        # the middle rung: hierarchical beats raw unicast-style rings
        # when only the relay engines (not the rails) are gone
        assert degraded.plan == "hierarchical"
        reg = default_registry()
        assert reg["repro_plan_infeasible_total"].value(
            op="allreduce", fabric=failed.name) >= 1

    def test_partition_raises_typed_error(self):
        topo = get_fabric("2x8")
        rails = {k for k in topo.links
                 if topo.server_of(k[0]) != topo.server_of(k[1])}
        failed = topo.with_failures(FailureState(dead_links=rails))
        planner = pl.Planner()
        with pytest.raises(pl.NoFeasiblePlanError) as ei:
            planner.choose("dispatch", BIG, failed,
                           executable_only=True)
        assert ei.value.op == "dispatch"
        assert ei.value.masked

    def test_partition_raises_for_programs_too(self):
        topo = get_fabric("2x8")
        rails = {k for k in topo.links
                 if topo.server_of(k[0]) != topo.server_of(k[1])}
        failed = topo.with_failures(FailureState(dead_links=rails))
        with pytest.raises(pl.NoFeasiblePlanError):
            pl.Planner().plan_program(moe_program(), failed)

    def test_healthy_errors_still_propagate(self):
        # masking only softens failures when a FailureState is present;
        # a healthy-fabric sweep keeps its exceptions loud
        topo = get_fabric("2x8")
        assert topo.failures is NO_FAILURES or not topo.failures
        with pytest.raises(ValueError):
            pl.Planner().choose("no_such_op", BIG, topo)


# ---------------------------------------------------------------------------
# hot plan re-bind (PlanBinder)
# ---------------------------------------------------------------------------

class _FakePlan:
    def __init__(self, fp):
        self.fingerprint = fp
        self.program = dataclasses.make_dataclass("P", ["name"])("prog")


class TestPlanBinder:
    def _binder(self):
        log = []

        def trace(plan):
            log.append(plan.fingerprint if plan else None)
            return ("lowered", plan.fingerprint if plan else None)

        return PlanBinder(trace, plan=_FakePlan("A")), log

    def test_initial_bind_traces_once(self):
        binder, log = self._binder()
        assert log == ["A"]
        assert binder.artifact == ("lowered", "A")
        assert binder.swaps == 0

    def test_stage_builds_off_path_swap_is_pointer_flip(self):
        binder, log = self._binder()
        assert binder.stage(_FakePlan("B")) is True
        assert log == ["A", "B"]          # built at STAGE time
        assert binder.plan.fingerprint == "A"   # not yet active
        assert binder.swap_if_pending() is True
        assert binder.plan.fingerprint == "B"
        assert log == ["A", "B"]          # swap built nothing
        assert binder.swaps == 1 and binder.cold_retraces == 0

    def test_flip_back_is_cache_hit(self):
        binder, log = self._binder()
        binder.stage(_FakePlan("B"))
        binder.swap_if_pending()
        binder.stage(_FakePlan("A"))      # recovery: back to original
        binder.swap_if_pending()
        assert binder.plan.fingerprint == "A"
        assert log == ["A", "B"]          # no retrace at all
        assert binder.cache_hits == 1 and binder.cold_retraces == 0

    def test_stage_active_plan_is_noop(self):
        binder, log = self._binder()
        assert binder.stage(_FakePlan("A")) is False
        assert binder.swap_if_pending() is False
        assert binder.swaps == 0

    def test_unstaged_swap_counts_cold_retrace(self):
        binder, log = self._binder()
        binder._pending = _FakePlan("C")  # bypass stage: no cache entry
        binder.swap_if_pending()
        assert binder.cold_retraces == 1
        reg = default_registry()
        assert reg["repro_rebind_cold_retrace_total"].value(
            program="prog") == 1

    def test_rebind_metrics(self):
        binder, _ = self._binder()
        binder.stage(_FakePlan("B"))
        binder.swap_if_pending()
        reg = default_registry()
        assert reg["repro_plan_rebind_total"].value(
            program="prog", fingerprint="B") == 1
        assert reg["repro_lowering_cache_misses_total"].value(
            program="prog") == 2


# ---------------------------------------------------------------------------
# probe hardening
# ---------------------------------------------------------------------------

class TestProbePolicy:
    def test_retries_then_succeeds(self):
        sleeps = []
        policy = ProbePolicy(retries=2, backoff_s=0.01, jitter=0.0,
                             sleep=sleeps.append)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ProbeTimeout("dark")
            return 42.0

        assert policy.run(flaky) == 42.0
        assert len(calls) == 3
        assert sleeps == pytest.approx([0.01, 0.02])  # exponential

    def test_exhausted_reraises(self):
        policy = ProbePolicy(retries=1, backoff_s=0.0, jitter=0.0,
                             sleep=lambda s: None)
        with pytest.raises(ProbeTimeout):
            policy.run(lambda: (_ for _ in ()).throw(ProbeTimeout("x")))

    def test_measure_safely_counts_dead_link_timeouts(self):
        topo = get_fabric("2x8")
        truth = GroundTruth().with_dead([(0, 8)])
        probe = SimProbe(truth)
        led = rail_probe_ledger(topo, (0, 8))
        policy = ProbePolicy(retries=1, backoff_s=0.0, jitter=0.0,
                             sleep=lambda s: None)
        out = measure_safely(probe, "linkprobe", "p2p", 1 << 20, topo,
                             policy=policy, ledger=led, knobs={},
                             src_node=0, dst_node=8)
        assert out is None
        reg = default_registry()
        assert reg["repro_probe_failures_total"].value(
            reason="timeout", fabric=topo.name) == 1
        # a healthy rail still measures
        led_ok = rail_probe_ledger(topo, (1, 9))
        assert measure_safely(probe, "linkprobe", "p2p", 1 << 20, topo,
                              policy=policy, ledger=led_ok, knobs={},
                              src_node=1, dst_node=9) > 0


class TestAttributedBottleneck:
    def test_measured_bandwidths_pick_the_truly_slow_direction(self):
        topo = get_fabric("2x8")
        # healthy direction carries MORE bytes — nominal attribution
        # would blame it; under measured bandwidths the 4x-slower
        # reverse direction dominates the time
        led = plan_ir.Ledger(topo=topo,
                             link_bytes={(0, 8): 1000.0, (8, 0): 1100.0},
                             relay_bytes={},
                             flow_counts={(0, 8): 1, (8, 0): 1})
        assert attributed_bottleneck(led, None) == (8, 0)
        hw = SimProbe(GroundTruth()).truth.hw.recalibrated(
            {"links": {(0, 8): topo.link(0, 8).bw / 4.0}})
        assert attributed_bottleneck(led, hw) == (0, 8)


# ---------------------------------------------------------------------------
# detection + the monitor failover arc
# ---------------------------------------------------------------------------

def _fast_policy():
    return ProbePolicy(retries=0, backoff_s=0.0, jitter=0.0,
                       sleep=lambda s: None)


class TestFailureDetector:
    def test_strike_hysteresis_and_revival(self):
        topo = get_fabric("2x8")
        det = FailureDetector(topo, strikes=2, policy=_fast_policy())
        dark = SimProbe(GroundTruth().with_dead([(0, 8)]))
        assert det.scan(dark) is False          # strike 1: not yet dead
        assert det.scan(dark) is True           # strike 2: declared
        assert det.dead_links() == frozenset({(0, 8)})
        assert det.failures().link_is_dead((0, 8))
        healthy = SimProbe(GroundTruth())
        assert det.scan(healthy) is True        # one success revives
        assert not det.dead_links()
        kinds = [e["kind"] for e in det.events]
        assert kinds == ["link_dead", "link_recovered"]

    def test_monitor_retargets_and_flips_back(self):
        topo = get_fabric("2x8")
        planner = pl.Planner()
        det = FailureDetector(topo, strikes=1, policy=_fast_policy())
        monitor = DriftMonitor(planner, CalibrationStore(":memory:"),
                               topo, detector=det)
        program = moe_program()
        eplan = planner.plan_program(program, topo)
        assert planner.plan_is_stale(eplan) is False

        dark = SimProbe(GroundTruth(seed=1).with_dead([(0, 8), (8, 0)]))
        monitor.run_cycle(dark)
        assert monitor.topo.fingerprint() != topo.fingerprint()
        # the bound plan is now stale: the program was retargeted
        assert planner.plan_is_stale(eplan) is True
        staged = monitor.staged_plan(program.name)
        assert staged is not None
        assert staged.fingerprint != eplan.fingerprint
        fs = FailureState(dead_links={(0, 8), (8, 0)})
        for role, led in pl.plan_site_ledgers(staged,
                                              monitor.topo).items():
            assert pl.ledger_infeasible(led, fs) is None, role
        assert monitor.events[-1]["kind"] == "failover"

        healthy = SimProbe(GroundTruth(seed=2))
        monitor.run_cycle(healthy)
        assert monitor.topo.fingerprint() == topo.fingerprint()
        assert monitor.events[-1]["kind"] == "failback"
        back = monitor.staged_plan(program.name)
        decisions = lambda p: {r: (p.decisions[r].plan,          # noqa: E731
                                   tuple(p.decisions[r].knobs))
                               for r in sorted(p.decisions)}
        assert decisions(back) == decisions(eplan)
