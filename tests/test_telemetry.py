"""Tests for the telemetry & online-calibration subsystem (ISSUE 3).

Covers:
  * CalibrationStore: JSONL round-trip, schema versioning, fabric/op
    keying, latest-record supersession.
  * SimProbe / GroundTruth: injectable degradation shows up only on the
    affected link class.
  * fit: per-link-class alpha/beta regression round-trip (fitted
    measurements reproduce injected bandwidths within tolerance),
    outlier rejection, confidence floor, and score_ledger ranking flips
    under the fitted model (the recalibrated round-trip satellite).
  * Planner: hw fingerprint in the LRU key (stale-cache regression),
    refresh_hardware invalidation, decision_log rows.
  * THE ACCEPTANCE PROPERTY: with a simulated 4x degradation of
    inter-server links, the monitor re-fits and the planner's dispatch
    decision flips from the pre-degradation choice without process
    restart.
  * Hot-expert (skewed) routing scenarios: traffic concentration,
    scenario cache keying, planner pricing.
  * ParallelContext calibration wiring.
  * The soak: a bound serving program through a 4x inter-rail
    degradation and its recovery — detection latency, fit convergence,
    planner-vs-oracle flips, one-shot stale warnings, SLO class walk.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import latency_model as lm
from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core import schedules as sch
from repro.core.topology import full_mesh, get_fabric, two_server_cluster
from repro.telemetry import (CalibrationStore, DriftMonitor, GroundTruth,
                             SimProbe, calibrated_hw, fit_link_classes,
                             fit_measurements, link_class, probe_sweep,
                             reset_default_registry, topo_key)

TOPO = two_server_cluster()


def healthy_records(noise=0.0, seed=0, hw=lm.DEFAULT):
    return probe_sweep(TOPO, SimProbe(GroundTruth(noise=noise, seed=seed)),
                       hw=hw)


def degraded_records(factor=4.0, noise=0.0, seed=0, hw=lm.DEFAULT):
    truth = GroundTruth(noise=noise, seed=seed).degraded(TOPO, factor)
    return probe_sweep(TOPO, SimProbe(truth), hw=hw)


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------

class TestCalibrationStore:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "cal.jsonl")
        store = CalibrationStore(path)
        recs = healthy_records()
        store.extend(recs)
        # fresh instance reads the same records back from disk
        again = CalibrationStore(path)
        assert len(again) == len(recs)
        assert again.records(op="dispatch")
        assert all(r["schema"] == 1 for r in again.records())

    def test_records_filtered_by_fabric_and_op(self):
        store = CalibrationStore(":memory:")
        store.extend(healthy_records())
        other = full_mesh(8)
        store.extend(probe_sweep(other, SimProbe(GroundTruth()),
                                 ops=("allgather",)))
        assert store.records(fabric=topo_key(other), op="dispatch") == []
        mine = store.records(fabric=topo_key(TOPO))
        assert mine and all(r["fabric"] == topo_key(TOPO) for r in mine)
        assert set(store.fabrics()) == {topo_key(TOPO), topo_key(other)}

    def test_latest_record_supersedes(self):
        """A re-probed (op, plan, bucket) replaces its older measurement
        in the fitter's view — degradations don't average against the
        healthy history."""
        store = CalibrationStore(":memory:")
        store.extend(healthy_records())
        store.extend(degraded_records())
        latest = store.latest_by_key(fabric=topo_key(TOPO))
        assert len(latest) < len(store)          # dedup happened
        some = next(r for r in latest.values()
                    if r["op"] == "dispatch" and r["plan"] == "unicast")
        healthy = next(r for r in healthy_records()
                       if r["op"] == "dispatch" and r["plan"] == "unicast"
                       and r["bucket"] == some["bucket"])
        assert some["measured_s"] > 2 * healthy["measured_s"]

    def test_newer_schema_skipped_on_read(self, tmp_path):
        path = str(tmp_path / "cal.jsonl")
        store = CalibrationStore(path)
        store.append(healthy_records()[0])
        with open(path, "a") as f:
            fut = dict(healthy_records()[1], schema=99)
            f.write(json.dumps(fut) + "\n")
            f.write("{torn line\n")
        again = CalibrationStore(path)
        assert len(again) == 1                   # v99 + torn line skipped

    def test_missing_fields_rejected(self):
        with pytest.raises(ValueError, match="missing"):
            CalibrationStore(":memory:").append({"op": "dispatch"})


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

class TestSimProbe:
    def test_measured_matches_predicted_when_truth_is_model(self):
        """Noise-free truth == calibration -> measured == predicted."""
        for r in healthy_records():
            assert r["measured_s"] == pytest.approx(r["predicted_s"],
                                                    rel=1e-9)

    def test_degradation_hits_only_inter_class(self):
        base = {(r["op"], r["plan"], r["bucket"]): r
                for r in healthy_records()}
        for r in degraded_records(4.0):
            ref = base[(r["op"], r["plan"], r["bucket"])]
            ratio = r["measured_s"] / ref["measured_s"]
            if r["bottleneck_class"] == "inter":
                # baseline plans are rail-serialization-dominated and
                # must slow near-proportionally; multiwrite plans keep
                # their (unaffected) relay-engine terms, so they slow
                # less — but every rail-crossing plan must slow SOME
                floor = 1.5 if r["plan"] in ("unicast", "baseline") else 1.05
                assert ratio > floor, (r["op"], r["plan"], ratio)
            elif r["class_bytes"]["inter"] == 0:
                assert ratio == pytest.approx(1.0, rel=1e-6)

    def test_records_carry_fit_regressors(self):
        for r in healthy_records():
            assert r["bottleneck_class"] in ("intra", "inter")
            assert r["class_bytes"][r["bottleneck_class"]] > 0
            assert r["bucket"] == pl.bucket_payload(r["payload_bytes"])

    def test_noise_is_lognormal_jitter(self):
        a = healthy_records(noise=0.05, seed=3)
        b = healthy_records(noise=0.0)
        ratios = [x["measured_s"] / y["measured_s"] for x, y in zip(a, b)]
        assert any(abs(r - 1) > 0.01 for r in ratios)
        assert all(0.5 < r < 2.0 for r in ratios)


# ---------------------------------------------------------------------------
# fit (recalibrated round-trip satellite)
# ---------------------------------------------------------------------------

class TestFit:
    def test_round_trip_recovers_injected_bandwidths(self):
        """Fitted measurements from a synthetic sweep reproduce the
        injected per-class bandwidths within tolerance."""
        fits = fit_link_classes(healthy_records())
        assert fits["intra"].trusted and fits["inter"].trusted
        assert fits["intra"].bw == pytest.approx(56e9, rel=0.10)
        assert fits["inter"].bw == pytest.approx(25e9, rel=0.10)
        # degrade 4x: the fit must follow the truth, not the datasheet
        fits4 = fit_link_classes(degraded_records(4.0))
        assert fits4["inter"].bw == pytest.approx(25e9 / 4, rel=0.15)
        assert fits4["intra"].bw == pytest.approx(56e9, rel=0.10)

    def test_measurements_feed_recalibrated(self):
        meas, fits = fit_measurements(degraded_records(4.0), TOPO)
        hw = lm.DEFAULT.recalibrated(meas, TOPO)
        inter = [bw for (a, b), bw in hw.measured_link_bw().items()
                 if TOPO.server_of(a) != TOPO.server_of(b)]
        assert inter and all(
            bw == pytest.approx(25e9 / 4, rel=0.15) for bw in inter)
        # alpha_base pinned by the relay-free allgather-baseline sweep
        assert meas["alpha_base"] == pytest.approx(20e-6, rel=0.25)

    def test_score_ledger_rankings_flip_under_fit(self):
        """score_ledger rankings must flip accordingly: at batch 64 the
        unicast dispatch ledger wins nominally but loses under the
        fitted 4x-degraded model."""
        meas, _ = fit_measurements(degraded_records(4.0), TOPO)
        hw_fit = lm.DEFAULT.recalibrated(meas, TOPO)
        scn = plan_ir.DispatchScenario(topo=TOPO)
        payload = 64 * lm.TOKEN_BYTES
        uni = plan_ir.get_plan("dispatch", "unicast").simulate(scn, payload)
        mw = plan_ir.get_plan("dispatch", "multiwrite").simulate(
            scn, payload)
        assert lm.score_ledger(uni) < lm.score_ledger(mw)
        assert lm.score_ledger(uni, hw_fit) > lm.score_ledger(mw, hw_fit)

    def test_outlier_rejection(self):
        recs = healthy_records()
        for r in recs:
            if r["op"] == "dispatch" and r["plan"] == "unicast":
                r["measured_s"] *= 10.0      # one corrupted sweep point
                break
        fits = fit_link_classes(recs)
        assert fits["inter"].trusted
        assert fits["inter"].n_rejected >= 1
        assert fits["inter"].bw == pytest.approx(25e9, rel=0.15)

    def test_confidence_floor_short_sweep(self):
        """Two payload points cannot pin a line: untrusted, and
        fit_measurements emits nothing for that class."""
        recs = [r for r in healthy_records()
                if r["op"] != "allgather"][:2]
        fits = fit_link_classes(recs)
        assert not any(f.trusted for f in fits.values())
        meas, _ = fit_measurements(recs, TOPO)
        assert meas == {}

    def test_confidence_floor_noisy_sweep(self):
        recs = healthy_records(noise=0.8, seed=7)
        fits = fit_link_classes(recs)
        untrusted = [f for f in fits.values() if not f.trusted]
        assert untrusted and all(f.reason for f in untrusted)

    def test_only_baseline_plans_feed_the_regression(self):
        """Multiwrite records carry their own payload-linear relay terms;
        the fitter must regress baselines only."""
        recs = [r for r in healthy_records()
                if r["plan"] in ("multiwrite", "multiwrite_paired")]
        fits = fit_link_classes(recs)
        assert not fits        # nothing to regress: all filtered out

    def test_calibrated_hw_store_surface(self):
        store = CalibrationStore(":memory:")
        assert calibrated_hw(store, TOPO) is lm.DEFAULT   # empty store
        store.extend(degraded_records(4.0))
        hw = calibrated_hw(store, TOPO)
        assert hw.link_bw
        # memoized per (store instance + revision, fabric): same object
        assert calibrated_hw(store, TOPO) is hw

    def test_calibrated_hw_distinct_memory_stores_never_alias(self):
        """Regression: two ':memory:' stores with identical record
        counts must not share memoization entries — the degraded store
        must NOT get the healthy store's cached fit."""
        s_healthy = CalibrationStore(":memory:")
        s_healthy.extend(healthy_records())
        s_degraded = CalibrationStore(":memory:")
        s_degraded.extend(degraded_records(4.0))
        assert len(s_healthy) == len(s_degraded)
        hw_h = calibrated_hw(s_healthy, TOPO)
        hw_d = calibrated_hw(s_degraded, TOPO)
        assert hw_h != hw_d
        rail = next(k for k, ln in TOPO.links.items()
                    if TOPO.server_of(ln.src) != TOPO.server_of(ln.dst))
        assert hw_d.measured_link_bw()[rail] == pytest.approx(25e9 / 4,
                                                              rel=0.15)


# ---------------------------------------------------------------------------
# planner: stale-cache regression + decision log
# ---------------------------------------------------------------------------

class TestPlannerRecalibration:
    def test_in_place_hw_swap_never_serves_stale_decisions(self):
        """Regression (stale-cache hazard): the LRU key carries the hw
        FINGERPRINT, so swapping planner.hw in place — without any
        explicit cache_clear — must re-sweep, not serve the decision
        scored under the old calibration."""
        planner = pl.Planner()
        payload = 64 * lm.TOKEN_BYTES
        d1 = planner.choose("dispatch", payload, TOPO,
                            token_bytes=lm.TOKEN_BYTES)
        assert d1.plan == "unicast"
        links = {k: ln.bw / 4 for k, ln in TOPO.links.items()
                 if TOPO.server_of(ln.src) != TOPO.server_of(ln.dst)}
        planner.hw = planner.hw.recalibrated({"links": links}, TOPO)
        d2 = planner.choose("dispatch", payload, TOPO,
                            token_bytes=lm.TOKEN_BYTES)
        assert d2.plan == "multiwrite"
        assert planner.cache_info()["misses"] == 2

    def test_value_equal_hw_share_cache_entries(self):
        planner = pl.Planner()
        d1 = planner.choose("allgather", 1 << 20, TOPO)
        clone = dataclasses.replace(lm.DEFAULT)
        d2 = planner.choose("allgather", 1 << 20, TOPO, hw=clone)
        assert d2 is d1
        assert planner.cache_info()["hits"] == 1

    def test_refresh_hardware_invalidates_and_counts(self):
        planner = pl.Planner()
        planner.choose("allgather", 1 << 20, TOPO)
        assert planner.cache_info()["size"] == 1
        planner.refresh_hardware(lm.IDEAL)
        assert planner.cache_info()["size"] == 0
        assert planner.recalibrations == 1
        assert planner.hw is lm.IDEAL

    def test_decision_log_rows_and_measurement_fill(self):
        planner = pl.Planner()
        d = planner.choose("dispatch", 64 * lm.TOKEN_BYTES, TOPO,
                           token_bytes=lm.TOKEN_BYTES)
        row = planner.decision_log[-1]
        assert row["plan"] == d.plan
        assert row["predicted_s"] == d.predicted_s
        assert row["measured_s"] is None
        planner.note_measurement(d, 123e-6)
        assert planner.decision_log[-1]["measured_s"] == 123e-6
        # cache hit adds no new row; a second measurement appends one
        planner.choose("dispatch", 64 * lm.TOKEN_BYTES, TOPO,
                       token_bytes=lm.TOKEN_BYTES)
        n = len(planner.decision_log)
        planner.note_measurement(d, 125e-6)
        assert len(planner.decision_log) == n + 1


# ---------------------------------------------------------------------------
# the closed loop (ACCEPTANCE)
# ---------------------------------------------------------------------------

class TestClosedLoop:
    def test_4x_degradation_flips_dispatch_without_restart(self):
        """ISSUE 3 acceptance: simulate a 4x degradation of inter-server
        links; the monitor must detect drift, re-fit, recalibrate the
        planner and flip its dispatch decision in-process."""
        planner = pl.Planner()
        store = CalibrationStore(":memory:")
        monitor = DriftMonitor(planner, store, TOPO, threshold=0.25)
        payload = 64 * lm.TOKEN_BYTES

        # healthy fabric: probes agree with the model, nothing trips
        assert monitor.run_cycle(SimProbe(GroundTruth(noise=0.01))) is None
        assert monitor.drift() < 0.1
        d_pre = planner.choose("dispatch", payload, TOPO,
                               token_bytes=lm.TOKEN_BYTES)
        assert d_pre.plan == "unicast"
        flip_pre = pl.emergent_flip_batch("dispatch", TOPO, planner=planner)

        # rails silently degrade 4x (only measured times change)
        truth = GroundTruth(noise=0.01, seed=1).degraded(TOPO, 4.0)
        event = None
        for _ in range(3):
            event = monitor.run_cycle(SimProbe(truth))
            if event:
                break
        assert event is not None, "monitor never tripped"
        assert event["drift"] > monitor.threshold
        assert event["fits"]["inter"]["trusted"]
        assert event["fits"]["inter"]["bw_gbps"] == pytest.approx(
            25 / 4, rel=0.2)

        # same planner object, no restart, no manual cache_clear:
        d_post = planner.choose("dispatch", payload, TOPO,
                                token_bytes=lm.TOKEN_BYTES)
        assert d_post.plan == "multiwrite"
        assert planner.recalibrations >= 1
        # the emergent flip batch moved down accordingly
        flip_post = pl.emergent_flip_batch("dispatch", TOPO,
                                           planner=planner)
        assert flip_post < 128 and flip_post < flip_pre

    def test_no_drift_no_recalibration(self):
        planner = pl.Planner()
        monitor = DriftMonitor(planner, CalibrationStore(":memory:"),
                               TOPO, threshold=0.25)
        for _ in range(2):
            assert monitor.run_cycle(SimProbe(GroundTruth())) is None
        assert planner.recalibrations == 0
        assert monitor.report()["recalibrations"] == 0

    def test_recovery_recalibrates_back(self):
        """Degrade, recalibrate, then heal: the monitor must walk the
        model back toward nominal (drift is symmetric)."""
        planner = pl.Planner()
        store = CalibrationStore(":memory:")
        monitor = DriftMonitor(planner, store, TOPO, threshold=0.25)
        truth_bad = GroundTruth(seed=1).degraded(TOPO, 4.0)
        for _ in range(2):
            if monitor.run_cycle(SimProbe(truth_bad)):
                break
        assert planner.recalibrations == 1
        # fabric heals: measured times shrink back, model now over-prices
        event = None
        for _ in range(3):
            event = monitor.run_cycle(SimProbe(GroundTruth()))
            if event:
                break
        assert event is not None
        assert event["fits"]["inter"]["bw_gbps"] == pytest.approx(25,
                                                                  rel=0.15)
        d = planner.choose("dispatch", 64 * lm.TOKEN_BYTES, TOPO,
                           token_bytes=lm.TOKEN_BYTES)
        assert d.plan == "unicast"

    def test_monitor_report_shape(self):
        planner = pl.Planner()
        monitor = DriftMonitor(planner, CalibrationStore(":memory:"), TOPO)
        monitor.run_cycle(SimProbe(GroundTruth()))
        rep = monitor.report()
        assert {"drift_pct", "observations", "recalibrations",
                "last_recalibration", "store_records"} <= set(rep)
        assert rep["observations"] > 0

    def test_monitor_fills_planner_decision_log(self):
        """The probe cycle closes the planner's audit trail: a logged
        decision whose plan the probe timed at the same payload bucket
        gets its measured_s filled."""
        planner = pl.Planner()
        d = planner.choose("dispatch", 512 * lm.TOKEN_BYTES, TOPO,
                           token_bytes=lm.TOKEN_BYTES)
        assert planner.decision_log[-1]["measured_s"] is None
        monitor = DriftMonitor(planner, CalibrationStore(":memory:"), TOPO)
        monitor.run_cycle(SimProbe(GroundTruth()))
        row = next(r for r in planner.decision_log
                   if r["plan"] == d.plan
                   and r["payload_bytes"] == d.payload_bytes)
        assert row["measured_s"] is not None and row["measured_s"] > 0


# ---------------------------------------------------------------------------
# hot-expert (skewed) routing scenarios
# ---------------------------------------------------------------------------

class TestPerRoleFits:
    """Per-link (directed ROLE) fits — the asymmetric-fabric debt item:
    ``2x8asym`` must no longer collapse both rail directions to one
    "inter" bandwidth."""

    def test_asym_directions_fit_separately(self):
        from repro.core.topology import get_fabric
        from repro.telemetry import link_role
        topo = get_fabric("2x8asym")        # return rails at half bw
        records = probe_sweep(topo, SimProbe(GroundTruth(noise=0.005)))
        meas, fits = fit_measurements(records, topo)
        links = meas["links"]
        rev = {bw for (a, b), bw in links.items()
               if link_role(topo, a, b) == "inter:1>0"}
        # the degraded (bottleneck) direction is identified near its
        # true 12.5 GB/s ...
        assert rev, f"no reverse-rail fits in {sorted(fits)}"
        for bw in rev:
            assert bw == pytest.approx(12.5e9, rel=0.1)
        # ... and the forward rails do NOT inherit the slow line: the
        # end-to-end times carry no evidence about the direction that
        # never bottlenecks, so it keeps the nominal 25 GB/s (no
        # override) instead of being mislabeled at ~12.5
        fwd = [k for k in topo.links
               if link_role(topo, *k) == "inter:0>1"]
        assert fwd and all(k not in links for k in fwd)

    def test_symmetric_fabric_fits_both_directions(self):
        from repro.telemetry import link_role
        records = healthy_records(noise=0.005)
        meas, fits = fit_measurements(records, TOPO)
        by_role = {}
        for (a, b), bw in meas["links"].items():
            by_role.setdefault(link_role(TOPO, a, b), []).append(bw)
        for role in ("inter:0>1", "inter:1>0"):
            assert role in by_role, sorted(by_role)
            for bw in by_role[role]:
                assert bw == pytest.approx(25e9, rel=0.1)

    def test_role_records_and_fit_surface(self):
        from repro.telemetry import fit_link_roles, ledger_role_bytes
        records = healthy_records()
        for r in records:
            assert "bottleneck_role" in r and "role_bytes" in r
        role_fits = fit_link_roles(records)
        assert any(f.trusted for f in role_fits.values())
        # ledger role bytes refine class bytes: the inter class max is
        # the max over the inter roles
        scenario = plan_ir.DispatchScenario(topo=TOPO)
        led = plan_ir.get_plan("dispatch", "unicast").simulate(
            scenario, 512 * lm.TOKEN_BYTES)
        roles = ledger_role_bytes(led)
        inter_roles = {k: v for k, v in roles.items() if k != "intra"}
        assert inter_roles
        from repro.telemetry import ledger_class_bytes
        assert max(inter_roles.values()) == \
            ledger_class_bytes(led)["inter"]

    def test_uniform_class_degradation_overrides_all_links(self):
        """On a nominally-UNIFORM fabric the class fit still generalizes
        to every link — a 4x inter degradation on 4x8 must override all
        96 inter links even though only a couple of directed roles ever
        set the bottleneck (the closed-loop property must not regress on
        >2-server fabrics)."""
        from repro.core.topology import get_fabric
        from repro.telemetry import link_class
        topo = get_fabric("4x8")
        truth = GroundTruth(noise=0.005).degraded(topo, 4.0)
        records = probe_sweep(topo, SimProbe(truth))
        meas, _ = fit_measurements(records, topo)
        inter = [k for k in topo.links if link_class(topo, *k) == "inter"]
        assert all(k in meas["links"] for k in inter)
        for k in inter:
            assert meas["links"][k] == pytest.approx(25e9 / 4, rel=0.1)

    def test_old_schema_records_fall_back_to_class(self):
        """Records without role fields (pre-role stores) still fit at
        the class level and override every link of the class."""
        records = healthy_records()
        for r in records:
            r.pop("bottleneck_role", None)
            r.pop("role_bytes", None)
        meas, _ = fit_measurements(records, TOPO)
        inter = [k for k in TOPO.links
                 if TOPO.server_of(k[0]) != TOPO.server_of(k[1])]
        assert all(k in meas["links"] for k in inter)


class TestSkewedRouting:
    def test_skew_concentrates_expert_traffic(self):
        flat = sch.make_routing(64, 16, 64, 8, seed=0)
        hot = sch.make_routing(64, 16, 64, 8, seed=0, skew=2.0)

        def npu_load(routing):
            loads = np.zeros(16)
            for dests in routing.token_dests:
                for d in dests:
                    loads[d] += 1
            return loads

        lf, lh = npu_load(flat), npu_load(hot)
        assert lh.max() / lh.mean() > 2 * lf.max() / lf.mean()
        assert int(np.argmax(lh)) == 0     # hot experts live on NPU 0

    def test_scenario_cache_key_includes_skew(self):
        s0 = plan_ir.DispatchScenario(topo=TOPO)
        s1 = plan_ir.DispatchScenario(topo=TOPO, skew=1.5)
        assert s0.cache_key() != s1.cache_key()
        c0 = plan_ir.CombineScenario(topo=TOPO)
        c1 = plan_ir.CombineScenario(topo=TOPO, skew=1.5)
        assert c0.cache_key() != c1.cache_key()

    def test_planner_prices_skew_separately(self):
        """Skewed routing simulates a different ledger (hot rail), so the
        planner must cache and price it separately from balanced."""
        planner = pl.Planner()
        payload = 256 * lm.TOKEN_BYTES
        d_flat = planner.choose("dispatch", payload, TOPO,
                                token_bytes=lm.TOKEN_BYTES)
        d_hot = planner.choose("dispatch", payload, TOPO,
                               token_bytes=lm.TOKEN_BYTES, skew=2.0)
        assert planner.cache_info()["misses"] == 2    # distinct keys
        assert d_hot.predicted_s != d_flat.predicted_s

    def test_skewed_unicast_ledger_has_hotter_rail(self):
        """Hot experts concentrate the unicast dispatch's redundant
        copies onto the hot NPUs' rails: the max/mean inter-link ratio
        must grow with skew."""
        scn_f = plan_ir.DispatchScenario(topo=TOPO)
        scn_h = plan_ir.DispatchScenario(topo=TOPO, skew=2.0)
        plan = plan_ir.get_plan("dispatch", "unicast")
        payload = 512 * lm.TOKEN_BYTES

        def rail_imbalance(ledger):
            rails = [v for (a, b), v in ledger.link_bytes.items()
                     if TOPO.server_of(a) != TOPO.server_of(b)]
            return max(rails) / (sum(rails) / len(rails))

        imb_f = rail_imbalance(plan.simulate(scn_f, payload))
        imb_h = rail_imbalance(plan.simulate(scn_h, payload))
        assert imb_h > 1.5 * imb_f

    def test_moe_decision_helper_accepts_skew(self):
        d = pl.moe_dispatch_decision(
            num_pods=2, ep_per_pod=8, num_experts=64, top_k=8,
            tokens_per_rank=2048, token_bytes=7168, skew=1.0)
        assert d.op == "dispatch"


# ---------------------------------------------------------------------------
# context wiring
# ---------------------------------------------------------------------------

class TestContextCalibration:
    @pytest.fixture()
    def pctx(self):
        import jax

        from repro.launch.mesh import make_test_mesh
        from repro.parallel.context import ParallelContext
        if len(jax.devices()) < 1:
            pytest.skip("no devices")
        mesh = make_test_mesh(shape=(1,), axes=("model",))
        return ParallelContext(mesh=mesh, pod_axis=None, data_axis="model",
                               model_axis="model", plan_policy="auto",
                               fabric=TOPO)

    def test_calibration_store_changes_resolved_scheme(self, pctx):
        """A calibration store holding 4x-degraded measurements must flip
        the trace-time dispatch resolution for the same workload."""
        base = pctx.moe_pipeline_kwargs(64, 8, tokens_per_rank=64,
                                        token_bytes=lm.TOKEN_BYTES)
        assert base["moe_scheme"] == "baseline"  # batch 64 nominal: unicast
        store = CalibrationStore(":memory:")
        store.extend(degraded_records(4.0))
        cal = dataclasses.replace(pctx, calibration=store)
        got = cal.moe_pipeline_kwargs(64, 8, tokens_per_rank=64,
                                      token_bytes=lm.TOKEN_BYTES)
        assert got["moe_scheme"] == "hierarchical"
        # the combine half resolves under the same fitted model, jointly
        assert got["moe_combine"] == "hierarchical"

    def _site_decision(self, pctx, tokens_per_rank):
        from repro.core import plan as plan_ir
        sites = pctx.moe_sites("t", num_experts=64, top_k=8,
                               tokens_per_rank=tokens_per_rank,
                               token_bytes=lm.TOKEN_BYTES)
        eplan = pctx.plan_collectives(plan_ir.CollectiveProgram("t", sites))
        return eplan.decision("t/moe_dispatch")

    def test_moe_skew_threads_to_planner(self, pctx):
        hot = dataclasses.replace(pctx, moe_skew=2.0)
        d_flat = self._site_decision(pctx, 256)
        d_hot = self._site_decision(hot, 256)
        assert d_hot.predicted_s != d_flat.predicted_s


# ---------------------------------------------------------------------------
# soak: a 4x inter-rail degradation and its recovery, one telemetry cycle
# per epoch, against a bound serving program
# ---------------------------------------------------------------------------

SOAK_EPOCHS = 6
SOAK_DETECT_WITHIN = 2     # epochs from an injected event to its recal
SOAK_SCHEDULE = ({"epoch": 1, "kind": "degrade", "factor": 4.0},
                 {"epoch": 4, "kind": "recover"})
SOAK_FLIP_BATCH = 64       # dispatch cell: unicast healthy, multiwrite at 4x
SOAK_SLO_BATCH = 512       # large-payload cell whose SLO class is read
SLO_WORST_FIRST = ("poor", "acceptable", "good")


def _true_inter_bw(truth, topo) -> float:
    """Mean bandwidth the truth's inter-server links actually deliver."""
    cur = dict(truth.link_bw)
    bws = [cur.get(key, ln.bw) for key, ln in topo.links.items()
           if link_class(topo, *key) == "inter"]
    return sum(bws) / len(bws)


def _apply_event(truth, topo, event):
    if event["kind"] == "degrade":
        return truth.degraded(topo, event["factor"])
    # recover: drop every per-link override and any blackout
    return dataclasses.replace(truth, link_bw=(), dead_links=())


def _slo_counts(reg, bucket) -> dict:
    """Cumulative probe SLO classes of the dispatch cell at ``bucket``."""
    counts = dict.fromkeys(SLO_WORST_FIRST, 0.0)
    for labels, v in reg["repro_slo_class_total"].samples():
        if (labels["op"] == "dispatch"
                and labels["payload_bucket"] == str(bucket)
                and labels["slo"] in counts):
            counts[labels["slo"]] += v
    return counts


@pytest.fixture(scope="module")
def soak():
    """Serve a bound prefill/decode program through the scheduled
    degradation: per epoch inject the event, run one DriftMonitor cycle
    on a fresh-seeded SimProbe, run the one-shot stale check twice
    (re-binding when stale), and read the planner's and a truth
    oracle's dispatch choice and the epoch's worst SLO class."""
    reg = reset_default_registry()
    topo = get_fabric("2x8")
    planner = pl.Planner()
    monitor = DriftMonitor(planner, CalibrationStore(":memory:"), topo)
    truth = GroundTruth(noise=0.01, seed=0)
    program = plan_ir.CollectiveProgram(
        name="soak_serve",
        sites=(*plan_ir.moe_sites("prefill", num_experts=64, top_k=8,
                                  tokens_per_rank=SOAK_FLIP_BATCH,
                                  token_bytes=lm.TOKEN_BYTES),
               *plan_ir.moe_sites("decode", num_experts=64, top_k=8,
                                  tokens_per_rank=4,
                                  token_bytes=lm.TOKEN_BYTES)))
    eplan = planner.plan_program(program, topo)
    flip_payload = float(SOAK_FLIP_BATCH) * lm.TOKEN_BYTES
    slo_bucket = pl.bucket_payload(float(SOAK_SLO_BATCH) * lm.TOKEN_BYTES)
    by_epoch = {ev["epoch"]: ev for ev in SOAK_SCHEDULE}

    run = {"recal_epochs": [], "changed_recals": [], "stale_warnings": [],
           "timeline": []}
    warned = False
    prev_slo = _slo_counts(reg, slo_bucket)
    prev_plan = None
    for epoch in range(SOAK_EPOCHS):
        if epoch in by_epoch:
            truth = _apply_event(truth, topo, by_epoch[epoch])
        probe = SimProbe(dataclasses.replace(truth, seed=1000 + epoch))
        recal = monitor.run_cycle(probe)
        if recal is not None:
            run["recal_epochs"].append(epoch)
            if any(p["changed"] for p in recal.get("programs", [])):
                run["changed_recals"].append(epoch)
        stale = False
        for _ in range(2):        # the second check must never warn again
            stale = bool(planner.plan_is_stale(eplan))
            if stale and not warned:
                warned = True
                run["stale_warnings"].append(epoch)
        if stale:
            eplan = (monitor.replanned(program.name)
                     or planner.plan_program(program, topo))
            warned = False
        decision = planner.choose("dispatch", flip_payload, topo)
        oracle = pl.Planner(hw=truth.true_hw()).choose(
            "dispatch", flip_payload, topo)
        slo = _slo_counts(reg, slo_bucket)
        # the epoch's class is the WORST class seen in it: SLOs report
        # the tail, so one poor probe among good ones makes it poor
        slo_class = next((cls for cls in SLO_WORST_FIRST
                          if slo[cls] > prev_slo[cls]), None)
        run["timeline"].append({
            "epoch": epoch,
            "fits": recal["fits"] if recal else None,
            "true_inter_bw": _true_inter_bw(truth, topo),
            "planner_plan": decision.plan,
            "oracle_plan": oracle.plan,
            "flipped": prev_plan is not None and decision.plan != prev_plan,
            "slo_class": slo_class,
        })
        prev_slo, prev_plan = slo, decision.plan
    return run


def _soak_detection(run):
    """Every injected event trips a recalibration within the window."""
    for ev in SOAK_SCHEDULE:
        window = range(ev["epoch"], ev["epoch"] + SOAK_DETECT_WITHIN + 1)
        assert any(e in window for e in run["recal_epochs"]), \
            (ev, run["recal_epochs"])


def _soak_convergence(run):
    """After each class-uniform event, the last trusted inter fit in the
    detection window lands within 20% of the injected true bandwidth."""
    for ev in SOAK_SCHEDULE:
        rows = [r for r in run["timeline"] if r["fits"]
                and ev["epoch"] <= r["epoch"]
                <= ev["epoch"] + SOAK_DETECT_WITHIN]
        assert rows, ev
        fit = rows[-1]["fits"].get("inter", {})
        assert fit.get("trusted", False), (ev, fit)
        true_bw = rows[-1]["true_inter_bw"]
        assert abs(fit["bw_gbps"] * 1e9 - true_bw) / true_bw <= 0.20, \
            (ev, fit, true_bw)


def _soak_flips(run):
    """Outside the detection grace windows the fitted planner agrees
    with the truth oracle, and at least one genuine flip happened."""
    grace = {e for ev in SOAK_SCHEDULE
             for e in range(ev["epoch"],
                            ev["epoch"] + SOAK_DETECT_WITHIN + 1)}
    mismatches = [r["epoch"] for r in run["timeline"]
                  if r["epoch"] not in grace
                  and r["planner_plan"] != r["oracle_plan"]]
    assert not mismatches, run["timeline"]
    assert sum(r["flipped"] for r in run["timeline"]) >= 1


def _soak_stale(run):
    """The stale-plan warning fires exactly once per recalibration that
    changed the bound program (a re-bind resets the one-shot)."""
    assert run["changed_recals"]
    assert run["stale_warnings"] == run["changed_recals"]


def _soak_slo(run):
    """The dispatch SLO class walks good -> poor (stale model at the
    degradation epoch) -> good (after recalibration)."""
    deg = next(ev["epoch"] for ev in SOAK_SCHEDULE
               if ev["kind"] == "degrade")
    classes = [r["slo_class"] for r in run["timeline"]]
    pre = [c for c in classes[:deg] if c]
    post = [c for c in classes[deg + 1:] if c]
    assert pre and pre[-1] == "good", classes
    assert classes[deg] == "poor", classes
    assert "good" in post, classes


SOAK_CHECKS = {"detection": _soak_detection,
               "convergence": _soak_convergence,
               "flips": _soak_flips,
               "stale": _soak_stale,
               "slo": _soak_slo}


@pytest.mark.parametrize("check", sorted(SOAK_CHECKS))
def test_soak(soak, check):
    SOAK_CHECKS[check](soak)
