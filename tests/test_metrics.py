"""Tests for the observability plane (ISSUE 8).

Covers:
  * MetricsRegistry: counter/gauge/histogram semantics, label keying,
    idempotent registration, schema-conflict rejection.
  * Prometheus text exposition: render -> parse_text round-trip,
    deterministic ordering, HELP/TYPE headers for zero-sample metrics.
  * Histogram bucket edges: an observation exactly equal to a bucket
    bound lands IN that bucket (le is inclusive), cumulative counts.
  * SLO classification: inclusive band boundaries (exactly 1.2x is
    good, exactly 2.0x is acceptable), missing/invalid predictions are
    "unknown", worst-class aggregation per cell.
  * MetricsExporter: live HTTP scrape on an ephemeral port, snapshot
    determinism (identical state -> byte-identical files).
  * Planner instrumentation: decision counters, cache hit/miss, the
    decision-flip counter, and the decision_log ring buffer (the
    unbounded-growth fix) — including that fit_overlap_eff still sees
    its measurement rows after trimming.
  * Docs-sync: every metric in METRIC_SPECS is documented in METRICS.md.
"""

import math
import os

import pytest

from repro.core import latency_model as lm
from repro.core.planner import Planner
from repro.core.topology import get_fabric
from repro.telemetry import metrics as m
from repro.telemetry import slo
from repro.telemetry.exporter import MetricsExporter, scrape, write_snapshot

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# registry + exposition format


def test_counter_basics():
    reg = m.MetricsRegistry()
    c = reg.counter("t_total", "help", ("op",))
    c.inc(op="dispatch")
    c.inc(2.5, op="dispatch")
    c.inc(op="combine")
    assert c.value(op="dispatch") == 3.5
    assert c.value(op="combine") == 1.0
    assert c.value(op="missing") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1.0, op="dispatch")


def test_registration_idempotent_and_conflicts():
    reg = m.MetricsRegistry()
    a = reg.counter("x_total", "help", ("op",))
    b = reg.counter("x_total", "help", ("op",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total", "help", ("op",))        # type conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", "help", ("other",))   # label conflict


def test_render_parse_round_trip():
    reg = m.MetricsRegistry()
    reg.counter("rt_total", "a counter", ("op", "fabric")).inc(
        3, op="dispatch", fabric="2x8")
    reg.gauge("rt_ratio", "a gauge", ("op",)).set(0.25, op="combine")
    h = reg.histogram("rt_seconds", "a histogram", (), buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    parsed = m.parse_text(reg.render())
    assert parsed[("rt_total",
                   (("fabric", "2x8"), ("op", "dispatch")))] == 3.0
    assert parsed[("rt_ratio", (("op", "combine"),))] == 0.25
    assert parsed[("rt_seconds_count", ())] == 2.0
    assert parsed[("rt_seconds_sum", ())] == pytest.approx(5.05)
    assert parsed[("rt_seconds_bucket", (("le", "0.1"),))] == 1.0
    assert parsed[("rt_seconds_bucket", (("le", "+Inf"),))] == 2.0


def test_render_deterministic_and_headers_always_present():
    # zero-sample metrics still render HELP/TYPE: a scraper sees the
    # full schema even before the first event (serve-scrape acceptance)
    reg = m.MetricsRegistry()
    reg.counter("zz_total", "never incremented", ("op",))
    reg.counter("aa_total", "also never", ())
    text = reg.render()
    assert "# HELP zz_total never incremented" in text
    assert "# TYPE zz_total counter" in text
    # metrics sorted by name
    assert text.index("aa_total") < text.index("zz_total")
    assert text == reg.render()


def test_label_escaping_round_trip():
    reg = m.MetricsRegistry()
    c = reg.counter("esc_total", "escapes", ("p",))
    weird = 'a"b\\c\nd'
    c.inc(p=weird)
    parsed = m.parse_text(reg.render())
    assert parsed[("esc_total", (("p", weird),))] == 1.0


def test_histogram_bucket_edge_inclusive():
    reg = m.MetricsRegistry()
    h = reg.histogram("edge_seconds", "h", (), buckets=(1.0, 2.0))
    h.observe(1.0)      # exactly at the bound: lands IN le=1.0
    h.observe(1.0001)   # just above: next bucket
    counts = h.bucket_counts()      # cumulative per le bound
    assert counts[1.0] == 1
    assert counts[2.0] == 2
    assert h.count() == 2
    # cumulative rendering: le=2.0 includes the le=1.0 observation
    parsed = m.parse_text(reg.render())   # le renders minimally: "1"
    assert parsed[("edge_seconds_bucket", (("le", "1"),))] == 1.0
    assert parsed[("edge_seconds_bucket", (("le", "2"),))] == 2.0
    assert parsed[("edge_seconds_bucket", (("le", "+Inf"),))] == 2.0


def test_default_registry_preregisters_all_specs():
    reg = m.default_registry()
    for name in m.METRIC_SPECS:
        assert name in reg
    # every spec'd metric renders headers even with no samples
    text = reg.render()
    for name in m.METRIC_SPECS:
        assert f"# TYPE {name} " in text


# ---------------------------------------------------------------------------
# SLO classification


def test_slo_band_boundaries_inclusive():
    assert slo.classify(1.2, 1.0) == "good"        # exactly 1.2x
    assert slo.classify(1.2000001, 1.0) == "acceptable"
    assert slo.classify(2.0, 1.0) == "acceptable"  # exactly 2.0x
    assert slo.classify(2.0000001, 1.0) == "poor"
    assert slo.classify(0.5, 1.0) == "good"


def test_slo_missing_or_invalid_prediction_is_unknown():
    assert slo.classify(1.0, None) == "unknown"
    assert slo.classify(1.0, 0.0) == "unknown"
    assert slo.classify(1.0, -1.0) == "unknown"
    assert slo.classify(1.0, math.nan) == "unknown"
    assert slo.classify(math.nan, 1.0) == "unknown"


def test_slo_classify_records_takes_worst_per_cell():
    records = [
        {"op": "dispatch", "bucket": 512, "predicted_s": 1.0,
         "measured_s": 1.0},
        {"op": "dispatch", "bucket": 512, "predicted_s": 1.0,
         "measured_s": 5.0},
    ]
    cells = slo.classify_records(records)
    assert cells[("dispatch", 512)] == "poor"


def test_slo_observe_record_zero_payload():
    reg = m.MetricsRegistry()
    for name in ("repro_slo_class_total", "repro_slo_ratio"):
        spec = m.METRIC_SPECS[name]
        getattr(reg, spec["type"])(name, spec["help"], spec["labels"])
    cls = slo.observe_record(
        {"op": "dispatch", "bucket": 0, "fabric_name": "2x8",
         "predicted_s": 1.0, "measured_s": 1.0}, registry=reg)
    assert cls == "good"
    assert reg["repro_slo_class_total"].value(
        op="dispatch", payload_bucket="0", fabric="2x8", slo="good") == 1.0


# ---------------------------------------------------------------------------
# exporter


def test_exporter_live_scrape():
    reg = m.MetricsRegistry()
    reg.counter("live_total", "scraped", ("op",)).inc(7, op="x")
    with MetricsExporter(0, registry=reg) as exp:
        assert exp.port != 0
        text = scrape(exp.url)
    parsed = m.parse_text(text)
    assert parsed[("live_total", (("op", "x"),))] == 7.0


def test_snapshot_deterministic(tmp_path):
    reg = m.MetricsRegistry()
    g = reg.gauge("snap_ratio", "g", ("op",))
    g.set(1.5, op="b")
    g.set(0.5, op="a")
    p1, p2 = str(tmp_path / "s1.prom"), str(tmp_path / "s2.prom")
    write_snapshot(p1, registry=reg)
    write_snapshot(p2, registry=reg)
    with open(p1, "rb") as f1, open(p2, "rb") as f2:
        b1, b2 = f1.read(), f2.read()
    assert b1 == b2
    assert b"snap_ratio" in b1


def test_serve_scrape_has_required_metric_families():
    # the acceptance scrape: drift, decision-flip and phase-budget SLO
    # families must be present in any scrape of the default registry
    with MetricsExporter(0) as exp:
        text = scrape(exp.url)
    for name in ("repro_drift_ratio", "repro_planner_decision_flips_total",
                 "repro_phase_budget_ok", "repro_slo_class_total"):
        assert f"# TYPE {name} " in text


# ---------------------------------------------------------------------------
# planner instrumentation + ring buffer (satellite 1)


def test_decision_log_ring_buffer():
    topo = get_fabric("2x8")
    planner = Planner(decision_log_max=4)
    batches = [2 ** i for i in range(14)]   # distinct payload buckets
    for batch in batches:
        planner.choose("dispatch", batch * lm.TOKEN_BYTES, topo,
                       token_bytes=lm.TOKEN_BYTES)
    assert len(planner.decision_log) <= 4
    assert planner.decision_log_dropped > 0
    # newest entries survive (it's a ring, not a truncation); logged
    # payloads are bucketed
    from repro.core.planner import bucket_payload
    assert (planner.decision_log[-1]["payload_bytes"]
            == bucket_payload(batches[-1] * lm.TOKEN_BYTES))


def test_note_measurement_fallback_is_bounded():
    # regression: the note_measurement fallback append used to grow
    # decision_log without bound
    topo = get_fabric("2x8")
    planner = Planner(decision_log_max=16)
    d = planner.choose("dispatch", 64 * lm.TOKEN_BYTES, topo,
                       token_bytes=lm.TOKEN_BYTES)
    for i in range(200):
        # the first call fills the logged row; every later one takes the
        # fallback append path (the row's measured_s is no longer None)
        planner.note_measurement(d, 1e-3 + i * 1e-6)
    assert len(planner.decision_log) <= 16
    assert planner.decision_log_dropped >= 200 - 16
    # fit_overlap_eff still sees measurement rows after trimming
    rows = [r for r in planner.decision_log
            if r.get("measured_s") is not None]
    assert rows, "measured rows must survive the ring buffer"


def test_planner_metrics_decisions_cache_and_flips():
    m.reset_default_registry()
    reg = m.default_registry()
    topo = get_fabric("2x8")
    planner = Planner()
    payload = 64 * lm.TOKEN_BYTES
    d1 = planner.choose("dispatch", payload, topo,
                        token_bytes=lm.TOKEN_BYTES)
    assert reg["repro_planner_cache_misses_total"].value() >= 1.0
    planner.choose("dispatch", payload, topo, token_bytes=lm.TOKEN_BYTES)
    assert reg["repro_planner_cache_hits_total"].value() >= 1.0
    # decision counter labeled by op/fabric
    total = sum(v for (labels, v) in
                reg["repro_planner_decisions_total"].samples()
                if labels["op"] == "dispatch")
    assert total >= 1.0
    # a recalibration that flips the winning scheme bumps the flip
    # counter (same planner instance, refreshed hw)
    links = {k: ln.bw / 4 for k, ln in topo.links.items()
             if topo.server_of(ln.src) != topo.server_of(ln.dst)}
    planner.refresh_hardware(
        planner.hw.recalibrated({"links": links}, topo))
    d2 = planner.choose("dispatch", payload, topo,
                        token_bytes=lm.TOKEN_BYTES)
    assert d2.plan != d1.plan
    flips = sum(v for (_, v) in
                reg["repro_planner_decision_flips_total"].samples())
    assert flips >= 1.0


# ---------------------------------------------------------------------------
# docs-sync


def test_every_metric_documented_in_metrics_md():
    path = os.path.join(REPO, "METRICS.md")
    assert os.path.exists(path), "METRICS.md missing"
    with open(path) as f:
        doc = f.read()
    missing = [name for name in m.METRIC_SPECS if name not in doc]
    assert not missing, f"undocumented metrics: {missing}"
