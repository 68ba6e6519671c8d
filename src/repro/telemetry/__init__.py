"""Telemetry & online calibration: measured collectives close the
planner's feedback loop.

    probe.py    timed execution of registered plans — live mesh or a
                pure-simulation backend with injectable ground truth
    store.py    append-only JSONL CalibrationStore (schema-versioned,
                keyed by fabric fingerprint / op / payload bucket)
    fit.py      per-link-class alpha/beta regression -> the measurements
                dict HardwareModel.recalibrated accepts
    monitor.py  drift watchdog: predicted-vs-measured divergence
                triggers re-fit + planner.refresh_hardware (LRU cache
                invalidated — decisions flip at runtime)
    metrics.py  dependency-free counter/gauge/histogram registry with
                Prometheus text exposition (METRIC_SPECS is the schema)
    exporter.py stdlib /metrics HTTP endpoint + snapshot-to-file
    slo.py      good/acceptable/poor banding of measured latency
                against the planner's own prediction
    spans.py    wall-clock spans of the serving path, in memory and on
                the profiler's clock; off unless spans.enable() is called

Consumed by: ParallelContext(calibration=...), train.py/serve.py
--calibrate, dryrun --calibration and ServeEngine.plan_report.
"""

from .exporter import MetricsExporter, scrape, write_snapshot
from .failover import FailureDetector, rail_probe_ledger
from .fit import (FitResult, calibrated_hw, fit_link_class,
                  fit_link_classes, fit_link_roles, fit_measurements,
                  fit_overlap_eff)
from .metrics import (METRIC_SPECS, Counter, Gauge, Histogram,
                      MetricsRegistry, default_registry, parse_text,
                      reset_default_registry)
from .monitor import DriftMonitor, StepAttribution, startup_calibration
from .probe import (GroundTruth, LiveProbe, ProbePolicy, ProbeTimeout,
                    SimProbe, attributed_bottleneck, default_payloads,
                    ledger_class_bytes, ledger_role_bytes, link_class,
                    link_role, measure_safely, probe_link_directions,
                    probe_record, probe_sweep)
from .slo import classify, classify_record, classify_records
from .store import (SCHEMA_VERSION, CalibrationStore, resolve_store,
                    topo_key)

__all__ = [
    "CalibrationStore", "Counter", "DriftMonitor", "FailureDetector",
    "FitResult", "Gauge", "GroundTruth", "Histogram", "LiveProbe",
    "METRIC_SPECS", "MetricsExporter", "MetricsRegistry", "ProbePolicy",
    "ProbeTimeout", "SCHEMA_VERSION", "SimProbe", "StepAttribution",
    "attributed_bottleneck", "calibrated_hw", "classify",
    "classify_record", "classify_records", "default_payloads",
    "default_registry", "fit_link_class", "fit_link_classes",
    "fit_link_roles", "fit_measurements", "fit_overlap_eff",
    "ledger_class_bytes", "ledger_role_bytes", "link_class", "link_role",
    "measure_safely", "parse_text", "probe_link_directions",
    "probe_record", "probe_sweep", "rail_probe_ledger",
    "reset_default_registry", "resolve_store", "scrape",
    "startup_calibration", "topo_key", "write_snapshot",
]
