"""Tests for telemetry/spans.py and the spans the serving path records.

The recorder on its own (off, on, nesting, attributes, the bound, self
time); a tiny CPU ``BatchScheduler`` over a ``ServeEngine`` with the
recorder on, and the ``first`` mark of its cache builds; the MoE layer's named scopes on the compiled collectives
of four forced host devices (in a subprocess, so that the device count
never reaches this process); and the chip benchmark's trace reduction
putting an idle gap down to the innermost program span.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.admission import AdmissionController
from repro.serving.queue import Request, RequestQueue
from repro.serving.scheduler import BatchScheduler
from repro.telemetry import spans
from repro.telemetry.spans import Row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each entry."""

    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.entered.append(self.name)

    def __exit__(self, *exc):
        return False


@pytest.fixture(autouse=True)
def recorder(monkeypatch):
    """The recorder off and empty before and after each test, with
    annotations logged instead of sent to the profiler."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    FakeAnnotation.entered = []
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


def test_off_records_nothing_and_enters_no_annotation():
    first = spans.span("engine.decode", batch=2, cohort=0)
    with first as sp:
        sp.set(rows=2)
        with spans.span("engine.launch"):
            pass
    spans.begin("request.queued", 1, rid=1)
    spans.end("request.queued", 1)
    assert spans.span("sched.iteration") is first      # one shared null
    assert spans.span("no.such.span") is first         # nothing checked
    assert spans.drain() == []
    assert FakeAnnotation.entered == []
    assert spans.dropped() == 0


def test_nesting_sets_parent_and_annotates_each_span():
    spans.enable()
    with spans.span("sched.iteration"):
        with spans.span("engine.decode"):
            with spans.span("engine.embed"):
                pass
            with spans.span("engine.wait"):
                pass
        with spans.span("sched.finalize"):
            pass
    with spans.span("sched.iteration"):
        pass
    rows = spans.drain()
    assert [(r.name, r.parent) for r in rows] == [
        ("sched.iteration", None), ("engine.decode", 0),
        ("engine.embed", 1), ("engine.wait", 1), ("sched.finalize", 0),
        ("sched.iteration", None)]
    assert FakeAnnotation.entered == [r.name for r in rows]
    for r in rows:
        assert r.start <= r.end
        if r.parent is not None:
            p = rows[r.parent]
            assert p.start <= r.start and r.end <= p.end
    assert spans.drain() == []


def test_attributes_and_rid_are_kept():
    spans.enable()
    spans.begin("request.queued", "a", rid=7)
    with spans.span("engine.prefill", batch=2, prompt_len=16, cohort=3):
        pass
    with spans.span("sched.admit") as sp:
        sp.set(admitted=1, rids=[7])
    spans.end("request.queued", "a")
    spans.end("request.queued", "never begun")
    rows = spans.drain()
    assert [(r.name, r.parent, r.attrs) for r in rows] == [
        ("engine.prefill", None, {"batch": 2, "prompt_len": 16,
                                  "cohort": 3}),
        ("sched.admit", None, {"admitted": 1, "rids": [7]}),
        ("request.queued", None, {"rid": 7})]
    # an asynchronous span is kept in memory only
    assert FakeAnnotation.entered == ["engine.prefill", "sched.admit"]
    queued = rows[2]
    assert queued.start <= rows[0].start and rows[1].end <= queued.end


def test_self_time_is_duration_less_children():
    rows = [Row("engine.decode", 0.0, 10.0, None, {}),
            Row("engine.embed", 1.0, 3.0, 0, {}),
            Row("engine.launch", 3.0, 5.0, 0, {}),
            Row("engine.wait", 5.0, 9.0, 0, {}),
            Row("sched.finalize", 11.0, 12.0, None, {})]
    assert spans.self_seconds(rows) == [2.0, 2.0, 2.0, 4.0, 1.0]


def test_bound_unknown_names_and_drain_inside_a_span(monkeypatch):
    monkeypatch.setattr(spans, "MAX_ROWS", 2)
    spans.enable()
    with pytest.raises(ValueError, match="unknown span"):
        spans.span("engine.decodes")
    for _ in range(3):
        with spans.span("sched.iteration"):
            pass
    assert len(spans.drain()) == 2 and spans.dropped() == 1
    with spans.span("sched.iteration"):
        with pytest.raises(RuntimeError):
            spans.drain()
    assert [r.name for r in spans.drain()] == ["sched.iteration"]


def test_disable_stops_recording_and_forgets_begun_spans():
    spans.enable()
    spans.begin("request.queued", 1, rid=1)
    with spans.span("sched.iteration"):
        pass
    spans.disable()
    with spans.span("sched.iteration"):
        spans.begin("request.queued", 2, rid=2)
    assert [r.name for r in spans.drain()] == ["sched.iteration"]
    spans.enable()
    spans.end("request.queued", 1)
    spans.end("request.queued", 2)
    assert spans.drain() == []
    assert FakeAnnotation.entered == ["sched.iteration"]


# -- the serving path, on a tiny engine ---------------------------------------

@pytest.fixture(scope="module")
def engine():
    from repro.configs.base import get_config
    from repro.models.api import build_model
    from repro.runtime.server import ServeConfig, ServeEngine
    cfg = get_config("qwen2_vl_2b").reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
        mrope_sections=(4, 2, 2))
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    return ServeEngine(model, params, ServeConfig(max_new_tokens=6))


def _serve(engine):
    """Five requests over a capacity of 2, so that cohorts overlap, and
    the first cohort holds a row whose request ends before the other's."""
    rng = np.random.default_rng(5)
    q = RequestQueue()
    for i, (arrival, max_new) in enumerate(
            ((0.0, 2), (0.0, 6), (0.001, 3), (0.002, 5), (0.003, 4))):
        q.push(Request(rid=i, arrival_s=arrival, max_new=max_new,
                       prompt=rng.integers(0, 256, size=8).astype(np.int32)))
    sched = BatchScheduler(
        queue=q, admission=AdmissionController(capacity=2, policy="greedy"),
        engine=engine, seed=0)
    sched.run_until_drained()
    return {r.rid: r.tokens for r in sched.completed}


def test_scheduler_over_engine_records_each_launch(engine):
    off = _serve(engine)
    assert spans.drain() == []
    spans.enable()
    on = _serve(engine)
    rows = spans.drain()
    assert on == off                          # the recorder changes nothing
    assert {r.name for r in rows} == spans.NAMES
    by = {}
    for i, r in enumerate(rows):
        by.setdefault(r.name, []).append(i)

    def kids(i):
        return [r.name for r in rows if r.parent == i]

    rounds = [rows[i] for i in by["sched.decode_round"]]
    decodes = [i for i in by["engine.decode"]]
    # one engine.decode per decode launch, each inside its round
    assert len(decodes) == sum(r.attrs["launches"] for r in rounds) > 0
    for i in decodes:
        assert rows[rows[i].parent].name == "sched.decode_round"
        assert kids(i) == ["engine.embed", "engine.launch", "engine.wait"]
        assert set(rows[i].attrs) == {"batch", "cohort"}
    for r in rounds:
        assert 0 < r.attrs["live"] <= r.attrs["rows"]
        assert rows[r.parent].name == "sched.iteration"
    assert any(r.attrs["live"] < r.attrs["rows"] for r in rounds)
    for i in by["engine.prefill"]:
        assert rows[rows[i].parent].name == "sched.iteration"
        assert kids(i) == ["engine.cache_init", "engine.embed",
                           "engine.launch", "engine.wait"]
        assert rows[i].attrs["prompt_len"] == 8
    cohorts = [rows[i].attrs["cohort"] for i in by["engine.prefill"]]
    assert len(set(cohorts)) == len(cohorts)
    assert {rows[i].attrs["cohort"] for i in decodes} <= set(cohorts)
    # one request.queued per request, closed by the admission that took it
    queued = [rows[i] for i in by["request.queued"]]
    assert sorted(r.attrs["rid"] for r in queued) == list(range(5))
    admitted = [rid for i in by["sched.admit"]
                for rid in rows[i].attrs["rids"]]
    assert sorted(admitted) == list(range(5))
    assert sum(rows[i].attrs["admitted"] for i in by["sched.admit"]) == 5
    assert all(r.parent is None and r.end >= r.start for r in queued)
    for name in ("sched.admit", "sched.finalize"):
        assert {rows[rows[i].parent].name for i in by[name]} == {
            "sched.iteration"}


def test_cache_init_marks_the_first_build_of_each_shape(engine):
    """``first`` is true where the engine's cache builder compiles: the
    first ``start_cohort`` of a (batch, max_len, dtype), and no other."""
    builder = engine.plan_binder.artifact.init_cache
    prompts = np.zeros((3, 8), np.int32)       # a batch no other test uses
    spans.enable()
    engine.start_cohort(prompts, max_new=4)
    compiled = builder._cache_size()
    engine.start_cohort(prompts, max_new=4)
    assert builder._cache_size() == compiled  # nothing compiled again
    engine.start_cohort(prompts, max_new=5)
    rows = spans.drain()
    assert [r.attrs for r in rows if r.name == "engine.cache_init"] == [
        {"first": True}, {"first": False}, {"first": True}]
    assert builder._cache_size() == compiled + 1


# -- the MoE layer's named scopes ---------------------------------------------

MOE_SCOPES = """
import re
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.launch.mesh import make_pctx
from repro.models import moe

cfg = get_config("dbrx_132b").reduced(n_layers=1, d_model=32, n_heads=2,
                                      d_ff=32, num_experts=4, vocab=64)
params = jax.eval_shape(lambda k: moe.init_moe(k, 32, 32, 4),
                        jax.random.key(0))
x = jax.ShapeDtypeStruct((4, 8, 32), jnp.float32)
for scheme in ("hierarchical", "baseline"):
    pctx = make_pctx(fsdp=False, plan_policy="fixed", moe_scheme=scheme)
    f = jax.jit(lambda p, x: moe.moe_ffn(p, x, cfg, pctx)[0])
    for line in f.lower(params, x).compile().as_text().splitlines():
        op = re.search(r" (all-to-all|collective-permute)(-start)?\\(", line)
        if op and " = " in line:
            name = re.search(r'op_name="([^"]*)"', line)
            print(scheme, op.group(1), name.group(1) if name else "")
"""


def test_moe_sites_carry_their_scope_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", MOE_SCOPES], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = {}
    for line in proc.stdout.splitlines():
        scheme, _, op_name = line.split(" ", 2)
        scopes = {s for s in op_name.split("/") if s.startswith("moe.")}
        # every exchange between chips is the dispatch or the combine
        assert scopes in ({"moe.dispatch"}, {"moe.combine"}), line
        seen.setdefault(scheme, set()).update(scopes)
    assert seen == {"hierarchical": {"moe.dispatch", "moe.combine"},
                    "baseline": {"moe.dispatch", "moe.combine"}}


# -- the chip benchmark's reduction, with the program's names -----------------

def test_trace_reduction_puts_a_gap_in_the_innermost_program_span():
    """A gap inside the harness's ``engine.step_cohort`` goes to the
    program span open at its middle, once the program's names are among
    the host spans read from the trace."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    import xplane

    def ev(plane, line, name, start_us, dur_us):
        return xplane.Event(plane, line, name, start_us * 1e3, dur_us * 1e3)

    host, dev = "/host:CPU", "/device:TPU:0"
    rows = [ev(host, "main", "bench.slice", 0, 1000),
            ev(host, "main", "engine.step_cohort", 0, 1000),
            ev(host, "main", "engine.decode", 10, 980),
            ev(host, "main", "engine.embed", 20, 200),
            ev(host, "main", "engine.launch", 220, 100),
            ev(host, "main", "engine.wait", 320, 600),
            ev(dev, "XLA Modules", "jit_decode(3)", 400, 500),
            ev(dev, "XLA Ops", "fusion.1", 400, 500)]
    gaps = xplane.reduce(rows).gap_s
    # idle 0-400 (middle 200, in engine.embed) and 900-1000 (middle 950,
    # past engine.wait and inside engine.decode)
    assert gaps == {"engine.embed": pytest.approx(400e-6),
                    "engine.decode": pytest.approx(100e-6)}
    harness_only = [r for r in rows if r.name not in spans.NAMES]
    assert xplane.reduce(harness_only).gap_s == {
        "engine.step_cohort": pytest.approx(500e-6)}
