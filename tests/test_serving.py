"""Serving tier: queue, traffic, planner-informed admission, the
iteration-level scheduler (join/exit between decode steps), bit-exact
continuous-vs-one-shot generation, the cohort cache built by one
compiled call, engine plan memoization, and the per-request SLO bands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import batch_bucket
from repro.core.topology import get_fabric, two_server_cluster
from repro.serving import (AdmissionController, BatchScheduler,
                           PlannerProbe, Request, RequestQueue,
                           TrafficConfig, TrafficGenerator)
from repro.telemetry.metrics import reset_default_registry

TOKEN_BYTES = 14336     # bf16 x d_model 7168: the Fig 8 decode payload


@pytest.fixture(scope="module")
def probe():
    return PlannerProbe(get_fabric("2x8"), token_bytes=TOKEN_BYTES)


# ---------------------------------------------------------------------------
# queue
# ---------------------------------------------------------------------------

class TestRequestQueue:
    def test_arrival_gating(self):
        q = RequestQueue()
        q.push(Request(rid=0, arrival_s=0.5, max_new=4))
        q.push(Request(rid=1, arrival_s=0.1, max_new=4))
        assert q.ready_count(0.0) == 0
        assert q.ready_count(0.2) == 1
        assert q.next_arrival_s(0.0) == pytest.approx(0.1)
        assert q.next_arrival_s(0.2) == pytest.approx(0.5)
        got = q.pop_ready(0.2, 8)
        assert [r.rid for r in got] == [1]
        assert len(q) == 1

    def test_class_priority_fifo_within_class(self):
        q = RequestQueue()
        q.push(Request(rid=0, slo_class="batch"))
        q.push(Request(rid=1, slo_class="interactive"))
        q.push(Request(rid=2, slo_class="standard"))
        q.push(Request(rid=3, slo_class="interactive"))
        got = q.pop_ready(0.0, 4)
        assert [r.rid for r in got] == [1, 3, 2, 0]

    def test_oldest_wait(self):
        q = RequestQueue()
        q.push(Request(rid=0, arrival_s=1.0))
        q.push(Request(rid=1, arrival_s=3.0))
        assert q.oldest_wait_s(5.0) == pytest.approx(4.0)
        assert q.oldest_wait_s(0.5) == 0.0


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

class TestTraffic:
    def test_deterministic_per_seed(self):
        cfg = TrafficConfig(arrival_rate_rps=100.0, num_requests=32,
                            prompt_lens=(16, 64), prompt_len_probs=(.5, .5),
                            max_news=(4, 8), max_new_probs=(.5, .5),
                            slo_classes=("interactive", "batch"),
                            slo_class_probs=(.5, .5), vocab=128, seed=3)
        a = TrafficGenerator(cfg).requests()
        b = TrafficGenerator(cfg).requests()
        assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
        assert [r.prompt_len for r in a] == [r.prompt_len for r in b]
        assert [r.slo_class for r in a] == [r.slo_class for r in b]
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra.prompt, rb.prompt)
        c = TrafficGenerator(
            TrafficConfig(arrival_rate_rps=100.0, num_requests=32,
                          vocab=128, seed=4)).requests()
        assert [r.arrival_s for r in a] != [r.arrival_s for r in c]

    def test_arrivals_monotone_and_open_loop(self):
        reqs = TrafficGenerator(TrafficConfig(
            arrival_rate_rps=50.0, num_requests=200, seed=0)).requests()
        arr = [r.arrival_s for r in reqs]
        assert arr == sorted(arr)
        # mean interarrival ~ 1/rate (law of large numbers, fixed seed)
        assert arr[-1] / len(arr) == pytest.approx(1 / 50.0, rel=0.3)


# ---------------------------------------------------------------------------
# planner-informed admission
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_probe_stale_scheme_penalty(self, probe):
        xover = probe.crossover_batch()
        assert xover != float("inf"), "2x8 must cross at this payload"
        big = int(xover) * 8
        fresh = probe.decode_step_s(big)
        stale = probe.decode_step_s(big, bound_batch=1)
        assert probe.scheme_at(1) != probe.scheme_at(big)
        assert stale > fresh      # the crossover-oblivious cost is real

    def test_crossover_aware_hold_vs_greedy(self, probe):
        xover = int(probe.crossover_batch())
        slo = probe.decode_step_s(xover) * 1.05
        planner = AdmissionController(probe, capacity=4 * xover,
                                      policy="planner", tpot_slo_s=slo,
                                      ttft_slo_s=0.08)
        greedy = AdmissionController(probe, capacity=4 * xover,
                                     policy="greedy", tpot_slo_s=slo)
        dec = planner.decide(in_flight=xover, ready=xover)
        assert dec.reason == "tpot_slo_hold"
        assert dec.admit == 0 and dec.held == xover
        assert dec.target_batch == xover       # held AT the crossover
        assert planner.holds == 1
        gdec = greedy.decide(in_flight=xover, ready=xover)
        assert gdec.reason == "greedy" and gdec.admit == xover

    def test_ttft_pressure_overrides_hold(self, probe):
        xover = int(probe.crossover_batch())
        slo = probe.decode_step_s(xover) * 1.05
        adm = AdmissionController(probe, capacity=4 * xover,
                                  policy="planner", tpot_slo_s=slo,
                                  ttft_slo_s=0.08)
        dec = adm.decide(in_flight=xover, ready=xover,
                         oldest_wait_s=0.05)     # > half the TTFT SLO
        assert dec.reason == "ttft_pressure"
        assert dec.admit == xover

    def test_bucket_crossing_stages_next_plan(self, probe):
        xover = int(probe.crossover_batch())
        adm = AdmissionController(
            probe, capacity=8 * xover, policy="planner",
            tpot_slo_s=probe.decode_step_s(8 * xover) * 2,  # generous
            ttft_slo_s=0.08)
        dec = adm.decide(in_flight=xover // 2, ready=xover // 2,
                         bound_bucket=xover // 2)
        assert dec.admit == xover // 2
        assert dec.stage_bucket == batch_bucket(xover)
        assert dec.reason == "crossover_rebind"   # growth crosses Fig 8
        # same-bucket growth stages nothing
        dec2 = adm.decide(in_flight=1, ready=1, bound_bucket=2)
        assert dec2.stage_bucket is None

    def test_capacity_reject(self, probe):
        reset_default_registry()
        adm = AdmissionController(probe, capacity=4, policy="greedy")
        dec = adm.decide(in_flight=4, ready=3)
        assert dec.admit == 0 and dec.reason == "capacity"
        assert adm.rejected == {"capacity": 3}


# ---------------------------------------------------------------------------
# scheduler (virtual-time simulation: engine=None)
# ---------------------------------------------------------------------------

class TestSchedulerSim:
    def _sched(self, probe, reqs, **kw):
        q = RequestQueue()
        for r in reqs:
            q.push(r)
        kw.setdefault("admission",
                      AdmissionController(probe, capacity=64,
                                          policy="greedy"))
        return BatchScheduler(queue=q, probe=probe, **kw)

    def test_join_and_exit_without_drain_barrier(self, probe):
        reset_default_registry()
        reqs = [Request(rid=0, arrival_s=0.0, prompt_len=16, max_new=2),
                Request(rid=1, arrival_s=0.0, prompt_len=16, max_new=64),
                Request(rid=2, arrival_s=1e-3, prompt_len=16, max_new=4)]
        sched = self._sched(probe, reqs).run_until_drained()
        assert len(sched.completed) == 3 and sched.idle
        by = {r.rid: r for r in sched.completed}
        # rid 0 exits after 2 tokens while rid 1 keeps decoding
        assert by[0].finish_s < by[1].finish_s
        # rid 2 joins mid-decode: first token BEFORE rid 1 finishes
        # (no drain barrier), in its own cohort after its arrival
        assert by[2].arrival_s < by[1].finish_s
        assert by[2].admit_s >= by[2].arrival_s
        assert by[2].first_token_s < by[1].finish_s
        assert sched.max_in_flight >= 2

    def test_virtual_clock_and_predictions_stamped(self, probe):
        reqs = [Request(rid=0, arrival_s=0.0, prompt_len=128, max_new=4)]
        sched = self._sched(probe, reqs).run_until_drained()
        (r,) = sched.completed
        assert r.predicted_ttft_s == pytest.approx(
            probe.prefill_s(1, 128))
        assert r.predicted_tpot_s == pytest.approx(probe.decode_step_s(1))
        assert r.ttft_s == pytest.approx(probe.prefill_s(1, 128))
        assert r.tpot_s == pytest.approx(probe.decode_step_s(1))
        assert sched.now == pytest.approx(
            probe.prefill_s(1, 128) + 3 * probe.decode_step_s(1))

    def test_bucket_growth_swaps_warm_plan(self, probe):
        reset_default_registry()
        from repro.core import latency_model as lm
        from repro.core import plan as plan_ir
        from repro.core.planner import default_planner
        from repro.parallel.context import PlanBinder
        topo = get_fabric("2x8")

        def plan_for_bucket(bucket):
            sites = plan_ir.moe_sites(
                "decode", num_experts=64, top_k=8, tokens_per_rank=bucket,
                token_bytes=TOKEN_BYTES,
                compute_s=lm.expert_compute_time_s(bucket, 8, 7168, 2048))
            return default_planner().plan_program(
                plan_ir.CollectiveProgram("serve", sites), topo, None)

        binder = PlanBinder(lambda p: {"fp": p.fingerprint},
                            plan=plan_for_bucket(4))
        reqs = [Request(rid=i, arrival_s=0.0, prompt_len=16, max_new=8)
                for i in range(4)]
        reqs += [Request(rid=4 + i, arrival_s=2e-3, prompt_len=16,
                         max_new=8) for i in range(28)]
        sched = self._sched(
            probe, reqs, binder=binder, plan_for_bucket=plan_for_bucket,
            admission=AdmissionController(
                probe, capacity=64, policy="planner",
                tpot_slo_s=probe.decode_step_s(64) * 2.0,
                ttft_slo_s=0.08)).run_until_drained()
        assert len(sched.completed) == 32
        assert sched.prefetch_rebinds >= 1      # 4 -> 32 staged a bucket
        assert sched.bound_bucket == 32
        assert binder.swaps >= 1
        assert binder.cold_retraces == 0        # pointer-flip growth
        from repro.telemetry.metrics import default_registry
        reg = default_registry()
        assert reg["repro_plan_prefetch_total"].value(program="serve") >= 1
        assert reg["repro_requests_total"].value(outcome="admitted") == 32
        assert reg["repro_requests_total"].value(outcome="completed") == 32

    def test_run_for_partial_then_drain(self, probe):
        reqs = TrafficGenerator(TrafficConfig(
            arrival_rate_rps=2000.0, num_requests=40, prompt_lens=(16,),
            max_news=(8,), seed=1)).requests()
        sched = self._sched(probe, reqs)
        assert sched.step()
        assert len(sched.completed) < 40
        sched.run_until_drained()
        assert len(sched.completed) == 40
        rep = sched.report(ttft_slo_s=0.08,
                           tpot_slo_s=probe.decode_step_s(64) * 1.15)
        assert rep["completed"] == 40 and rep["pending"] == 0
        assert rep["ttft_p99_s"] >= rep["ttft_p50_s"] >= 0
        assert rep["goodput_rps"] > 0


# ---------------------------------------------------------------------------
# continuous vs one-shot generate: bit-exact on a live engine
# ---------------------------------------------------------------------------

class TestEngineCohorts:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.configs.base import get_config
        from repro.models.api import build_model
        from repro.runtime.server import ServeConfig, ServeEngine
        cfg = get_config("rwkv6_7b").reduced(n_layers=2, d_model=32,
                                             n_heads=2, d_ff=64, vocab=64)
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        return ServeEngine(model, params, ServeConfig(max_new_tokens=6))

    def test_staggered_continuous_matches_one_shot(self, engine):
        prompts = np.random.default_rng(2).integers(
            0, 64, size=(4, 8)).astype(np.int32)
        ref = engine.generate(prompts)
        q = RequestQueue()
        for i in range(4):
            q.push(Request(rid=i, arrival_s=0.002 * i,
                           prompt=prompts[i], max_new=6))
        sched = BatchScheduler(
            queue=q,
            admission=AdmissionController(capacity=2, policy="greedy"),
            engine=engine, eos_id=engine.cfg.eos_id, seed=0)
        sched.run_until_drained()
        assert len(sched.completed) == 4
        out = np.zeros_like(ref)
        for r in sched.completed:
            out[r.rid, :len(r.tokens[:6])] = r.tokens[:6]
        np.testing.assert_array_equal(out, ref)

    def test_mixed_prompt_lens_form_separate_cohorts(self, engine):
        # cohorts are position-aligned: one shared prompt_len each —
        # staggered arrivals land in separate cohorts and both drain
        q = RequestQueue()
        rng = np.random.default_rng(3)
        q.push(Request(rid=0, prompt=rng.integers(
            0, 64, size=8).astype(np.int32), max_new=3))
        q.push(Request(rid=1, arrival_s=1e-5, prompt=rng.integers(
            0, 64, size=12).astype(np.int32), max_new=3))
        sched = BatchScheduler(
            queue=q,
            admission=AdmissionController(capacity=4, policy="greedy"),
            engine=engine, seed=0)
        sched.run_until_drained()
        assert len(sched.completed) == 2

    def test_mixed_prompt_lens_in_one_wave_rejected(self, engine):
        # a single admission wave cannot mix prompt lengths (padding is
        # the caller's job, as one-shot generate does)
        q = RequestQueue()
        rng = np.random.default_rng(4)
        for rid, size in ((0, 8), (1, 12)):
            q.push(Request(rid=rid, prompt=rng.integers(
                0, 64, size=size).astype(np.int32), max_new=2))
        sched = BatchScheduler(
            queue=q,
            admission=AdmissionController(capacity=4, policy="greedy"),
            engine=engine, seed=0)
        with pytest.raises(ValueError, match="one cohort"):
            sched.run_until_drained()


# ---------------------------------------------------------------------------
# the cohort's cache: one compiled call, the same cache as the eager builder
# ---------------------------------------------------------------------------

def _eager_cache(engine):
    """``engine`` building each cohort's cache with ``model.init_cache``
    run eagerly (one dispatch per buffer), as the engine once did."""
    lowering = engine.plan_binder.artifact
    lowering.init_cache = lowering.model.init_cache
    return engine


def _handed_caches(engine):
    """The caches ``start_cohort`` hands to the prefill, in order."""
    lowering = engine.plan_binder.artifact
    prefill, handed = lowering.prefill, []

    def recording(params, batch, cache):
        handed.append(cache)
        return prefill(params, batch, cache)

    lowering.prefill = recording
    return handed


def _same_cache(built, eager):
    assert (jax.tree_util.tree_structure(built)
            == jax.tree_util.tree_structure(eager))
    for a, e in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(eager)):
        assert (a.shape, a.dtype, a.sharding) == (e.shape, e.dtype,
                                                  e.sharding)
        assert not np.asarray(a, np.float32).any()


class TestCohortCacheBuilder:
    @pytest.mark.parametrize("arch", ["qwen2_vl_2b", "mistral_nemo_12b",
                                      "dbrx_132b", "zamba2_7b",
                                      "rwkv6_7b"])
    def test_jitted_cache_and_tokens_match_the_eager_builder(self, arch):
        from repro.configs.base import get_config
        from repro.models.api import build_model
        from repro.runtime.server import ServeConfig, ServeEngine
        cfg = get_config(arch).reduced(n_layers=2, d_model=32, n_heads=2,
                                       d_ff=64, vocab=64)
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        prompts = np.random.default_rng(6).integers(
            0, 64, size=(2, 8)).astype(np.int32)
        engine = ServeEngine(model, params, ServeConfig(max_new_tokens=4))
        handed = _handed_caches(engine)
        out = engine.generate(prompts)
        (built,) = handed
        _same_cache(built, model.init_cache(2, 12, engine.cfg.cache_dtype))
        eager = _eager_cache(ServeEngine(model, params,
                                         ServeConfig(max_new_tokens=4)))
        np.testing.assert_array_equal(out, eager.generate(prompts))


EP_CACHE = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.launch import serve

cfg = get_config("dbrx_132b").reduced(n_layers=2, d_model=32, n_heads=2,
                                      d_ff=32, num_experts=4, vocab=64)
args = serve.parse_args(["--arch", "dbrx_132b", "--smoke", "--prompts", "4",
                         "--prompt-len", "8", "--max-new", "3",
                         "--plan-policy", "fixed"])
prompts = np.random.default_rng(7).integers(0, 64, size=(4, 8)).astype(
    np.int32)
out = {}
for path in ("jitted", "eager"):
    engine = serve.build_engine(args, cfg)
    lowering = engine.plan_binder.artifact
    if path == "eager":
        lowering.init_cache = lowering.model.init_cache
    prefill, handed = lowering.prefill, []
    lowering.prefill = lambda p, b, c: handed.append(c) or prefill(p, b, c)
    tokens = engine.generate(prompts)
    leaves = jax.tree_util.tree_leaves(handed[0])
    out[path] = {
        "mesh": dict(engine.pctx.mesh.shape),
        "tokens": tokens.tolist(),
        "leaves": [[list(a.shape), str(a.dtype), repr(a.sharding),
                    bool(np.asarray(a, np.float32).any())] for a in leaves]}
print(json.dumps(out))
"""


def test_jitted_cache_on_the_expert_parallel_mesh_matches_eager():
    """Four forced host devices (in a subprocess, so the count never
    reaches this process): a cohort served on the expert-parallel mesh
    with the jitted builder gets the eager builder's tokens, and its
    cache leaves the same shapes, dtypes, placement and zeros."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    proc = subprocess.run([sys.executable, "-c", EP_CACHE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    jitted, eager = out["jitted"], out["eager"]
    assert jitted["mesh"] == {"pod": 2, "data": 2, "model": 1}
    assert jitted["tokens"] == eager["tokens"]
    assert jitted["leaves"] == eager["leaves"]
    assert not any(leaf[3] for leaf in jitted["leaves"])


# ---------------------------------------------------------------------------
# engine plan memoization (per-step queries must not re-plan)
# ---------------------------------------------------------------------------

class TestEnginePlanMemo:
    @pytest.fixture()
    def moe_engine(self):
        from repro.configs.base import get_config
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.context import ParallelContext
        from repro.runtime.server import ServeEngine
        cfg = get_config("dbrx_132b").reduced()
        mesh = make_test_mesh(shape=(1,), axes=("model",))
        pctx = ParallelContext(mesh=mesh, pod_axis=None,
                               data_axis="model", model_axis="model",
                               plan_policy="auto",
                               fabric=two_server_cluster())

        class _Stub:
            def __init__(self, c):
                self.cfg = c
            prefill = staticmethod(lambda *a: None)
            decode = staticmethod(lambda *a: None)
            init_cache = staticmethod(lambda *a: None)

        return ServeEngine(_Stub(cfg), None, pctx=pctx)

    def test_program_and_plan_identity_cached(self, moe_engine):
        p1 = moe_engine.serving_program(8, 32)
        assert p1.sites                        # MoE arch declares sites
        assert moe_engine.serving_program(8, 32) is p1
        assert moe_engine.serving_program(16, 32) is not p1
        pl1 = moe_engine._fresh_plan(8, 32)
        assert pl1 is not None
        assert moe_engine._fresh_plan(8, 32) is pl1
        moe_engine.invalidate_plan_cache()
        assert (8, 32) not in moe_engine._plan_cache
        # re-planning may legitimately return the planner-LRU's identical
        # object; what matters is the memo refills and fingerprints agree
        pl2 = moe_engine._fresh_plan(8, 32)
        assert (8, 32) in moe_engine._plan_cache
        assert pl2.fingerprint == pl1.fingerprint
        assert moe_engine.serving_program(8, 32) is p1  # programs stay

    def test_repeated_plan_report_hits_caches(self, moe_engine):
        from repro.core.planner import default_planner
        moe_engine.plan_report(8, 32)          # warm
        misses0 = default_planner().cache_info()["misses"]
        for _ in range(5):
            moe_engine.plan_report(8, 32)
        assert default_planner().cache_info()["misses"] == misses0

    def test_probe_memoizes_planner_queries(self, probe):
        from repro.core.planner import default_planner
        probe.decode_step_s(32)                # warm
        misses0 = default_planner().cache_info()["misses"]
        for _ in range(20):
            probe.decode_step_s(32)
            probe.decode_step_s(32, bound_batch=1)
            probe.crossover_batch()
        assert default_planner().cache_info()["misses"] == misses0


# ---------------------------------------------------------------------------
# per-request SLO bands
# ---------------------------------------------------------------------------

class TestRequestSLO:
    def test_inclusive_band_edges(self):
        from repro.telemetry.slo import classify_request
        out = classify_request({"ttft": 1.2, "tpot": 2.0},
                               {"ttft": 1.0, "tpot": 1.0})
        assert out["ttft"] == "good"           # exactly 1.2x is good
        assert out["tpot"] == "acceptable"     # exactly 2.0x
        assert out["overall"] == "acceptable"  # worst metric wins

    def test_class_slack_scales_prediction(self):
        from repro.telemetry.slo import classify_request
        tight = classify_request({"ttft": 2.4, "tpot": 1.0},
                                 {"ttft": 1.0, "tpot": 1.0})
        assert tight["ttft"] == "poor"
        batchy = classify_request({"ttft": 2.4, "tpot": 1.0},
                                  {"ttft": 1.0, "tpot": 1.0}, slack=8.0)
        assert batchy["ttft"] == "good"

    def test_missing_prediction_is_unknown(self):
        from repro.telemetry.slo import classify_request
        out = classify_request({"ttft": 1.0}, {})
        assert out["ttft"] == "unknown" and out["overall"] == "unknown"

    def test_observe_request_counts_classes(self):
        from repro.telemetry.metrics import default_registry
        from repro.telemetry.slo import observe_request
        reset_default_registry()
        observe_request({"ttft": 1.0, "tpot": 3.0},
                        {"ttft": 1.0, "tpot": 1.0})
        reg = default_registry()
        assert reg["repro_request_slo_class_total"].value(
            metric="ttft", slo="good") == 1
        assert reg["repro_request_slo_class_total"].value(
            metric="tpot", slo="poor") == 1
