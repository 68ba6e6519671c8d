"""Planned gradient sync: AllReduce / ReduceScatter as planner ops.

Covers the ISSUE-6 acceptance properties:
  * registry: both reduce ops carry a full scheme family; lossy /
    accounting-only variants are never auto-bound (executable=False);
  * the scheme CROSSOVER is emergent: Planner.choose flips between at
    least two allreduce schemes across the payload sweep on a
    multi-server fabric (latency-optimal tree small, relay-reduce
    multiwrite large);
  * gradient sync as a CollectiveSite: the train program carries a
    grad_sync role whose pipelined (chunked, overlap-aware) score beats
    the serial one on 2x8 — the backward pass hides wire time;
  * the trainer's grad_sync hook reduces gradients BEFORE clipping.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import latency_model as lm
from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core import schedules as sch
from repro.core.topology import get_fabric, full_mesh

TOPO = get_fabric("2x8")

REDUCE_PLANS = {
    ("allreduce", "ring"): True,
    ("allreduce", "tree"): True,
    ("allreduce", "hierarchical"): True,
    ("allreduce", "multiwrite"): True,
    ("allreduce", "compressed"): False,
    ("reduce_scatter", "ring"): True,
    ("reduce_scatter", "a2a"): True,
    ("reduce_scatter", "multiwrite"): False,
}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_all_reduce_plans_registered(self):
        for (op, scheme), executable in REDUCE_PLANS.items():
            plans = {p.name: p for p in plan_ir.plans_for(op)}
            assert scheme in plans, (op, scheme)
            assert plans[scheme].executable == executable, (op, scheme)

    def test_baseline_plan_is_the_flat_ring(self):
        # flat ring == what GSPMD lowers an unannotated psum to
        assert plan_ir.BASELINE_PLAN["allreduce"] == "ring"
        assert plan_ir.BASELINE_PLAN["reduce_scatter"] == "ring"

    def test_default_scenarios_cover_reduce_ops(self):
        scen = plan_ir.default_scenarios(TOPO)
        assert "allreduce" in scen and "reduce_scatter" in scen

    def test_every_plan_simulates_on_every_fabric(self):
        from repro.core.topology import FABRICS
        for fname in FABRICS:
            topo = get_fabric(fname)
            scen = plan_ir.default_scenarios(topo)
            for op in ("allreduce", "reduce_scatter"):
                for p in plan_ir.plans_for(op):
                    led = p.simulate_fn(scen[op], 1 << 20, microbatch=1)
                    t = pl.score_ledger(led, lm.DEFAULT)
                    assert t > 0 and math.isfinite(t), (fname, op, p.name)


# ---------------------------------------------------------------------------
# ledger shape sanity
# ---------------------------------------------------------------------------

class TestLedgers:
    def test_multiwrite_rail_bottleneck_beats_ring(self):
        """The relay-reduce schedule puts 1/P of the payload on each rail
        link where the flat ring puts ~2N — the bottleneck-link saving
        the scheme exists for."""
        n = float(1 << 24)
        mw = sch.reduce_multiwrite_ledger(TOPO, n)
        ring = sch.reduce_ring_ledger(TOPO, n, phases=2)

        def max_rail(led):
            return max((v for (a, b), v in led.link_bytes.items()
                        if TOPO.server_of(a) != TOPO.server_of(b)),
                       default=0.0)
        assert max_rail(mw) < max_rail(ring) / 4

    def test_hierarchical_rail_bytes_are_p_fold_smaller(self):
        n = float(1 << 24)
        meta = TOPO.meta
        led = sch.reduce_hierarchical_ledger(TOPO, n, phases=2)
        rail = [v for (a, b), v in led.link_bytes.items()
                if TOPO.server_of(a) != TOPO.server_of(b)]
        want = 2.0 * (n / meta.npus_per_server) * \
            (meta.num_servers - 1) / meta.num_servers
        assert rail and max(rail) == pytest.approx(want)

    def test_tree_is_log_depth(self):
        assert sch.reduce_tree_depth(TOPO) == 4  # ceil(log2 8) + ceil(log2 2)
        assert sch.reduce_tree_depth(full_mesh(8)) == 3

    def test_compressed_quarters_the_wire(self):
        scen = plan_ir.default_scenarios(TOPO)["allreduce"]
        plans = {p.name: p for p in plan_ir.plans_for("allreduce")}
        full = plans["ring"].simulate_fn(scen, 1 << 22, microbatch=1)
        quarter = plans["compressed"].simulate_fn(scen, 1 << 22, microbatch=1)
        assert sum(quarter.link_bytes.values()) == pytest.approx(
            sum(full.link_bytes.values()) / 4)

    def test_single_server_degrades_cleanly(self):
        topo = full_mesh(8)
        for (op, scheme) in REDUCE_PLANS:
            led = sch._REDUCE_LEDGERS[(op, scheme)](topo, float(1 << 20))
            assert all(v >= 0 for v in led.link_bytes.values()), (op, scheme)
            assert led.link_bytes, (op, scheme)


# ---------------------------------------------------------------------------
# emergent scheme crossover (acceptance criterion)
# ---------------------------------------------------------------------------

class TestCrossover:
    def test_at_least_two_schemes_win_across_sweep(self):
        planner = pl.Planner()
        winners = {}
        for log2 in range(16, 28, 2):
            d = planner.choose("allreduce", float(1 << log2), TOPO,
                               executable_only=True)
            winners[log2] = d.plan
        assert len(set(winners.values())) >= 2, winners

    def test_latency_optimal_small_bandwidth_optimal_large(self):
        planner = pl.Planner()
        small = planner.choose("allreduce", float(1 << 16), TOPO,
                               executable_only=True)
        large = planner.choose("allreduce", float(1 << 26), TOPO,
                               executable_only=True)
        assert small.plan == "tree"
        assert large.plan == "multiwrite"
        assert large.delta_vs_baseline > 0

    def test_crossover_moves_with_fabric(self):
        """A slower inter-server fabric pulls the tree->multiwrite flip
        to a smaller payload (rail bandwidth matters earlier)."""
        def flip(topo):
            planner = pl.Planner()
            for log2 in range(14, 30):
                if planner.choose("allreduce", float(1 << log2), topo,
                                  executable_only=True).plan != "tree":
                    return log2
            return 30
        assert flip(get_fabric("tpu_2x16")) < flip(get_fabric("2x8"))

    def test_lossy_scheme_never_auto_bound(self):
        for topo in (TOPO, get_fabric("tpu_2x16")):
            planner = pl.Planner()
            for log2 in (16, 20, 24, 28):
                d = planner.choose("allreduce", float(1 << log2), topo,
                                   executable_only=True)
                assert d.plan != "compressed", (topo.name, log2)

    def test_reduce_scatter_has_a_winner(self):
        d = pl.Planner().choose("reduce_scatter", float(1 << 22), TOPO,
                                executable_only=True)
        assert d.plan in ("ring", "a2a")
        assert d.predicted_s > 0


# ---------------------------------------------------------------------------
# gradient sync as a collective site (acceptance criterion)
# ---------------------------------------------------------------------------

class TestGradSyncProgram:
    def _program(self, payload=512 * 2 ** 20, compute_s=None):
        if compute_s is None:
            # ~8B params, 2k tokens/rank backward — the tail the chunked
            # sync hides behind
            compute_s = lm.backward_compute_s(8_000_000_000, 2048)
        site = plan_ir.grad_sync_site("train", payload_bytes=payload,
                                      compute_s=compute_s, topo=TOPO)
        return plan_ir.CollectiveProgram("train", (site,))

    def test_site_role_and_op(self):
        prog = self._program()
        (site,) = prog.sites
        assert site.op == "allreduce"
        assert site.role == "train/grad_sync"

    def test_pipelined_beats_serial_on_2x8(self):
        eplan = pl.Planner().plan_program(self._program(), TOPO)
        d = eplan.decisions["train/grad_sync"]
        assert d.shard_map_kwargs["microbatch"] > 1
        assert d.predicted_s < d.predicted_serial_s
        assert d.predicted_s < d.baseline_s

    def test_bound_kwargs_carry_scheme_and_chunks(self):
        eplan = pl.Planner().plan_program(self._program(), TOPO)
        kw = eplan.site_kwargs("train/grad_sync")
        assert kw["reduce_scheme"] in ("ring", "tree", "hierarchical",
                                       "multiwrite")
        assert kw["microbatch"] >= 1

    def test_no_compute_context_means_no_overlap_win(self):
        """With zero backward compute to hide behind, chunking only adds
        launch overhead — G stays at 1."""
        eplan = pl.Planner().plan_program(
            self._program(compute_s=0.0), TOPO)
        d = eplan.decisions["train/grad_sync"]
        assert d.shard_map_kwargs["microbatch"] == 1

    def test_backward_compute_model(self):
        t = lm.backward_compute_s(1_000_000_000, 1024)
        assert t > 0
        assert lm.backward_compute_s(2_000_000_000, 1024) == \
            pytest.approx(2 * t)
        assert lm.backward_compute_s(1_000_000_000, 1024, tp=8) == \
            pytest.approx(t / 8)


# ---------------------------------------------------------------------------
# trainer hook
# ---------------------------------------------------------------------------

class TestTrainerHook:
    def _setup(self):
        from repro.configs.base import get_config
        from repro.data.pipeline import DataConfig, SyntheticLM, \
            batch_for_model
        from repro.models.api import build_model
        cfg = get_config("mistral_nemo_12b").reduced(
            n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab=128)
        model = build_model(cfg, dtype=jnp.float32)
        data = SyntheticLM(DataConfig(vocab=128, seq_len=16, global_batch=2))
        return model, batch_for_model(cfg, data.batch(0))

    def test_grad_sync_applied_before_clipping(self):
        from repro.optim import sgd
        from repro.runtime.trainer import TrainState, make_train_step
        model, batch = self._setup()
        params = model.init(jax.random.key(0))
        opt = sgd(lr=1e-2)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32))
        # a sync that zeroes every gradient: the visible grad_norm must
        # be 0 (hook runs before clip) and the sgd update must be a no-op
        zero_sync = lambda g: jax.tree_util.tree_map(      # noqa: E731
            jnp.zeros_like, g)
        step = make_train_step(model, opt, donate=False,
                               grad_sync=zero_sync)
        new_state, metrics = step(state, batch)
        assert float(metrics["grad_norm"]) == 0.0
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(new_state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_identity_sync_matches_default_step(self):
        from repro.optim import sgd
        from repro.runtime.trainer import TrainState, make_train_step
        model, batch = self._setup()
        params = model.init(jax.random.key(0))
        opt = sgd(lr=1e-2)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32))
        s1, m1 = make_train_step(model, opt, donate=False)(state, batch)
        s2, m2 = make_train_step(model, opt, donate=False,
                                 grad_sync=lambda g: g)(state, batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]))
        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
