"""Tests for the parametric fabric family + combine-path planning (ISSUE 2).

Covers:
  * ClusterSpec generation: multi-rail striping, N-server rail-first
    routing, per-rail / asymmetric bandwidths, fabric metadata.
  * parse_fabric / get_fabric (the --fabric CLI surface).
  * The "combine" planner op: executable kwargs, Fig 8-style flip,
    independence from the dispatch decision, ledger mirror property.
  * HardwareModel.recalibrated round-trip (measured bandwidths fold back
    into scoring) and Topology.with_link_bws.
  * moe_ffn tracing with planner-chosen dispatch AND combine schemes
    under plan_policy="auto", and the hierarchical_combine_unicast
    lowering agreeing with hierarchical_combine.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import latency_model as lm
from repro.core import plan as plan_ir
from repro.core import planner as pl
from repro.core import schedules as sch
from repro.core.multiwrite import MultiWriteSimulator
from repro.core.topology import (
    FABRICS, ClusterSpec, Topology, full_mesh, get_fabric, parse_fabric,
    two_server_cluster,
)


# ---------------------------------------------------------------------------
# fabric family generation
# ---------------------------------------------------------------------------

class TestClusterSpec:
    def test_two_server_is_single_rail_instance(self):
        """two_server_cluster() == ClusterSpec(2, 8, 1): same links, same
        forwarding decisions."""
        a = two_server_cluster()
        b = ClusterSpec(num_servers=2, npus_per_server=8,
                        name="two_server").build()
        assert set(a.links) == set(b.links)
        for src in range(16):
            for dst in range(16):
                if src != dst:
                    assert a.next_hop(src, dst) == b.next_hop(src, dst)

    def test_meta_attached(self):
        t = ClusterSpec(num_servers=3, npus_per_server=4,
                        rails_per_npu=2).build()
        assert t.meta.num_servers == 3
        assert t.meta.npus_per_server == 4
        assert t.meta.rails_per_npu == 2
        assert t.server_of(9) == 2
        assert t.server_nodes(1) == [4, 5, 6, 7]
        assert t.rail_peers(1, 2) == [9, 10]
        # full mesh gets a single-server meta
        m = full_mesh(6)
        assert m.meta.num_servers == 1 and m.meta.npus_per_server == 6

    def test_multi_rail_links_and_striping(self):
        """r rails per NPU per remote server; the forwarding override
        stripes a remote server's destinations over the r rails."""
        t = ClusterSpec(rails_per_npu=2).build()          # 2x8r2
        assert t.has_link(0, 8) and t.has_link(0, 9)      # rails of node 0
        assert not t.has_link(0, 10)
        groups = t.partition_by_next_hop(0, list(range(8, 16)))
        assert set(groups) == {8, 9}                      # 2 busy rails
        assert sorted(groups[8]) == [8, 10, 12, 14]       # even stripe
        assert sorted(groups[9]) == [9, 11, 13, 15]       # odd stripe

    def test_n_server_rail_first(self):
        """Every server pair is rail-connected; cross-server routes go
        rail-first (one hop onto the destination server, then intra)."""
        t = ClusterSpec(num_servers=4, npus_per_server=8).build()
        assert t.num_nodes == 32
        for sv in (1, 2, 3):
            path = t.path(3, sv * 8 + 5)
            assert len(path) == 3                          # rail + intra hop
            assert path[1] == sv * 8 + 3                   # own-index rail
        groups = t.partition_by_next_hop(0, list(range(8, 32)))
        assert set(groups) == {8, 16, 24}                  # one rail/server

    def test_per_rail_bandwidths(self):
        t = ClusterSpec(rails_per_npu=2, inter_bw=(25e9, 12.5e9)).build()
        assert t.link(0, 8).bw == 25e9                     # rail 0
        assert t.link(0, 9).bw == 12.5e9                   # rail 1

    def test_asymmetry_scales_links(self):
        t = get_fabric("2x8asym")
        assert t.link(8, 0).bw == pytest.approx(t.link(0, 8).bw * 0.5)
        # asymmetric fabrics fingerprint differently from symmetric ones
        assert t.fingerprint() != two_server_cluster().fingerprint()

    def test_degenerate_specs_rejected(self):
        with pytest.raises(ValueError):
            ClusterSpec(num_servers=2, npus_per_server=4, rails_per_npu=5)
        with pytest.raises(ValueError):
            ClusterSpec(num_servers=0)

    def test_with_link_bws_recalibration(self):
        t = two_server_cluster()
        t2 = t.with_link_bws({"0->8": 20e9, (8, 0): 10e9})
        assert t2.link(0, 8).bw == 20e9
        assert t2.link(8, 0).bw == 10e9
        assert t.link(0, 8).bw == 25e9                     # original intact
        assert t2.fingerprint() != t.fingerprint()         # cache keys split
        with pytest.raises(KeyError):
            t.with_link_bws({(0, 9): 1e9})                 # no such link


class TestParseFabric:
    def test_basic_shapes(self):
        s = parse_fabric("4x8")
        assert (s.num_servers, s.npus_per_server, s.rails_per_npu) == (4, 8, 1)
        s = parse_fabric("2x8r2")
        assert s.rails_per_npu == 2

    def test_bandwidths(self):
        s = parse_fabric("2x8r2@25,12.5:56")
        assert s.inter_bw == (25e9, 12.5e9)
        assert s.intra_bw == 56e9
        assert parse_fabric("2x8@12.5").inter_bw == 12.5e9

    def test_bad_specs_raise(self):
        for bad in ("x8", "2x", "2x8r", "2x8@abc", "mesh"):
            with pytest.raises(ValueError):
                parse_fabric(bad)

    def test_registry_and_acceptance_fabrics(self):
        """A 4-server and a 2-rail fabric are registered scenarios."""
        assert "4x8" in FABRICS and "2x8r2" in FABRICS
        t = get_fabric("4x8")
        assert t.meta.num_servers == 4
        assert get_fabric("2x8r2").meta.rails_per_npu == 2
        # inline specs resolve too
        assert get_fabric("3x4").num_nodes == 12


# ---------------------------------------------------------------------------
# combine as a planner op
# ---------------------------------------------------------------------------

class TestCombinePlanning:
    def test_combine_plans_registered(self):
        assert {p.name for p in plan_ir.plans_for("combine")} >= \
            {"unicast", "multiwrite"}
        assert plan_ir.BASELINE_PLAN["combine"] == "unicast"

    def test_choose_combine_returns_executable_kwargs(self):
        """Acceptance: Planner.choose("combine", ...) yields a decision
        with executable shard_map kwargs."""
        planner = pl.Planner()
        topo = two_server_cluster()
        d = planner.choose("combine", 2048 * lm.TOKEN_BYTES, topo,
                           token_bytes=lm.TOKEN_BYTES)
        assert d.op == "combine"
        assert d.shard_map_kwargs["moe_combine"] in ("hierarchical",
                                                     "baseline")
        assert d.plan == "multiwrite"
        assert d.delta_vs_baseline > 0

    def test_combine_fig8_flip(self):
        """Small batches stay on the unicast return, large flip to the
        relay-reduced return (Fig 8 mirrored onto the combine path)."""
        planner = pl.Planner()
        topo = two_server_cluster()
        small = planner.choose("combine", 8 * lm.TOKEN_BYTES, topo,
                               token_bytes=lm.TOKEN_BYTES)
        large = planner.choose("combine", 2048 * lm.TOKEN_BYTES, topo,
                               token_bytes=lm.TOKEN_BYTES)
        assert small.plan == "unicast"
        assert large.plan == "multiwrite"

    def test_dispatch_and_combine_flip_independently(self):
        """On a high-bandwidth-rail fabric the dispatch keeps its unicast
        plan while the combine still flips: the two halves face different
        redundancy structures, hence different crossovers — the reason
        combine is a first-class op."""
        planner = pl.Planner()
        topo = get_fabric("2x8@50")
        dflip = pl.emergent_flip_batch("dispatch", topo, planner=planner)
        cflip = pl.emergent_flip_batch("combine", topo, planner=planner)
        assert cflip < dflip
        d = planner.choose("dispatch", 2048 * lm.TOKEN_BYTES, topo,
                           token_bytes=lm.TOKEN_BYTES)
        c = planner.choose("combine", 2048 * lm.TOKEN_BYTES, topo,
                           token_bytes=lm.TOKEN_BYTES)
        assert d.plan == "unicast" and c.plan == "multiwrite"

    def test_flip_moves_with_inter_bw(self):
        """Acceptance: the Fig 8-style flip point moves with inter-server
        bandwidth (slower rails -> earlier flip)."""
        planner = pl.Planner()
        flips = [pl.emergent_flip_batch("dispatch", get_fabric(s),
                                        planner=planner)
                 for s in ("2x8@6.25", "2x8@12.5", "2x8", "2x8@50")]
        assert flips == sorted(flips)
        assert flips[0] < flips[-1]

    def test_combine_mirror_of_dispatch_single_rail(self):
        """Symmetric single-rail fabric: the multiwrite combine ledger is
        the exact link-reverse of the multiwrite dispatch ledger."""
        topo = two_server_cluster()
        routing = sch.make_routing(8, 16, 64, 8, seed=11)
        disp, comb = MultiWriteSimulator(topo), MultiWriteSimulator(topo)
        sch.dispatch_multiwrite(disp, routing, 512)
        sch.combine_multiwrite(comb, routing, 512)
        sch.check_combine(comb, routing, 512)
        assert dict(comb.link_bytes) == \
            {(b, a): v for (a, b), v in disp.link_bytes.items()}

    def test_combine_dedup_on_rail(self):
        """Multiwrite combine puts fewer return bytes on every rail than
        unicast combine (the §3.2 single-copy property, mirrored)."""
        topo = two_server_cluster()
        routing = sch.make_routing(16, 16, 64, 8, seed=2)
        uni, mw = MultiWriteSimulator(topo), MultiWriteSimulator(topo)
        sch.combine_unicast(uni, routing, 256)
        sch.combine_multiwrite(mw, routing, 256)
        sch.check_combine(uni, routing, 256)

        def rail_total(sim):
            return sum(v for (a, b), v in sim.link_bytes.items()
                       if topo.server_of(a) != topo.server_of(b))

        assert rail_total(mw) < rail_total(uni)
        assert 2.5 <= rail_total(uni) / rail_total(mw) <= 6.0

    def test_multi_rail_combine_stripes(self):
        """On a 2-rail fabric the combine relays stripe the reverse rails
        like the dispatch stripes the forward rails."""
        topo = get_fabric("2x8r2")
        routing = sch.make_routing(8, 16, 64, 8, seed=5)
        disp, comb = MultiWriteSimulator(topo), MultiWriteSimulator(topo)
        sch.dispatch_multiwrite(disp, routing, 512)
        sch.combine_multiwrite(comb, routing, 512)

        def cross(sim, pred):
            return sum(v for (a, b), v in sim.link_bytes.items()
                       if topo.server_of(a) != topo.server_of(b)
                       and pred(a, b))

        total_fwd = cross(disp, lambda a, b: True)
        total_back = cross(comb, lambda a, b: True)
        assert total_back == total_fwd                    # same crossings
        # both directions use both stripes of node 0's rail pair
        used_fwd = {k for k in disp.link_bytes if k[0] == 0 and k[1] >= 8}
        assert used_fwd == {(0, 8), (0, 9)}

    def test_every_plan_simulates_on_every_registered_fabric(self):
        """No registered plan raises on any registered fabric's default
        scenarios."""
        for fname in sorted(FABRICS):
            topo = get_fabric(fname)
            scenarios = plan_ir.default_scenarios(topo)
            for (op, pname), plan in sorted(plan_ir.PLAN_REGISTRY.items()):
                ledger = plan.simulate(scenarios[op], 1 << 16)
                assert lm.score_ledger(ledger) >= 0.0, (fname, op, pname)


# ---------------------------------------------------------------------------
# HardwareModel.recalibrated
# ---------------------------------------------------------------------------

class TestRecalibration:
    def test_roundtrip_through_benchmark_json(self, tmp_path):
        """Measured bandwidths written to a benchmark JSON fold back into
        the model and change scoring; a no-op recalibration is identity."""
        meas = {"alpha_hop": 5e-6, "copy_bw": 1.2e12,
                "links": {"0->8": 12.5e9, "8->0": 20e9}}
        path = tmp_path / "measured.json"
        path.write_text(json.dumps(meas))
        hw = lm.DEFAULT.recalibrated(json.loads(path.read_text()))
        assert hw.alpha_hop == 5e-6
        assert hw.copy_bw == 1.2e12
        assert dict(hw.link_bw) == {(0, 8): 12.5e9, (8, 0): 20e9}
        assert hw.alpha_base == lm.DEFAULT.alpha_base      # untouched
        assert lm.DEFAULT.recalibrated({}) == lm.DEFAULT
        # models stay hashable (they key the planner cache)
        hash(hw)

    def test_recalibrated_validates_links_against_topology(self):
        topo = two_server_cluster()
        hw = lm.DEFAULT.recalibrated({"links": {"0->8": 20e9}}, topo=topo)
        assert dict(hw.link_bw) == {(0, 8): 20e9}
        with pytest.raises(KeyError):
            lm.DEFAULT.recalibrated({"links": {"0->80": 20e9}}, topo=topo)

    def test_measured_bw_drives_scoring(self):
        """A measured slowdown on the rail shows up in score_ledger."""
        topo = two_server_cluster()
        sim = MultiWriteSimulator(topo)
        sim.multiwrite(0, {d: "x" for d in (9, 10, 12)},
                       np.zeros(1 << 20, np.uint8))
        ledger = plan_ir.Ledger.from_sim(sim)
        base = lm.score_ledger(ledger, lm.DEFAULT)
        slow = lm.DEFAULT.recalibrated({"links": {"0->8": 25e9 / 10}})
        assert lm.score_ledger(ledger, slow) > base * 5

    def test_recalibrated_model_invalidates_planner_cache(self):
        planner = pl.Planner()
        topo = two_server_cluster()
        planner.choose("dispatch", 2 ** 20, topo)
        hw = lm.DEFAULT.recalibrated({"alpha_hop": 1e-6})
        planner.choose("dispatch", 2 ** 20, topo, hw)
        assert planner.cache_info()["misses"] == 2


# ---------------------------------------------------------------------------
# end-to-end: moe_ffn resolves dispatch AND combine through the planner
# ---------------------------------------------------------------------------

def _mesh_pctx(**kw):
    import jax

    from repro.launch.mesh import make_test_mesh
    from repro.parallel.context import ParallelContext
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = make_test_mesh(shape=(1, 1), axes=("data", "model"))
    return ParallelContext(mesh=mesh, pod_axis=None, **kw)


class TestContextCombine:
    def test_combine_fixed_follows_dispatch(self):
        pctx = _mesh_pctx()
        assert pctx.moe_pipeline_kwargs(
            64, 8, 1024, 7168)["moe_combine"] == "hierarchical"
        pctx2 = dataclasses.replace(pctx, moe_scheme="baseline")
        assert pctx2.moe_pipeline_kwargs(
            64, 8, 1024, 7168)["moe_combine"] == "baseline"
        pctx3 = dataclasses.replace(pctx, moe_combine="baseline")
        assert pctx3.moe_pipeline_kwargs(
            64, 8, 1024, 7168)["moe_combine"] == "baseline"

    def test_auto_policy_with_fabric_resolves_both(self):
        """Acceptance: under plan_policy="auto" both halves come from the
        planner (jointly, one shared pipeline); an explicit fabric moves
        the decisions."""
        fabric = two_server_cluster()
        pctx = _mesh_pctx(plan_policy="auto", fabric=fabric)
        big = pctx.moe_pipeline_kwargs(64, 8, 2048, 7168)
        assert big["moe_scheme"] == "hierarchical"
        assert big["moe_combine"] == "hierarchical"
        small = pctx.moe_pipeline_kwargs(64, 8, 8, 7168)
        assert small["moe_scheme"] == "baseline"
        assert small["moe_combine"] == "baseline"
        # the per-site combine view of the joint plan
        sites = pctx.moe_sites("t", num_experts=64, top_k=8,
                               tokens_per_rank=2048, token_bytes=7168)
        eplan = pctx.plan_collectives(
            plan_ir.CollectiveProgram("t", sites))
        d = eplan.decision("t/moe_combine")
        assert d.op == "combine"
        assert d.shard_map_kwargs["moe_combine"] == "hierarchical"

    def test_moe_ffn_traces_with_planner_combine(self):
        """moe_ffn runs under plan_policy="auto" with a fabric, resolving
        dispatch and combine through the planner, and the
        hierarchical_combine_unicast lowering agrees numerically with the
        relay-reduced combine."""
        import types

        import jax
        import jax.numpy as jnp

        from repro.models import moe

        cfg = types.SimpleNamespace(num_experts=8, top_k=2, act="silu",
                                    moe_capacity=2.0)
        key = jax.random.key(0)
        params = moe.init_moe(key, d=8, f=16, num_experts=8)
        x = jax.random.normal(jax.random.key(1), (2, 8, 8), jnp.float32)

        pctx_auto = _mesh_pctx(plan_policy="auto",
                               fabric=two_server_cluster())
        out_auto, aux, _ = moe.moe_ffn(params, x, cfg, pctx_auto)
        assert out_auto.shape == x.shape
        assert np.isfinite(np.asarray(out_auto)).all()

        # fixed hierarchical dispatch, both combine lowerings
        pctx_h = _mesh_pctx(moe_scheme="hierarchical")
        pctx_hu = dataclasses.replace(pctx_h, moe_combine="baseline")
        out_h, _, _ = moe.moe_ffn(params, x, cfg, pctx_h)
        out_hu, _, _ = moe.moe_ffn(params, x, cfg, pctx_hu)
        np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_hu),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# split-TP allgather in the model layers (tp_subgroups path)
# ---------------------------------------------------------------------------

class TestSplitTPAllgatherLayer:
    def test_degenerate_single_domain(self):
        """tp_subgroups == 1: plain full gather, no planner consulted."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.models import layers as L
        from jax import shard_map
        pctx = _mesh_pctx()
        x = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)

        fn = shard_map(lambda a: L.split_tp_allgather(a, pctx),
                       mesh=pctx.mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
        with pctx.mesh:
            out = jax.jit(fn)(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x[None]))

    def test_planner_routed_branch_selection(self):
        """Under "auto" the layer goes through planned_allgather — the
        planner decision (not a hard-coded mode) selects the lowering."""
        from repro.core.topology import split_tp_full_mesh
        topo, _ = split_tp_full_mesh(8, tp=4)
        planner = pl.Planner()
        small = planner.choose("allgather", 64 * 2 ** 10, topo,
                               executable_only=True)
        big = planner.choose("allgather", 16 * 2 ** 20, topo,
                             executable_only=True)
        assert small.shard_map_kwargs["mode"] is None
        assert big.shard_map_kwargs["mode"] in ("paired", "full")
