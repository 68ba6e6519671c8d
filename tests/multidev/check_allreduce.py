"""8-device checks for the planned gradient-sync lowerings.

Every registered executable allreduce scheme's ``planned_psum`` must be
bit-compatible with ``lax.psum / R`` (float summation order aside); the
lossy compressed opt-in must land within its quantization tolerance.

Run by tests/test_allreduce_multidev.py in a subprocess.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.collectives import butterfly_psum, planned_psum  # noqa: E402
from jax import shard_map  # noqa: E402
from repro.parallel.compression import hierarchical_psum_flat  # noqa: E402


def check(name, ok):
    print(f"{'PASS' if ok else 'FAIL'} {name}")
    if not ok:
        raise SystemExit(1)


def _run(fn_inner, gs, out_spec=None):
    mesh = jax.make_mesh((8,), ("data",))
    f = jax.jit(shard_map(fn_inner, mesh=mesh, in_specs=P("data"),
                          out_specs=out_spec or P("data"),
                          check_vma=False))
    return np.asarray(f(jnp.asarray(gs.reshape(-1))))


def run_every_scheme_matches_psum():
    rng = np.random.default_rng(0)
    n = 4096
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)
    for scheme in ("ring", "tree", "hierarchical", "multiwrite"):
        out = _run(lambda g, s=scheme: planned_psum(
            g, "data", num_servers=2, reduce_scheme=s), gs).reshape(8, n)
        ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(8))
        check(f"planned_psum[{scheme}] == mean on every rank", ok)


def run_planner_decided_scheme():
    """decision=None: the process planner picks from payload + fabric;
    whatever it picks must still be the exact mean."""
    rng = np.random.default_rng(1)
    n = 2048
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)
    out = _run(lambda g: planned_psum(g, "data", num_servers=2),
               gs).reshape(8, n)
    ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(8))
    check("planned_psum[planner-decided] == mean on every rank", ok)


def run_bound_decision_scheme():
    """The bound ExecutionPlan path: plan a train program with a
    grad_sync site, feed its decision into planned_psum."""
    from repro.core import plan as plan_ir
    from repro.core import planner as pl
    from repro.core.topology import get_fabric

    topo = get_fabric("2x8")
    site = plan_ir.grad_sync_site("train", payload_bytes=8 * 4096 * 4,
                                  compute_s=1e-3, topo=topo)
    eplan = pl.Planner().plan_program(
        plan_ir.CollectiveProgram("train", (site,)), topo)
    d = eplan.decisions["train/grad_sync"]
    rng = np.random.default_rng(2)
    n = 4096
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)
    out = _run(lambda g: planned_psum(g, "data", num_servers=2,
                                      decision=d), gs).reshape(8, n)
    ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(8))
    check(f"planned_psum[bound:{d.plan}] == mean on every rank", ok)


def run_compressed_within_tolerance():
    rng = np.random.default_rng(3)
    n = 4096
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)
    out = _run(lambda g: planned_psum(g, "data",
                                      reduce_scheme="compressed"),
               gs).reshape(8, n)
    # int8 wire format: two quantization steps of error
    tol = 2 * (np.abs(gs).max() / 127 + np.abs(mean).max() / 127)
    err = np.abs(out[0] - mean).max()
    check(f"planned_psum[compressed] within int8 tolerance "
          f"(err {err:.4f} < tol {tol:.4f})", err < tol)


def run_butterfly_is_exact_sum():
    rng = np.random.default_rng(4)
    n = 512
    gs = rng.normal(size=(8, n)).astype(np.float32)
    out = _run(lambda g: butterfly_psum(g, "data"), gs).reshape(8, n)
    ok = all(np.allclose(out[r], gs.sum(0), atol=1e-4) for r in range(8))
    check("butterfly_psum == exact sum on every rank", ok)


def run_hierarchical_flat_grouping():
    """hierarchical_psum_flat derives (servers x npus) groups from the
    fabric meta: correct on a 2x4 grouping of one flat 8-rank axis, and
    on the degenerate 1-server grouping."""
    rng = np.random.default_rng(5)
    n = 1000                      # non-divisible by P=4: exercises padding
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)
    for servers in (1, 2, 4):
        out = _run(lambda g, s=servers: hierarchical_psum_flat(
            g, "data", s), gs).reshape(8, n)
        ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(8))
        check(f"hierarchical_psum_flat[{servers} servers] == mean", ok)


def run_non_pow2_and_unfactorable_fallbacks():
    """tree on a non-pow2 axis and hierarchical on an unfactorable axis
    fall back to the ring — still the exact mean."""
    mesh = jax.make_mesh((8,), ("data",))
    del mesh
    import jax.sharding as shd
    devs = jax.devices()[:6]
    mesh6 = jax.sharding.Mesh(np.array(devs), ("data",))
    rng = np.random.default_rng(6)
    n = 600
    gs = rng.normal(size=(6, n)).astype(np.float32)
    mean = gs.mean(0)
    for scheme, kw in (("tree", {}), ("hierarchical", {"num_servers": 4})):
        f = jax.jit(shard_map(
            lambda g, s=scheme, k=kw: planned_psum(g, "data",
                                                   reduce_scheme=s, **k),
            mesh=mesh6, in_specs=shd.PartitionSpec("data"),
            out_specs=shd.PartitionSpec("data"), check_vma=False))
        out = np.asarray(f(jnp.asarray(gs.reshape(-1)))).reshape(6, n)
        ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(6))
        check(f"planned_psum[{scheme}] fallback on awkward axis == mean",
              ok)


if __name__ == "__main__":
    assert len(jax.devices()) == 8
    run_every_scheme_matches_psum()
    run_planner_decided_scheme()
    run_bound_decision_scheme()
    run_compressed_within_tolerance()
    run_butterfly_is_exact_sum()
    run_hierarchical_flat_grouping()
    run_non_pow2_and_unfactorable_fallbacks()
    print("ALL OK")
