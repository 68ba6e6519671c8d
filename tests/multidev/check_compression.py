"""8-device checks for the compressed / hierarchical gradient collectives.

Run by tests/test_compression.py in a subprocess.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from repro.parallel.compression import (  # noqa: E402
    compressed_psum, hierarchical_psum)


def check(name, ok):
    print(f"{'PASS' if ok else 'FAIL'} {name}")
    if not ok:
        raise SystemExit(1)


def run_compressed_psum():
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    n = 4096
    # per-rank gradients: rank r holds g_r; mean = average
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)

    def inner(g):
        out, err = compressed_psum(g, "data")
        return out, err

    fn = jax.jit(shard_map(inner, mesh=mesh, in_specs=P("data"),
                               out_specs=(P("data"), P("data")),
                               check_vma=False))
    out, err = fn(jnp.asarray(gs.reshape(-1)))
    out = np.asarray(out).reshape(8, n)
    # every rank sees the same (quantized) mean
    for r in range(1, 8):
        check_ok = np.allclose(out[0], out[r])
        if not check_ok:
            check("compressed_psum replicas agree", False)
    # int8 quantization error bound: 2 quant steps of the max |value|
    step1 = np.abs(gs).max() / 127
    step2 = np.abs(mean).max() / 127
    tol = 2 * (step1 + step2)
    err_to_mean = np.abs(out[0] - mean).max()
    check(f"compressed_psum ~= mean (err {err_to_mean:.4f} < tol {tol:.4f})",
          err_to_mean < tol)
    # error feedback residual: g + (-sent) == err
    check("error-feedback residual finite",
          np.isfinite(np.asarray(err)).all())


def run_error_feedback_convergence():
    """With error feedback, the time-average of compressed means converges
    to the true mean (residuals don't accumulate)."""
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    n = 512
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)

    def inner(g, err):
        return compressed_psum(g, "data", err)

    fn = jax.jit(shard_map(inner, mesh=mesh,
                               in_specs=(P("data"), P("data")),
                               out_specs=(P("data"), P("data")),
                               check_vma=False))
    err = jnp.zeros((8 * n,), jnp.float32)
    g = jnp.asarray(gs.reshape(-1))
    acc = np.zeros(n)
    steps = 20
    for _ in range(steps):
        out, err = fn(g, err)
        acc += np.asarray(out).reshape(8, n)[0]
    drift = np.abs(acc / steps - mean).max()
    naive = np.abs(mean).max() / 127 * 2
    check(f"error feedback keeps time-avg near mean "
          f"(drift {drift:.5f} <= {naive:.5f})", drift <= naive + 1e-5)


def run_hierarchical_psum():
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    rng = np.random.default_rng(2)
    n = 1024
    gs = rng.normal(size=(8, n)).astype(np.float32)
    mean = gs.mean(0)

    def inner(g):
        return hierarchical_psum(g, "pod", "data")

    fn = jax.jit(shard_map(
        inner, mesh=mesh, in_specs=P(("pod", "data")),
        out_specs=P(("pod", "data")), check_vma=False))
    out = np.asarray(fn(jnp.asarray(gs.reshape(-1)))).reshape(8, n)
    ok = all(np.allclose(out[r], mean, atol=1e-5) for r in range(8))
    check("hierarchical_psum == exact mean on every rank", ok)

    # DCN byte check: pod-axis bytes should be ~1/data_size of flat ring
    from repro.launch.hlo_analysis import MeshLayout
    from repro.launch.hlo_module import analyze_module
    layout = MeshLayout(("pod", "data"), (2, 4))
    text = fn.lower(jax.ShapeDtypeStruct((8 * n,), jnp.float32)) \
        .compile().as_text()
    cost = analyze_module(text, layout)
    pod_b = cost.collective_by_axis.get("pod", 0)
    flat_ring = 2 * n * 4          # what a flat 8-rank ring would move
    check(f"hierarchical pod bytes {pod_b:.0f} < flat ring {flat_ring}",
          0 < pod_b < flat_ring)


if __name__ == "__main__":
    assert len(jax.devices()) == 8
    run_compressed_psum()
    run_error_feedback_convergence()
    run_hierarchical_psum()
    print("ALL OK")
