"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than present, so what the chip's compiler would
refuse (block shapes off the (8, 128) tiling, too much VMEM, a program
that does not fit HBM) fails here at no chip time.  Nothing runs:
results are checked by the interpret-mode tests in ``test_kernels.py``.

Covered: the four Pallas kernels at real model widths on one chip, and
the expert-parallel MoE layer (``models/moe.py``) at dbrx_132b's widths
on the 2x2 host mesh (pod 2, data 2, model 1) under MultiWrite
(hierarchical) and unicast (baseline) dispatch.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

HBM_BYTES = 16 * 10**9           # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 host, with the persistent compile cache off
    (a compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, spec):
    """(jitted fn, argument shapes) of one kernel at its model's widths."""
    from repro.kernels.dispatch_pack import dispatch_pack
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.rwkv6_scan import rwkv6_scan

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=spec)

    f32 = jnp.float32
    if name == "flash_attention":            # qwen2_vl_2b: BH 12, S 4096
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                [s((12, 4096, 128))] * 3)
    if name == "dispatch_pack":              # dbrx_132b: N 4096, H 6144
        return (lambda t, b, v: dispatch_pack(
                    t, b, v, num_dests=8, capacity=1024, interpret=False),
                [s((4096, 6144)), s((4096,), jnp.int32),
                 s((4096,), jnp.bool_)])
    if name == "mamba2_scan":                # zamba2_7b: BH 112, S 4096
        return (lambda *a: mamba2_scan(*a, interpret=False),
                [s((112, 4096, 64)), s((112, 4096), f32), s((112,), f32),
                 s((112, 4096, 64)), s((112, 4096, 64)), s((112,), f32)])
    if name == "rwkv6_scan":                 # rwkv6_7b: BH 64, S 4096
        return (lambda *a: rwkv6_scan(*a, interpret=False),
                [s((64, 4096, 64)), s((64, 4096, 64)), s((64, 4096, 64)),
                 s((64, 4096, 64), f32), s((64, 64), f32)])
    raise ValueError(name)


@pytest.mark.parametrize("name", ["flash_attention", "dispatch_pack",
                                  "mamba2_scan", "rwkv6_scan"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("scheme", ["hierarchical", "baseline"])
def test_moe_layer_compiles_on_v5e_2x2(scheme, topo):
    """dbrx_132b's MoE layer (d 6144, expert d_ff 10752, 16 experts,
    top-4) expert-parallel over the four chips: 4 experts per chip, the
    dispatch and combine as all-to-alls."""
    from repro.configs.base import get_config
    from repro.models.moe import moe_ffn
    from repro.parallel import sharding as shd
    from repro.parallel.context import ParallelContext

    cfg = get_config("dbrx_132b")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2, 1),
                ("pod", "data", "model"))
    pctx = ParallelContext(mesh=mesh, pod_axis="pod", fsdp=False,
                           plan_policy="fixed", moe_scheme=scheme)
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    shapes = {"moe": {"router": jax.ShapeDtypeStruct((d, e), jnp.bfloat16),
                      "w1": jax.ShapeDtypeStruct((e, d, f), jnp.bfloat16),
                      "w3": jax.ShapeDtypeStruct((e, d, f), jnp.bfloat16),
                      "w2": jax.ShapeDtypeStruct((e, f, d), jnp.bfloat16)}}
    params = shd.with_sharding(shapes, shd.param_specs(shapes, cfg, pctx),
                               pctx)["moe"]
    x = jax.ShapeDtypeStruct((4, 1024, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(pctx.dp_axes)))
    compiled = jax.jit(
        lambda p, x: moe_ffn(p, x, cfg, pctx)).lower(params, x).compile()
    assert "all-to-all" in compiled.as_text()
    per_expert = 3 * d * f * 2                            # bf16 w1, w3, w2
    mem = compiled.memory_analysis()
    # per device: its 4 experts' weights (not all 16) and a token shard
    assert ((e // 4) * per_expert <= mem.argument_size_in_bytes
            < (e // 4 + 1) * per_expert)
    assert mem.argument_size_in_bytes < HBM_BYTES
