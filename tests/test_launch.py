"""Launch surfaces: mesh layouts per device count, the compile-cache
rule, how kernels pick their mode, and the serving construction that
``launch/serve.py`` and ``chip_smoke.py`` share."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch import mesh as launch_mesh
from repro.launch import serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_each_layout_spans_its_device_count():
    assert launch_mesh.LAYOUTS[4] == ((2, 2, 1), ("pod", "data", "model"))
    for n, (shape, axes) in launch_mesh.LAYOUTS.items():
        assert math.prod(shape) == n and len(shape) == len(axes)


def test_device_count_without_a_layout_is_an_error():
    with pytest.raises(ValueError, match="no mesh layout for 3"):
        launch_mesh.make_mesh(3)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # none set in code


def test_compile_cache_default_is_a_fixed_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_kernel_mode_follows_the_backend(monkeypatch):
    from repro.kernels import ops
    assert ops._interpret() is True                      # this CPU run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas kernel path"):
        ops._interpret()


def _smoke_args(*extra):
    return serve.parse_args(["--arch", "qwen2_vl_2b", "--smoke",
                             "--prompts", "2", "--prompt-len", "8",
                             "--max-new", "4", *extra])


def test_build_engine_serves_what_the_no_cache_forward_predicts():
    from repro.data.pipeline import batch_for_model
    args = _smoke_args()
    cfg = serve.serving_config(args)
    engine = serve.build_engine(args, cfg)
    assert engine.pctx is None                     # one device: no mesh
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(2, 8)).astype(np.int32)
    out = engine.generate(prompts)
    seq = np.concatenate([prompts, out[:, :-1]], axis=1)
    batch = batch_for_model(cfg, {"tokens": seq, "labels": seq})
    batch.pop("labels")
    logits = jax.jit(engine.model.logits)(engine.params, batch)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(logits[:, 7:], -1)),
                                  out)


def test_init_params_are_created_sharded_in_the_asked_dtype():
    from repro.models.api import build_model
    from repro.parallel.context import ParallelContext
    args = _smoke_args()
    cfg = serve.serving_config(args)
    pctx = ParallelContext(mesh=launch_mesh.make_test_mesh((1, 1, 1)),
                           pod_axis="pod", fsdp=False)
    model = build_model(cfg, pctx, dtype=jnp.float32)
    params = serve.init_params(model, pctx, 0, jnp.bfloat16)
    for leaf in jax.tree_util.tree_leaves(params):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
        assert leaf.sharding.mesh.shape == pctx.mesh.shape
        assert leaf.dtype == jnp.bfloat16
