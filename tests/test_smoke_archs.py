"""Per-architecture smoke tests: REDUCED same-family configs, one forward
+ train-grad step on CPU, asserting output shapes and no NaNs — plus
prefill/decode-vs-forward consistency for the cache paths, and eval_shape
parameter-count fidelity for the FULL configs (no allocation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models.api import build_model, make_batch, param_count_shape_only

BATCH, SEQ = 2, 32


def small(arch):
    return get_config(arch).reduced()


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestSmoke:
    def test_forward_and_grad_step(self, arch):
        cfg = small(arch)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg, "train", BATCH, SEQ)

        @jax.jit
        def step(p):
            (l, metrics), g = jax.value_and_grad(model.loss,
                                                 has_aux=True)(p, batch)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                 for x in jax.tree_util.tree_leaves(g)))
            return l, metrics["ce"], gnorm

        loss, ce, gnorm = step(params)
        assert np.isfinite(float(loss)), f"{arch}: loss NaN/inf"
        assert np.isfinite(float(gnorm)), f"{arch}: grad NaN/inf"
        # untrained CE should be near log(vocab)
        assert 0.2 * np.log(cfg.vocab) < float(ce) < 3 * np.log(cfg.vocab)

    def test_prefill_decode_shapes(self, arch):
        cfg = small(arch)
        model = build_model(cfg)
        params = model.init(jax.random.key(1))
        batch = make_batch(cfg, "train", BATCH, SEQ)
        cache = model.init_cache(BATCH, SEQ + 4)
        if cfg.family in ("rwkv",):
            cache = model.init_cache(BATCH, SEQ)
        logits, cache, _ = jax.jit(model.prefill)(params, batch, cache)
        assert logits.shape == (BATCH, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        dec_batch = make_batch(cfg, "decode", BATCH, 1)
        logits2, cache, _ = jax.jit(model.decode)(params, dec_batch, cache)
        assert logits2.shape == (BATCH, cfg.vocab)
        assert np.isfinite(np.asarray(logits2, np.float32)).all()
        assert int(cache["len"]) == SEQ + 1


# ---------------------------------------------------------------------------
# cache correctness: teacher-forced forward logits == prefill+decode logits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mistral_nemo_12b", "gemma2_9b",
                                  "rwkv6_7b", "zamba2_7b", "dbrx_132b"])
def test_decode_matches_forward(arch):
    """Prefill on s tokens then decode token s must equal the teacher-forced
    forward logits at position s (same params, fp32 compute)."""
    cfg = small(arch)
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, size=(BATCH, SEQ + 1)).astype(np.int32)

    # teacher-forced logits at position SEQ-1 predict token SEQ
    from repro.models import transformer as T
    from repro.models import layers as L

    full_batch = {"tokens": jnp.asarray(toks),
                  "labels": jnp.asarray(toks)}
    # hidden via the model's internal path: use loss's logits indirectly —
    # easier: prefill on SEQ+1 tokens returns logits at the LAST position.
    cache_a = model.init_cache(BATCH, SEQ + 1, jnp.float32)
    ref_logits, _, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(toks)}, cache_a)

    # prefill on SEQ tokens, then decode token SEQ
    cache_b = model.init_cache(BATCH, SEQ + 1, jnp.float32)
    _, cache_b, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(toks[:, :SEQ])}, cache_b)
    got_logits, _, _ = jax.jit(model.decode)(
        params, {"tokens": jnp.asarray(toks[:, SEQ:SEQ + 1])}, cache_b)

    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(ref_logits),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# full-config parameter fidelity (eval_shape only — no allocation)
# ---------------------------------------------------------------------------

PUBLISHED_PARAMS = {
    # arch: (published total, tolerance) — stub-frontend archs compare
    # against the published BACKBONE share.
    "starcoder2_15b": (15.0e9, 0.10),
    "minitron_8b": (8.0e9, 0.08),
    "mistral_nemo_12b": (12.2e9, 0.05),
    "gemma2_9b": (9.2e9, 0.05),
    "dbrx_132b": (132e9, 0.03),
    "kimi_k2_1t": (1000e9, 0.05),
    "qwen2_vl_2b": (1.5e9, 0.10),       # backbone share of the 2B VLM
    "seamless_m4t_medium": (0.6e9, 0.15),  # text backbone of 1.2B model
    "zamba2_7b": (7.0e9, 0.08),
    "rwkv6_7b": (7.0e9, 0.10),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_count(arch):
    n = param_count_shape_only(get_config(arch))
    target, tol = PUBLISHED_PARAMS[arch]
    assert abs(n - target) / target < tol, \
        f"{arch}: {n/1e9:.2f}B vs published {target/1e9:.1f}B"
