"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle.

Every kernel is swept over shapes and dtypes per the deliverable spec,
plus hypothesis property tests on the packing kernel's invariants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - property tests skipped
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.dispatch_pack import dispatch_pack
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("bh,s,d,bq,bk", [
        (2, 128, 64, 64, 64),
        (1, 96, 32, 32, 64),     # padding on q
        (3, 130, 16, 64, 64),    # padding on q and k
        (2, 64, 128, 16, 16),
    ])
    def test_causal_matches_ref(self, bh, s, d, bq, bk, dtype):
        rng = np.random.default_rng(s + d)
        q, k, v = (jnp.asarray(rng.normal(size=(bh, s, d)), dtype)
                   for _ in range(3))
        got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        exp = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32), **tol(dtype))

    def test_noncausal(self):
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 80, 32)), jnp.float32)
                   for _ in range(3))
        got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
        exp = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 128, 32)), jnp.float32)
                   for _ in range(3))
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
        exp = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    def test_softcap(self):
        rng = np.random.default_rng(2)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.float32)
                   for _ in range(3))
        got = flash_attention(q, k, v, causal=True, softcap=30.0,
                              block_q=32, block_k=32, interpret=True)
        exp = ref.attention_ref(q, k, v, causal=True, softcap=30.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    def test_cross_attention_lengths(self):
        """enc-dec: kv length != q length."""
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, 40, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 72, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 72, 32)), jnp.float32)
        got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
        exp = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# mamba2
# ---------------------------------------------------------------------------

class TestMamba2:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("bh,s,dh,ds,chunk", [
        (2, 64, 32, 16, 16),
        (1, 100, 16, 8, 32),     # padding
        (3, 32, 64, 32, 32),
    ])
    def test_matches_scan_ref(self, bh, s, dh, ds, chunk, dtype):
        rng = np.random.default_rng(s * 7 + dh)
        x = jnp.asarray(rng.normal(size=(bh, s, dh)), dtype)
        dt = jnp.asarray(
            np.log1p(np.exp(rng.normal(size=(bh, s)))), jnp.float32) * 0.1
        a = jnp.asarray(-np.abs(rng.normal(size=(bh,))) - 0.1, jnp.float32)
        b = jnp.asarray(rng.normal(size=(bh, s, ds)), dtype)
        c = jnp.asarray(rng.normal(size=(bh, s, ds)), dtype)
        d = jnp.asarray(rng.normal(size=(bh,)), jnp.float32)
        got = mamba2_scan(x, dt, a, b, c, d, chunk=chunk, interpret=True)
        exp = ref.mamba2_ref(x, dt, a, b, c, d)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_decode_step_consistent_with_scan(self):
        """Running T decode steps == the scan over T steps."""
        rng = np.random.default_rng(11)
        bh, s, dh, ds = 2, 16, 8, 4
        x = jnp.asarray(rng.normal(size=(bh, s, dh)), jnp.float32)
        dt = jnp.asarray(np.abs(rng.normal(size=(bh, s))) * 0.1 + 0.01,
                         jnp.float32)
        a = jnp.asarray([-0.5, -1.0], jnp.float32)
        b = jnp.asarray(rng.normal(size=(bh, s, ds)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(bh, s, ds)), jnp.float32)
        d = jnp.asarray(rng.normal(size=(bh,)), jnp.float32)
        exp = np.asarray(ref.mamba2_ref(x, dt, a, b, c, d))
        h = jnp.zeros((bh, ds, dh), jnp.float32)
        for t in range(s):
            h, y = ref.mamba2_decode_step(h, x[:, t], dt[:, t], a, b[:, t],
                                          c[:, t], d)
            np.testing.assert_allclose(np.asarray(y), exp[:, t],
                                       atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

class TestRWKV6:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("bh,s,dk,dv,chunk", [
        (2, 64, 16, 16, 16),
        (1, 100, 8, 32, 32),     # padding
        (3, 32, 32, 8, 8),
    ])
    def test_matches_scan_ref(self, bh, s, dk, dv, chunk, dtype):
        rng = np.random.default_rng(s * 13 + dk)
        r = jnp.asarray(rng.normal(size=(bh, s, dk)), dtype)
        k = jnp.asarray(rng.normal(size=(bh, s, dk)), dtype)
        v = jnp.asarray(rng.normal(size=(bh, s, dv)), dtype)
        logw = jnp.asarray(-np.abs(rng.normal(size=(bh, s, dk))) * 0.3 - 0.05,
                           jnp.float32)
        u = jnp.asarray(rng.normal(size=(bh, dk)), jnp.float32)
        got = rwkv6_scan(r, k, v, logw, u, chunk=chunk, interpret=True)
        exp = ref.rwkv6_ref(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_decode_step_consistent_with_scan(self):
        rng = np.random.default_rng(17)
        bh, s, dk, dv = 2, 12, 8, 8
        r = jnp.asarray(rng.normal(size=(bh, s, dk)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(bh, s, dk)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(bh, s, dv)), jnp.float32)
        logw = jnp.asarray(-np.abs(rng.normal(size=(bh, s, dk))) * 0.2 - 0.05,
                           jnp.float32)
        u = jnp.asarray(rng.normal(size=(bh, dk)), jnp.float32)
        exp = np.asarray(ref.rwkv6_ref(r, k, v, logw, u))
        S = jnp.zeros((bh, dk, dv), jnp.float32)
        for t in range(s):
            S, y = ref.rwkv6_decode_step(S, r[:, t], k[:, t], v[:, t],
                                         logw[:, t], u)
            np.testing.assert_allclose(np.asarray(y), exp[:, t],
                                       atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# dispatch pack
# ---------------------------------------------------------------------------

class TestDispatchPack:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,h,d,c,br", [
        (32, 16, 4, 16, 8),
        (17, 8, 8, 3, 4),        # padding + overflow
        (64, 128, 16, 64, 16),
        (8, 4, 31, 2, 8),
    ])
    def test_matches_jnp_oracle(self, n, h, d, c, br, dtype):
        rng = np.random.default_rng(n + d * 3)
        tokens = jnp.asarray(rng.normal(size=(n, h)), dtype)
        bitmap = jnp.asarray(rng.integers(0, 1 << d, size=n), jnp.int32)
        valid = jnp.asarray(rng.random(n) > 0.25)
        got_t, got_i = dispatch_pack(tokens, bitmap, valid, num_dests=d,
                                     capacity=c, block_rows=br,
                                     interpret=True)
        exp_t, exp_i = ref.pack_ref(tokens, bitmap, valid, d, c)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(exp_i))
        np.testing.assert_array_equal(np.asarray(got_t, np.float32),
                                      np.asarray(exp_t, np.float32))

    if HAVE_HYPOTHESIS:
        @settings(max_examples=25, deadline=None)
        @given(n=st.integers(1, 48), d=st.integers(1, 12),
               c=st.integers(1, 10),
               br=st.sampled_from([4, 8]), seed=st.integers(0, 10**6))
        def test_property_matches_oracle(self, n, d, c, br, seed):
            rng = np.random.default_rng(seed)
            tokens = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
            bitmap = jnp.asarray(rng.integers(0, 1 << d, size=n), jnp.int32)
            valid = jnp.asarray(rng.random(n) > 0.3)
            got_t, got_i = dispatch_pack(tokens, bitmap, valid, num_dests=d,
                                         capacity=c, block_rows=br,
                                         interpret=True)
            exp_t, exp_i = ref.pack_ref(tokens, bitmap, valid, d, c)
            np.testing.assert_array_equal(np.asarray(got_i),
                                          np.asarray(exp_i))
            np.testing.assert_array_equal(np.asarray(got_t),
                                          np.asarray(exp_t))
    else:
        def test_property_matches_oracle(self):
            pytest.skip("hypothesis not installed")


# ---------------------------------------------------------------------------
# ops dispatch layer
# ---------------------------------------------------------------------------

class TestOps:
    def test_ops_pallas_vs_ref_toggle(self):
        rng = np.random.default_rng(5)
        q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.float32)
                   for _ in range(3))
        a = ops.flash_attention(q, k, v, use_pallas=True,
                                block_q=32, block_k=32)
        b = ops.flash_attention(q, k, v, use_pallas=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
