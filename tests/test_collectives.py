"""Tests for core/collectives.py.

Multi-device equality runs in a subprocess (so the forced 8-device XLA flag
never leaks into this process); single-device logic (packing, routing) and
hypothesis property tests run inline.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import collectives as cl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_multidevice_collectives_subprocess():
    """8-device shard_map equality suite (allgather + MoE dispatch)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never a chip
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests/multidev/check_collectives.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL OK" in proc.stdout


# ---------------------------------------------------------------------------
# pack_by_bitmap (single device)
# ---------------------------------------------------------------------------

def np_pack_oracle(tokens, bitmap, valid, num_dests, capacity):
    """Straightforward python oracle for pack_by_bitmap."""
    n, h = tokens.shape
    out = np.zeros((num_dests, capacity, h), tokens.dtype)
    idx = np.full((num_dests, capacity), -1, np.int32)
    counts = [0] * num_dests
    for row in range(n):
        if not valid[row]:
            continue
        for d in range(num_dests):
            if (int(bitmap[row]) >> d) & 1:
                if counts[d] < capacity:
                    out[d, counts[d]] = tokens[row]
                    idx[d, counts[d]] = row
                    counts[d] += 1
    return out, idx


class TestPackByBitmap:
    @pytest.mark.parametrize("n,h,d,c", [(16, 4, 3, 16), (32, 8, 8, 5),
                                         (5, 2, 31, 2), (64, 16, 16, 64)])
    def test_matches_oracle(self, n, h, d, c):
        rng = np.random.default_rng(n * 31 + d)
        tokens = rng.normal(size=(n, h)).astype(np.float32)
        bitmap = rng.integers(0, 1 << d, size=n).astype(np.int32)
        valid = rng.random(n) > 0.2
        got_t, got_i = jax.jit(cl.pack_by_bitmap, static_argnums=(3, 4))(
            jnp.asarray(tokens), jnp.asarray(bitmap), jnp.asarray(valid), d, c)
        exp_t, exp_i = np_pack_oracle(tokens, bitmap, valid, d, c)
        np.testing.assert_array_equal(np.asarray(got_i), exp_i)
        np.testing.assert_array_equal(np.asarray(got_t), exp_t)

    def test_priority_is_token_order(self):
        tokens = np.arange(10, dtype=np.float32)[:, None]
        bitmap = np.ones(10, np.int32)
        _, idx = cl.pack_by_bitmap(jnp.asarray(tokens), jnp.asarray(bitmap),
                                   jnp.ones(10, bool), 1, 4)
        np.testing.assert_array_equal(np.asarray(idx)[0], [0, 1, 2, 3])

    if HAVE_HYPOTHESIS:
        @settings(max_examples=40, deadline=None)
        @given(n=st.integers(1, 40), d=st.integers(1, 31),
               c=st.integers(1, 12), seed=st.integers(0, 2**31))
        def test_property_matches_oracle(self, n, d, c, seed):
            rng = np.random.default_rng(seed)
            tokens = rng.normal(size=(n, 3)).astype(np.float32)
            bitmap = rng.integers(0, 1 << d, size=n,
                                  dtype=np.int64).astype(np.int32)
            valid = rng.random(n) > 0.3
            got_t, got_i = cl.pack_by_bitmap(
                jnp.asarray(tokens), jnp.asarray(bitmap), jnp.asarray(valid),
                d, c)
            exp_t, exp_i = np_pack_oracle(tokens, bitmap, valid, d, c)
            np.testing.assert_array_equal(np.asarray(got_i), exp_i)
            np.testing.assert_array_equal(np.asarray(got_t), exp_t)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

class TestRouteTopK:
    def test_topk_properties(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
        gates, ids = cl.route_topk(logits, 4)
        assert gates.shape == (32, 4) and ids.shape == (32, 4)
        # normalized, positive, distinct ids, ids are true argmax set
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
        assert (np.asarray(gates) > 0).all()
        for row in np.asarray(ids):
            assert len(set(row.tolist())) == 4
        top4 = np.argsort(-np.asarray(logits), axis=-1)[:, :4]
        np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                      np.sort(top4, -1))


# ---------------------------------------------------------------------------
# single-chip MoE path (p=1, d=1: all collectives degenerate)
# ---------------------------------------------------------------------------

class TestSingleChipDispatch:
    def test_roundtrip_identity_experts(self):
        mesh = cl.EPMesh(pod_axis=None, ep_axis="ep", num_pods=1, ep_per_pod=1)
        cfg = cl.DispatchConfig(num_experts=8, top_k=2)
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.normal(size=(32, 6)).astype(np.float32))
        logits = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
        gates, ids = cl.route_topk(logits, 2)
        exp_tok, exp_gate, state = cl.hierarchical_dispatch(
            tokens, ids, gates, cfg, mesh)
        assert exp_tok.shape[0] == 8  # all experts local
        out = cl.hierarchical_combine(exp_tok, exp_gate, state)
        # identity experts, gates sum to 1 -> out == tokens
        np.testing.assert_allclose(np.asarray(out), np.asarray(tokens),
                                   atol=1e-5)

    def test_dispatch_pod_bytes_accounting(self):
        """Analytic pod-bytes: multiwrite <= baseline, ratio ~ k_remote."""
        cfg = cl.DispatchConfig(num_experts=64, top_k=8)
        mesh = cl.EPMesh("pod", "ep", num_pods=2, ep_per_pod=16)
        rng = np.random.default_rng(5)
        ids = np.stack([rng.choice(64, 8, replace=False) for _ in range(256)])
        base, mw = cl.dispatch_pod_bytes(ids, cfg, mesh, h=128)
        assert mw < base
        assert base / mw > 2.0  # expected ~4 distinct remote ranks vs ~1 pod
