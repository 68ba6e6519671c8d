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


# ---------------------------------------------------------------------------
# stage sizing: headroom x balanced load, capped at what can arrive
# ---------------------------------------------------------------------------

SIZING_SHAPES = [(n, k, p, d, per_rank)
                 for n in (1, 2, 7, 64, 256, 2048) for k in (1, 2, 4, 8)
                 for p in (1, 2, 4) for d in (1, 2, 4) for per_rank in (1, 4)]


def _capped(n, rows, k, p, d, per_rank, cf, unicast):
    """Each stage's slots for a chunk of ``rows`` of a rank's ``n`` rows,
    (Cp, Cd, Ce) or (Cr, Ce), as :func:`repro.models.moe.plan_moe` sizes
    them."""
    from repro.models import moe
    mesh = cl.EPMesh("pod" if p > 1 else None, "ep", p, d)
    headroom = moe.balanced_capacities(n, k, p, d, per_rank, cf, unicast)
    return cl.dispatch_slots(cl.capped_capacities(headroom, mesh, rows,
                                                  unicast),
                             mesh, rows, unicast)


def _uncapped(n, rows, k, p, d, per_rank, cf, unicast):
    """The slots the sizing gave before any stage was capped: headroom
    ``cf`` times the balanced load, set at ``n`` rows and applied to a
    chunk of ``rows`` with ``max(1, round(...))`` at each stage."""
    def slots(x):
        return max(1, int(round(x)))
    ce_target = slots(n * k / per_rank * cf)
    if unicast:
        rank_cap = min(1.0, k / (p * d)) * cf
        exp_cap = ce_target / (p * d * slots(n * rank_cap))
        cr = slots(rows * rank_cap)
        return cr, slots(p * d * cr * exp_cap)
    pod_cap = min(1.0, k / p) * cf
    ep_cap = min(1.0, (k / p) / d) * cf
    exp_cap = ce_target / (d * slots(p * slots(n * pod_cap) * ep_cap))
    cp = slots(rows * pod_cap)
    cd = slots(p * cp * ep_cap)
    return cp, cd, slots(d * cd * exp_cap)


@pytest.mark.parametrize("unicast", [False, True], ids=["multiwrite",
                                                         "unicast"])
@pytest.mark.parametrize("cf", [0.25, 1.25, 4.0, 8.0])
def test_stage_slots_are_headroom_capped_at_the_worst_case(cf, unicast):
    """Each stage allocates min(headroom x balanced load, most rows that
    can arrive there): stage 1 at most the chunk's rows (one copy per
    token and pod), stage 2 at most P*Cp (one per stage-1 slot and ep
    rank), unicast at most the rows per rank, an expert at most every
    row that reaches its rank.  Wherever the headroom's size is within
    that worst case it is kept, in whole launches and in chunks."""
    for n, k, p, d, per_rank in SIZING_SHAPES:
        for g in (1, 2, 8):
            if n % g:
                continue
            rows = n // g
            got = _capped(n, rows, k, p, d, per_rank, cf, unicast)
            want = _uncapped(n, rows, k, p, d, per_rank, cf, unicast)
            fans = (1, p * d) if unicast else (1, p, d)
            worst = rows
            for stage, (slots, wish, fan) in enumerate(zip(got, want, fans)):
                worst *= fan
                assert slots == min(wish, worst), (n, g, k, p, d, per_rank,
                                                   stage, got, want)
                worst = slots


@pytest.mark.parametrize("shape,want", [
    # 2 pods x 4 ranks, top 2: stage 1 wants 80 of 64 rows, and stage 2
    # keeps its 50 slots of the 128 that can arrive
    ((64, 64, 2, 2, 4, 1, 1.25, False), (64, 50, 160)),
    # top 8 of 384 experts over 2 x 8 ranks: stage 2 keeps 400 of 512
    ((256, 256, 8, 2, 8, 24, 1.25, False), (256, 400, 107)),
    # the headroom within every worst case: nothing changes
    ((256, 256, 4, 2, 2, 4, 0.25, False), (64, 32, 64)),
    ((256, 256, 4, 2, 2, 4, 0.25, True), (64, 64)),
])
def test_slots_keep_the_headroom_within_the_worst_case(shape, want):
    """Where headroom times the balanced load is within a stage's worst
    case, the stage keeps those slots: a capped stage before it does
    not shrink it."""
    assert _capped(*shape) == want


@pytest.mark.parametrize("launch,rows,g,want", [
    ("prefill", 2048, 8, (256, 512, 1024)),
    ("decode", 2, 1, (2, 8)),
])
def test_ep4_cell_slots(launch, rows, g, want):
    """DBRX on the 2x2 mesh at capacity 4.0 (16 experts top 4, 4 a
    chip): a prefill chunk of 256 rows a chip under MultiWrite G = 8, a
    decode of 2 rows a chip under unicast.  Stage 1, stage 2 and the
    unicast buffer hold what can arrive; the expert slots, 1024 and 8,
    are the no-drop worst case and the headroom's, as before the cap."""
    unicast = launch == "decode"
    assert _capped(rows, rows // g, 4, 2, 2, 4, 4.0, unicast) == want
    assert _uncapped(rows, rows // g, 4, 2, 2, 4, 4.0, unicast)[-1] == want[-1]


@pytest.mark.parametrize("dispatch,field", [
    ("hierarchical", "pod_capacity"), ("hierarchical", "ep_capacity"),
    ("hierarchical", "expert_capacity"), ("baseline", "pod_capacity"),
    ("baseline", "expert_capacity")])
def test_dispatch_refuses_slots_that_cannot_fill(dispatch, field):
    """A share above 1 of a stage's worst case is refused at trace time,
    before any slot is allocated."""
    import dataclasses
    mesh = cl.EPMesh(pod_axis=None, ep_axis="ep", num_pods=1, ep_per_pod=1)
    cfg = dataclasses.replace(cl.DispatchConfig(num_experts=8, top_k=2),
                              **{field: 1.5})
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    gates, ids = cl.route_topk(
        jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32)), 2)
    fn = getattr(cl, f"{dispatch}_dispatch")
    with pytest.raises(ValueError, match="can arrive"):
        fn(tokens, ids, gates, cfg, mesh)
