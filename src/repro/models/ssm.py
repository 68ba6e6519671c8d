"""Mamba2 blocks + the Zamba2 hybrid stack.

Zamba2 interleaves a backbone of Mamba2 (SSD) blocks with a SHARED
attention+MLP block applied every ``shared_attn_every`` layers (weight
sharing is Zamba's signature trick — the same global block re-reads the
residual stream at multiple depths).  The released model alternates two
shared blocks with per-invocation LoRA deltas; we implement one shared
block (see DESIGN.md §Arch-fidelity).

Mamba2 block:  in_proj -> (z gate, x, B, C, dt) -> causal depthwise conv
on x -> SSD scan (Pallas kernel / jnp ref) -> z-gated RMSNorm -> out_proj.

Decode state per layer: conv tail [B, d_inner, conv-1] + SSD state
[B, heads, ds, dh] — O(1) per token, which is why this arch runs the
long_500k cell.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models import layers as L
from repro.parallel.context import shard


def _inner_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = d_inner // cfg.ssm_head_dim
    return d_inner, heads


def init_mamba2_block(key, cfg: ModelConfig):
    d = cfg.d_model
    d_inner, heads = _inner_dims(cfg)
    ds = cfg.ssm_state
    ks = jax.random.split(key, 6)
    proj_out = 2 * d_inner + 2 * ds + heads   # z, x, B, C, dt
    return {
        "ln": L.init_rmsnorm(d),
        "in_proj": L.truncated_normal(ks[0], (d, proj_out), 1 / math.sqrt(d)),
        "conv": L.truncated_normal(ks[1], (cfg.ssm_conv, d_inner), 0.5),
        "A_log": jnp.zeros((heads,), jnp.float32),       # A = -exp(A_log)
        "D": jnp.ones((heads,), jnp.float32),
        "dt_bias": jnp.zeros((heads,), jnp.float32),
        "out_norm": L.init_rmsnorm(d_inner),
        "out_proj": L.truncated_normal(ks[2], (d_inner, d),
                                       1 / math.sqrt(d_inner)),
    }


def _split_proj(proj, cfg, d_inner, heads):
    ds = cfg.ssm_state
    z = proj[..., :d_inner]
    x = proj[..., d_inner:2 * d_inner]
    b = proj[..., 2 * d_inner:2 * d_inner + ds]
    c = proj[..., 2 * d_inner + ds:2 * d_inner + 2 * ds]
    dt = proj[..., 2 * d_inner + 2 * ds:]
    return z, x, b, c, dt


def _causal_conv(x, w, state=None):
    """Depthwise causal conv.  x: [B, S, C]; w: [K, C].
    state: [B, K-1, C] tail from previous tokens (decode) or None."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None].astype(x.dtype)
              for i in range(k))
    new_state = xp[:, -(k - 1):]
    return jax.nn.silu(out), new_state


def mamba2_block(p, x, cfg, pctx, *, use_pallas=False):
    """Train/prefill.  x: [B, S, D] -> [B, S, D]."""
    b, s, d = x.shape
    d_inner, heads = _inner_dims(cfg)
    dh, ds = cfg.ssm_head_dim, cfg.ssm_state
    dt_ = x.dtype
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    proj = h @ p["in_proj"].astype(dt_)
    z, xc, bmat, cmat, dt_raw = _split_proj(proj, cfg, d_inner, heads)
    xc, _ = _causal_conv(xc, p["conv"])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])             # [B, S, heads]
    a = -jnp.exp(p["A_log"])                          # [heads]
    # head-major layout for the scan kernel: [B*heads, S, dh]
    xh = xc.reshape(b, s, heads, dh).transpose(0, 2, 1, 3).reshape(
        b * heads, s, dh)
    dth = dt.transpose(0, 2, 1).reshape(b * heads, s)
    bh = jnp.broadcast_to(bmat[:, None], (b, heads, s, ds)).reshape(
        b * heads, s, ds)
    ch = jnp.broadcast_to(cmat[:, None], (b, heads, s, ds)).reshape(
        b * heads, s, ds)
    ah = jnp.tile(a, b)
    dskip = jnp.tile(p["D"], b)
    y = ops.mamba2_scan(xh, dth, ah, bh.astype(dt_), ch.astype(dt_), dskip,
                        use_pallas=use_pallas)
    y = y.reshape(b, heads, s, dh).transpose(0, 2, 1, 3).reshape(
        b, s, d_inner)
    y = L.rmsnorm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ p["out_proj"].astype(dt_)


def mamba2_block_decode(p, x, state, cfg, pctx):
    """One token.  x: [B, 1, D]; state: {"conv": [B,K-1,d_inner],
    "ssd": [B, heads, ds, dh]}."""
    b, _, d = x.shape
    d_inner, heads = _inner_dims(cfg)
    dh, ds = cfg.ssm_head_dim, cfg.ssm_state
    dt_ = x.dtype
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    proj = h @ p["in_proj"].astype(dt_)
    z, xc, bmat, cmat, dt_raw = _split_proj(proj, cfg, d_inner, heads)
    xc, conv_state = _causal_conv(xc, p["conv"], state["conv"])
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = xc[:, 0].reshape(b * heads, dh)
    dth = dt.reshape(b * heads)
    bh = jnp.broadcast_to(bmat[:, 0, None], (b, heads, ds)).reshape(-1, ds)
    ch = jnp.broadcast_to(cmat[:, 0, None], (b, heads, ds)).reshape(-1, ds)
    ssd = state["ssd"].reshape(b * heads, ds, dh)
    ssd, y = ref.mamba2_decode_step(
        ssd, xh.astype(jnp.float32), dth, jnp.tile(a, b),
        bh.astype(jnp.float32), ch.astype(jnp.float32), jnp.tile(p["D"], b))
    y = y.reshape(b, 1, d_inner).astype(dt_)
    y = L.rmsnorm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return (y @ p["out_proj"].astype(dt_),
            {"conv": conv_state, "ssd": ssd.reshape(b, heads, ds, dh)})


# ---------------------------------------------------------------------------
# Zamba2 hybrid stack
# ---------------------------------------------------------------------------

def init_zamba2(key, cfg: ModelConfig):
    from repro.models.transformer import _init_layer
    ks = jax.random.split(key, 5)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    return {
        "embed": L.init_embedding(ks[1], cfg.vocab, cfg.d_model),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "mamba": jax.vmap(
            lambda k: init_mamba2_block(k, cfg))(layer_keys),
        "shared": _init_layer(ks[2], cfg, moe=False),   # ONE shared block
    }


def _shared_positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def zamba2_hidden(params, cfg, pctx, x, *, use_pallas=False):
    """Forward through 81 mamba blocks with the shared attn block every
    ``shared_attn_every`` layers.  Grouped scan: scan over groups of
    mamba layers, shared block applied between groups (python loop —
    group count is small)."""
    from repro.models.transformer import _dense_block
    b, s, _ = x.shape
    period = cfg.shared_attn_every
    n = cfg.n_layers
    positions = _shared_positions(b, s)

    def mamba_body(carry, lp):
        def inner(lp_, x_):
            from repro.parallel.context import shard_residual
            return shard_residual(
                x_ + mamba2_block(lp_, x_, cfg, pctx,
                                  use_pallas=use_pallas), pctx)
        from repro.models.transformer import _remat
        return _remat(inner, pctx)(lp, carry), None

    done = 0
    gi = 0
    while done < n:
        take = min(period, n - done)
        group = jax.tree_util.tree_map(
            lambda a: a[done:done + take], params["mamba"])
        x, _ = jax.lax.scan(mamba_body, x, group)
        done += take
        if done < n:
            x, _ = _dense_block(params["shared"], x, positions, cfg, pctx,
                                window=None)
        gi += 1
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), \
        jnp.zeros((), jnp.float32)


def zamba2_init_state(cfg, batch, max_len, dtype=jnp.bfloat16):
    d_inner, heads = _inner_dims(cfg)
    n_shared = max(0, (cfg.n_layers - 1) // cfg.shared_attn_every)
    g, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, d_inner),
                          dtype),
        "ssd": jnp.zeros((cfg.n_layers, batch, heads, cfg.ssm_state,
                          cfg.ssm_head_dim), jnp.float32),
        "k": tuple(jnp.zeros((batch, max_len, g, dh), dtype)
                   for _ in range(n_shared)),
        "v": tuple(jnp.zeros((batch, max_len, g, dh), dtype)
                   for _ in range(n_shared)),
        "len": jnp.zeros((), jnp.int32),
    }


def mamba2_block_prefill(p, x, cfg, pctx):
    """Like mamba2_block but returns decode states (conv tail + final SSD
    state) via the chunked-jnp scan."""
    b, s, d = x.shape
    d_inner, heads = _inner_dims(cfg)
    dh, ds = cfg.ssm_head_dim, cfg.ssm_state
    dt_ = x.dtype
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    proj = h @ p["in_proj"].astype(dt_)
    z, xc, bmat, cmat, dt_raw = _split_proj(proj, cfg, d_inner, heads)
    xc, conv_tail = _causal_conv(xc, p["conv"])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = xc.reshape(b, s, heads, dh).transpose(0, 2, 1, 3).reshape(
        b * heads, s, dh)
    dth = dt.transpose(0, 2, 1).reshape(b * heads, s)
    bh = jnp.broadcast_to(bmat[:, None], (b, heads, s, ds)).reshape(
        b * heads, s, ds)
    ch = jnp.broadcast_to(cmat[:, None], (b, heads, s, ds)).reshape(
        b * heads, s, ds)
    y, hf = ref.mamba2_chunked_jnp(
        xh, dth, jnp.tile(a, b), bh.astype(dt_), ch.astype(dt_),
        jnp.tile(p["D"], b), return_final=True)
    y = y.reshape(b, heads, s, dh).transpose(0, 2, 1, 3).reshape(
        b, s, d_inner)
    y = L.rmsnorm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"].astype(dt_)
    return out, {"conv": conv_tail, "ssd": hf.reshape(b, heads, ds, dh)}


def zamba2_prefill(params, cfg, pctx, x, state):
    """Prefill the hybrid stack, capturing every layer's decode state."""
    from repro.models.transformer import _attn_part, _ffn_part
    b, s, _ = x.shape
    period = cfg.shared_attn_every
    n = cfg.n_layers
    positions = _shared_positions(b, s)
    conv_s, ssd_s = state["conv"], state["ssd"]
    ks, vs = list(state["k"]), list(state["v"])

    def mamba_body(x, lp):
        y, st = mamba2_block_prefill(lp, x, cfg, pctx)
        return x + y, st

    done = si = 0
    while done < n:
        take = min(period, n - done)
        group = jax.tree_util.tree_map(
            lambda a: a[done:done + take], params["mamba"])
        x, sts = jax.lax.scan(mamba_body, x, group)
        conv_s = jax.lax.dynamic_update_slice(
            conv_s, sts["conv"].astype(conv_s.dtype), (done, 0, 0, 0))
        ssd_s = jax.lax.dynamic_update_slice(
            ssd_s, sts["ssd"], (done, 0, 0, 0, 0))
        done += take
        if done < n:
            a, (k, v) = _attn_part(params["shared"], x, positions, cfg,
                                   pctx, window=None, return_kv=True)
            x = x + a
            f, _, _ = _ffn_part(params["shared"], x, cfg, pctx)
            x = x + f
            pad = ks[si].shape[1] - k.shape[1]
            if pad:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            ks[si] = k.astype(ks[si].dtype)
            vs[si] = v.astype(vs[si].dtype)
            si += 1
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"conv": conv_s, "ssd": ssd_s, "k": tuple(ks), "v": tuple(vs),
               "len": jnp.asarray(s, jnp.int32)}


def zamba2_decode_step(params, cfg, pctx, x, state):
    """One token through the hybrid stack."""
    from repro.models.transformer import _decode_attn, _ffn_part
    period = cfg.shared_attn_every
    n = cfg.n_layers
    cur = state["len"]
    conv_s, ssd_s = state["conv"], state["ssd"]
    ks, vs = list(state["k"]), list(state["v"])
    si = 0
    for li in range(n):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["mamba"])
        y, new_s = mamba2_block_decode(
            lp, x, {"conv": conv_s[li], "ssd": ssd_s[li]}, cfg, pctx)
        x = x + y
        conv_s = conv_s.at[li].set(new_s["conv"].astype(conv_s.dtype))
        ssd_s = ssd_s.at[li].set(new_s["ssd"].astype(ssd_s.dtype))
        if (li + 1) % period == 0 and li + 1 < n:
            a, ck, cv = _decode_attn(params["shared"], x, ks[si], vs[si],
                                     cur, cfg, pctx, window=None)
            x = x + a
            f, _, _ = _ffn_part(params["shared"], x, cfg, pctx)
            x = x + f
            ks[si], vs[si] = ck, cv
            si += 1
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"conv": conv_s, "ssd": ssd_s, "k": tuple(ks), "v": tuple(vs),
               "len": cur + 1}
