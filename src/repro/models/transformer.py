"""Decoder-only and encoder-decoder transformer stacks (dense + MoE).

Covers families: dense (starcoder2, minitron, mistral-nemo, gemma2,
qwen2-vl backbone), moe (dbrx, kimi-k2), encdec (seamless-m4t).

Structure:
  * layers are scanned (``lax.scan`` over stacked params [L, ...]) with an
    optional remat wrapper — HLO stays small for 40-81 layer models;
  * per-layer static variation (gemma2 local/global alternation) rides in
    scan xs as a traced window size;
  * MoE stacks keep ``first_k_dense`` leading layers unscanned;
  * decode carries stacked KV caches through the same scan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.parallel.context import ParallelContext, shard, shard_residual

BIG_WINDOW = 1 << 30


def _dims(cfg: ModelConfig) -> L.AttnDims:
    return L.AttnDims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)


def _remat(fn, pctx):
    if pctx is None or pctx.remat == "none":
        return fn
    if pctx.remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, *, moe: bool, cross: bool = False):
    ks = jax.random.split(key, 8)
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model),
        "attn": L.init_attention(ks[0], _dims(cfg)),
        "ln2": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.post_norm:
        p["pn1"] = L.init_rmsnorm(cfg.d_model)
        p["pn2"] = L.init_rmsnorm(cfg.d_model)
    if moe:
        p["moe"] = M.init_moe(ks[1], cfg.d_model, cfg.expert_d_ff,
                              cfg.num_experts)
        if cfg.n_shared_experts:
            p["shared_mlp"] = L.init_mlp(
                ks[2], cfg.d_model,
                cfg.expert_d_ff * cfg.n_shared_experts, cfg.mlp_gated)
    else:
        p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_gated)
    if cross:
        p["lnx"] = L.init_rmsnorm(cfg.d_model)
        p["xattn"] = L.init_attention(ks[4], _dims(cfg))
        if cfg.post_norm:
            p["pnx"] = L.init_rmsnorm(cfg.d_model)
    return p


def init_transformer(key, cfg: ModelConfig):
    keys = jax.random.split(key, 8)
    moe = cfg.is_moe
    n_scan = cfg.n_layers - (cfg.first_k_dense if moe else 0)
    layer_keys = jax.random.split(keys[0], n_scan)
    params = {
        "embed": L.init_embedding(keys[1], cfg.vocab, cfg.d_model),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "layers": jax.vmap(
            lambda k: _init_layer(k, cfg, moe=moe))(layer_keys),
    }
    if moe and cfg.first_k_dense:
        params["layers_prefix"] = [
            _init_layer(k, cfg, moe=False)
            for k in jax.random.split(keys[2], cfg.first_k_dense)]
    if not cfg.tie_embeddings:
        params["unembed"] = {"w": L.truncated_normal(
            keys[3], (cfg.d_model, cfg.vocab), cfg.d_model ** -0.5)}
    if cfg.family == "encdec":
        enc_keys = jax.random.split(keys[4], cfg.n_enc_layers)
        dec_keys = jax.random.split(keys[5], cfg.n_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, moe=False))(enc_keys)
        params["enc_norm"] = L.init_rmsnorm(cfg.d_model)
        params["layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, moe=False, cross=True))(dec_keys)
    return params


# ---------------------------------------------------------------------------
# per-layer window schedule (gemma2 alternation)
# ---------------------------------------------------------------------------

def window_schedule(cfg: ModelConfig, n_layers: int):
    """None if the arch has no windows; else [L] int32 (BIG = global)."""
    if cfg.window is None:
        return None
    if not cfg.local_global_alternating:
        return jnp.full((n_layers,), cfg.window, jnp.int32)
    return jnp.where(jnp.arange(n_layers) % 2 == 0, cfg.window,
                     BIG_WINDOW).astype(jnp.int32)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _attn_part(lp, x, positions, cfg, pctx, *, window, causal=True,
               return_kv=False):
    h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
    out = L.attention(
        lp["attn"], h, positions, _dims(cfg), pctx, causal=causal,
        window=window, softcap=cfg.attn_softcap,
        rope_theta=cfg.rope_theta, mrope=cfg.mrope_sections,
        return_kv=return_kv)
    kv = None
    if return_kv:
        out, kv = out
    if cfg.post_norm:
        out = L.rmsnorm(lp["pn1"], out, cfg.norm_eps)
    return (out, kv) if return_kv else out


def _cross_attention(p, x, enc_out, cfg, pctx):
    """Decoder cross-attention (no rope, no mask)."""
    b, s, d = x.shape
    dims = _dims(cfg)
    h, g, dh = dims.n_heads, dims.n_kv, dims.d_head
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(b, s, h, dh)
    k = (enc_out @ p["wk"].astype(dt)).reshape(b, -1, g, dh)
    v = (enc_out @ p["wv"].astype(dt)).reshape(b, -1, g, dh)
    o = L.flash_attention_jnp(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return o @ p["wo"].astype(dt)


def _ffn_part(lp, x, cfg, pctx):
    """(out, aux loss, dropped): ``dropped`` is the MoE layer's per-rank
    count of (token, expert) pairs that found no slot (``moe_ffn``), and
    None in a dense layer."""
    h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    dropped = None
    if "moe" in lp:
        out, aux, dropped = M.moe_ffn(lp["moe"], h, cfg, pctx)
        if "shared_mlp" in lp:
            out = out + L.mlp(lp["shared_mlp"], h, cfg.act, pctx)
    else:
        out = L.mlp(lp["mlp"], h, cfg.act, pctx)
    if cfg.post_norm:
        out = L.rmsnorm(lp["pn2"], out, cfg.norm_eps)
    return out, aux, dropped


def _split_tp_seq_gather(x, pctx: Optional[ParallelContext]):
    """SP -> TP boundary gather through the §3.1 split-TP AllGather.

    With sequence parallelism the residual enters the block S-sharded
    over the model axis; attention needs the full sequence back.  When
    the model axis is divided into ``tp_subgroups`` domains, that gather
    decomposes hierarchically: each domain reassembles its own sequence
    span via :func:`repro.models.layers.split_tp_allgather` — which
    consumes the bound ExecutionPlan's per-site decision (or the planner
    under "auto"); its multiwrite plans exploit the otherwise-idle
    cross-domain links — then ONE cross-domain gather of
    the domain-assembled chunks completes the sequence.  Bit-identical
    to the implicit single-stage GSPMD gather it replaces (the multidev
    suite pins transformer forward equality against ``tp_subgroups=1``).

    No-op (GSPMD keeps gathering implicitly) when there are no split-TP
    domains or the shapes don't tile the mesh.
    """
    if pctx is None or pctx.tp_subgroups <= 1 or not pctx.seq_parallel:
        return x
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.models import layers as L
    from jax import shard_map

    m = pctx.model_size
    nd = pctx.tp_subgroups
    b, s, d = x.shape
    dp = pctx.num_pods * pctx.data_size
    if m % nd or s % m or b % dp:
        return x
    h = m // nd                      # chips per TP domain
    axis = pctx.model_axis

    def gather(xl):                  # xl: [B/dp, S/m, D]
        frag = L.split_tp_allgather(xl, pctx)          # [h, B/dp, S/m, D]
        dom = jnp.moveaxis(frag, 0, 1).reshape(
            xl.shape[0], h * xl.shape[1], d)           # this domain's span
        groups = [[dd * h + i for dd in range(nd)] for i in range(h)]
        allg = lax.all_gather(dom, axis, axis_index_groups=groups)
        return jnp.moveaxis(allg, 0, 1).reshape(xl.shape[0], s, d)

    return shard_map(
        gather, mesh=pctx.mesh,
        in_specs=P(pctx.dp_axes, pctx.model_axis, None),
        out_specs=P(pctx.dp_axes, None, None),
        check_vma=False)(x)


def _dense_block(lp, x, positions, cfg, pctx, *, window):
    x = _split_tp_seq_gather(x, pctx)
    a = _attn_part(lp, x, positions, cfg, pctx, window=window)
    x = x + a
    f, aux, _ = _ffn_part(lp, x, cfg, pctx)
    x = x + f
    x = shard_residual(x, pctx)
    return x, aux


# ---------------------------------------------------------------------------
# forward (train) — scanned stack
# ---------------------------------------------------------------------------

def forward_hidden(params, cfg: ModelConfig, pctx, x, positions):
    """Run the (decoder) stack on hidden states x [B,S,D].  Returns
    (hidden, aux_loss_sum)."""
    n_scan = params["layers"]["ln1"]["w"].shape[0]
    wins = window_schedule(cfg, cfg.n_layers)
    aux_total = jnp.zeros((), jnp.float32)

    for lp in params.get("layers_prefix", []):
        x, aux = _dense_block(lp, x, positions, cfg, pctx,
                              window=None if wins is None else wins[0])
        aux_total += aux

    offset = cfg.first_k_dense if cfg.is_moe else 0

    def body(carry, xs):
        x, aux_sum = carry
        lp, win = xs
        blk = functools.partial(_dense_block, cfg=cfg, pctx=pctx)

        def inner(lp_, x_, win_):
            return blk(lp_, x_, positions, window=win_)

        inner = _remat(inner, pctx)
        x, aux = inner(lp, x, win)
        return (x, aux_sum + aux), None

    win_xs = (jnp.full((n_scan,), BIG_WINDOW, jnp.int32) if wins is None
              else wins[offset:])
    (x, aux_total), _ = jax.lax.scan(
        body, (x, aux_total), (params["layers"], win_xs))
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux_total


def logits_fn(params, cfg, x, last_only=False):
    if last_only:
        x = x[:, -1:]
    out_proj = params["unembed"]["w"] if "unembed" in params else None
    return L.unembed(params["embed"], x, out_proj, cfg.final_softcap)


# ---------------------------------------------------------------------------
# encoder (enc-dec only)
# ---------------------------------------------------------------------------

def encode(params, cfg, pctx, src_embeds):
    b, s, d = src_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = src_embeds

    def body(carry, lp):
        def inner(lp_, x_):
            a = _attn_part(lp_, x_, positions, cfg, pctx, window=None,
                           causal=False)
            x_ = x_ + a
            f, _, _ = _ffn_part(lp_, x_, cfg, pctx)
            return x_ + f

        return _remat(inner, pctx)(lp, carry), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward_hidden_encdec(params, cfg, pctx, tgt_embeds, positions, enc_out):
    def body(carry, lp):
        def inner(lp_, x_):
            a = _attn_part(lp_, x_, positions, cfg, pctx, window=None)
            x_ = x_ + a
            xa = _cross_attention(lp_["xattn"],
                                  L.rmsnorm(lp_["lnx"], x_, cfg.norm_eps),
                                  enc_out, cfg, pctx)
            if cfg.post_norm:
                xa = L.rmsnorm(lp_["pnx"], xa, cfg.norm_eps)
            x_ = x_ + xa
            f, _, _ = _ffn_part(lp_, x_, cfg, pctx)
            return x_ + f

        return _remat(inner, pctx)(lp, carry), None

    x, _ = jax.lax.scan(body, tgt_embeds, params["layers"])
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# prefill / decode (KV caches)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """KV caches are PER-LAYER tuples (not a stacked [L, ...] array): each
    layer's buffer is updated in place by an unrolled decode step — the
    production serving layout (stacked caches carried through a layer loop
    force XLA loop-carry copies of the full cache every step)."""
    g, dh = cfg.n_kv_heads, cfg.head_dim
    n = cfg.n_layers
    return {
        "k": tuple(jnp.zeros((batch, max_len, g, dh), dtype)
                   for _ in range(n)),
        "v": tuple(jnp.zeros((batch, max_len, g, dh), dtype)
                   for _ in range(n)),
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(params, cfg, pctx, x, positions, cache):
    """Forward pass that also fills the cache.  Decoder-only families."""
    wins = window_schedule(cfg, cfg.n_layers)
    seq = x.shape[1]
    cdt = cache["k"][0].dtype
    max_len = cache["k"][0].shape[1]
    new_k, new_v = [], []

    idx = 0
    for lp in params.get("layers_prefix", []):
        a, (k, v) = _attn_part(lp, x, positions, cfg, pctx,
                               window=None if wins is None else wins[idx],
                               return_kv=True)
        x = x + a
        f, _, _ = _ffn_part(lp, x, cfg, pctx)
        x = x + f
        new_k.append(jax.lax.dynamic_update_slice(
            cache["k"][idx], k.astype(cdt), (0, 0, 0, 0)))
        new_v.append(jax.lax.dynamic_update_slice(
            cache["v"][idx], v.astype(cdt), (0, 0, 0, 0)))
        idx += 1

    offset = cfg.first_k_dense if cfg.is_moe else 0
    n_scan = cfg.n_layers - offset

    def body(x, xs):
        lp, win = xs
        a, (k, v) = _attn_part(lp, x, positions, cfg, pctx, window=win,
                               return_kv=True)
        x = x + a
        f, _, dropped = _ffn_part(lp, x, cfg, pctx)
        return x + f, (k.astype(cdt), v.astype(cdt), dropped)

    win_xs = (jnp.full((n_scan,), BIG_WINDOW, jnp.int32) if wins is None
              else wins[offset:])
    x, (ks, vs, dropped) = jax.lax.scan(body, x, (params["layers"], win_xs))
    pad = max_len - seq
    for li in range(n_scan):
        k_full = (jnp.pad(ks[li], ((0, 0), (0, pad), (0, 0), (0, 0)))
                  if pad else ks[li])
        v_full = (jnp.pad(vs[li], ((0, 0), (0, pad), (0, 0), (0, 0)))
                  if pad else vs[li])
        new_k.append(k_full)
        new_v.append(v_full)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x, last_only=True)
    cache = {"k": tuple(new_k), "v": tuple(new_v),
             "len": jnp.asarray(seq, jnp.int32)}
    return logits, cache, _step_stats(
        None if dropped is None else jnp.sum(dropped, axis=0))


def _step_stats(dropped) -> dict:
    """The device counters a prefill or decode step returns beside its
    logits and cache: ``moe_dropped``, the MoE layers' summed per-rank
    counts of (token, expert) pairs without a slot, where the stack has
    MoE layers; empty (no program output) for a dense stack."""
    return {} if dropped is None else {"moe_dropped": dropped}


def decode_step(params, cfg, pctx, x, cache):
    """One decode token, UNROLLED over layers with per-layer cache buffers
    updated in place (donated) — the production serving structure.
    x: [B, 1, D] hidden input; returns (logits, cache)."""
    wins = window_schedule(cfg, cfg.n_layers)
    cur = cache["len"]
    new_k = list(cache["k"])
    new_v = list(cache["v"])

    idx = 0
    for lp in params.get("layers_prefix", []):
        a, ck, cv = _decode_attn(lp, x, new_k[idx], new_v[idx], cur, cfg,
                                 pctx,
                                 window=None if wins is None else wins[idx])
        x = x + a
        f, _, _ = _ffn_part(lp, x, cfg, pctx)
        x = x + f
        new_k[idx], new_v[idx] = ck, cv
        idx += 1

    offset = cfg.first_k_dense if cfg.is_moe else 0
    n_scan = cfg.n_layers - offset
    dropped = None
    for li in range(n_scan):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        win = None if wins is None else wins[offset + li]
        a, ck, cv = _decode_attn(lp, x, new_k[offset + li],
                                 new_v[offset + li], cur, cfg, pctx,
                                 window=win)
        x = x + a
        f, _, d = _ffn_part(lp, x, cfg, pctx)
        if d is not None:
            dropped = d if dropped is None else dropped + d
        x = x + f
        new_k[offset + li], new_v[offset + li] = ck, cv
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x, last_only=True)
    return (logits, {"k": tuple(new_k), "v": tuple(new_v), "len": cur + 1},
            _step_stats(dropped))


def _decode_attn(lp, x, ck, cv, cur, cfg, pctx, *, window):
    h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
    out, ck, cv = L.decode_attention_block(
        lp["attn"], h, ck, cv, cur, _dims(cfg), pctx, window=window,
        softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
        mrope=cfg.mrope_sections)
    if cfg.post_norm:
        out = L.rmsnorm(lp["pn1"], out, cfg.norm_eps)
    return out, ck, cv


# ---------------------------------------------------------------------------
# enc-dec serving
# ---------------------------------------------------------------------------

def prefill_encdec(params, cfg, pctx, src_embeds, tgt_embeds, positions,
                   cache):
    """Encode the source once, run the decoder prefix, fill the decoder
    self-attn cache (per-layer tuples) and stash encoder states."""
    enc_out = encode(params, cfg, pctx, src_embeds)
    seq = tgt_embeds.shape[1]
    cdt = cache["k"][0].dtype
    max_len = cache["k"][0].shape[1]

    def body(x, lp):
        a, (k, v) = _attn_part(lp, x, positions, cfg, pctx, window=None,
                               return_kv=True)
        x = x + a
        xa = _cross_attention(lp["xattn"],
                              L.rmsnorm(lp["lnx"], x, cfg.norm_eps),
                              enc_out, cfg, pctx)
        if cfg.post_norm:
            xa = L.rmsnorm(lp["pnx"], xa, cfg.norm_eps)
        x = x + xa
        f, _, _ = _ffn_part(lp, x, cfg, pctx)
        return x + f, (k.astype(cdt), v.astype(cdt))

    x, (ks, vs) = jax.lax.scan(body, tgt_embeds, params["layers"])
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x, last_only=True)
    pad = max_len - seq
    new_k, new_v = [], []
    for li in range(cfg.n_layers):
        kf = (jnp.pad(ks[li], ((0, 0), (0, pad), (0, 0), (0, 0)))
              if pad else ks[li])
        vf = (jnp.pad(vs[li], ((0, 0), (0, pad), (0, 0), (0, 0)))
              if pad else vs[li])
        new_k.append(kf)
        new_v.append(vf)
    enc_full = enc_out.astype(cache["enc_out"].dtype)
    if cache["enc_out"].shape[1] > enc_full.shape[1]:
        enc_full = jnp.pad(
            enc_full, ((0, 0),
                       (0, cache["enc_out"].shape[1] - enc_full.shape[1]),
                       (0, 0)))
    return logits, {"k": tuple(new_k), "v": tuple(new_v),
                    "len": jnp.asarray(seq, jnp.int32),
                    "enc_out": enc_full}


def decode_step_encdec(params, cfg, pctx, x, cache):
    cur = cache["len"]
    enc_out = cache["enc_out"]
    new_k = list(cache["k"])
    new_v = list(cache["v"])
    for li in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        a, ck, cv = _decode_attn(lp, x, new_k[li], new_v[li], cur, cfg,
                                 pctx, window=None)
        x = x + a
        xa = _cross_attention(lp["xattn"],
                              L.rmsnorm(lp["lnx"], x, cfg.norm_eps),
                              enc_out, cfg, pctx)
        if cfg.post_norm:
            xa = L.rmsnorm(lp["pnx"], xa, cfg.norm_eps)
        x = x + xa
        f, _, _ = _ffn_part(lp, x, cfg, pctx)
        x = x + f
        new_k[li], new_v[li] = ck, cv
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, x, last_only=True)
    return logits, {"k": tuple(new_k), "v": tuple(new_v), "len": cur + 1,
                    "enc_out": enc_out}
