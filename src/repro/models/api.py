"""Unified model API over all architecture families.

``build_model(cfg, pctx)`` returns a :class:`Model` whose members are pure
functions (jit-able, shardable):

  init(rng)                      -> params
  loss(params, batch)            -> (scalar loss, metrics)
  logits(params, batch)          -> full-sequence logits [B, S, V] (no
                                    cache: the reference serving is
                                    checked against)
  prefill(params, batch, cache)  -> (next-token logits [B, V], cache,
                                     stats)
  decode(params, batch, cache)   -> (logits [B, V], cache, stats)
                                    (stats: a dict of device counters,
                                    {} for a stack without MoE layers,
                                    so its program has no extra output;
                                    MoE stacks give "moe_dropped",
                                    per-rank counts [R] int32 of the
                                    (token, expert) pairs the dispatch
                                    dropped, summed over layers)
  init_cache(batch, max_len)     -> cache pytree (zeros; dry-run uses
                                    eval_shape on this)
  moe_plan(n_tokens)             -> the MoEPlan each MoE layer runs for
                                    a call of n_tokens token rows (None
                                    for a model without MoE layers)

Batch formats (kind -> keys):
  tokens     {"tokens" [B,S] i32, "labels" [B,S] i32}
  embeddings {"embeds" [B,S,D], "positions" [B,S,3] i32, "labels" [B,S]}
             (qwen2-vl stub frontend)
  encdec     {"src_embeds" [B,S,D], "tgt_tokens" [B,S], "labels" [B,S]}
             (seamless stub frontend)
  decode     {"tokens" [B,1]} (or {"embeds" [B,1,D]} for qwen2-vl)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rwkv, ssm, transformer as T
from repro.parallel.context import ParallelContext

Params = Any
Batch = dict
Cache = dict


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    pctx: Optional[ParallelContext]
    init: Callable
    loss: Callable
    logits: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable
    moe_plan: Callable = lambda n_tokens: None


def _positions_for(cfg, b, s):
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


def build_model(cfg: ModelConfig, pctx: Optional[ParallelContext] = None,
                *, use_kernels: bool = False,
                dtype=jnp.bfloat16) -> Model:
    fam = cfg.family

    # ---- init ---------------------------------------------------------------
    if fam in ("dense", "moe", "encdec"):
        init = lambda key: T.init_transformer(key, cfg)      # noqa: E731
    elif fam == "hybrid":
        init = lambda key: ssm.init_zamba2(key, cfg)         # noqa: E731
    elif fam == "rwkv":
        init = lambda key: rwkv.init_rwkv6(key, cfg)         # noqa: E731
    else:
        raise ValueError(fam)

    # ---- embedding of inputs --------------------------------------------------
    def embed_in(params, batch):
        if cfg.input_mode == "embeddings" and "embeds" in batch:
            x = batch["embeds"].astype(dtype)
            pos = batch.get(
                "positions",
                _positions_for(cfg, x.shape[0], x.shape[1]))
            return x, pos
        toks = batch["tokens"]
        x = L.embed(params["embed"], toks, dtype)
        if cfg.tie_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)  # gemma-style
        return x, _positions_for(cfg, toks.shape[0], toks.shape[1])

    # ---- hidden-stack dispatch -------------------------------------------------
    def hidden_train(params, batch):
        if fam == "encdec":
            enc_out = T.encode(params, cfg, pctx,
                               batch["src_embeds"].astype(dtype))
            tgt = L.embed(params["embed"], batch["tgt_tokens"], dtype)
            b, s = batch["tgt_tokens"].shape
            pos = _positions_for(cfg, b, s)
            h = T.forward_hidden_encdec(params, cfg, pctx, tgt, pos, enc_out)
            return h, jnp.zeros((), jnp.float32)
        x, pos = embed_in(params, batch)
        if fam == "hybrid":
            return ssm.zamba2_hidden(params, cfg, pctx, x,
                                     use_pallas=use_kernels)
        if fam == "rwkv":
            return rwkv.rwkv6_hidden(params, cfg, pctx, x,
                                     use_pallas=use_kernels)
        return T.forward_hidden(params, cfg, pctx, x, pos)

    # ---- loss -------------------------------------------------------------------
    def loss(params, batch):
        h, aux = hidden_train(params, batch)
        if "unembed" in params:
            w, tied = params["unembed"]["w"], False
        else:
            w, tied = params["embed"]["emb"], True
        ce = L.chunked_cross_entropy(h, w, batch["labels"], tied=tied,
                                     final_softcap=cfg.final_softcap)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    def logits(params, batch):
        h, _ = hidden_train(params, batch)
        return T.logits_fn(params, cfg, h)

    # ---- caches -------------------------------------------------------------------
    def init_cache(batch, max_len, cache_dtype=jnp.bfloat16):
        if fam == "hybrid":
            return ssm.zamba2_init_state(cfg, batch, max_len, cache_dtype)
        if fam == "rwkv":
            return rwkv.rwkv6_init_state(cfg, batch, cache_dtype)
        if fam == "encdec":
            g, dh = cfg.n_kv_heads, cfg.head_dim
            return {
                "k": tuple(jnp.zeros((batch, max_len, g, dh), cache_dtype)
                           for _ in range(cfg.n_layers)),
                "v": tuple(jnp.zeros((batch, max_len, g, dh), cache_dtype)
                           for _ in range(cfg.n_layers)),
                "len": jnp.zeros((), jnp.int32),
                "enc_out": jnp.zeros((batch, max_len, cfg.d_model),
                                     cache_dtype),
            }
        return T.init_cache(cfg, batch, max_len, cache_dtype)

    # ---- prefill ---------------------------------------------------------------------
    def next_logits(params, h):
        return T.logits_fn(params, cfg, h, last_only=True)[:, 0]

    def prefill(params, batch, cache):
        if fam == "encdec":
            tgt = L.embed(params["embed"], batch["tgt_tokens"], dtype)
            b, s = batch["tgt_tokens"].shape
            pos = _positions_for(cfg, b, s)
            logits, cache = T.prefill_encdec(
                params, cfg, pctx, batch["src_embeds"].astype(dtype), tgt,
                pos, cache)
            return logits[:, 0], cache, {}
        x, pos = embed_in(params, batch)
        if fam == "hybrid":
            h, cache = ssm.zamba2_prefill(params, cfg, pctx, x, cache)
            return next_logits(params, h), cache, {}
        if fam == "rwkv":
            h, cache = rwkv.rwkv6_prefill(params, cfg, pctx, x, cache)
            return next_logits(params, h), cache, {}
        logits, cache, stats = T.prefill(params, cfg, pctx, x, pos, cache)
        return logits[:, 0], cache, stats

    # ---- decode ----------------------------------------------------------------------
    def decode(params, batch, cache):
        if cfg.input_mode == "embeddings" and "embeds" in batch:
            x = batch["embeds"].astype(dtype)
        else:
            x = L.embed(params["embed"], batch["tokens"], dtype)
            if cfg.tie_embeddings:
                x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
        if fam == "encdec":
            logits, cache = T.decode_step_encdec(params, cfg, pctx, x, cache)
            return logits[:, 0], cache, {}
        if fam == "hybrid":
            h, cache = ssm.zamba2_decode_step(params, cfg, pctx, x, cache)
            return next_logits(params, h), cache, {}
        if fam == "rwkv":
            h, cache = rwkv.rwkv6_decode_step(params, cfg, pctx, x, cache)
            return next_logits(params, h), cache, {}
        logits, cache, stats = T.decode_step(params, cfg, pctx, x, cache)
        return logits[:, 0], cache, stats

    def moe_plan(n_tokens):
        if not cfg.is_moe:
            return None
        return M.plan_moe(cfg, pctx, n_tokens, cfg.d_model, cfg.expert_d_ff,
                          jnp.dtype(dtype).itemsize)

    return Model(cfg=cfg, pctx=pctx, init=init, loss=loss, logits=logits,
                 prefill=prefill, decode=decode, init_cache=init_cache,
                 moe_plan=moe_plan)


# ---------------------------------------------------------------------------
# synthetic batch builders (smoke tests + data pipeline + dry-run specs)
# ---------------------------------------------------------------------------

def make_batch(cfg: ModelConfig, kind: str, batch: int, seq: int,
               rng_seed: int = 0):
    """Concrete synthetic batch (smoke tests / examples)."""
    import numpy as np
    rng = np.random.default_rng(rng_seed)
    toks = rng.integers(0, cfg.vocab, size=(batch, seq)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1).astype(np.int32)
    labels[:, -1] = -1
    if kind == "decode":
        if cfg.input_mode == "embeddings" and cfg.family != "encdec":
            return {"embeds": jnp.asarray(
                rng.normal(size=(batch, 1, cfg.d_model)).astype(np.float32))}
        return {"tokens": jnp.asarray(toks[:, :1])}
    if cfg.family == "encdec":
        emb = rng.normal(size=(batch, seq, cfg.d_model)).astype(np.float32)
        return {"src_embeds": jnp.asarray(emb),
                "tgt_tokens": jnp.asarray(toks),
                "labels": jnp.asarray(labels)}
    if cfg.input_mode == "embeddings":
        emb = rng.normal(size=(batch, seq, cfg.d_model)).astype(np.float32)
        pos = np.broadcast_to(np.arange(seq, dtype=np.int32)[None, :, None],
                              (batch, seq, 3)).copy()
        return {"embeds": jnp.asarray(emb), "positions": jnp.asarray(pos),
                "labels": jnp.asarray(labels)}
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def param_count_shape_only(cfg: ModelConfig) -> int:
    """Parameter count WITHOUT allocation (eval_shape on init)."""
    import math
    shapes = jax.eval_shape(
        lambda k: build_model(cfg).init(k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(shapes))