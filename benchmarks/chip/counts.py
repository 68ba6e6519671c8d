"""Operations and bytes of a model call, from the configuration's shapes.

Computed from the configuration file alone, never from the program, so
that every implementation is charged the same work.  A FLOP is one
multiply or one add (a multiply-add is 2).  Only the work the model's
mathematics needs counts: matrix products of every layer, attention
scores and values over the causal context, the output projection at the
positions whose logits are used, and no padding, recomputation or
finished rows.  Bytes are bf16 (2 per element) and are what one chip
must read from HBM at least, under the configuration's deployment:
attention and tables replicated, experts and batch rows divided over
the chips.
"""

from __future__ import annotations

import dataclasses
import math

BYTES = 2   # bf16 weights, activations and KV cache


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int                # dense FFN width, or one expert's width
    vocab: int
    experts: int            # 0: dense
    top_k: int
    tied: bool              # output projection is the embedding table
    token_table: bool       # input ids are looked up in the table
    chips: int


def shape_of(conf: dict) -> Shape:
    """The sizes of a configuration file, under either key convention
    (Qwen2/Llama style, or DBRX's).  ``token_table`` is the file's own
    key: false where a token id enters through a stub frontend (a hash
    embedding) rather than as a row of the embedding table."""
    if "d_model" in conf:                                   # DBRX style
        ffn = conf["ffn_config"]
        d, heads = conf["d_model"], conf["n_heads"]
        return Shape(d=d, layers=conf["n_layers"], heads=heads,
                     kv_heads=conf["attn_config"]["kv_n_heads"],
                     head_dim=d // heads, ffn=ffn["ffn_hidden_size"],
                     vocab=conf["vocab_size"],
                     experts=ffn["moe_num_experts"], top_k=ffn["moe_top_k"],
                     tied=conf["tie_word_embeddings"],
                     token_table=conf["token_table"],
                     chips=conf["chips"])
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return Shape(d=d, layers=conf["num_hidden_layers"], heads=heads,
                 kv_heads=conf["num_key_value_heads"],
                 head_dim=conf.get("head_dim", d // heads),
                 ffn=conf["intermediate_size"], vocab=conf["vocab_size"],
                 experts=0, top_k=0, tied=conf["tie_word_embeddings"],
                 token_table=conf["token_table"],
                 chips=conf["chips"])


def attn_params(s: Shape) -> int:
    return s.d * s.head_dim * (2 * s.heads + 2 * s.kv_heads)


def ffn_params(s: Shape) -> int:
    """All FFN parameters of one layer (every expert and the router)."""
    if s.experts:
        return s.experts * 3 * s.d * s.ffn + s.d * s.experts
    return 3 * s.d * s.ffn


def param_count(s: Shape) -> int:
    """Parameters of the whole model: layers (norms included), final
    norm, embedding table and, when untied, the output projection."""
    per_layer = attn_params(s) + ffn_params(s) + 2 * s.d
    table = s.vocab * s.d
    return s.layers * per_layer + s.d + table * (1 if s.tied else 2)


def layer_matmul_flops(s: Shape) -> int:
    """Matrix-product FLOPs of one token through one layer, without the
    attention over the context."""
    ffn = (s.top_k * 3 * s.d * s.ffn + s.d * s.experts if s.experts
           else 3 * s.d * s.ffn)
    return 2 * (attn_params(s) + ffn)


def attention_flops(s: Shape, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys,
    all layers."""
    return s.layers * 4 * s.heads * s.head_dim * context


def logits_flops(s: Shape) -> int:
    return 2 * s.d * s.vocab


def prefill_flops(s: Shape, batch: int, prompt_len: int) -> int:
    """One prefill of ``batch`` prompts: every prompt token through every
    layer over its causal context, and the logits of the last token."""
    causal = prompt_len * (prompt_len + 1) // 2
    return batch * (prompt_len * s.layers * layer_matmul_flops(s)
                    + attention_flops(s, causal) + logits_flops(s))


def decode_token_flops(s: Shape, context: int) -> int:
    """One output token at a cache of ``context`` positions (itself
    included)."""
    return (s.layers * layer_matmul_flops(s) + attention_flops(s, context)
            + logits_flops(s))


def decode_bytes_per_chip(s: Shape, batch: int, contexts) -> int:
    """HBM bytes one chip must read for one decode step of ``batch`` rows
    whose caches hold ``contexts`` positions each: its weights (its
    share of the experts, everything else whole), the rows of the table
    it looks up, and the KV cache of its share of the rows."""
    experts_here = math.ceil(s.experts / s.chips) if s.experts else 0
    ffn = (experts_here * 3 * s.d * s.ffn + s.d * s.experts if s.experts
           else 3 * s.d * s.ffn)
    weights = s.layers * (attn_params(s) + ffn + 2 * s.d) + s.d
    weights += s.vocab * s.d                       # the output projection
    rows_here = math.ceil(batch / s.chips)
    gathered = rows_here * s.d if s.token_table else 0
    positions = sum(contexts) * rows_here / batch
    kv = s.layers * 2 * s.kv_heads * s.head_dim * positions
    return int(BYTES * (weights + gathered + kv))
