"""Pallas TPU kernel: FlashAttention-2 style fused attention.

The dense-transformer compute hot spot.  Online-softmax tiling over the KV
sequence with q/k/v blocks staged through VMEM; supports

  * causal masking,
  * sliding-window attention (gemma2 local layers),
  * logit soft-capping (gemma2),
  * GQA (kv heads broadcast outside the kernel — the kernel sees matched
    head counts).

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost so the running
(max, sum, acc) state for one q block lives in VMEM scratch across kv
steps.  Block sizes default to MXU-aligned (128) tiles.

Oracle: :func:`repro.kernels.ref.attention_ref` (pure jnp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 softcap: float | None, block_q: int, block_k: int,
                 num_kv_blocks: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [Bq, D]
    k = k_ref[0].astype(jnp.float32)                    # [Bk, D]
    v = v_ref[0].astype(jnp.float32)                    # [Bk, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < kv_len          # padded keys never attend
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # [Bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                              # [Bq, Bk]
    corr = jnp.exp(m_prev - m_new)                      # [Bq, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = jnp.where(
            l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """Fused attention.  q/k/v: [BH, S, D] (matched heads; GQA broadcast is
    the caller's job).  Returns [BH, S, D] in q's dtype.  ``interpret``
    runs the kernel body in interpret mode (CPU validation)."""
    bh, s_len, d = q.shape
    assert k.shape == v.shape == (bh, k.shape[1], d)
    kv_len = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, s_len)
    block_k = min(block_k, kv_len)
    pad_q = (-s_len) % block_q
    pad_k = (-kv_len) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    sq, sk = q.shape[1], k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_kv_blocks=nk,
        kv_len=kv_len)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :s_len]
    return out
