"""Pallas TPU kernel: RWKV-6 ("Finch") chunked time-mix scan.

rwkv6-7b's compute hot spot.  Per head, with data-dependent per-channel
decay w_t in (0,1) and bonus u:

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T                  (S: [dk, dv])

Chunked evaluation: per-channel cumulative decays P_t = prod_{m<=t} w_m
turn the intra-chunk sum into a strictly-lower-triangular [Q, Q] matmul of
scaled r~ = r * P_{t-1} and k~ = k / P_t vectors (plus the diag(u) bonus
term), and the state is carried in VMEM scratch across chunks — same grid
structure as the mamba2 kernel.

Numerics: P ratios are formed in log space; the chunk length bounds the
log-range (default 32) so k/P stays in f32 range for realistic decays.

Oracle: :func:`repro.kernels.ref.rwkv6_ref` (per-step lax.scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, h_ref, *,
                  chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    r = r_ref[0].astype(jnp.float32)          # [Q, dk]
    k = k_ref[0].astype(jnp.float32)          # [Q, dk]
    v = v_ref[0].astype(jnp.float32)          # [Q, dv]
    logw = w_ref[0].astype(jnp.float32)       # [Q, dk] log decays (<= 0)
    u = u_ref[0].astype(jnp.float32)          # [1, dk]
    dk = logw.shape[1]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive log P_t as a triangular matmul (Mosaic has no cumsum)
    cum = jax.lax.dot_general(
        (ii >= jj).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)   # [Q, dk]
    cum_prev = cum - logw                     # log P_{t-1} (P_{-1} = 1)
    r_s = r * jnp.exp(cum_prev)               # r~
    k_s = k * jnp.exp(-cum)                   # k~
    # strictly lower triangular intra-chunk attention + bonus diagonal
    att = jax.lax.dot_general(r_s, k_s, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Q, Q]
    att = jnp.where(ii > jj, att, 0.0)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)    # [Q, 1]
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y += bonus * v
    # state contribution
    h_prev = h_ref[...]                       # [dk, dv]
    y += jax.lax.dot_general(r_s, h_prev, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)
    # state update: S = diag(P_last) h_prev + sum_j (P_last / P_j) k_j v_j^T
    total = jnp.sum(logw, axis=0, keepdims=True)         # [1, dk] log P_last
    di = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    dj = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    total_col = jax.lax.dot_general(                     # [dk, 1]
        (di == dj).astype(jnp.float32), total, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    k_up = k * jnp.exp(total - cum)           # [Q, dk]
    h_ref[...] = jnp.exp(total_col) * h_prev + jax.lax.dot_general(
        k_up, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
               u: jax.Array, *, chunk: int = 32,
               interpret: bool) -> jax.Array:
    """Chunked RWKV6 time-mix.

    Args:
      r, k: [BH, S, dk]; v: [BH, S, dv].
      logw: [BH, S, dk] log decays (<= 0; w = exp(logw)).
      u:    [BH, dk] bonus.
      chunk: chunk length Q.
      interpret: run the kernel body in interpret mode (CPU validation).

    Returns: y [BH, S, dv] in v.dtype.
    """
    bh, s, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0)))
    sp = r.shape[1]
    nc = sp // chunk
    out = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dv), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            # u as one [1, dk] row per head: a block equal to the array's
            # last two dims, which the TPU lowering accepts for any dk
            pl.BlockSpec((1, 1, dk), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u.reshape(bh, 1, dk))
    return out[:, :s]
