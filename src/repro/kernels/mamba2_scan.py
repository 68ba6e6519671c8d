"""Pallas TPU kernel: Mamba2 (SSD) chunked selective-state-space scan.

zamba2-7b's compute hot spot.  The recurrence per head

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T        (h: [ds, dh])
    y_t = C_t^T h_t + D * x_t

is evaluated chunk-parallel (the SSD formulation): within a chunk of Q
steps the contribution is a masked [Q, Q] matmul (MXU work), and the
[ds, dh] state is carried across chunks in VMEM scratch — the kernel grid
is (batch*heads, num_chunks) with chunks innermost, so the state scratch
persists across the sequential chunk dimension.

Cumulative decays are computed in log space (dt*A <= 0) for stability.

Oracle: :func:`repro.kernels.ref.mamba2_ref` (per-step lax.scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba2_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref,
                   h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # [Q, dh]
    dt = dt_ref[0, 0].astype(jnp.float32)     # [1, Q] step sizes as a row
    a = a_ref[0]                              # [1, 1] A (negative)
    b = b_ref[0].astype(jnp.float32)          # [Q, ds]
    c = c_ref[0].astype(jnp.float32)          # [Q, ds]
    d = d_ref[0]                              # [1, 1] skip

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = (ii >= jj).astype(jnp.float32)
    log_a = dt * a                            # [1, Q] log decay per step (<=0)
    # inclusive prefix sums as triangular matmuls, in both orientations
    # (Mosaic has no cumsum and no vector transpose of a [1, Q] row)
    cum_col = _nt(lower, log_a)               # [Q, 1]
    cum_row = _nt(log_a, lower)               # [1, Q]
    dt_col = _nt((ii == jj).astype(jnp.float32), dt)     # [Q, 1]
    # intra-chunk: M[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j<=i
    s = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    decay = jnp.where(ii >= jj, jnp.exp(cum_col - cum_row), 0.0)
    m = s * decay * dt
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, dh]
    # inter-chunk: y += exp(cum_i) * C_i^T h_prev
    h_prev = h_ref[...]                       # [ds, dh]
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        c, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (y + d * x).astype(o_ref.dtype)
    # state update: h = exp(cum_Q) h_prev + sum_j exp(cum_Q - cum_j) dt_j B_j x_j^T
    total = jnp.sum(log_a, axis=1, keepdims=True)        # [1, 1] == cum_Q
    w = jnp.exp(total - cum_col) * dt_col     # [Q, 1]
    h_ref[...] = jnp.exp(total) * h_prev + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _nt(lhs, rhs):
    """``lhs @ rhs.T`` in full f32 precision."""
    return jax.lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def mamba2_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, *, chunk: int = 64,
                interpret: bool) -> jax.Array:
    """Chunked SSD scan.

    Args:
      x:  [BH, S, dh] inputs per head.
      dt: [BH, S] step sizes (post-softplus, > 0).
      a:  [BH] per-head A (negative).
      b:  [BH, S, ds] input projections.
      c:  [BH, S, ds] output projections.
      d:  [BH] skip coefficients.
      chunk: chunk length Q (sequence must pad to a multiple).
      interpret: run the kernel body in interpret mode (CPU validation).

    Returns: y [BH, S, dh] in x.dtype.
    """
    bh, s, dh = x.shape
    ds = b.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk
    # dt travels as one [1, Q] row per (head, chunk) and a/d as [1, 1]
    # per head: blocks whose last two dims equal the array's, which the
    # TPU lowering accepts for any Q (a (1, Q) block over [BH, S] is not)
    out = pl.pallas_call(
        functools.partial(_mamba2_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, ds), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(bh, nc, 1, chunk), a.reshape(bh, 1, 1).astype(jnp.float32),
      b, c, d.reshape(bh, 1, 1).astype(jnp.float32))
    return out[:, :s]
