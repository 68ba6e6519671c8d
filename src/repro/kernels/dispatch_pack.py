"""Pallas TPU kernel: bitmap-driven dispatch packing (paper cs_send/cs_relay).

This is the compute hot spot of the MultiWrite data plane: given N token
rows and a per-row destination bitmap (the §4.1 in-packet metadata), pack
each row into the send buffer of every destination whose bit is set —
ONE buffer slot per (row, destination), capacity-bounded, token-order
priority.  The same kernel serves

  * the source node's send-buffer build (cs_send: stage-1 pod packing),
  * the relay's replicate-and-forward step (cs_relay: stage-2 ep packing),
  * local per-expert grouping (stage 3),

because the recursive execution model (§4.3.3) runs the *same logic at
every node*.

TPU adaptation: rather than a byte-stream packet copy loop (AICPU), the
kernel is tiled for VMEM — the grid is (num_dests, column_blocks,
row_blocks); each program scans a [block_rows, H_blk] tile resident in
VMEM, tests its destination bit, and appends matching rows to the
destination's output tile with a running counter in SMEM.  The bitmap
rides in SMEM, because each row's test steers a scalar branch and a
dynamic store offset; rows move as 32-bit words.  H should be
lane-aligned (multiples of 128) for production shapes.

Validated against :func:`repro.kernels.ref.pack_ref` (== the jnp
implementation used by core/collectives.py) in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(live_ref, tok_ref, out_ref, idx_ref, count_ref, *,
                 capacity: int, block_rows: int):
    d = pl.program_id(0)
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        count_ref[0] = 0

    # per-row decisions are scalar work: the bitmap (already masked by
    # validity) sits in SMEM and the running slot counter is a scalar
    pos = count_ref[0]
    for i in range(block_rows):                         # static unroll
        row = nb * block_rows + i
        ok = (live_ref[row] >> d) & 1

        @pl.when((ok == 1) & (pos < capacity))
        def _store(i=i, row=row, pos=pos):
            out_ref[0, pl.ds(pos, 1), :] = tok_ref[0, pl.ds(i, 1), :]
            idx_ref[0, pl.ds(pos, 1), :] = jnp.full((1, 1), row, jnp.int32)

        pos = pos + ok
    count_ref[0] = pos


def _word_block(hw: int, capacity: int, budget: int = 4 << 20) -> int:
    """Width (in words) of one column block of the [C, HW] output tile:
    the largest halving of ``hw`` that keeps the tile within ``budget``
    bytes and lane-aligned, so double-buffered tiles fit scoped VMEM."""
    blk = hw
    while capacity * blk * 4 > budget and blk % 256 == 0:
        blk //= 2
    return blk


def _as_words(x):
    """[N, H] rows of a <=32-bit dtype -> [N, ceil(H*itemsize/4)] uint32.

    The kernel moves rows in 32-bit words: a one-row store at a dynamic
    offset is legal on the TPU only for unpacked (32-bit) tiles."""
    n, h = x.shape
    per = 4 // x.dtype.itemsize
    if per == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    pad = (-h) % per
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return jax.lax.bitcast_convert_type(
        x.reshape(n, (h + pad) // per, per), jnp.uint32)


def _from_words(w, dtype, h):
    """Inverse of :func:`_as_words` on [..., HW] uint32 words."""
    out = jax.lax.bitcast_convert_type(w, dtype)
    if out.ndim > w.ndim:
        out = out.reshape(*w.shape[:-1], -1)
    return out[..., :h]


@functools.partial(jax.jit,
                   static_argnames=("num_dests", "capacity", "block_rows",
                                    "interpret"))
def dispatch_pack(tokens: jax.Array, bitmap: jax.Array, valid: jax.Array,
                  *, num_dests: int, capacity: int, block_rows: int = 8,
                  interpret: bool):
    """Pack rows into per-destination buffers (Pallas).

    Args:
      tokens: [N, H] rows.
      bitmap: [N] int32 destination bitmap (bit d => destination d).
      valid:  [N] bool.
      num_dests: D <= 31.
      capacity: C slots per destination.
      block_rows: VMEM row-tile size.
      interpret: run the kernel body in interpret mode (CPU validation).

    Returns:
      (out [D, C, H], src_idx [D, C] int32 with -1 for empty slots).
    """
    n, h = tokens.shape
    assert num_dests <= 31
    pad = (-n) % block_rows
    if pad:
        tokens = jnp.concatenate(
            [tokens, jnp.zeros((pad, h), tokens.dtype)])
        bitmap = jnp.concatenate([bitmap, jnp.zeros((pad,), bitmap.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)])
    nb = tokens.shape[0] // block_rows
    kernel = functools.partial(_pack_kernel, capacity=capacity,
                               block_rows=block_rows)
    hw = -(-h * tokens.dtype.itemsize // 4)
    hb = _word_block(hw, capacity)
    live = jnp.where(valid.astype(bool), bitmap.astype(jnp.int32), 0)
    words = _as_words(tokens)
    # src_idx is produced as [D, C, 1]: a one-row dynamic store on the
    # sublane axis, like the token rows (a lane-axis dynamic store is not)
    out, idx = pl.pallas_call(
        kernel,
        grid=(num_dests, hw // hb, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_rows, hb), lambda d, j, b: (0, b, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, capacity, hb), lambda d, j, b: (d, 0, j)),
            pl.BlockSpec((1, capacity, 1), lambda d, j, b: (d, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_dests, capacity, hw), jnp.uint32),
            jax.ShapeDtypeStruct((num_dests, capacity, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(live, words[None])
    out = _from_words(out, tokens.dtype, h)
    idx = idx[..., 0]
    return out, idx
