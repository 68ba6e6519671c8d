"""int8 error-feedback compressed gradient all-reduce (beyond-paper).

The paper removes redundant bytes from *many-to-many* collectives; the
same bottleneck-link first principle (§3.3) applies to the DP gradient
all-reduce when it crosses the pod (DCN) axis.  This module implements the
classic bandwidth lever for that path:

  ring-equivalent all-reduce at 1/4 wire bytes via int8 quantization with
  per-chunk scales + error feedback (the quantization residual is carried
  to the next step, preserving convergence — 1-bit-Adam lineage).

Schedule (inside shard_map over the DP axis, R ranks):
  1. chunk the flat gradient into R pieces;
  2. quantize (int8, per-chunk fp32 scale) and ``all_to_all`` so rank r
     collects every rank's chunk r          — wire: N bytes int8;
  3. local dequant + sum -> reduced chunk r;
  4. re-quantize and ``all_gather``         — wire: N bytes int8;
  5. dequant -> full reduced gradient; residual = input - dequant(sent).

fp32 ring all-reduce moves ~2N*4 bytes; this moves ~2N bytes -> 4x less
on the bottleneck link, directly shrinking the collective roofline term.

Also here: :func:`hierarchical_psum` — reduce-scatter intra-pod, exchange
one pre-reduced shard per pod over DCN, all-gather intra-pod.  This is the
MultiWrite dual (relay-side reduction) applied to gradients: ONE copy of
each reduced byte crosses the slow axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size


def _quantize_int8(x: jax.Array):
    """Symmetric per-tensor int8 quant.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(g: jax.Array, axis: str,
                    err: Optional[jax.Array] = None):
    """Mean-reduce ``g`` over ``axis`` with int8 wire format + error
    feedback.  g: flat [N] fp32 (caller flattens).  Returns (mean, new_err).

    Must run inside shard_map with ``axis`` present.
    """
    r = axis_size(axis)
    n = g.shape[0]
    pad = (-n) % r
    gf = g.astype(jnp.float32)
    if err is not None:
        gf = gf + err
    gp = jnp.pad(gf, (0, pad))
    chunks = gp.reshape(r, -1)                                # [R, N/R]

    # step 2: per-chunk scales ride along as fp32 (R values — negligible)
    scales = jnp.max(jnp.abs(chunks), axis=1) / 127.0
    scales = jnp.maximum(scales, 1e-12)
    q = jnp.clip(jnp.round(chunks / scales[:, None]), -127, 127
                 ).astype(jnp.int8)
    sent_dequant = q.astype(jnp.float32) * scales[:, None]    # what we sent
    new_err = (gp - sent_dequant.reshape(-1))[:n]             # residual

    mine_q = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                            tiled=True).reshape(r, -1)        # [R, N/R]
    mine_s = lax.all_to_all(jnp.tile(scales, r), axis, split_axis=0,
                            concat_axis=0, tiled=True).reshape(r, r)
    me = lax.axis_index(axis)
    my_scales = mine_s[:, me]                                  # scale of my chunk per src... see note
    # NOTE: after tiled a2a of the [R] scale vector replicated R times,
    # row p holds rank p's scales; column me is rank p's scale for chunk me.
    reduced = jnp.sum(mine_q.astype(jnp.float32)
                      * my_scales[:, None], axis=0) / r       # mean

    # step 4: requantize the reduced chunk and all-gather
    q2, s2 = _quantize_int8(reduced)
    full_q = lax.all_gather(q2, axis)                          # [R, N/R] int8
    full_s = lax.all_gather(s2, axis)                          # [R]
    out = (full_q.astype(jnp.float32) * full_s[:, None]).reshape(-1)[:n]
    return out, new_err


def hierarchical_psum(g: jax.Array, pod_axis: str, data_axis: str):
    """Pod-aware gradient mean: reduce-scatter over the fast intra-pod axis,
    ONE pre-reduced shard per pod crosses DCN, all-gather intra-pod.

    DCN bytes per chip: N/D (vs N for a flat all-reduce ring crossing pods
    D times per chip-position) — the §3.3 bottleneck-link principle applied
    to the reduction direction.

    Requires a mesh that already factors the replicas into two named
    axes.  When the replicas live on ONE flat mesh axis (the trainer's
    ``data`` axis), use :func:`hierarchical_psum_flat`, which derives
    the same two-level schedule from the fabric's server grouping via
    ``axis_index_groups``.
    """
    d = axis_size(data_axis)
    n = g.shape[0]
    pad = (-n) % d
    gp = jnp.pad(g.astype(jnp.float32), (0, pad))
    # reduce-scatter intra-pod: rank i keeps reduced chunk i
    mine = lax.psum_scatter(gp.reshape(d, -1), data_axis, scatter_dimension=0,
                            tiled=False)                       # [N/D]
    # cross-pod exchange of the pre-reduced shard (the slow-axis hop)
    mine = lax.psum(mine, pod_axis)
    # all-gather intra-pod
    full = lax.all_gather(mine, data_axis).reshape(-1)[:n]
    return full / (d * axis_size(pod_axis))


def hierarchical_psum_flat(g: jax.Array, axis: str, num_servers: int):
    """:func:`hierarchical_psum` on a single flat mesh axis, with the
    two-level factorization derived from the FABRIC rather than
    hard-coded into the mesh shape: ranks on the axis are grouped
    ``num_servers`` x ``npus_per_server`` in fabric order (server-major,
    matching ``ClusterSpec.build``'s node numbering), so the schedule is
    correct on ``4x8`` / ``tpu_2x16``-class shapes, not just 2-server
    meshes.

    Reduce-scatter within each server group (fast links), exchange the
    pre-reduced 1/P shard across same-index rail peers, all-gather back
    within the server group.  Returns the MEAN over the axis.
    """
    r = axis_size(axis)
    s = max(1, int(num_servers))
    if r % s:
        raise ValueError(
            f"axis size {r} does not factor into {s} servers")
    p = r // s
    n = g.shape[0]
    gf = g.astype(jnp.float32)
    if p == 1 or s == 1:
        # degenerate grouping: one level is trivial — a flat psum IS the
        # hierarchical schedule then
        return lax.psum(gf, axis) / r
    intra = [list(range(sv * p, (sv + 1) * p)) for sv in range(s)]
    inter = [[sv * p + i for sv in range(s)] for i in range(p)]
    pad = (-n) % p
    gp = jnp.pad(gf, (0, pad))
    mine = lax.psum_scatter(gp.reshape(p, -1), axis, scatter_dimension=0,
                            tiled=False, axis_index_groups=intra)
    mine = lax.psum(mine, axis, axis_index_groups=inter)
    full = lax.all_gather(mine, axis,
                          axis_index_groups=intra).reshape(-1)[:n]
    return full / r


def tree_compressed_psum(grads, axis: str, err_tree=None):
    """Apply compressed_psum across a pytree (flatten → one fused call)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    sizes = [x.size for x in leaves]
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])
    err = err_tree if err_tree is not None else jnp.zeros_like(flat)
    red, new_err = compressed_psum(flat, axis, err)
    out = []
    off = 0
    for x, sz in zip(leaves, sizes):
        out.append(red[off:off + sz].reshape(x.shape))
        off += sz
    return treedef.unflatten(out), new_err
