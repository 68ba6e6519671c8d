"""Public jit'd wrappers for the Pallas kernels.

Each op dispatches between the Pallas kernel (TPU: compiled; CPU:
interpret mode for validation) and the pure-jnp reference, controlled by
``use_pallas``.  Model code imports from here only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .dispatch_pack import dispatch_pack as _dispatch_pack_kernel
from .flash_attention import flash_attention as _flash_attention_kernel
from .mamba2_scan import mamba2_scan as _mamba2_kernel
from .rwkv6_scan import rwkv6_scan as _rwkv6_kernel


def _interpret() -> bool:
    """Compiled on the TPU, interpret mode on the CPU; any other backend
    has no Pallas path here and is an error, not a silent fallback."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
    return backend == "cpu"


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, block_q=128, block_k=128,
                    use_pallas: bool = True):
    """[BH, S, D] fused attention (GQA broadcast is the caller's job)."""
    if use_pallas:
        return _flash_attention_kernel(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=_interpret())
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale)


def decode_attention(q, k, v, kv_len=None, *, scale=None, softcap=None,
                     window=None):
    """Decode-step attention (memory-bound matvec; jnp is already optimal
    on TPU for this shape — no kernel needed)."""
    return ref.decode_attention_ref(q, k, v, kv_len, scale=scale,
                                    softcap=softcap, window=window)


def mamba2_scan(x, dt, a, b, c, d, *, chunk=64, use_pallas: bool = True):
    if use_pallas:
        return _mamba2_kernel(x, dt, a, b, c, d, chunk=chunk,
                              interpret=_interpret())
    # chunked jnp twin (same algorithm as the kernel): touches the [ds,dh]
    # state once per CHUNK, not per step — the per-step scan oracle thrashes
    # HBM chunk-times harder and lives in ref.mamba2_ref for tests only.
    return ref.mamba2_chunked_jnp(x, dt, a, b, c, d, chunk=chunk)


def rwkv6_scan(r, k, v, logw, u, *, chunk=32, use_pallas: bool = True):
    if use_pallas:
        return _rwkv6_kernel(r, k, v, logw, u, chunk=chunk,
                             interpret=_interpret())
    return ref.rwkv6_chunked_jnp(r, k, v, logw, u, chunk=chunk)


def dispatch_pack(tokens, bitmap, valid, *, num_dests, capacity,
                  block_rows=8, use_pallas: bool = True):
    if use_pallas:
        return _dispatch_pack_kernel(
            tokens, bitmap, valid, num_dests=num_dests, capacity=capacity,
            block_rows=block_rows, interpret=_interpret())
    return ref.pack_ref(tokens, bitmap, valid, num_dests, capacity)
