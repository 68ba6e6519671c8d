"""Plain reference forward pass, and the comparison that decides `correct`.

Written from the configuration file alone and imports nothing of the
program.  It reads the weights the benchmark made (``seedweights.py``),
by the names of the program's parameter tree, and runs the model's
mathematics straight: no cache, no batching across requests, no
dispatch.  Every matrix product is float32 at full precision
(``precision=HIGHEST``), or, for the control, float8 (e4m3) inputs with
a scale per row of the activations and per output column of the
weights: the step below the bf16 the configurations state.

Where the program departs from the published model (its stub frontend,
no q/k/v bias, RMSNorm for DBRX's LayerNorm), the reference follows the
program and the configuration file says so under ``assumed``.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import counts

F8_MAX = 448.0   # largest finite float8_e4m3fn
OUT_BLOCK = 16384   # vocabulary rows per block of the output projection


def hash_embed(ids: np.ndarray, d: int) -> np.ndarray:
    """The stub frontend's embedding of token ids, [..., d] float32: a
    multiplicative hash of the id, then a linear congruential step per
    feature, mapped to [-0.05, 0.05)."""
    base = ids[..., None].astype(np.int64) * 2654435761 % 2 ** 31
    idx = base + np.arange(d, dtype=np.int64)
    vals = ((idx * 1103515245 + 12345) % 65536).astype(np.float32)
    return (vals / 32768.0 - 1.0) * 0.05


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the contraction axis), back in float32."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def _mm(x, w, mode):
    """x [..., K] @ w [K, N] in float32, inputs rounded to fp8 first in
    the control mode."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp
    x = x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding, halves rotated (x: [S, H, dh], position = row)."""
    import jax.numpy as jnp
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, sh: counts.Shape, theta, mode):
    """Causal GQA self-attention over one sequence, x: [S, D]."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    g, rep, dh = sh.kv_heads, sh.heads // sh.kv_heads, sh.head_dim
    q = _rope(_mm(x, p["wq"], mode).reshape(s, sh.heads, dh), theta)
    k = _rope(_mm(x, p["wk"], mode).reshape(s, g, dh), theta)
    v = _mm(x, p["wv"], mode).reshape(s, g, dh)
    q = q.reshape(s, g, rep, dh)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", probs, v,
                   precision=jax.lax.Precision.HIGHEST)
    return _mm(o.reshape(s, sh.heads * dh), p["wo"], mode)


def _ffn(x, w1, w3, w2, mode):
    import jax
    return _mm(jax.nn.silu(_mm(x, w1, mode)) * _mm(x, w3, mode), w2, mode)


def _moe(x, p, sh: counts.Shape, mode, ep):
    """Top-k mixture of experts over x [N, D]: every expert applied to
    every token, weighted by its renormalised router probability (zero
    for experts not in a token's top k).  ``ep``: None, or (mesh, axes)
    when the experts lie divided over the mesh's ``axes``; each device
    then applies its own experts and the partial sums are added."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(_mm(x, p["router"], mode), axis=-1)   # [N, E]
    top, ids = jax.lax.top_k(probs, sh.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ids].set(top)           # [N, E]

    def local(x, weight, w1, w3, w2, first):
        # one expert at a time, so that one expert's weights are in
        # float32 at once
        def add(j, out):
            gate = jax.lax.dynamic_slice_in_dim(weight, first + j, 1, 1)
            return out + gate * _ffn(x, w1[j], w3[j], w2[j], mode)
        return jax.lax.fori_loop(0, w1.shape[0], add, jnp.zeros_like(x))

    if ep is None:
        return local(x, weight, p["w1"], p["w3"], p["w2"], 0)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh, axes = ep

    def per_device(x, weight, w1, w3, w2):
        first = jax.lax.axis_index(axes) * w1.shape[0]
        return jax.lax.psum(local(x, weight, w1, w3, w2, first), axes)

    spec = P(axes, None, None)
    return shard_map(per_device, mesh=mesh,
                     in_specs=(P(), P(), spec, spec, spec), out_specs=P(),
                     check_vma=False)(x, weight, p["w1"], p["w3"], p["w2"])


def expert_mesh(params):
    """(mesh, axes) over which the stacked expert weights are divided, or
    None when they are whole on one device."""
    moe = params["layers"].get("moe")
    if moe is None:
        return None
    sharding = moe["w1"].sharding
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) < 2 or spec[1] is None:
        return None
    mesh = sharding.mesh
    axes = spec[1] if isinstance(spec[1], tuple) else (spec[1],)
    for ax in spec[2:]:
        if ax is not None and math.prod(
                mesh.shape[a] for a in (ax if isinstance(ax, tuple)
                                        else (ax,))) > 1:
            raise NotImplementedError(
                f"expert widths divided over {ax!r}: the reference applies "
                f"whole experts per device")
    return mesh, axes


def forward_logits(params, inputs, *, conf: dict, mode: str, first: int,
                   ep=None):
    """Logits [T, V] float32 at positions ``first .. first + T - 1`` of one
    sequence.  ``inputs``: [S] int32 ids (table lookup) or [S, D] float32
    embeddings (stub frontend)."""
    import jax
    import jax.numpy as jnp
    sh = counts.shape_of(conf)
    theta = float(conf["rope_theta"] if "rope_theta" in conf
                  else conf["attn_config"]["rope_theta"])
    eps = float(conf.get("rms_norm_eps", conf.get("norm_eps", 1e-5)))
    if sh.token_table:
        x = params["embed"]["emb"][inputs].astype(jnp.float32)
        if sh.tied:
            x = x * math.sqrt(sh.d)
    else:
        x = inputs.astype(jnp.float32)

    def layer(x, lp):
        x = x + _attention(_rmsnorm(x, lp["ln1"]["w"], eps), lp["attn"], sh,
                           theta, mode)
        h = _rmsnorm(x, lp["ln2"]["w"], eps)
        if "moe" in lp:
            f = _moe(h, lp["moe"], sh, mode, ep)
        else:
            f = _ffn(h, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"],
                     mode)
        return x + f, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["w"], eps)[first:]
    return _logits(x, params, sh, mode)


def _logits(x, params, sh: counts.Shape, mode):
    """x [T, D] through the output projection, a block of the vocabulary
    at a time, so that no float32 copy of the whole table is made."""
    import jax
    import jax.numpy as jnp
    block = max(c for c in range(1, min(sh.vocab, OUT_BLOCK) + 1)
                if sh.vocab % c == 0)

    def part(i):
        if sh.tied:
            w = jax.lax.dynamic_slice_in_dim(params["embed"]["emb"],
                                             i * block, block, 0).T
        else:
            w = jax.lax.dynamic_slice_in_dim(params["unembed"]["w"],
                                             i * block, block, 1)
        return _mm(x, w, mode)

    out = jax.lax.map(part, jnp.arange(sh.vocab // block))   # [n, T, block]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], sh.vocab)


class Reference:
    """Teacher-forced reference logits for served requests.

    ``logits(prompt, served)`` gives, for each served token, the logits
    the model assigns at the position that produced it: the prompt and
    all served tokens but the last go in, [len(served), V] float32
    comes out.  One compiled program per (prompt length, served length)
    and mode."""

    def __init__(self, conf: dict, params):
        self.conf = conf
        self.params = params
        self.shape = counts.shape_of(conf)
        self.ep = expert_mesh(params)
        self._fns = {}

    def _fn(self, mode: str, first: int):
        import jax
        key = (mode, first)
        if key not in self._fns:
            self._fns[key] = jax.jit(functools.partial(
                forward_logits, conf=self.conf, mode=mode, first=first,
                ep=self.ep))
        return self._fns[key]

    def inputs(self, prompt: np.ndarray, served: np.ndarray):
        ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if self.shape.token_table:
            return ids
        return hash_embed(ids, self.shape.d)

    def logits(self, prompt, served, mode: str = "f32") -> np.ndarray:
        import jax
        with jax.default_matmul_precision("highest"):
            out = self._fn(mode, len(prompt) - 1)(
                self.params, self.inputs(prompt, served))
        return np.asarray(out, np.float32)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies,
    position by position (0 where the token is the reference's best)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None].astype(np.int64),
                             axis=-1)[:, 0]
    return best - got
