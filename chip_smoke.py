#!/usr/bin/env python3
"""Chip smoke test: the serving path on a TPU, checked against a reference.

  python chip_smoke.py            one chip.  qwen2_vl_2b at its published
                                  widths serves 4 prompts x 128 tokens, 32
                                  new tokens each, greedy, through
                                  ServeEngine.generate; the served tokens
                                  and logits are checked against a no-cache
                                  full-sequence forward.  Then the four
                                  Pallas kernels run compiled against their
                                  jnp references.
  python chip_smoke.py --chips 4  four chips (one 2x2 host) and nothing
                                  else.  dbrx_132b at its published widths,
                                  depth cut to fit, experts over (pod 2,
                                  data 2); the same requests are served
                                  under MultiWrite (hierarchical), unicast
                                  (baseline) and the planner (auto), in
                                  bf16 and again with f32 activations; the
                                  f32 logits of the three must agree.

Built the way ``launch/serve.py`` builds its engine
(``serve.build_engine``).  The compile cache follows
``repro.launch.compile_cache``.  A run that finds no TPU, or the wrong
number of chips, or fails any check, exits non-zero and prints no
result.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
These are smoke readings, not benchmark results.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

PROMPTS, PROMPT_LEN, MAX_NEW, SEED = 4, 128, 32, 0
# Stated tolerance of a served position against what it is compared
# with, by activation dtype: relative L2 error of the logit vector, and
# the margin below which the compared argmax counts as a tie (a rounding
# can flip it).
TOL = {"bfloat16": (0.05, 0.1), "float32": (1e-3, 1e-3)}
# dbrx_132b on four v5e chips: bf16 weights, 4 of the 16 experts per chip.
# Per chip 1.76 GB a layer plus 2.47 GB of replicated embed/unembed
# tables: 11.3 GB at 5 layers, next to at most 2.5 GB of temporaries
# (f32-activation prefill; AOT compile for v5e:2x2) under 16 GB.
DBRX_DEPTH = 5


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), keyed by the jitted function's name."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.secs = collections.Counter()
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.EVENTS:
            self.secs[str(kw.get("fun_name", "?"))] += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        """Seconds per function since the last take."""
        out = dict(self.secs)
        self.secs.clear()
        return out


def require_tpu(count: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform "
            f"{devices[0].platform!r}); this script runs only on a chip")
    if len(devices) != count:
        raise SystemExit(f"chip_smoke: expected {count} TPU chip(s), JAX "
                         f"sees {len(devices)}")
    return devices


def prompts_for(vocab: int):
    import numpy as np
    return np.random.default_rng(SEED).integers(
        0, vocab, size=(PROMPTS, PROMPT_LEN)).astype(np.int32)


def served_logits(engine, prompts, tokens):
    """Teacher-forced replay of ``tokens`` through the engine's cohort
    API (what ``generate`` drives): the logits the served path produced
    for each new position, [B, T, V] f32, plus prefill and decode wall
    seconds, each ending in ``block_until_ready``."""
    import jax
    import numpy as np
    t0 = time.perf_counter()
    state, _, _ = engine.start_cohort(prompts, MAX_NEW, seed=SEED)
    jax.block_until_ready((state.logits, state.cache))
    prefill_s = time.perf_counter() - t0
    out = [np.asarray(state.logits, np.float32)]
    t0 = time.perf_counter()
    for t in range(tokens.shape[1] - 1):
        state, _, _ = engine.step_cohort(state, tokens[:, t])
        out.append(np.asarray(state.logits, np.float32))
    jax.block_until_ready((state.logits, state.cache))
    decode_s = time.perf_counter() - t0
    return np.stack(out, axis=1), prefill_s, decode_s


def compare(name, got, want, dtype="bfloat16", strict=True):
    """Check ``got`` logits [B, T, V] against ``want`` at ``dtype``'s
    stated tolerance; raises on a mismatch.  ``strict``: every position
    within tolerance and ``got``'s argmax ``want``'s up to ties;
    otherwise the median position within tolerance (the rest are
    printed)."""
    import numpy as np
    rel_tol, tie_tol = TOL[dtype]
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
    best, chosen = np.argmax(want, axis=-1), np.argmax(got, axis=-1)
    margin = (np.take_along_axis(want, best[..., None], -1)
              - np.take_along_axis(want, chosen[..., None], -1))[..., 0]
    flips = chosen != best
    ties = int(np.sum(flips & (margin <= tie_tol)))
    report = {"positions": int(chosen.size),
              "max_rel_l2": float(rel.max()),
              "median_rel_l2": float(np.median(rel)),
              "positions_over_tol": int(np.sum(rel > rel_tol)),
              "max_abs": float(np.abs(got - want).max()),
              "argmax_agree": int(chosen.size - flips.sum()),
              "ties": ties}
    print(f"check[{name}]: {json.dumps(report)} ({dtype} tolerance "
          f"rel_l2<={rel_tol}, tie margin<={tie_tol}"
          f"{'' if strict else '; enforced on the median position'})")
    failed = (rel.max() > rel_tol or int(flips.sum()) > ties if strict
              else np.median(rel) > rel_tol)
    if failed:
        raise AssertionError(f"check[{name}] failed: {report}")


def serve_requests(engine, prompts, clock, label):
    """Serve the requests twice through ``generate`` (cold, then warm)
    and return the tokens; prints compile seconds and wall times."""
    import numpy as np
    t0 = time.perf_counter()
    tokens = engine.generate(prompts, max_new=MAX_NEW, seed=SEED)
    cold_s = time.perf_counter() - t0
    compiled = clock.take()
    t0 = time.perf_counter()
    again = engine.generate(prompts, max_new=MAX_NEW, seed=SEED)
    warm_s = time.perf_counter() - t0
    if not np.array_equal(tokens, again):
        raise AssertionError(f"{label}: a second generate served other "
                             f"tokens")
    pre = sum(v for k, v in compiled.items() if "prefill" in k)
    dec = sum(v for k, v in compiled.items() if "decode" in k)
    print(f"{label}: generate {tokens.shape} cold {cold_s:.3f}s, warm "
          f"{warm_s:.3f}s; compile prefill {pre:.3f}s, decode {dec:.3f}s, "
          f"all {sum(compiled.values()):.3f}s; persistent-cache hits "
          f"{clock.cache_hits}")
    return tokens


def one_chip(clock):
    import jax
    import numpy as np

    from repro.data.pipeline import batch_for_model
    from repro.launch import serve

    args = serve.parse_args(
        ["--arch", "qwen2_vl_2b", "--prompts", str(PROMPTS),
         "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
         "--seed", str(SEED)])
    cfg = serve.serving_config(args)
    print(f"model: {cfg.name} at published widths: {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; bf16 weights and "
          f"activations")
    t0 = time.perf_counter()
    engine = serve.build_engine(args, cfg)
    jax.block_until_ready(engine.params)
    print(f"init: {time.perf_counter() - t0:.3f}s, compile "
          f"{sum(clock.take().values()):.3f}s")
    prompts = prompts_for(cfg.vocab)
    tokens = serve_requests(engine, prompts, clock, "serve")
    got, prefill_s, decode_s = served_logits(engine, prompts, tokens)
    print(f"timing: prefill {prefill_s:.4f}s ({PROMPTS}x{PROMPT_LEN} "
          f"tokens), decode {decode_s:.4f}s ({MAX_NEW - 1} steps, "
          f"{(MAX_NEW - 1) * PROMPTS / decode_s:.1f} tokens/s)")
    if not (got.argmax(-1) == tokens).all():
        raise AssertionError("replayed cohort logits disagree with the "
                             "tokens generate served")
    # reference: one no-cache forward over prompt + served prefix
    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    batch = batch_for_model(cfg, {"tokens": seq, "labels": seq})
    batch.pop("labels")
    ref = jax.jit(engine.model.logits)(engine.params, batch)
    want = np.asarray(ref[:, PROMPT_LEN - 1:], np.float32)
    compare("served vs no-cache forward", got, want)
    kernels()


def kernels():
    """The four Pallas kernels, compiled, against their references (run
    at full f32 matmul precision): relative L2 error within 2 %, and the
    packing kernel bit-exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.dispatch_pack import dispatch_pack
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.rwkv6_scan import rwkv6_scan

    rng = np.random.default_rng(SEED)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def check(name, kernel, reference, *args):
        got = np.asarray(kernel(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(*args), np.float32)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        print(f"kernel[{name}]: relative L2 error {err:.3e}")
        if not err <= 2e-2:
            raise AssertionError(f"kernel[{name}] disagrees with its "
                                 f"reference: {err}")

    qkv = [normal(4, 512, 128) for _ in range(3)]
    check("flash_attention",
          lambda q, k, v: flash_attention(q, k, v, interpret=False),
          ref.attention_ref, *qkv)
    x, b, c = normal(4, 256, 64), normal(4, 256, 64), normal(4, 256, 64)
    dt = jnp.abs(normal(4, 256)) * 0.1 + 0.01
    a, d = -jnp.abs(normal(4)) - 0.1, normal(4)
    check("mamba2_scan",
          lambda *t: mamba2_scan(*t, interpret=False),
          ref.mamba2_ref, x, dt, a, b, c, d)
    r, k, v = normal(4, 256, 64), normal(4, 256, 64), normal(4, 256, 64)
    logw = -jnp.abs(normal(4, 256, 64)) * 0.3 - 0.05
    check("rwkv6_scan",
          lambda *t: rwkv6_scan(*t, interpret=False),
          ref.rwkv6_ref, r, k, v, logw, normal(4, 64))
    tokens = jnp.asarray(rng.normal(size=(512, 256)), jnp.bfloat16)
    bitmap = jnp.asarray(rng.integers(0, 1 << 8, size=512), jnp.int32)
    valid = jnp.asarray(rng.random(512) > 0.25)
    got_t, got_i = dispatch_pack(tokens, bitmap, valid, num_dests=8,
                                 capacity=128, interpret=False)
    exp_t, exp_i = ref.pack_ref(tokens, bitmap, valid, 8, 128)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(exp_i))
    np.testing.assert_array_equal(np.asarray(got_t, np.float32),
                                  np.asarray(exp_t, np.float32))
    print("kernel[dispatch_pack]: bit-exact")


def dbrx_config(depth: int):
    """dbrx_132b at its published widths with the stated cuts."""
    from repro.configs.base import get_config
    full = get_config("dbrx_132b")
    cfg = dataclasses.replace(full, n_layers=depth, moe_capacity=4.0)
    reduced = {
        "n_layers": f"{full.n_layers} -> {depth} (bf16 weights of 4 of 16 "
                    f"experts per chip + replicated embed/unembed fill 16 GB)",
        "moe_capacity": f"{full.moe_capacity} -> 4.0 (every stage holds all "
                        f"global tokens: drop-free, so both schemes route "
                        f"the same tokens)",
    }
    return cfg, reduced


def four_chips(clock):
    """Serve the requests under each scheme in bf16 (the serving dtype),
    then again with f32 activations over the same bf16 weights.  In bf16
    a rounding difference between the schemes' combine sums can flip a
    token's top-k expert choice, a discrete change a few positions'
    logits show, so bf16 is held to its tolerance at the median
    position; the f32 runs leave no such flips and must agree at every
    position to f32 tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve

    cfg, reduced = dbrx_config(DBRX_DEPTH)
    print(f"model: {cfg.name} at published widths: d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, {cfg.num_experts} experts "
          f"top-{cfg.top_k}, expert d_ff {cfg.expert_d_ff}, vocab "
          f"{cfg.vocab}; bf16 weights")
    print(f"reduced: {json.dumps(reduced)}")
    base = ["--arch", cfg.name, "--prompts", str(PROMPTS),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--seed", str(SEED)]
    runs = {"hierarchical": ["--plan-policy", "fixed",
                             "--moe-scheme", "hierarchical"],
            "baseline": ["--plan-policy", "fixed",
                         "--moe-scheme", "baseline"],
            "auto": ["--plan-policy", "auto"]}
    prompts = prompts_for(cfg.vocab)
    params = tokens = None
    # f32 matmuls at full precision: the TPU's default rounds their
    # inputs to bf16, which would bring the flips back
    for dtype, precision in (("bfloat16", "default"),
                             ("float32", "highest")):
        first = None
        for name, extra in runs.items():
            label = f"{name}/{dtype}"
            args = serve.parse_args(base + extra)
            t0 = time.perf_counter()
            with jax.default_matmul_precision(precision):
                engine = serve.build_engine(args, cfg, params,
                                            jnp.dtype(dtype))
            if params is None:
                params = engine.params
                jax.block_until_ready(params)
                print(f"init: {time.perf_counter() - t0:.3f}s, compile "
                      f"{sum(clock.take().values()):.3f}s, mesh "
                      f"{dict(engine.pctx.mesh.shape)}")
            with jax.default_matmul_precision(precision):
                served = serve_requests(engine, prompts, clock,
                                        f"serve[{label}]")
                got, prefill_s, decode_s = served_logits(
                    engine, prompts, tokens if tokens is not None
                    else served)
            if name == "auto":
                for phase, rep in engine.plan_report(PROMPTS,
                                                     PROMPT_LEN).items():
                    if isinstance(rep, dict) and "dispatch" in rep:
                        joint = rep.get("joint", rep["dispatch"])
                        print(f"auto bound[{phase}/{dtype}]: dispatch "
                              f"{rep['dispatch']['plan']}, combine "
                              f"{rep.get('combine', {}).get('plan')}, G "
                              f"{joint['knobs'].get('microbatch', 1)}")
            if tokens is None:
                tokens = served
            print(f"timing[{label}]: prefill {prefill_s:.4f}s, decode "
                  f"{decode_s:.4f}s ({MAX_NEW - 1} steps)")
            if first is None:
                first = got
            else:
                compare(f"{label} vs hierarchical/{dtype}", got, first,
                        dtype, strict=dtype == "float32")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="1: one-chip serving check (default); 4: the "
                         "four-chip expert-parallel phase only")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    print(f"device_kind: {dev.device_kind} x {len(devices)}")
    clock = CompileClock()
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(clock)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"peak_bytes_in_use: {peak} (max over {len(devices)} chip(s)); "
          f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
