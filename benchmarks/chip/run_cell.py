"""Run one benchmark cell once on the chips of this machine.

  python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix by name (see
``cellspec.py``), makes the weights on the device from the seed, builds
the serving stack the way ``launch/serve.py`` does, warms every cohort
shape the mix can produce, serves the mix for ``--seconds`` on the wall
clock (``serveloop.py``), and checks the served tokens against the
plain reference (``reference.py``).  ``--trace 1`` also records a
profiler trace of a slice of the window and reports the per-layer
metrics; ``--trace 0`` reports the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, beside its
limit).  Everything else goes to standard error.  Without a TPU, or
with another number of chips than the cell asks for, it exits non-zero
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cellspec  # noqa: E402
import counts  # noqa: E402
import serveloop  # noqa: E402

CHECK_REQUESTS = 4           # requests compared with the reference per run
TRACE_START = 0.4            # the traced slice starts at this share ...
TRACE_SECONDS = 3.0          # ... and lasts this long (or to the close)


class NoChip(RuntimeError):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def devices_for(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX sees {devices[0].platform!r} devices; "
                         f"this benchmark measures only on a chip")
        if len(devices) != chips:
            raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    elif len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} device(s), JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at ``<checkout>/.jax_cache`` (the program's own
    default); every program cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cell, seed: int):
    """The serving engine of ``launch/serve.py`` over weights made here,
    in the serving dtype, with the program's sharding."""
    import jax.numpy as jnp

    import seedweights
    from repro.launch import serve
    from repro.models.api import build_model

    conf = cell.conf
    prog = conf["program"]
    mcfg = cellspec.program_config(conf)
    dtype = jnp.dtype(prog["dtype"])
    mix = cell.mix
    args = serve.parse_args([
        "--arch", prog["arch"], "--prompts", str(mix["capacity"]),
        "--prompt-len", str(mix["prompt_len"]),
        "--max-new", str(max(mix["max_news"])),
        "--plan-policy", prog["plan_policy"], "--seed", str(seed % 2 ** 31)])
    shardings = None
    pctx = None
    if cell.chips > 1:
        from repro.launch.mesh import make_pctx
        from repro.parallel import sharding as shd
        pctx = make_pctx(fsdp=False, plan_policy=args.plan_policy,
                         moe_scheme=args.moe_scheme)

        def shardings(shapes):
            return shd.named(shd.param_specs(shapes, mcfg, pctx), pctx)
    init = build_model(mcfg, pctx, dtype=dtype).init
    params = seedweights.make_params(init, seed, dtype, shardings)
    return serve.build_engine(args, mcfg, params, dtype)


def warm(engine, shapes):
    """One prefill and one decode step of every cohort shape."""
    import numpy as np
    for b, s, max_new in shapes:
        state, tokens, _ = engine.start_cohort(
            np.zeros((b, s), np.int32), max_new=max_new, seed=0)
        engine.step_cohort(state, tokens)


def served_gaps(ref, window, seed: int, t_max: int, control=False):
    """Gaps, token by token, over the served tokens of a seeded sample of
    finished requests, between the reference's best logit and the served
    token's (with ``control``, also of the token the float8 reference
    puts first at each of those positions)."""
    import numpy as np

    import reference
    served, low = [], []
    for rid in serveloop.check_sample(window, seed, CHECK_REQUESTS):
        req = window.requests[rid]
        toks = np.asarray(req.tokens[:req.max_new], np.int32)
        padded = np.zeros(t_max, np.int32)
        padded[:len(toks)] = toks
        prompt = np.asarray(req.prompt, np.int32)
        exact = ref.logits(prompt, padded)[:len(toks)]
        served.append(reference.gaps(exact, toks))
        if control:
            first = ref.logits(prompt, padded, mode="fp8")[:len(toks)]
            low.append(reference.gaps(exact, first.argmax(-1)))
    return (np.concatenate(served) if served else np.zeros(0),
            np.concatenate(low) if low else np.zeros(0))


def gap_numbers(gaps) -> dict:
    """The numbers a configuration may compare: the widest gap, the mean
    gap, and the share of tokens that are not the reference's first
    choice (inf when nothing finished, which vouches for nothing)."""
    if not len(gaps):
        return dict.fromkeys(("widest_gap", "mean_gap", "mismatch_share"),
                             float("inf"))
    return {"widest_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "mismatch_share": float((gaps > 0).mean())}


def check(cell, engine, window, seed: int) -> dict:
    """Each number the configuration gives a limit for, beside it."""
    import reference
    ref = reference.Reference(cell.conf, engine.params)
    gaps, _ = served_gaps(ref, window, seed, max(cell.mix["max_news"]))
    values = gap_numbers(gaps)
    log(f"reference gaps over {len(gaps)} served tokens: {values}")
    return {name: {"value": values[name], "limit": float(limit)}
            for name, limit in cell.conf["check"].items()}


class TraceSlice:
    """Starts the profiler at ``start`` seconds into the window and stops
    it ``length`` seconds later (or when asked), inside a ``bench.slice``
    annotation.  The first start of the profiler in a process takes
    seconds (10-20 s on a v5e host), which would stall the window and
    pile up an open loop's arrivals before the slice: ``warm`` pays it
    in set-up, with a trace of nothing that is thrown away."""

    def __init__(self, trace_dir: str, start: float, length: float):
        self.dir, self.start, self.stop_at = trace_dir, start, start + length
        self.t0 = self.t1 = None
        self.start_s = None          # how long the window's start took
        self._ann = None

    @staticmethod
    def _options():
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0          # host spans, not every call
        return options

    def warm(self):
        import jax
        warm_dir = os.path.join(self.dir, "warm")
        jax.profiler.start_trace(warm_dir, profiler_options=self._options())
        jax.profiler.stop_trace()
        shutil.rmtree(warm_dir, ignore_errors=True)

    def tick(self, elapsed: float):
        import jax
        if self.t0 is None and elapsed >= self.start:
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir,
                                     profiler_options=self._options())
            self.start_s = time.perf_counter() - t
            self._ann = jax.profiler.TraceAnnotation("bench.slice")
            self._ann.__enter__()
            self.t0 = time.perf_counter()
        elif self.t0 is not None and self.t1 is None and \
                elapsed >= self.stop_at:
            self.close()

    def close(self):
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


def per_layer(cell, root, window, engine_log, spans, trace, peaks):
    import traced
    import xplane
    events = xplane.load(xplane.latest(trace.dir),
                         serveloop.Spans.NAMES | {xplane.SLICE})
    red = xplane.reduce(events)
    run = traced.TracedSlice(
        reduction=red, shape=counts.shape_of(cell.conf), peaks=peaks,
        chips=cell.chips,
        calls=[c for c in engine_log.calls
               if trace.t0 <= c.t0 and c.t1 <= trace.t1],
        spans=[s for s in spans.rows if trace.t0 <= s[1] and s[2] <= trace.t1],
        decode_tokens=[ctx for t, ctx in window.token_log
                       if trace.t0 <= t <= trace.t1])
    metrics = {}
    for m in cell.per_layer:
        value = cellspec.reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_chip: bool = True,
        t_start: float = T_START, wrap_engine=None) -> dict:
    """One run of one cell; returns the result object.  ``wrap_engine``
    (tests): a function applied to the engine the scheduler drives."""
    cell = cellspec.Cell(root, workload)
    devices = devices_for(cell.chips, require_chip)
    import seedtraffic
    log(f"cell {workload}: config {cell.conf['name']}, traffic "
        f"{cell.entry['traffic']}, {cell.chips} chip(s), seed {seed}")
    log(f"compile cache: {enable_cache(root)}")
    peaks_table = cellspec.load_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if require_chip and kind not in peaks_table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    peaks = peaks_table.get(kind)
    clock = serveloop.CompileClock()
    engine = build(cell, seed)
    traffic = seedtraffic.Traffic(cell.mix, seed, seconds,
                                  cell.conf["vocab_size"])
    shapes = traffic.cohort_shapes()
    warm(engine, shapes)
    spans = serveloop.Spans(annotate=trace)
    driven = serveloop.TimedEngine(engine, spans)
    if wrap_engine is not None:
        driven.engine = wrap_engine(engine)
    slicer = None
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        slicer = TraceSlice(trace_dir, TRACE_START * seconds,
                            min(TRACE_SECONDS, (1 - TRACE_START) * seconds))
        slicer.warm()
    traces_before = clock.traces
    window_start = time.perf_counter()
    setup_s = window_start - t_start
    log(f"set-up: {setup_s:.3f}s, {len(shapes)} cohort shape(s) warmed, "
        f"{clock.traces} trace(s), {clock.cache_hits} persistent-cache hit(s)")
    window = serveloop.serve_window(
        driven, traffic, seconds, spans,
        on_tick=None if slicer is None else slicer.tick)
    if slicer is not None:
        slicer.close()
    in_window = clock.traces - traces_before
    lat = serveloop.latencies(window)
    late = window.lateness_s
    log(f"window: {seconds}s, {window.steps} scheduler steps, "
        f"{len(window.stamps)} requests, "
        f"{sum(st.emitted for st in window.stamps)} tokens; "
        f"drained {window.t_drained - window.t_end:.3f}s after the close; "
        f"{in_window} trace(s) inside the window")
    if late:
        log(f"generator lateness: median "
            f"{1e3 * serveloop.percentile(late, 50):.3f}ms, p99 "
            f"{1e3 * serveloop.percentile(late, 99):.3f}ms, max "
            f"{1e3 * max(late):.3f}ms")
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)

    metrics = {}
    breakdown = None
    if trace:
        metrics, red = per_layer(cell, root, window, driven, spans, slicer,
                                 peaks)
        breakdown = red.breakdown()
        log(f"trace: profiler start {slicer.start_s:.3f}s in the window; "
            f"{red.devices} device(s), busy {red.busy_s:.6f}s of "
            f"{red.window_s:.6f}s, ops' own time {sum(red.op_s.values()):.6f}s;"
            f" module runs {red.module_runs}")
    else:
        # an end-to-end metric is one of these quantities, by the part of
        # its name before a dot: "tpot_p95_ms.ep4" is the TPOT tail held
        # to a bound of its own in the cells that report it
        values = {
            "ttft_p95_ms": 1e3 * serveloop.percentile(lat["ttft_s"], 95),
            "tpot_p95_ms": 1e3 * serveloop.percentile(lat["tpot_s"], 95),
            "tokens_per_s": serveloop.tokens_per_s(window),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    log(f"latency: {len(lat['ttft_s'])} TTFT, {len(lat['tpot_s'])} TPOT "
        f"samples; unfinished {lat['unfinished']}")

    t_check = time.perf_counter()
    checks = check(cell, engine, window, seed)
    checks["unfinished_requests"] = {"value": len(lat["unfinished"]),
                                     "limit": 0}
    log(f"reference check: {time.perf_counter() - t_check:.3f}s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if trace:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    out = {"correct": bool(correct), "attempted": len(window.stamps),
           "failed": len(lat["unfinished"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"run_cell: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
