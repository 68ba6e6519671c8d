"""Readings that a cell's `correct` limit is set from, on the chip.

  python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
      --seeds 11 12 13 ...

One process, one engine: for each seed, weights made from that seed
replace the last ones, the cell's mix is served for ``--seconds`` at the
cell's own load and drained, and the same seeded sample of requests a
run compares (``run_cell.check``) is read twice:

  program   widest and mean gap between the reference's best logit and
            the served token's, and the share of served tokens that are
            not the reference's first choice (``run_cell.gap_numbers``;
            a run compares those its configuration gives limits for)
  control   the same reference computed with float8 (e4m3) matrix
            inputs, in the program's place: the same numbers for the
            token it puts first, at each position of the same prompts
            and tokens

Prints one JSON line per seed.  A limit lies between the largest
program reading over a dozen seeds or more and the smallest control
reading (PERF.md gives both).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run_cell  # noqa: E402


def readings(ref, window, seed: int, t_max: int) -> dict:
    """Program and control numbers over the run's check sample."""
    served, low = run_cell.served_gaps(ref, window, seed, t_max,
                                       control=True)
    row = {"served_tokens": len(served)}
    for who, gaps in (("program", served), ("control", low)):
        for name, value in run_cell.gap_numbers(gaps).items():
            row[f"{who}_{name}"] = value
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    import cellspec
    import reference
    import seedtraffic
    import seedweights
    import serveloop
    cell = cellspec.Cell(run_cell.ROOT, args.workload)
    run_cell.devices_for(cell.chips, require_chip=True)
    run_cell.enable_cache(run_cell.ROOT)
    engine = run_cell.build(cell, args.seeds[0])
    vocab = cell.conf["vocab_size"]
    run_cell.warm(engine, seedtraffic.Traffic(
        cell.mix, 0, 1.0, vocab).cohort_shapes())
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, engine.params)
    dtype = jnp.dtype(cell.conf["program"]["dtype"])
    limits = cell.conf["check"]
    for i, seed in enumerate(args.seeds):
        if i:
            init = engine._binder.artifact.model.init
            engine.params = None
            engine.params = seedweights.make_params(
                init, seed, dtype, lambda _: shardings)
        traffic = seedtraffic.Traffic(cell.mix, seed, args.seconds, vocab)
        spans = serveloop.Spans()
        window = serveloop.serve_window(
            serveloop.TimedEngine(engine, spans), traffic, args.seconds,
            spans)
        ref = reference.Reference(cell.conf, engine.params)
        row = readings(ref, window, seed, max(cell.mix["max_news"]))
        row.update(workload=args.workload, seed=seed, limits=limits,
                   unfinished=len(serveloop.latencies(window)["unfinished"]))
        print(json.dumps(row), flush=True)
        del ref, window
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
