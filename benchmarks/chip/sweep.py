"""Find an open-loop cell's knee: serve its mix at several fixed rates.

  python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
      --seconds <s> --rates 1 2 3

One process: the engine is built and warmed once, then each rate's
stream is served for ``--seconds`` and drained.  Prints one JSON line
per rate: requests, TTFT and TPOT percentiles, tokens per second, and
how long the backlog took to drain after the close (a backlog that
grows through the window drains for long).  Run on the chip; the knee
it finds is recorded in PERF.md and fixes the cell's rate.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import cellspec
    import seedtraffic
    import serveloop
    cell = cellspec.Cell(run_cell.ROOT, args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("sweep: only an open-loop mix has a rate")
    run_cell.devices_for(cell.chips, require_chip=True)
    run_cell.enable_cache(run_cell.ROOT)
    engine = run_cell.build(cell, args.seed)
    shapes = seedtraffic.Traffic(cell.mix, args.seed, 1.0,
                                 cell.conf["vocab_size"]).cohort_shapes()
    run_cell.warm(engine, shapes)
    for rate in args.rates:
        mix = dict(cell.mix, rate_rps=rate)
        traffic = seedtraffic.Traffic(mix, args.seed, args.seconds,
                                      cell.conf["vocab_size"])
        spans = serveloop.Spans()
        w = serveloop.serve_window(serveloop.TimedEngine(engine, spans),
                                   traffic, args.seconds, spans)
        lat = serveloop.latencies(w)
        pct = serveloop.percentile
        print(json.dumps({
            "rate_rps": rate, "requests": len(w.stamps),
            "ttft_p50_ms": 1e3 * pct(lat["ttft_s"], 50),
            "ttft_p95_ms": 1e3 * pct(lat["ttft_s"], 95),
            "tpot_p50_ms": 1e3 * pct(lat["tpot_s"], 50),
            "tpot_p95_ms": 1e3 * pct(lat["tpot_s"], 95),
            "tokens_per_s": serveloop.tokens_per_s(w),
            "drain_s": w.t_drained - w.t_end,
            "lateness_p99_ms": 1e3 * pct(w.lateness_s, 99),
            "unfinished": len(lat["unfinished"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
