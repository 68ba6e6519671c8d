"""Device milliseconds of collective ops (all-to-all, collective-permute,
all-gather, all-reduce, reduce-scatter) inside the prefill program, per
prefill execution, averaged over the chips: the expert dispatch and
combine of ``core/collectives.py`` under the bound plan; moves
``ttft_p95_ms.ep4``."""


def read(run):
    runs = run.reduction.module_runs.get("jit_prefill", 0)
    if not runs:
        return None
    return run.reduction.collective_s.get("jit_prefill", 0.0) * 1e3 / runs
