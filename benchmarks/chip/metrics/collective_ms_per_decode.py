"""Device milliseconds of collective ops (all-to-all, collective-permute,
all-gather, all-reduce, reduce-scatter) inside the decode program, per
decode execution, averaged over the chips: the expert dispatch and
combine of ``core/collectives.py`` under the plan bound for a decode
step; moves ``tpot_p95_ms``."""


def read(run):
    runs = run.reduction.module_runs.get("jit_decode", 0)
    if not runs:
        return None
    return run.reduction.collective_s.get("jit_decode", 0.0) * 1e3 / runs
