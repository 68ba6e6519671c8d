"""Device milliseconds of ``jit_prefill`` per 1,000 prompt tokens,
averaged over the chips, expert dispatch and combine included where the
cell has them (``TracedSlice.prefill_ms_per_ktok``); moves the cell's
TTFT tail."""


def read(run):
    return run.prefill_ms_per_ktok()
