"""Host milliseconds per scheduler iteration outside the engine's calls
(``TracedSlice.sched_host_ms``); moves the end-to-end metric its entry
in ``BENCHMARK.json`` names."""


def read(run):
    return run.sched_host_ms()
