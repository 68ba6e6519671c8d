"""Share of the slice in which a chip ran no op, averaged over the
chips, in % (``TracedSlice.idle_share``); moves the end-to-end metric
its entry in ``BENCHMARK.json`` names."""


def read(run):
    return run.idle_share()
