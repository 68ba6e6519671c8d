"""Model FLOP/s utilisation of the whole step over the cell's chips, in
% (``TracedSlice.mfu``); moves the cell's token rate."""


def read(run):
    return run.mfu()
