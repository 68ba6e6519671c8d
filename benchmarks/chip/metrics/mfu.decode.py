"""Model FLOP/s utilisation of the decode step as the host sees it, over
the cell's chips, in % (``TracedSlice.mfu_decode``): the whole step's
share of the peak beside ``decode_hbm_roofline``; moves the cell's TPOT
tail."""


def read(run):
    return run.mfu_decode()
