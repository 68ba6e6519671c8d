"""Share of the HBM roofline a decode step reaches on one chip, in %
(``TracedSlice.decode_hbm_roofline``); moves the cell's TPOT tail."""


def read(run):
    return run.decode_hbm_roofline()
