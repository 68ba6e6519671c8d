"""Device milliseconds of one ``jit_decode`` execution, averaged over
the chips (``TracedSlice.decode_device_ms``); moves the cell's TPOT
tail."""


def read(run):
    return run.decode_device_ms()
