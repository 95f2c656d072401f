"""Host ms per signature set in checking the flush's signatures for G2
membership on the device (the program's span `bls.flush.sig_subgroup`:
their encoding, the one launch and its readback)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "bls.flush.sig_subgroup", "sets")
