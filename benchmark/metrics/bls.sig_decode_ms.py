"""Host ms per signature set in decompressing its G2 signature, a square
root and the subgroup check (the program's span `bls.prep.sig_decode`)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "bls.prep.sig_decode", "sets")
