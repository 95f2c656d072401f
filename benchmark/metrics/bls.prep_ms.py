"""Host ms per signature set in the flush's per-request prep loop (the
program's span `bls.prep`): signature decompression, hash-to-curve and the
pubkey aggregation of each set."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "bls.prep", "sets")
