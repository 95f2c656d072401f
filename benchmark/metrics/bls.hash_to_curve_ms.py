"""Host ms per signature set in hashing its message to G2 (the program's
span `bls.prep.hash_to_curve`)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "bls.prep.hash_to_curve", "sets")
