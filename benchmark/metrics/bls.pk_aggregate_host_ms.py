"""Host ms per signature set in aggregating the set's pubkeys (the program's
span `bls.prep.aggregate`): the wall time of the prep's aggregation, the
`msm` class's dispatch and its device round trip included; the device's
own part is `bls.pk_aggregate_ms`."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "bls.prep.aggregate", "sets")
