"""Host ms per signature set in the flush's host prep and packing (the
program's span `bls.flush.pack`)."""


def read(run):
    sets = run.work.get("sets")
    spans = run.spans("bls.flush.pack")
    if not sets or not spans:
        return None
    return 1000.0 * sum(s["duration"] for s in spans) / sets
