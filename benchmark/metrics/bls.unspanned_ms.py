"""Host ms per signature set inside the deferred flush (the program's span
`bls.deferred_flush`) that no span nested in it on its thread covers: the
flush's time that the program's spans leave unnamed."""
from benchmark.spans import self_seconds


def read(run):
    sets = run.work.get("sets")
    flushes = run.spans("bls.deferred_flush")
    if not sets or not flushes:
        return None
    every = run.spans(None)  # all the window's spans, to find the nested ones
    return 1000.0 * sum(self_seconds(f, every) for f in flushes) / sets
