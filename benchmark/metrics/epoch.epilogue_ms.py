"""Host ms per epoch in the resident loop's deferred epoch epilogue: eth1
votes, historical roots, the sync-committee rotation and the slot mirror
(the program's span `engine.epilogue`)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "engine.epilogue", "epochs")
