"""Host ms per epoch in refreshing the device state root's trees for the
epochs stepped since the last root, or building them where there are none
(the program's span `engine.root_refresh`)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "engine.root_refresh", "epochs")
