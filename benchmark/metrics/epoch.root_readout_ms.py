"""Host ms per epoch in reading the state root's field roots off the device
(the program's span `engine.root_readout`): the launch of the field-root
program and the wait for every root program queued before it."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "engine.root_readout", "epochs")
