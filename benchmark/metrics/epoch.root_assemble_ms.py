"""Host ms per epoch in assembling the state root on the host from the
device's field roots and the host-owned fields (the program's span
`engine.root_assemble`)."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "engine.root_assemble", "epochs")
