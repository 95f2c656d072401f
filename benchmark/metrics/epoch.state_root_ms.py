"""Host ms per epoch in the post-epoch state root (the program's span
`engine.state_root`), which its three children `engine.root_refresh`,
`engine.root_readout` and `engine.root_assemble` partition; the deferred
epoch epilogue it drains first is outside it."""
from benchmark.spans import ms_per


def read(run):
    return ms_per(run, "engine.state_root", "epochs")
