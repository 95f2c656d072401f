"""Host ms per epoch in the resident loop's dispatch and aux readout
(the program's spans `engine.dispatch` and `engine.aux_readout`)."""


def read(run):
    epochs = run.work.get("epochs")
    spans = run.spans("engine.dispatch") + run.spans("engine.aux_readout")
    if not epochs or not spans:
        return None
    return 1000.0 * sum(s["duration"] for s in spans) / epochs
