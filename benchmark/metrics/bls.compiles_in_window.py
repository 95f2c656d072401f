"""Compilations (the program's CompileTracker) that began inside the window."""


def read(run):
    return run.compiles_in_window if run.work.get("sets") is not None else None
