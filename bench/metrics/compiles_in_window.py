"""Backend compiles on the main thread inside the window. Should be 0:
set-up warms every block shape the window uses."""


def read(run):
    return run.window.compiles
