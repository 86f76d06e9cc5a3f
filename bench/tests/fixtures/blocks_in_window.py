"""A per-layer metric that a later change could add as one file: the
blocks finished in the window (self-test fixture)."""


def read(run):
    return run.window.blocks
