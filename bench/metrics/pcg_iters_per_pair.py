"""Mean PCG iterations per pair, from the counts the driver saved with
each of the window's blocks."""


def read(run):
    return float(run.saved.iterations.mean())
