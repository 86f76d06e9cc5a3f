"""Graph pairs solved to the cell's tolerance and saved, over all the
time of the window (host clock)."""


def read(run):
    return len(run.saved.values) / run.window.seconds
