"""Process start to the window: TPU start-up, the dataset, bucketing,
tracing and compiling (or loading from the cache), the warm-up blocks."""


def read(run):
    return run.setup_s
