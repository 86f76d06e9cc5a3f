"""Device idle time of the traced window per block finished in it: what
the host's block loop (batching, packing, dispatch, the store) costs the
device for each block."""


def read(run):
    t = run.trace
    return 1000.0 * (t["window_s"] - t["busy_s"]) / run.window.blocks
