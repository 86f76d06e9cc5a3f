"""Device time of the sparse XMV kernels (``xmv_gram_tile``,
``xmv_row_panel``) over the device's busy time; nothing where they did
not run."""
import devtrace

KERNELS = ("xmv_gram_tile", "xmv_row_panel")


def read(run):
    kernel = devtrace.op_seconds(run.trace, KERNELS)
    return 100.0 * kernel / run.trace["busy_s"] if kernel > 0 else None
