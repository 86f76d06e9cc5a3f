"""Share of the roofline reached by the sparse XMV kernels: the least
time the window's pair-matvecs could take on this chip (bench/work.py,
counted from the graphs) over the device time of the kernels' events.
Nothing where they did not run."""
import devtrace
import work

KERNELS = ("xmv_gram_tile", "xmv_row_panel")


def read(run):
    kernel = devtrace.op_seconds(run.trace, KERNELS)
    if kernel <= 0:
        return None
    s = run.saved
    flops, bytes_ = work.window_work(s.rows, s.cols, s.iterations,
                                     run.nnz, run.nodes, run.edge_flops)
    return 100.0 * work.roofline_seconds(flops, bytes_, run.peaks) / kernel
