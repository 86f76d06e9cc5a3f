"""The whole window's share of the chips' peak FLOP/s: the FLOPs the
window's pair-matvecs need (bench/work.py, counted from the graphs),
over the traced window's length times the chips' peak. Bounds every
kernel's share whatever path the solve takes."""
import work


def read(run):
    s = run.saved
    flops, _ = work.window_work(s.rows, s.cols, s.iterations, run.nnz,
                                run.nodes, run.edge_flops)
    return 100.0 * flops / (run.trace["window_s"] * run.chips
                            * run.peaks["flops_per_s"])
