"""Pair-matvecs the PCG solves ran per pair saved in the window: the
program's ``matvec_pairs`` counter over the window's builds. A block
runs until its slowest pair converges, so this reads above
``pcg_iters_per_pair`` by the lockstep waste. Nothing where the program
keeps no counters."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    if c is None or not c.get("matvec_pairs"):
        return None
    return c["matvec_pairs"] / len(run.saved.values)
