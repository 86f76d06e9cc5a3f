"""Bytes the program put on the device per block of the window (pair
batches and packs): its ``h2d_bytes`` counter over the window's builds,
in KiB. Nothing where the program keeps no counters."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    return None if c is None else \
        c.get("h2d_bytes", 0) / 1024.0 / run.window.blocks
