"""Blocking device-to-host reads the program made per block of the
window: its ``host_syncs`` counter over the window's builds. Nothing
where the program keeps no counters."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    return None if c is None else c.get("host_syncs", 0) / run.window.blocks
