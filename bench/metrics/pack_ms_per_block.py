"""Host time of the pack cache per block of the traced window: the self
time of the program's ``mgk.pack`` spans (building a graph's packs on a
miss) and ``mgk.stack`` spans (per-graph slices, pad-and-stack and the
transfer of a block's packs), in ms. Nothing where no pack is used."""
import progtrace


def read(run):
    return progtrace.self_ms_per_block(run, ("mgk.pack", "mgk.stack"))
