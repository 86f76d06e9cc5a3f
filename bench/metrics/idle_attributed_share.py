"""Share of the device's idle time in the traced window that falls in a
stage of the program's block loop: under an ``mgk.`` span other than
the whole build or block (``mgk.build``, ``mgk.block``), each idle gap
split where spans begin and end. Nothing where the program opens no
such span."""
import progtrace

OUTER = ("mgk.build", "mgk.block")


def read(run):
    r = progtrace.of_run(run)
    if r is None:
        return None
    staged = sum(t for name, t in r["idle"].items()
                 if name.startswith("mgk.") and name not in OUTER)
    total = sum(r["idle"].values())
    return 100.0 * staged / total if staged > 0 else None
