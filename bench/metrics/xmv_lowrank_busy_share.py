"""Device time of the lowrank XMV (operations in the named scope
``xmv_lowrank``) over the device's busy time in the traced window;
nothing where no operation carries the scope."""
import progtrace


def read(run):
    r = progtrace.of_run(run)
    if r is None or r["scope_s"] <= 0:
        return None
    return 100.0 * r["scope_s"] / r["busy_s"]
