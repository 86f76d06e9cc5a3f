#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload nws96.lowrank --seed 7 --seconds 30 \
        --trace 0

From the root of a checkout. The cells, their metrics and bounds are in
``BENCHMARK.json``; see ``PERF.md`` for what each measures. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is the result as one JSON object; the
numbers that decide ``correct``, each beside its limit, close standard
error and the result. Without a TPU, or with fewer chips than the cell
asks for, it exits with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.join(ROOT, "src"))
# libtpu would log under /tmp; a run writes only inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
