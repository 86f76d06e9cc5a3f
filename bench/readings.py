#!/usr/bin/env python3
"""Readings for the limits of the correctness check: the numbers that
``bench/run.py`` compares, read for the program and for each control of
a cell over many seeds, in one process so that set-up compiles once.

    python3 bench/readings.py --workload nws96.lowrank --seeds 1-12 \
        --seconds 10 --control none --control loose-tol

A control is named in the cell's configuration or traffic file
(``"controls"``): driver settings that break a guarantee the
configuration states or that lower the precision. ``none`` is the
program as the cell runs it. One JSON line per run on standard output.
The benchmark's own runs never run a control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.join(ROOT, "src"))
# libtpu would log under /tmp; a run writes only inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="append", default=[])
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    controls = {**cell.config.get("controls", {}),
                **cell.traffic.get("controls", {})}
    for name in args.control or ["none"]:
        overrides = None if name == "none" else controls[name]
        for seed in args.seeds:
            r = harness.run_cell(cell, seed, args.seconds, False, devices,
                                 time.perf_counter(), overrides)
            print(json.dumps({"workload": cell.name, "control": name,
                              "seed": seed, "correct": r["correct"],
                              "checks": r["checks"],
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
