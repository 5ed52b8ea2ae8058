#!/usr/bin/env python3
"""Readings that set a cell's output-check limit, on the chip.

    python bench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <n> ...]

For every seed, one run of the cell as ``run.py`` makes it (the same
window, at the cell's own load), in this one process, printing the widest
gap of a served token below the float32 reference (``served_gap``).  For
the control seeds it also prints ``control_gap``: the widest gap, at the
same positions of the same sampled requests, of the token the reference
computed in float8 puts first -- the precision one step below the
configuration's.  The limit in ``bench/limits/<cell>.json`` lies between
the largest ``served_gap`` and the smallest ``control_gap`` (PERF.md).
One JSON line per seed; exits non-zero without a TPU.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from harness import runner, spec
    root = os.path.dirname(BENCH)
    cell = spec.load_cell(root, args.workload)
    devices = runner.require_devices(cell.chips)
    runner.enable_cache(root)
    runner.import_program(root)
    for seed in args.seeds + args.control_seeds:
        ctl = seed in args.control_seeds
        out, _ = runner.execute(root, cell, seed, float(args.seconds),
                                False, devices, runner.process_age,
                                control=ctl)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          **{k: v["value"] for k, v in out["checks"].items()},
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
