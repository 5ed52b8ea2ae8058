#!/usr/bin/env python3
"""Where a cell's served step spends its time, phase by phase, on the chip.

    python bench/phases.py --workload <cell> --seed <n> --seconds <s>

One traced run of the cell as ``run.py --trace 1`` makes it, in this one
process; after its result line, one more JSON line ``{"phases": ...}``: the
``spec_step``'s device milliseconds per execution under each of its scopes
(``spec.draft``, ``spec.verify``, ``spec.commit``, ``unscoped``), the host
milliseconds of an ``engine.step`` outside its wait for the done flags, the
device's idle seconds under each innermost host span, and the longest idle
gaps named by those spans (``harness/phases.py``).  Exits non-zero without
a TPU.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import jax
    from harness import phases, runner, spec, tracing
    root = os.path.dirname(BENCH)
    cell = spec.load_cell(root, args.workload)
    devices = runner.require_devices(cell.chips)
    runner.enable_cache(root)
    # the scopes live in the ops' metadata, which the compile cache's
    # default key leaves out: keyed on it, this run cannot load a step
    # compiled without them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    runner.import_program(root)
    # the run reduces its trace with tracing.reduce_file and then deletes
    # it: read the phases from the file in the same call
    base = tracing.reduce_file

    def reduce_file(path):
        out = base(path)
        out["phases"] = phases.reduce_file(path)
        return out
    tracing.reduce_file = reduce_file
    try:
        out, run = runner.execute(root, cell, args.seed, float(args.seconds),
                                  True, devices, runner.process_age)
    finally:
        tracing.reduce_file = base
    runner.report(out)
    r = run.trace["phases"]
    print(json.dumps({"phases": {**phases.summary(r),
                                 "idle_by_span": r["idle_by_span"],
                                 "gaps": r["gaps"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
