#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates <r> [<r> ...]

First the cell's traffic as a saturated closed loop (twice as many clients
as slots): the requests it completes per second are the most the system
sustains.  Then the open loop at each given mean rate, each a run as
``run.py`` makes it: the p95 latency and the queue wait show where a
backlog starts to grow.  The cell's ``rate_per_s`` is then set by hand to
4/5 of the knee (PERF.md).  All runs share this one process.
"""
import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from harness import runner, spec
    root = os.path.dirname(BENCH)
    cell = spec.load_cell(root, args.workload)
    devices = runner.require_devices(cell.chips)
    runner.enable_cache(root)
    runner.import_program(root)
    p = cell.traffic_data
    mixes = [("closed", dict(p, loop="closed", clients=2 * p["slots"],
                             pool=8 * p["slots"]))]
    mixes += [(r, dict(p, rate_per_s=r)) for r in args.rates]
    for label, mix in mixes:
        c = dataclasses.replace(cell, traffic_data=mix)
        out, run = runner.execute(root, c, args.seed, float(args.seconds),
                                  False, devices, runner.process_age)
        done = [r for r in run.recs if r.completed is not None
                and r.completed <= run.t1 and not r.error]
        print(json.dumps({
            "rate_per_s": label, "correct": out["correct"],
            "completed_per_s": len(done) / (run.t1 - run.t0),
            "tokens_per_s": sum(r.new_tokens for r in done)
            / (run.t1 - run.t0),
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "queue_wait_p95_s": spec.reader(root, "queue_wait_p95_s")(run)
            if label != "closed" else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
