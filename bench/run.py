#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for: the cell (an entry of ``BENCHMARK.json``) names its model
configuration and traffic mix, which are found by name under ``bench/``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Every run checks a
sample of the served tokens against the plain float32 reference and prints
each number compared, with its limit, as the last lines on standard error
and under ``checks`` at the end of the result line.  Without a TPU, or with
fewer chips than the cell needs, it exits non-zero and prints nothing on
standard output.
"""
import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the traffic (the weights are the "
                         "configuration's)")
    ap.add_argument("--seconds", type=int, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window, report per-layer metrics")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from harness import runner
    return runner.main(os.path.dirname(BENCH), args)


if __name__ == "__main__":
    sys.exit(main())
