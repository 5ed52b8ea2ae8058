#!/usr/bin/env python3
"""A cut of a cell's device trace, as test data for the trace's readers.

    python bench/excerpt.py --workload <cell> --seed <n> --seconds <s> \
        --out <file.textproto> [--ms 20]

One traced run of the cell as ``run.py --trace 1`` makes it, in this one
process, printing its result line; then the first ``--ms`` milliseconds of
its traced window go to ``--out`` as a text ``XSpace``, as the files under
``bench/tests/data/`` are: every chip's ``XLA Modules`` and ``XLA Ops``
events that overlap that cut, each operation's name cut to ``%name =
op()`` (a kernel's ``custom_call_target`` kept), and the host's
``bench.*`` spans clipped to it, the window span ending with the cut.
Exits non-zero without a TPU.
"""
import argparse
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"%([\w\-.]+?)\s*=")
KERNEL = 'custom_call_target="tpu_custom_call"'
LINES = ("XLA Modules", "XLA Ops")


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _op(name: str) -> str:
    m = _NAME.match(name)
    if not m:
        return name
    return f"%{m.group(1)} = op()" + (f", {KERNEL}" if KERNEL in name
                                      else "")


def _plane(pid: int, name: str, lines, lo: int) -> str:
    """``lines``: [(line name, [(event name, start ns, end ns)])]."""
    meta = {}
    out = [f"planes {{ id: {pid} name: {_quote(name)}"]
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f"  lines {{ id: {lid} name: {_quote(lname)} "
                   f"timestamp_ns: {lo}")
        for n, a, b in events:
            k = meta.setdefault(n, len(meta) + 1)
            out.append(f"    events {{ metadata_id: {k} offset_ps: "
                       f"{(a - lo) * 1000} duration_ps: {(b - a) * 1000} }}")
        out.append("  }")
    out += [f"  event_metadata {{ key: {k} value {{ id: {k} name: "
            f"{_quote(n)} }} }}" for n, k in meta.items()]
    out.append("}")
    return "\n".join(out)


def excerpt(pd, ms: float) -> str:
    """The first ``ms`` milliseconds of ``pd``'s ``bench.window`` span, as
    a text ``XSpace``."""
    from harness import tracing
    host, window = [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    a, b = int(e.start_ns), int(e.end_ns)
                    if e.name == tracing.WINDOW_SPAN:
                        window = (a, b)
                    elif e.name in tracing.HOST_SPANS:
                        host.append((e.name, a, b))
    lo = window[0]
    hi = min(window[1], lo + int(ms * 1e6))
    planes, pid = [], 1
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = []
        for line in plane.lines:
            if line.name in LINES:
                cut = _op if line.name == "XLA Ops" else (lambda n: n)
                lines.append((line.name, [
                    (cut(e.name), int(e.start_ns), int(e.end_ns))
                    for e in line.events
                    if int(e.end_ns) > lo and int(e.start_ns) < hi]))
        planes.append(_plane(pid, plane.name, lines, lo))
        pid += 1
    spans = [(tracing.WINDOW_SPAN, lo, hi)] + sorted(
        (n, max(a, lo), min(b, hi)) for n, a, b in host if b > lo and a < hi)
    planes.append(_plane(pid, "/host:CPU", [("spans", spans)], lo))
    return "\n".join(planes) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from harness import runner, spec, tracing
    root = os.path.dirname(BENCH)
    cell = spec.load_cell(root, args.workload)
    devices = runner.require_devices(cell.chips)
    runner.enable_cache(root)
    runner.import_program(root)
    # the run reduces its trace with tracing.reduce_file and then deletes
    # it: cut the excerpt from the file in the same call
    base = tracing.reduce_file

    def reduce_file(path):
        from jax.profiler import ProfileData
        with open(args.out, "w") as f:
            f.write(excerpt(ProfileData.from_file(path), args.ms))
        return base(path)
    tracing.reduce_file = reduce_file
    try:
        out, _ = runner.execute(root, cell, args.seed, float(args.seconds),
                                True, devices, runner.process_age)
    finally:
        tracing.reduce_file = base
    runner.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
