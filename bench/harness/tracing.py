"""Reduction of one profiler trace (an ``.xplane.pb``) to what the per-layer
metrics read.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with a line
``XLA Modules`` -- one event per execution of a compiled program, named
``jit_<function>(<fingerprint>)`` -- and a line ``XLA Ops`` -- one event per
operation, named by its HLO instruction (``%spec_attention.7 = ...``; a
Pallas kernel's custom call carries the kernel's name).  The host plane
holds the benchmark's own spans (``bench.*``) on the same clock.  Only
events inside the ``bench.window`` span count.  The profiler keeps a bounded
number of device events (about six million on a v5e): a plane that filled
up stops while the host still dispatches steps.  Where the host plane shows
a whole ``bench.step`` after a chip's last operation, that plane lost the
rest of the window, and the window is cut where the earliest such chip's
last operation ends (``kept_end``); a device that merely idles at the end
keeps its idle tail.  Every reading, busy and idle time, programs, kernels
and gaps, is taken over that kept window.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

import numpy as np

STEP_SPAN = "bench.step"
HOST_SPANS = (STEP_SPAN, "bench.submit", "bench.results")
WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"%([\w\-]+?)(?:\.\d+)?\s*=")
_MODULE = re.compile(r"(?:jit_)?([\w\-]+)")


def instruction(name: str) -> str:
    """``%spec_attention.7 = bf16[...] custom-call(...)`` -> ``spec_attention``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name.split(" ")[0]


def module(name: str) -> str:
    """``jit_spec_step(1387...)`` -> ``spec_step``."""
    m = _MODULE.match(name)
    return m.group(1) if m else name


def kept_end(lines, lo: int, hi: int,
             steps: List[Tuple[int, int]]) -> Tuple[int, List[int]]:
    """The end of the part of the window [lo, hi) that every chip's plane
    holds, and each chip's last operation's end (ns).  A chip's plane was
    cut short where one of the host's ``steps`` (start, end) lies wholly
    after its last operation, since every step runs work on every chip;
    the window then ends with the earliest such plane, else at ``hi``.
    ``lines``: each chip's ``_Ops`` lines read over [lo, hi)."""
    last = [max([lo] + [o.last_end() for o in chip]) for chip in lines]
    cut = [t for t in last if any(t < a and b <= hi for a, b in steps)]
    return min([hi] + cut), last


def reduce_profile(pd) -> Dict:
    """``pd``: a ``jax.profiler.ProfileData``.  Returns, in seconds:
    ``window_s`` (the kept window, ``kept_end``), ``span_s`` (the whole
    ``bench.window`` span) and ``last_op_s`` (each chip's last operation's
    end, from the window's start); ``events`` (device operations in the kept
    window, all chips); ``busy_s`` (union of operations, mean over chips)
    and ``busy_by_chip``; ``modules`` and ``kernels`` ({name: {"s", "n"}},
    summed over chips); ``ops_by_chip`` (self time per operation name,
    numbering stripped, one dict per chip); ``top_ops`` (the 10 operation
    names with the most self time, summed over chips) and ``gaps`` (the 10
    longest idle gaps, each named by the host span it fell in)."""
    host_spans: List[Tuple[int, int, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (int(e.start_ns), int(e.end_ns))
                elif e.name in HOST_SPANS:
                    host_spans.append((int(e.start_ns), int(e.end_ns),
                                       e.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, span_hi = window
    lines = [[_Ops(line, lo, span_hi) for line in plane.lines
              if line.name == "XLA Ops"] for plane in devices]
    steps = [(a, b) for a, b, n in host_spans if n == STEP_SPAN]
    hi, last = kept_end(lines, lo, span_hi, steps)
    modules = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    kernels = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    by_chip, busy, gaps, events = [], [], [], 0
    for plane, chip in zip(devices, lines):
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        m = modules[module(e.name)]
                        m["s"] += e.duration_ns * 1e-9
                        m["n"] += 1
        ops = collections.Counter()
        busy_ns = 0
        for o in chip:
            b, g, n = o.fold(lo, hi, ops, kernels)
            busy_ns += b
            gaps += g
            events += n
        by_chip.append(dict(ops))
        busy.append(busy_ns * 1e-9)
    gaps.sort(key=lambda g: g[0] - g[1])
    total = sum((collections.Counter(o) for o in by_chip),
                collections.Counter())
    return {"window_s": (hi - lo) * 1e-9, "span_s": (span_hi - lo) * 1e-9,
            "last_op_s": [(t - lo) * 1e-9 for t in last], "events": events,
            "busy_s": sum(busy) / len(devices), "busy_by_chip": busy,
            "chips": len(devices),
            "modules": dict(modules), "kernels": dict(kernels),
            "ops_by_chip": by_chip,
            "top_ops": [[n, s] for n, s in total.most_common(10)],
            "gaps": [[_span_at((a + b) // 2, host_spans), (b - a) * 1e-9]
                     for a, b in gaps[:10]]}


class _Ops:
    """The operations of one ``XLA Ops`` line that overlap [lo, hi), as
    recorded: start and end (ns) and an index into ``kinds``, (name with
    numbering stripped, is a kernel's custom call) per event name."""

    def __init__(self, line, lo: int, hi: int):
        names: Dict[str, int] = {}
        self.kinds: List[Tuple[str, bool]] = []
        starts, ends, ids = [], [], []
        for e in line.events:
            a, b = int(e.start_ns), int(e.end_ns)
            if b <= lo or a >= hi:
                continue
            n = e.name
            i = names.get(n)
            if i is None:
                i = names[n] = len(self.kinds)
                self.kinds.append((instruction(n),
                                   'custom_call_target="tpu_custom_call"'
                                   in n))
            starts.append(a)
            ends.append(b)
            ids.append(i)
        self.st = np.asarray(starts, np.int64)
        self.en = np.asarray(ends, np.int64)
        self.ids = np.asarray(ids, np.int64)

    def last_end(self) -> int:
        return int(self.en.max()) if self.en.size else 0

    def fold(self, lo: int, hi: int, ops, kernels):
        """Fold the operations that overlap [lo, hi) into ``ops`` (self
        seconds per name, clipped to it) and ``kernels`` (whole calls);
        returns the busy nanoseconds inside [lo, hi), the idle gaps there
        and the number of operations."""
        keep = (self.en > lo) & (self.st < hi)
        if not keep.any():
            return 0, [(lo, hi)], 0
        idx = self.ids[keep]
        call = (self.en - self.st)[keep]
        n_kinds = len(self.kinds)
        k_s = np.bincount(idx, weights=call, minlength=n_kinds)
        k_n = np.bincount(idx, minlength=n_kinds)
        for i, (name, kernel) in enumerate(self.kinds):
            if kernel and k_n[i]:
                kernels[name]["s"] += k_s[i] * 1e-9
                kernels[name]["n"] += int(k_n[i])
        st = np.maximum(self.st[keep], lo)
        en = np.minimum(self.en[keep], hi)
        order = np.lexsort((-en, st))      # by start, the outer one first
        st, en, idx = st[order], en[order], idx[order]
        # union: a new interval starts where the start passes every end
        run_end = np.maximum.accumulate(en)
        new = np.ones(len(st), bool)
        new[1:] = st[1:] > run_end[:-1]
        first = np.flatnonzero(new)
        last = np.append(first[1:], len(st)) - 1
        u_lo, u_hi = st[first], run_end[last]
        busy = int((u_hi - u_lo).sum())
        edges = np.concatenate([[lo], np.stack([u_lo, u_hi], 1).ravel(),
                                [hi]])
        gaps = [(int(a), int(b)) for a, b in zip(edges[0::2], edges[1::2])
                if b > a]
        # self time: an operation nested in another (a while's body) is
        # taken off its parent
        dur = (en - st).astype(np.float64)
        self_ns = dur.copy()
        stack: List[int] = []
        for j, (a, b) in enumerate(zip(st.tolist(), en.tolist())):
            while stack and en[stack[-1]] <= a:
                stack.pop()
            if stack:
                self_ns[stack[-1]] -= dur[j]
            stack.append(j)
        per = np.bincount(idx, weights=self_ns, minlength=n_kinds)
        for i, (name, _) in enumerate(self.kinds):
            if per[i]:
                ops[name] += per[i] * 1e-9
        return busy, gaps, len(st)


def _span_at(t: int, spans) -> str:
    """The innermost benchmark span on the host at time ``t``."""
    inside = [(b - a, name) for a, b, name in spans if a <= t < b]
    return min(inside)[1] if inside else "host.other"


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
