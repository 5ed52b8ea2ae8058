"""Reduction of one profiler trace (an ``.xplane.pb``) to what the per-layer
metrics read.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with a line
``XLA Modules`` -- one event per execution of a compiled program, named
``jit_<function>(<fingerprint>)`` -- and a line ``XLA Ops`` -- one event per
operation, named by its HLO instruction (``%spec_attention.7 = ...``; a
Pallas kernel's custom call carries the kernel's name).  The host plane
holds the benchmark's own spans (``bench.*``) on the same clock.  Only
events inside the ``bench.window`` span count.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

import numpy as np

HOST_SPANS = ("bench.step", "bench.submit", "bench.results")
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


def reduce_profile(pd) -> Dict:
    """``pd``: a ``jax.profiler.ProfileData``.  Returns, in seconds:
    ``window_s``; ``busy_s`` (union of operations, mean over chips);
    ``modules`` and ``kernels`` ({name: {"s", "n"}}, summed over chips);
    ``top_ops`` (the 10 operation names, numbering stripped, with the most
    self time) and ``gaps`` (the 10 longest idle gaps, each named by the
    host span it fell in)."""
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
    lo, hi = window
    modules = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    kernels = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    ops = collections.Counter()
    busy_ns, gaps = 0, []
    for plane in devices:
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        m = modules[module(e.name)]
                        m["s"] += e.duration_ns * 1e-9
                        m["n"] += 1
            elif line.name == "XLA Ops":
                b, g = _ops_line(line, lo, hi, ops, kernels)
                busy_ns += b
                gaps += g
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy_ns * 1e-9 / len(devices),
            "chips": len(devices),
            "modules": dict(modules), "kernels": dict(kernels),
            "top_ops": [[n, s] for n, s in ops.most_common(10)],
            "gaps": [[_span_at((a + b) // 2, host_spans), (b - a) * 1e-9]
                     for a, b in gaps[:10]]}


def _ops_line(line, lo: int, hi: int, ops, kernels):
    """Fold one ``XLA Ops`` line into ``ops`` (self seconds per name) and
    ``kernels``; returns its busy nanoseconds inside [lo, hi) and its idle
    gaps there."""
    names: Dict[str, int] = {}        # event name -> index into `kinds`
    kinds: List[Tuple[str, bool]] = []
    starts, ends, ids = [], [], []
    for e in line.events:
        a, b = int(e.start_ns), int(e.end_ns)
        if b <= lo or a >= hi:
            continue
        n = e.name
        i = names.get(n)
        if i is None:
            i = names[n] = len(kinds)
            kinds.append((instruction(n),
                          'custom_call_target="tpu_custom_call"' in n))
        if kinds[i][1]:
            k = kernels[kinds[i][0]]
            k["s"] += (b - a) * 1e-9
            k["n"] += 1
        starts.append(max(a, lo))
        ends.append(min(b, hi))
        ids.append(i)
    if not starts:
        return 0, [(lo, hi)]
    st = np.asarray(starts, np.int64)
    en = np.asarray(ends, np.int64)
    order = np.lexsort((-en, st))          # by start, the outer one first
    st, en, idx = st[order], en[order], np.asarray(ids)[order]
    # union: a new interval starts where the start passes every end so far
    run_end = np.maximum.accumulate(en)
    new = np.ones(len(st), bool)
    new[1:] = st[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], len(st)) - 1
    u_lo, u_hi = st[first], run_end[last]
    busy = int((u_hi - u_lo).sum())
    edges = np.concatenate([[lo], np.stack([u_lo, u_hi], 1).ravel(), [hi]])
    gaps = [(int(a), int(b)) for a, b in zip(edges[0::2], edges[1::2])
            if b > a]
    # self time: an operation nested in another (a while's body) is taken
    # off its parent
    dur = (en - st).astype(np.float64)
    self_ns = dur.copy()
    stack: List[int] = []
    for j, (a, b) in enumerate(zip(st.tolist(), en.tolist())):
        while stack and en[stack[-1]] <= a:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= dur[j]
        stack.append(j)
    per = np.bincount(idx, weights=self_ns, minlength=len(kinds))
    for i, (name, _) in enumerate(kinds):
        ops[name] += per[i] * 1e-9
    return busy, gaps


def _span_at(t: int, spans) -> str:
    """The innermost benchmark span on the host at time ``t``."""
    inside = [(b - a, name) for a, b, name in spans if a <= t < b]
    return min(inside)[1] if inside else "host.other"


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
