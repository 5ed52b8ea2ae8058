"""Which requests each step of the window served, and lower bounds on the
work it did for them, from what the host sees without a sync of its own.

A request admitted in step ``a`` is in the verify call of steps ``a`` ..
``a + calls - 1`` (``calls`` from its stats at retirement; a request still
open is counted to the window's last step).  Its cache then holds its
bucketed prompt plus the tokens committed so far; every call commits at
least one token, so at step ``s`` it holds at least ``bucket + (s - a)``
positions.  The bounds can understate work, never overstate it.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def bucket_of(n: int, buckets) -> int:
    return min((b for b in buckets if b >= n), default=max(buckets))


def window(run) -> Tuple[float, float]:
    """The window on the benchmark's clock.  In a traced run, the traced
    part of it: from the start of its trace to the end of the part that
    every chip's trace holds (``tracing.kept_end``), so that work counted
    here and time read from the trace cover the same steps."""
    if run.trace:
        return run.trace_t0, min(run.t1, run.trace_t0
                                 + run.trace["window_s"])
    return run.t0, run.t1


def window_steps(run) -> List[int]:
    """Steps that returned inside the window (the traced part of it, in a
    traced run)."""
    lo, hi = window(run)
    return [s for s, t in enumerate(run.step_times) if lo < t <= hi]


def live_by_step(run) -> Dict[int, List[int]]:
    """step -> lower bounds on the live cache length of each request in
    its verify call, for the window's steps."""
    steps = window_steps(run)
    if not steps:
        return {}
    first, last = steps[0], steps[-1]
    out: Dict[int, List[int]] = {s: [] for s in steps}
    for r in run.recs:
        if r.admit_step is None or r.error:
            continue
        end = (r.admit_step + r.calls - 1 if r.completed is not None
               else last)
        b = bucket_of(r.req.prompt_tokens, run.traffic["buckets"])
        for s in range(max(r.admit_step, first), min(end, last) + 1):
            out[s].append(b + s - r.admit_step)
    return out


def committed_in_window(run) -> List[tuple]:
    """Per request: (lower bound on output tokens committed by the
    window's verify calls, prompt tokens, prefilled in the window)."""
    steps = window_steps(run)
    if not steps:
        return []
    first, last = steps[0], steps[-1]
    w1 = run.spec_w + 1
    out = []
    for r in run.recs:
        if r.admit_step is None or r.error:
            continue
        done = r.completed is not None
        end = r.admit_step + r.calls - 1 if done else last
        m = max(0, min(end, last) - max(r.admit_step, first) + 1)
        tokens = m
        if done and m:
            # calls before or after the window committed at most w+1 each
            tokens = max(m, (r.new_tokens - 1) - (r.calls - m) * w1)
        out.append((tokens, r.req.prompt_tokens, first <= r.admit_step <= last))
    return out


def percentile(values, q: float):
    """Linear-interpolated percentile; None for no values."""
    v = np.asarray(list(values), float)
    return float(np.percentile(v, q)) if v.size else None
