"""The program's own spans and scopes in one profiler trace (an
``.xplane.pb``): where the served step's time goes, phase by phase.

``ServingEngine`` marks its host work with ``engine.*`` spans on the host
plane, on the device ops' clock (``engine.step``, ``engine.done_wait``,
``engine.readback``, ``engine.retire``, ``engine.admit``,
``engine.dispatch``), and its ``spec_step`` names its phases in its ops'
``op_name`` (``spec.draft``, ``spec.verify``, ``spec.commit``).  The
``op_name`` is the ``tf_op`` stat of each device op's event metadata, which
``jax.profiler.ProfileData`` does not expose, so ``op_names`` reads it from
the protobuf wire format.

This sits beside ``tracing.py`` and leaves its reduction as it is; as
there, only events inside the ``bench.window`` span count, up to the end of
the part of it that every chip's plane holds (``tracing.kept_end``).
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

import numpy as np

from . import tracing

ENGINE = "engine."          # the program's host spans
STEP_MODULE = "spec_step"   # the program whose ops are split by scope
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?:^|/)(spec\.\w+)")


def span_name(name: str) -> str:
    """A host span's name without the arguments some profilers append to it
    (``engine.admit#request_id=7#`` -> ``engine.admit``)."""
    return name.split("#", 1)[0]


def scope_of(op_name: str) -> str:
    """The first ``spec.*`` scope in an op's ``op_name``, else ``""``."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else ""


def reduce_profile(pd, raw: bytes) -> Dict:
    """``pd``: a ``jax.profiler.ProfileData``; ``raw``: the serialized
    trace it was read from, for the ops' ``op_name``.  Returns, in seconds:
    ``window_s``;
    ``step`` ({"s", "n"}: the ``spec_step`` executions that start in the
    window, summed over chips);
    ``spans`` ({name: {"s", "n"}}: the ``engine.*`` spans that start in the
    window);
    ``scopes`` (self time of the operations of those executions by the
    first ``spec.*`` scope of their ``op_name``, summed over chips; an
    operation whose ``op_name`` names none takes the scope of the operation
    it runs inside, a while loop's body that of the loop; ``unscoped`` the
    rest);
    ``idle_by_span`` (the device's idle time under each innermost host
    span, gaps split at the spans' edges, mean over chips; ``host.other``
    where no span is open) and ``gaps`` (the 10 longest idle gaps, each
    named by the innermost ``bench.*`` or ``engine.*`` span it fell in)."""
    host: List[Tuple[int, int, str]] = []
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
                name = span_name(e.name)
                if name == tracing.WINDOW_SPAN:
                    window = (int(e.start_ns), int(e.end_ns))
                elif name in tracing.HOST_SPANS or name.startswith(ENGINE):
                    host.append((int(e.start_ns), int(e.end_ns), name))
    if window is None:
        raise ValueError(f"no {tracing.WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, span_hi = window
    lines = [[tracing._Ops(line, lo, span_hi) for line in plane.lines
              if line.name == "XLA Ops"] for plane in devices]
    hi = tracing.kept_end(lines, lo, span_hi,
                          [(a, b) for a, b, n in host
                           if n == tracing.STEP_SPAN])[0]
    spans = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    for a, b, name in host:
        if name.startswith(ENGINE) and lo <= a < hi:
            spans[name]["s"] += (b - a) * 1e-9
            spans[name]["n"] += 1
    tf_ops = op_names(raw)
    pieces = _innermost(host, lo, hi)
    step = {"s": 0.0, "n": 0}
    scopes = collections.Counter()
    idle = collections.Counter()
    gaps = []
    for plane, chip in zip(devices, lines):
        execs, programs = [], set()
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                if (lo <= e.start_ns < hi
                        and tracing.module(e.name) == STEP_MODULE):
                    execs.append((int(e.start_ns), int(e.end_ns)))
                    programs.add(_fingerprint(e.name))
                    step["s"] += e.duration_ns * 1e-9
                    step["n"] += 1
        named = {name: scope_of(op) for (name, program), op
                 in tf_ops.get(plane.name, {}).items() if program in programs}
        for line, ops in zip((x for x in plane.lines
                              if x.name == "XLA Ops"), chip):
            _, g, _ = ops.fold(lo, hi, collections.Counter(),
                               collections.defaultdict(
                                   lambda: {"s": 0.0, "n": 0}))
            gaps += g
            _idle_by_piece(pieces, g, idle)
            _scopes_line(line, sorted(execs), named, scopes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"window_s": (hi - lo) * 1e-9, "step": step,
            "spans": dict(spans),
            "scopes": {k: float(v) * 1e-9 for k, v in scopes.items()},
            "idle_by_span": {k: float(v) * 1e-9 / len(devices)
                             for k, v in idle.items()},
            "gaps": [[tracing._span_at((a + b) // 2, host), (b - a) * 1e-9]
                     for a, b in gaps[:10]]}


def summary(r: Dict) -> Dict:
    """Per ``spec_step`` execution: each scope's device milliseconds;
    per ``engine.step``: its host milliseconds outside ``engine.done_wait``
    (the host work between the flags and the next dispatch)."""
    n, s = r["step"]["n"], r["spans"]
    out = {"step_ms": 1e3 * r["step"]["s"] / n if n else None,
           "scope_ms": {k: 1e3 * v / n for k, v in r["scopes"].items()}
           if n else {}}
    step = s.get(ENGINE + "step")
    if step and step["n"]:
        wait = s.get(ENGINE + "done_wait", {"s": 0.0})["s"]
        out["host_step_ms"] = 1e3 * (step["s"] - wait) / step["n"]
    return out


def _scopes_line(line, execs, named, scopes) -> None:
    """Add to ``scopes`` the self nanoseconds of the operations of one
    ``XLA Ops`` line that start inside one of the ``execs`` intervals
    (sorted), by the scope ``named`` gives their event name."""
    if not execs:
        return
    e_lo = np.asarray([a for a, _ in execs], np.int64)
    e_hi = np.asarray([b for _, b in execs], np.int64)
    ops = []
    for e in line.events:
        a = int(e.start_ns)
        k = int(np.searchsorted(e_lo, a, side="right")) - 1
        if k >= 0 and a < e_hi[k]:
            ops.append((a, int(e.end_ns), named.get(e.name, "")))
    ops.sort(key=lambda o: (o[0], -o[1]))   # by start, the outer one first
    self_ns = [b - a for a, b, _ in ops]
    scope = [s for _, _, s in ops]
    stack: List[int] = []
    for j, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            # nested (a while's body): off its parent's self time, and in
            # its parent's scope where it names none
            self_ns[stack[-1]] -= b - a
            scope[j] = scope[j] or scope[stack[-1]]
        stack.append(j)
    for s, ns in zip(scope, self_ns):
        scopes[s or UNSCOPED] += ns


def _innermost(spans, lo: int, hi: int):
    """[lo, hi) cut at every host span's edges: (edges, names), piece ``i``
    being [edges[i], edges[i+1]) under the innermost span ``names[i]``."""
    edges = np.unique(np.clip(np.asarray(
        [lo, hi] + [t for a, b, _ in spans for t in (a, b)], np.int64),
        lo, hi))
    mids = (edges[:-1] + edges[1:]) // 2
    label = np.full(len(mids), -1)
    # the longest first, so that a span nested in it overwrites it
    for k in sorted(range(len(spans)),
                    key=lambda k: spans[k][0] - spans[k][1]):
        a, b, _ = spans[k]
        label[np.searchsorted(mids, a):np.searchsorted(mids, b)] = k
    names = [spans[k][2] if k >= 0 else "host.other" for k in label]
    return edges, names


def _idle_by_piece(pieces, gaps, idle) -> None:
    """Add to ``idle`` the nanoseconds of the sorted, disjoint idle
    ``gaps`` that fall in each host piece (``_innermost``)."""
    if not gaps:
        return
    edges, names = pieces
    g = np.asarray(gaps, np.int64)
    cum = np.concatenate([[0], np.cumsum(g[:, 1] - g[:, 0])])
    # idle nanoseconds before each edge
    i = np.searchsorted(g[:, 0], edges, side="right")
    last = np.maximum(i - 1, 0)
    part = np.where(i > 0, np.minimum(edges, g[last, 1]) - g[last, 0], 0)
    before = np.where(i > 0, cum[last] + part, 0)
    for name, ns in zip(names, np.diff(before).tolist()):
        if ns:
            idle[name] += ns


def _fingerprint(name: str) -> int:
    """``jit_spec_step(1387...)`` -> 1387..., the program's id."""
    m = re.search(r"\((\d+)\)", name)
    return int(m.group(1)) if m else -1


def op_names(raw: bytes) -> Dict[str, Dict[Tuple[str, int], str]]:
    """{device plane: {(op event name, program id): tf_op}} from a
    serialized trace: the ``tf_op`` and ``program_id`` stats of each
    plane's event metadata, read straight from the protobuf wire format
    (``XSpace`` in the profiler's ``xplane.proto``; only the metadata is
    decoded, the events are skipped)."""
    out = {}
    for f, v in _fields(raw, 0, len(raw)):
        if f != 1:                                    # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for g, w in _fields(raw, *v):
            if g == 2:                                # XPlane.name
                name = raw[w[0]:w[1]].decode()
            elif g == 4:                              # event_metadata
                events.append(w)
            elif g == 5:                              # stat_metadata
                sid, sname = 0, ""
                for h, x in _fields(raw, *_entry_value(raw, w)):
                    if h == 1:
                        sid = x
                    elif h == 2:
                        sname = raw[x[0]:x[1]].decode()
                stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        ops = out.setdefault(name, {})
        for w in events:
            ename, stats = "", {}
            for h, x in _fields(raw, *_entry_value(raw, w)):
                if h == 2:                            # XEventMetadata.name
                    ename = raw[x[0]:x[1]].decode(errors="replace")
                elif h == 5:                          # XEventMetadata.stats
                    key, val = None, None
                    for k, y in _fields(raw, *x):
                        if k == 1:
                            key = stat_names.get(y)
                        elif k == 5:                  # str_value
                            val = raw[y[0]:y[1]].decode(errors="replace")
                        elif k in (3, 4):             # uint64, int64
                            val = y
                        elif k == 7:                  # ref_value
                            val = stat_names.get(y, "")
                    stats[key] = val
            if "tf_op" in stats:
                ops[(ename, int(stats.get("program_id") or -1))] = \
                    str(stats["tf_op"])
    return out


def _entry_value(raw: bytes, entry) -> Tuple[int, int]:
    """The bounds of a protobuf map entry's value (field 2)."""
    for f, v in _fields(raw, *entry):
        if f == 2:
            return v
    return (entry[1], entry[1])


def _fields(raw: bytes, i: int, end: int):
    """(field number, value) of each field of the message in raw[i:end]: an
    int for a varint, (start, stop) bounds for a length-delimited field."""
    while i < end:
        key, i = _varint(raw, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(raw, i)
        elif kind == 2:
            n, i = _varint(raw, i)
            v, i = (i, i + n), i + n
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unexpected protobuf wire type {kind}")
        yield key >> 3, v


def _varint(raw: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = raw[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw), raw)
