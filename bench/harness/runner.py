"""One run of one cell: set-up, the measured window, the output check, the
metrics, and the result line.

    set-up   the configuration's weights (one jitted call on the device;
             with a mesh, straight into the program's shardings), the
             program's ``ServingEngine`` with the default ``SpecConfig``
             and that mesh (it builds the drafter's tables), a warm-up
             request per prompt bucket served to the end (every program
             the window runs is compiled or loaded from the cache), and
             for a closed loop the clients' first requests admitted
    window   ``--seconds`` of traffic through ``submit`` / ``step``; a
             traced run records its last ``TRACE_S`` seconds
    after    the requests still open are served to the end (a minute at
             most), peak memory is read, the program's state is freed, and
             a sample of finished requests is checked against the plain
             float32 reference (``reference.py``)
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import random
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np

from . import accounting, driver as drv, model, reference, spec, tracing
from . import traffic as tr

# a request still open this long after the window closed never came
DRAIN_S = 60.0
# a traced run records the window's last seconds, not all of it: stopping
# the profiler takes 25-45 us a device event on v5e hosts, which run 0.2-0.5
# million events a second, and a run has to end within 360 s
TRACE_S = 5.0
BOS = 257          # the program's byte tokenizer: BOS, which also pads
# a compilation, or a load from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""
    root: str
    cell: spec.Cell
    dims: object                   # the family's Dims of the configuration
    traffic: dict
    seed: int
    seconds: float
    spec_k: int
    spec_w: int
    chips: int
    device_kind: str
    recs: List[drv.Rec]
    step_times: List[float]
    t0: float
    t1: float
    setup_s: float
    trace: Optional[dict] = None
    trace_t0: Optional[float] = None   # the traced part's start

    def peak(self) -> dict:
        """The chip's published peaks; an unknown chip is an error."""
        with open(os.path.join(self.root, "bench", "peaks.json")) as f:
            table = json.load(f)
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in bench/peaks.json")
        return table[self.device_kind]


def process_age() -> float:
    """Seconds since this process started (from /proc where it exists)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def require_devices(chips: int):
    """The devices JAX sees, or exit non-zero with nothing on stdout."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, but JAX's first device is "
                 f"{devs[0].platform!r}; refusing to run")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs[:chips]


def enable_cache(root: str) -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout's root (a fixed path:
    the path is part of the cache key).  Every program is cached, however
    quick to compile, so that a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def import_program(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"bench: {src}/repro not found: run from a checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


def prompt_row(prompt: str, buckets) -> np.ndarray:
    """The token row the program prefills for ``prompt``: BOS and the
    prompt's bytes, left-padded with BOS to its bucket."""
    ids = [BOS] + list(prompt.encode("utf-8"))
    b = accounting.bucket_of(len(ids), buckets)
    return np.asarray([BOS] * (b - len(ids)) + ids[-b:], np.int32)


def _sync(engine) -> None:
    """Wait for the device to finish what has been dispatched.  The one
    read of the program's internals: it makes the trace's edges clean and
    ``setup_s`` end when the warm-up has run, so it fails loudly where the
    engine no longer holds its decode state as ``_cont_state``."""
    import jax
    state = getattr(engine, "_cont_state", None)
    if state is None:
        raise RuntimeError("bench: ServingEngine holds no _cont_state after "
                           "its first step; the benchmark cannot wait for "
                           "the device")
    jax.block_until_ready(state)


def check(run: Run, weights, unfinished: List[drv.Rec],
          control: bool = False) -> dict:
    """The numbers compared, each {"value", "limit"}: the widest gap by
    which a served token's float32 reference logit lies below the
    reference's best, over a sample of finished requests drawn from the
    seed with the longest among them; requests that failed or never came;
    answers shorter than asked."""
    p = run.traffic
    cap = int(p["max_new_cap"])
    done = [r for r in run.recs if r.completed is not None and not r.error]
    failed = sum(1 for r in run.recs if r.error) + len(unfinished)
    short = sum(1 for r in done
                if r.new_tokens != min(r.req.max_new_tokens, cap)
                or len(r.output_ids) != r.new_tokens)
    checks = {"failed": {"value": failed, "limit": 0},
              "short_answers": {"value": short, "limit": 0}}
    if not done:
        checks["finished"] = {"value": 0, "limit": 1}
        return checks
    longest = max(done, key=lambda r: (r.new_tokens, r.rid))
    rest = [r for r in done if r is not longest]
    k = min(int(p["reference_requests"]) - 1, len(rest))
    sample = [longest] + random.Random(f"{run.seed}:reference").sample(
        rest, k)
    g = reference.served_gaps(
        run.cell.family, run.cell.config_data, weights,
        [prompt_row(r.req.prompt, p["buckets"]) for r in sample],
        [r.output_ids for r in sample],
        T=max(p["buckets"]) + cap, n_max=cap, control=control)
    checks["served_gap"] = {"value": g["served_gap"],
                            "limit": float(run.cell.limits["served_gap"])}
    if control:
        # the control in the program's place, held to the same limit
        checks["control_gap"] = {"value": g["control_gap"],
                                 "limit": float(run.cell.limits["served_gap"])}
    print(f"reference: {len(sample)} requests, {g['served_tokens']} served "
          f"tokens, {g['served_argmax']} of them the reference's argmax",
          file=sys.stderr)
    return checks


def execute(root: str, cell: spec.Cell, seed: int, seconds: float,
            trace: bool, devices, t_age0: Callable[[], float],
            control: bool = False):
    """Run ``cell`` once on ``devices``; returns the result object and the
    run's record.
    ``control`` also reads the float8 control on the same sample and holds
    it to the served-gap limit, so that a control run is not correct (for
    ``bench/control.py``; the benchmark's runs never do)."""
    import jax
    from repro.core.spec_engine import SpecConfig
    from repro.serving import ServingEngine

    fam, c, p = cell.family, cell.config_data, cell.traffic_data
    dims = fam.Dims(c)
    mesh = model.make_mesh(c.get("mesh"), devices)
    # the weights are the configuration's, the same in every run: with
    # random weights the acceptance of the drafts is a property of the
    # draw, and weights drawn per seed moved tokens_per_s by 30% between
    # seeds (PERF.md); the seed draws the traffic
    weights = model.make_weights(fam, dims, int(c["weights_seed"]), mesh)
    engine = ServingEngine(
        fam.program_params(dims, weights), fam.program_config(c, cell.config),
        SpecConfig(),
        max_batch=int(p["slots"]), buckets=tuple(p["buckets"]),
        max_new_cap=int(p["max_new_cap"]), sampling=False, mesh=mesh)
    reqs, warm = tr.workload(p, seed, seconds)
    d = drv.Driver(engine)
    drv.warm_up(d, warm)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup = {}
    compiles = []

    def on_compile(event, secs, **kw):
        if event in COMPILE_EVENTS and "s" in setup:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    def on_open():
        _sync(engine)
        setup["s"] = t_age0()

    def on_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # the benchmark's own spans; nothing of JAX's host internals
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        # made after the trace starts: a span made before is not recorded
        setup["span"] = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        setup["span"].__enter__()
        setup["trace_t0"] = time.perf_counter()

    mark = (max(0.0, seconds - TRACE_S), on_trace) if trace else None
    if p["loop"] == "open":
        t0, t1 = drv.run_open(d, reqs, seconds, on_open, mark)
    else:
        t0, t1 = drv.run_closed(d, reqs, int(p["clients"]), seconds, on_open,
                                mark)
    _sync(engine)
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if trace:
        setup["span"].__exit__(None, None, None)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: stop_trace {time.perf_counter() - t_stop:.1f} s",
              file=sys.stderr)
    print(f"compiles or cache loads in the window: {len(compiles)}",
          file=sys.stderr)
    d.drain(DRAIN_S)
    unfinished = d.unfinished()
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in devices)
    run = Run(root=root, cell=cell, dims=dims, traffic=p,
              seed=seed, seconds=seconds, spec_k=engine.spec.k,
              spec_w=engine.spec.w, chips=len(devices),
              device_kind=devices[0].device_kind, recs=d.recs,
              step_times=d.step_times, t0=t0, t1=t1, setup_s=setup["s"],
              trace_t0=setup.get("trace_t0"))
    # free the program's state before the reference runs
    d.engine = None
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    checks = check(run, weights, unfinished, control)
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    del weights
    if trace:
        files = [os.path.join(a, f) for a, _, fs in os.walk(tdir)
                 for f in fs if f.endswith(".xplane.pb")]
        t_red = time.perf_counter()
        run.trace = t = tracing.reduce_file(files[0])
        print(f"trace: {os.path.getsize(files[0])} bytes read and reduced "
              f"in {time.perf_counter() - t_red:.1f} s; kept "
              f"{t['window_s']:.3f} s of the {t['span_s']:.3f} s window, "
              f"{t['events']} device op events; each chip's last op ends "
              f"{[round(x, 4) for x in t['last_op_s']]} s into it",
              file=sys.stderr)
        shutil.rmtree(tdir, ignore_errors=True)
    metrics = spec.metrics_of(root, cell.per_layer if trace
                              else cell.end_to_end, run)
    due = [r for r in run.recs
           if r.scheduled is None or r.scheduled < t1]
    out = {"correct": all(v["value"] <= v["limit"] for k, v in
                          checks.items() if k != "finished")
           and "finished" not in checks,
           "attempted": len(due),
           "failed": checks["failed"]["value"],
           "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": int(peak or 0)}}
    if trace:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["top_ops"],
                            "idle_gaps": run.trace["gaps"]}
    out["checks"] = checks
    return out, run


def report(out: dict) -> None:
    for name, v in out["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


def main(root: str, args, t_age0: Callable[[], float] = process_age) -> int:
    cell = spec.load_cell(root, args.workload)
    devices = require_devices(cell.chips)
    enable_cache(root)
    import_program(root)
    report(execute(root, cell, args.seed, float(args.seconds),
                   bool(args.trace), devices, t_age0)[0])
    return 0
