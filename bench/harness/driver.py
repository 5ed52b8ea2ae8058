"""Drives the program's served path, ``ServingEngine.submit`` and
``ServingEngine.step``, as clients would, and records what each request saw
on the benchmark's clock.

Times are ``time.perf_counter()`` seconds.  A request is *admitted* at the
return of the ``step()`` call after which it has left
``scheduler.queued_requests()``, and *completed* at the return of the
``step()`` that hands it back.  Spans around the calls into the program
(``bench.submit``, ``bench.step``, ``bench.results``) go into the
profiler's trace when one is recording, so that idle gaps on the device can
be put down to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from .traffic import Request


@dataclasses.dataclass
class Rec:
    req: Request
    rid: int                          # the program's request id
    submitted: float
    scheduled: Optional[float] = None  # open loop: when it was due
    admitted: Optional[float] = None
    admit_step: Optional[int] = None
    completed: Optional[float] = None
    new_tokens: int = 0
    calls: int = 0
    error: Optional[str] = None
    output_ids: Optional[np.ndarray] = None


class Driver:
    def __init__(self, engine):
        self.engine = engine
        self.recs: List[Rec] = []
        self.step_times: List[float] = []
        self._by_id: Dict[int, Rec] = {}
        self._waiting: Dict[int, Rec] = {}
        self._open: Dict[int, Rec] = {}

    def submit(self, req: Request, scheduled: Optional[float] = None) -> Rec:
        with jax.profiler.TraceAnnotation("bench.submit"):
            h = self.engine.submit(req.prompt,
                                   max_new_tokens=req.max_new_tokens)
        rec = Rec(req, h.request_id, time.perf_counter(), scheduled)
        self.recs.append(rec)
        self._by_id[h.request_id] = rec
        self._waiting[h.request_id] = rec
        self._open[h.request_id] = rec
        return rec

    def busy(self) -> bool:
        return bool(self._open)

    def step(self) -> List[Rec]:
        """One ``engine.step()``; returns the requests it completed."""
        with jax.profiler.TraceAnnotation("bench.step"):
            done = self.engine.step()
        t = time.perf_counter()
        s = len(self.step_times)
        self.step_times.append(t)
        out = []
        with jax.profiler.TraceAnnotation("bench.results"):
            if self._waiting:
                queued = {r.request_id
                          for r in self.engine.scheduler.queued_requests()}
                for rid in [r for r in self._waiting if r not in queued]:
                    rec = self._waiting.pop(rid)
                    rec.admitted, rec.admit_step = t, s
            for h in done:
                rec = self._by_id[h.request_id]
                self._open.pop(h.request_id, None)
                rec.completed = t
                st = h.stats or {}
                rec.error = st.get("error")
                rec.new_tokens = int(st.get("new_tokens", 0))
                rec.calls = int(st.get("model_calls", 0))
                rec.output_ids = np.asarray(h.output_ids)
                out.append(rec)
        return out

    def drain(self, limit_s: float) -> None:
        """Step until every submitted request is back, for ``limit_s`` at
        most; what is still open then never came."""
        t_end = time.perf_counter() + limit_s
        while self._open and time.perf_counter() < t_end:
            self.step()

    def unfinished(self) -> List[Rec]:
        return list(self._open.values())


def warm_up(driver: Driver, warmup: List[Request],
            limit_s: float = 900.0) -> None:
    """Serve the warm-up requests to the end: every program the window
    will run is compiled (or loaded from the cache) here."""
    for r in warmup:
        driver.submit(r)
    driver.drain(limit_s)
    if driver.unfinished():
        raise RuntimeError(f"warm-up requests not served in {limit_s} s")
    driver.recs.clear()
    driver.step_times.clear()


Mark = Optional[Tuple[float, Callable[[], None]]]


def _at_mark(mark: Mark, t0: float, now: float) -> Mark:
    """Run ``mark`` = (seconds into the window, fn) once it is due; returns
    what is left to run."""
    if mark is not None and now >= t0 + mark[0]:
        mark[1]()
        return None
    return mark


def run_closed(driver: Driver, reqs: Iterator[Request], clients: int,
               seconds: float, on_open: Callable[[], None],
               mark: Mark = None) -> tuple:
    """``clients`` clients, each sending its next request as soon as its
    previous one is back.  The first requests are submitted and admitted
    (one step) before the window opens; ``on_open`` runs just before it
    does, and ``mark`` (seconds into the window, fn) before the first step
    due at or after that time.  Returns (t0, t1)."""
    for _ in range(clients):
        driver.submit(next(reqs))
    driver.step()
    on_open()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    while (now := time.perf_counter()) < t1:
        mark = _at_mark(mark, t0, now)
        for _ in driver.step():
            driver.submit(next(reqs))
    return t0, time.perf_counter()


def run_open(driver: Driver, reqs: List[Request], seconds: float,
             on_open: Callable[[], None], mark: Mark = None) -> tuple:
    """Submit each request at its scheduled time (offset from the window's
    start, which follows ``on_open``), stepping the engine whenever it has
    work; ``mark`` as for ``run_closed``.  Requests due in the window but
    not yet sent when it closes are sent then, so that the drain serves
    them.  Returns (t0, t1)."""
    on_open()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    i, n = 0, len(reqs)
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        mark = _at_mark(mark, t0, now)
        while i < n and t0 + reqs[i].scheduled <= now:
            driver.submit(reqs[i], scheduled=t0 + reqs[i].scheduled)
            i += 1
        if driver.busy():
            driver.step()
        else:
            nxt = t0 + reqs[i].scheduled if i < n else t1
            if mark is not None:
                nxt = min(nxt, t0 + mark[0])
            time.sleep(max(0.0, min(nxt, t1) - now))
    end = time.perf_counter()
    for r in reqs[i:]:
        if t0 + r.scheduled < t1:
            driver.submit(r, scheduled=t0 + r.scheduled)
    return t0, end
