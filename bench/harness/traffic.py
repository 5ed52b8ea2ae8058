"""The one traffic generator: turns a mix's parameters and a seed into
requests and, for an open loop, their arrival schedule.

A mix is a JSON file under ``bench/traffic/`` with the keys

    source             where its lengths, mix and arrivals come from
    loop               "closed" (clients wait for their answer) or "open"
    slots              the engine's max_batch
    clients            closed loop: concurrent clients
    pool               closed loop: sizes in the pool (see below)
    rate_per_s         open loop: mean arrival rate
    phases             open loop: [[rate multiplier, seconds], ...], repeated
    mix                task -> weight, over the corpus tasks code, math, chat
    corpus_split       lengths from the corpus: a request is one example
                       of its task, cut at this fraction; the prompt is the
                       first part and the answer asks for as many tokens as
                       the rest has (the program's ``make_prompts`` cuts its
                       examples in half the same way)
    prompt_tokens      or else lengths from a log-normal: {median, sigma,
    output_tokens      min, max} of each, from the source the mix names
    buckets            the engine's prompt buckets
    max_new_cap        the engine's max_new_cap
    warmup_new_tokens  output tokens of each warm-up request
    reference_requests finished requests the output check samples

Every seed gets the same multiset of sizes in another order, so that seeds
differ in content and order but not in the amount of work:

* the pool of (task, prompt, output) sizes is fixed by the mix alone:
  with ``corpus_split`` the sizes of a fixed draw of corpus examples, else
  a stratified grid of each log-normal cut to [min, max] (``pool``
  quantiles evenly spaced in probability between the cut points, prompts
  and outputs paired in a fixed order); the seed permutes the pool and
  draws the text of each request afresh at its size;
* an open loop has a fixed number of arrivals in every phase of its rate
  schedule (the phases alternate at fixed lengths), placed uniformly at
  random inside the phase -- a Poisson process conditioned on its count.

Lengths are in tokens of the program's byte tokenizer, whose prompts start
with one BOS token: a prompt of n tokens is n - 1 ASCII characters.  The
open-loop arrival schedule and its lateness accounting follow the program's
own ``benchmarks/continuous_batching.py`` (latency counts from the
scheduled arrival, not from the submit).
"""
from __future__ import annotations

import dataclasses
import itertools
import random
import statistics
from typing import Iterator, List, Optional, Tuple

import numpy as np

from . import corpus


@dataclasses.dataclass
class Request:
    task: str
    prompt: str
    prompt_tokens: int              # with the BOS token
    max_new_tokens: int
    scheduled: Optional[float] = None   # open loop: seconds after t0


def _rng(seed: int, salt: int) -> random.Random:
    # a str seed is hashed with SHA-512: the same in every process
    return random.Random(f"{int(seed)}:{salt}")


def stratified_lengths(dist: dict, n: int) -> List[int]:
    """``n`` lengths at evenly spaced probabilities of a log-normal with
    the given median and sigma, cut to [min, max] (sorted ascending)."""
    lo, hi = int(dist["min"]), int(dist["max"])
    sigma = float(dist.get("sigma", 0.0))
    if sigma == 0.0 or lo == hi:
        return [int(min(max(round(dist["median"]), lo), hi))] * n
    nd = statistics.NormalDist()
    mu = np.log(float(dist["median"]))
    f_lo = nd.cdf((np.log(lo) - mu) / sigma)
    f_hi = nd.cdf((np.log(hi) - mu) / sigma)
    out = []
    for i in range(n):
        u = f_lo + (i + 0.5) / n * (f_hi - f_lo)
        out.append(int(min(max(round(np.exp(mu + sigma * nd.inv_cdf(u))),
                                lo), hi)))
    return out


def _tasks(mix: dict, n: int) -> List[str]:
    names = sorted(mix)
    w = np.asarray([float(mix[t]) for t in names])
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1
    return [t for t, c in zip(names, counts) for _ in range(c)]


Size = Tuple[str, int, int]     # (task, prompt tokens, output tokens)


def sizes(p: dict, n: int) -> List[Size]:
    """The pool of ``n`` request sizes, fixed by the mix alone."""
    tasks = _tasks(p["mix"], n)
    if "corpus_split" in p:
        split = float(p["corpus_split"])
        rng = random.Random("corpus")
        out = []
        for t in tasks:
            n_chars = len(corpus.MAKERS[t](rng))
            cut = int(n_chars * split)
            out.append((t, cut + 1, n_chars - cut))
        return out
    plen = stratified_lengths(p["prompt_tokens"], n)
    olen = stratified_lengths(p["output_tokens"], n)
    random.Random("pairs").shuffle(olen)
    return list(zip(tasks, plen, olen))


def requests(p: dict, seed: int, pool: List[Size]) -> Iterator[Request]:
    """Endless requests: successive permutations of the pool of sizes,
    each request with text of its own."""
    rng = _rng(seed, 0)
    pool = list(pool)
    for _ in itertools.count():
        rng.shuffle(pool)
        for task, n_in, n_out in pool:
            yield Request(task, corpus.document(task, n_in - 1, rng),
                          n_in, n_out)


def arrival_times(p: dict, seed: int, seconds: float) -> List[float]:
    """Open-loop schedule over ``seconds``: each phase of the repeated
    ``phases`` list gets round(multiplier * rate * length) arrivals, placed
    uniformly at random inside it."""
    rng = _rng(seed, 1)
    out, t, i = [], 0.0, 0
    phases = p["phases"]
    while t < seconds:
        mult, length = phases[i % len(phases)]
        length = min(float(length), seconds - t)
        n = int(round(mult * float(p["rate_per_s"]) * length))
        out += sorted(t + rng.random() * length for _ in range(n))
        t += length
        i += 1
    return out


def warmup_requests(p: dict, seed: int,
                    pool: List[Size]) -> List[Request]:
    """One short request per prompt bucket that some size of the pool
    falls in, so that set-up compiles every admission shape the window
    will use, and no other."""
    rng = _rng(seed, 2)
    tasks = sorted(p["mix"])
    buckets = sorted(p["buckets"])
    out = []
    for i, b in enumerate(buckets):
        lo = buckets[i - 1] + 1 if i else 1
        fits = [n for _, n, _ in pool if lo <= n <= b]
        if not fits:
            continue
        task = tasks[i % len(tasks)]
        out.append(Request(task, corpus.document(task, min(fits) - 1, rng),
                           min(fits), int(p["warmup_new_tokens"])))
    return out


def workload(p: dict, seed: int, seconds: float):
    """(requests, warm-up requests) of one run.  Closed loop: an endless
    iterator over permutations of a pool of ``pool`` sizes; open loop: a
    list with one request per arrival, ``scheduled`` set."""
    if p["loop"] == "open":
        times = arrival_times(p, seed, seconds)
        pool = sizes(p, len(times))
        reqs = list(itertools.islice(requests(p, seed, pool), len(times)))
        for r, t in zip(reqs, times):
            r.scheduled = t
        return reqs, warmup_requests(p, seed, pool)
    pool = sizes(p, int(p["pool"]))
    return requests(p, seed, pool), warmup_requests(p, seed, pool)
