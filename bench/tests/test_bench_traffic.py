"""The traffic generator: deterministic per seed, the stated lengths, the
same sizes for every seed, and open-loop latency from the schedule."""
import itertools
import json
import os
import random
import statistics

import pytest

import tiny
from tiny import BENCH
from harness import corpus, driver, traffic
from harness.runner import Run

MIXES = ["decode-b4", "arrivals-b16", "tiny"]


def _mix(name):
    if name == "tiny":              # a log-normal mix
        return dict(tiny.CLOSED)
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _take(p, seed, n=96):
    """Open loop: every request of a 40 s window; closed: the first n."""
    reqs, warm = traffic.workload(p, seed, 40)
    return (reqs if isinstance(reqs, list)
            else list(itertools.islice(reqs, n))), warm


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    p = _mix(mix)
    a, wa = _take(p, 2**33 + 5)
    b, wb = _take(p, 2**33 + 5)
    c, _ = _take(p, 7)
    key = lambda rs: [(r.prompt, r.max_new_tokens, r.scheduled) for r in rs]
    assert key(a) == key(b) and key(wa) == key(wb)
    assert key(a) != key(c)


def _halves(task, n=4000):
    """(prompt tokens, output tokens) of corpus examples cut in half."""
    rng = random.Random(0)
    out = []
    for _ in range(n):
        m = len(corpus.MAKERS[task](rng))
        out.append((m // 2 + 1, m - m // 2))
    return out


@pytest.mark.parametrize("mix", ["decode-b4", "arrivals-b16"])
def test_lengths_are_the_corpus_examples_cut_in_half(mix):
    p = _mix(mix)
    reqs, _ = _take(p, 3, int(p.get("pool", 96)))
    for task in ("code", "math", "chat"):
        rs = [r for r in reqs if r.task == task]
        assert len(rs) >= len(reqs) // 3
        ref = _halves(task)
        lo_in, hi_in = min(x for x, _ in ref), max(x for x, _ in ref)
        for r in rs:
            assert lo_in <= r.prompt_tokens <= hi_in
            # one example: its two parts add up to a whole one
            assert r.max_new_tokens in (r.prompt_tokens - 1,
                                        r.prompt_tokens)
            assert len(r.prompt.encode()) == r.prompt_tokens - 1
            assert r.prompt.isascii()
        med = statistics.median(x for x, _ in ref)
        assert statistics.median(r.prompt_tokens for r in rs) == \
            pytest.approx(med, rel=0.1)
    assert max(r.prompt_tokens for r in reqs) <= max(p["buckets"])
    assert max(r.max_new_tokens for r in reqs) <= p["max_new_cap"]


def test_log_normal_lengths_and_medians():
    p = _mix("tiny")
    reqs, _ = _take(p, 3, p["pool"])
    for r in reqs:
        assert p["prompt_tokens"]["min"] <= r.prompt_tokens \
            <= p["prompt_tokens"]["max"]
        assert len(r.prompt.encode()) == r.prompt_tokens - 1
        assert p["output_tokens"]["min"] <= r.max_new_tokens \
            <= min(p["output_tokens"]["max"], p["max_new_cap"])
    med_in = statistics.median(r.prompt_tokens for r in reqs)
    med_out = statistics.median(r.max_new_tokens for r in reqs)
    assert med_in == pytest.approx(p["prompt_tokens"]["median"], rel=0.1)
    assert med_out == pytest.approx(p["output_tokens"]["median"], rel=0.1)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes(mix):
    p = _mix(mix)
    sizes = lambda s: sorted((r.task, r.prompt_tokens, r.max_new_tokens)
                             for r in _take(p, s, p.get("pool", 0))[0])
    assert sizes(1) == sizes(2)


def test_open_loop_schedule_follows_the_phases():
    p = dict(_mix("arrivals-b16"), rate_per_s=5.0)
    times = traffic.arrival_times(p, 11, 40.0)
    assert times == sorted(times) and 0 <= times[0] and times[-1] < 40.0
    low = [t for t in times if t % 10.0 < 20 / 3]
    assert len(low) == 4 * round(0.6 * 5.0 * 20 / 3)
    assert len(times) - len(low) == 4 * round(1.8 * 5.0 * 10 / 3)


@pytest.mark.parametrize("mix", MIXES)
def test_warmup_covers_each_bucket_once(mix):
    p = _mix(mix)
    reqs, warm = _take(p, 0, int(p.get("pool", 96)))
    bucket = lambda n: min(b for b in p["buckets"] if b >= n)
    assert sorted(bucket(r.prompt_tokens) for r in warm) == \
        sorted({bucket(r.prompt_tokens) for r in reqs})


def _rec(scheduled, submitted, admitted, completed, tokens=10):
    r = driver.Rec(traffic.Request("code", "x", 2, tokens), 0,
                   submitted, scheduled=scheduled, admitted=admitted,
                   completed=completed, new_tokens=tokens)
    return r


def _load(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_open_loop_latency_counts_from_the_scheduled_arrival():
    # sent 2 s late (the generator was stuck), served in 1 s: latency 3 s;
    # one still open at the window's end counts at its age then
    recs = [_rec(1.0 + i * 0.01, 1.0 + i * 0.01, 1.1, 1.5 + i * 0.01)
            for i in range(18)]
    recs.append(_rec(0.0, 2.0, 2.1, 3.0))
    recs.append(_rec(5.0, 5.0, None, None))
    run = Run(root="", cell=None, dims=None, traffic={}, seed=0,
              seconds=10.0, spec_k=10, spec_w=10, chips=1,
              device_kind="", recs=recs, step_times=[], t0=0.0, t1=10.0,
              setup_s=0.0)
    lat = sorted([0.5] * 18 + [3.0, 5.0])
    import numpy as np
    assert _load("request_latency_p95_s")(run) == pytest.approx(
        float(np.percentile(lat, 95)))
    assert _load("gen_lag_p95_ms")(run) > 0
    assert _load("queue_wait_p95_s")(run) >= 2.1
