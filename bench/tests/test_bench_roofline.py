"""Roofline and step_mfu arithmetic: work counted at live lengths, peaks
keyed by device kind."""
import importlib.util
import json
import os

import pytest

from tiny import BENCH, ROOT
from harness import accounting, runner, spec
from harness.driver import Rec
from harness.traffic import Request


def _load(name):
    path = os.path.join(BENCH, *name.split("/")) + ".py"
    spec = importlib.util.spec_from_file_location(name.replace("/", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


attn = _load("roofline/spec_attention")
step = _load("roofline/step")
PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
DIMS = dict(heads=32, kv_heads=32, head_dim=64, rows=110, w1=11)


def _run(recs, step_times, t0, t1, trace=None, kind="TPU v5 lite"):
    with open(os.path.join(BENCH, "configs", "stablelm-2-1.6b.json")) as f:
        c = json.load(f)
    return runner.Run(
        root=ROOT, cell=None, dims=spec.family(ROOT, c["family"]).Dims(c),
        traffic={"buckets": [256, 512, 1024]}, seed=0, seconds=t1 - t0,
        spec_k=10, spec_w=10, chips=1, device_kind=kind, recs=recs,
        step_times=step_times, t0=t0, t1=t1, setup_s=1.0, trace=trace,
        trace_t0=t0 if trace else None)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        _run([], [], 0.0, 1.0, kind="TPU v99").peak()
    assert _run([], [], 0.0, 1.0).peak()["flops"] == 197e12


def test_a_kernel_reading_only_live_blocks_stays_within_its_roofline():
    live = [300, 700, 1100, 40]
    f, b = attn.call_work(live, **DIMS)
    best = attn.seconds(f, b, PEAK["flops"], PEAK["hbm_bytes_per_s"])
    # the fastest a kernel can be: its live bytes at peak bandwidth and its
    # operations at peak rate, whichever is slower -> a share of exactly 1
    fastest = max(f / PEAK["flops"], b / PEAK["hbm_bytes_per_s"])
    assert best / fastest == pytest.approx(1.0)
    # a kernel that streams the whole 2048-slot buffer takes longer
    fs, bs = attn.call_work([2048] * 4, **DIMS)
    streaming = max(fs / PEAK["flops"], bs / PEAK["hbm_bytes_per_s"])
    assert best / streaming < 1.0


def test_roofline_reader_counts_live_lengths_per_step():
    # one request admitted at step 1 of a window of steps 1..3 with a
    # 512-token bucket: live lengths are at least 512, 513, 514
    req = Request("code", "x" * 400, 401, 4)
    rec = Rec(req, 0, 0.0, admitted=1.0, admit_step=1, completed=3.5, new_tokens=4, calls=3)
    times = [0.5, 1.5, 2.5, 3.5, 4.5]
    live = accounting.live_by_step(_run([rec], times, 1.0, 3.6))
    assert live == {1: [512], 2: [513], 3: [514]}
    per_call = sum(
        attn.seconds(*attn.call_work(v, **DIMS), PEAK["flops"],
                     PEAK["hbm_bytes_per_s"]) for v in live.values()) / 3
    trace = {"kernels": {"spec_attention": {"s": 24 * 3 * per_call,
                                            "n": 24 * 3}},
             "window_s": 3.0, "busy_s": 2.0, "chips": 1, "modules": {}}
    share = _load("metrics/spec_attention_roofline").read(
        _run([rec], times, 1.0, 3.6, trace))
    assert share == pytest.approx(100.0)


def test_mfu_counts_no_rejected_draft_positions():
    # 3 calls that verified 3 * 10 * 11 positions but committed 5 tokens
    req = Request("code", "x" * 99, 100, 6)
    rec = Rec(req, 0, 0.0, admitted=1.0, admit_step=1, completed=3.5, new_tokens=6, calls=3)
    run = _run([rec], [0.5, 1.5, 2.5, 3.5, 4.5], 1.0, 3.6,
               {"window_s": 2.6, "busy_s": 1.0, "chips": 1})
    (tokens, prompt, prefilled), = accounting.committed_in_window(run)
    assert (tokens, prompt, prefilled) == (5, 100, True)
    m = run.dims
    dims = dict(non_embedding=m.non_embedding_params(), d_model=m.d,
                vocab=m.vocab, layers=m.layers, heads=m.heads,
                head_dim=m.hd, window=m.window)
    want = step.prefill_flops(100, **dims) + 5 * step.token_flops(100,
                                                                  **dims)
    got = _load("metrics/step_mfu").read(run)
    assert got == pytest.approx(100.0 * want / (2.6 * PEAK["flops"]))
    assert got < 100.0


def test_tokens_outside_the_window_are_a_lower_bound():
    # admitted before the window: only the window's 2 calls are counted,
    # at least one token each
    req = Request("code", "x" * 99, 100, 30)
    rec = Rec(req, 0, 0.0, admitted=0.5, admit_step=0, completed=3.5, new_tokens=30, calls=3)
    run = _run([rec], [0.5, 1.5, 2.5, 3.5], 1.0, 3.0)
    (tokens, _, prefilled), = accounting.committed_in_window(run)
    assert not prefilled
    assert 2 <= tokens <= 29


def test_prefill_flops_closed_form():
    dims = dict(non_embedding=10, d_model=2, vocab=3, layers=1, heads=1,
                head_dim=1)
    loop = sum(step.token_flops(i + 1, head=(i == 6), **dims)
               for i in range(7))
    assert step.prefill_flops(7, **dims) == pytest.approx(loop)
    win = sum(step.token_flops(i + 1, head=(i == 6), window=3, **dims)
              for i in range(7))
    assert step.prefill_flops(7, window=3, **dims) == pytest.approx(win)


def test_peaks_name_their_source():
    for kind, p in json.load(open(os.path.join(BENCH,
                                               "peaks.json"))).items():
        assert p["flops"] > 0 and p["hbm_bytes_per_s"] > 0 and p["source"]
