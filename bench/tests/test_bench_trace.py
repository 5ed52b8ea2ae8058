"""The trace reduction, on a recorded v5e trace of one StableLM-2-1.6B
``spec_step`` (4 slots; the ops line cut to instruction names)."""
import collections
import os

import pytest

from harness import tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.textproto")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def reduced(profile):
    return tracing.reduce_profile(profile)


def _all_gaps(profile):
    """Every idle gap of the kept window, not only the 10 longest."""
    lo, hi = [(e.start_ns, e.end_ns) for p in profile.planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name == tracing.WINDOW_SPAN][0]
    chips = [[tracing._Ops(line, lo, hi) for line in p.lines
              if line.name == "XLA Ops"] for p in profile.planes
             if p.name.startswith("/device:TPU:")]
    steps = [(e.start_ns, e.end_ns) for p in profile.planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == tracing.STEP_SPAN]
    end = tracing.kept_end(chips, lo, hi, steps)[0]
    kernels = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    return [g for chip in chips for o in chip
            for g in o.fold(lo, end, collections.Counter(), kernels)[1]]


def test_programs_and_kernels_by_name(reduced):
    assert reduced["modules"]["spec_step"]["n"] == 1
    # one verify-attention call per layer, one n-gram sweep per step
    assert reduced["kernels"]["spec_attention"]["n"] == 24
    assert reduced["kernels"]["ngram_match"]["n"] == 1
    assert set(reduced["kernels"]) == {"spec_attention", "ngram_match"}


def test_busy_is_the_union_within_the_window(reduced, profile):
    step = reduced["modules"]["spec_step"]["s"]
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # nested operations (a while and its body) are counted once
    assert reduced["busy_s"] == pytest.approx(step, rel=0.01)
    idle = reduced["window_s"] - reduced["busy_s"]
    gaps = sorted((b - a) * 1e-9 for a, b in _all_gaps(profile))[::-1]
    assert sum(gaps) == pytest.approx(idle, rel=1e-3)
    # the breakdown lists the 10 longest of them
    assert [s for _, s in reduced["gaps"]] == pytest.approx(gaps[:10],
                                                            rel=1e-9)


def test_top_ops_are_self_times(reduced):
    names = [n for n, _ in reduced["top_ops"]]
    assert "while" not in names
    assert len(names) <= 10
    assert sum(s for _, s in reduced["top_ops"]) <= reduced["busy_s"]


def test_gaps_are_named_by_host_span(reduced):
    assert reduced["gaps"][0][0] == "bench.step"
    assert all(n in tracing.HOST_SPANS + ("host.other",)
               for n, _ in reduced["gaps"])


@pytest.mark.parametrize("text,want", [
    ("%spec_attention.7 = bf16[4,32,110,64] custom-call()", "spec_attention"),
    ("%ngram_match = (s32[4]) custom-call()", "ngram_match"),
    ("%compare_select_fusion.36 = (s32[4]) fusion()",
     "compare_select_fusion"),
])
def test_instruction_names(text, want):
    assert tracing.instruction(text) == want


def test_module_names():
    assert tracing.module("jit_spec_step(13879119432686850200)") == \
        "spec_step"
    assert tracing.module("jit_admit_slot(1)") == "admit_slot"


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read().replace('"bench.window"', '"other"')
    with pytest.raises(ValueError):
        tracing.reduce_profile(ProfileData.from_text_proto(text))


def _plane(pid, chip, ops, modules):
    """A device plane: ``ops`` and ``modules`` as (name, start us, us)."""
    names = sorted({n for n, _, _ in ops + modules})
    mid = {n: i + 1 for i, n in enumerate(names)}
    ev = lambda xs: "".join(
        f"events {{ metadata_id: {mid[n]} offset_ps: {int(a * 1e6)} "
        f"duration_ps: {int(d * 1e6)} }} " for n, a, d in xs)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }} ' for n, i in mid.items())
    return (f'planes {{ id: {pid} name: "/device:TPU:{chip}" '
            f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 '
            f'{ev(modules)}}} lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 '
            f'{ev(ops)}}} {meta}}} ')


def _two_chips(last_b, last_a=100, host_until=100):
    """Two chips that each run a 10 us step every 20 us in a 100 us window,
    each step dispatched inside a host ``bench.step`` span, up to
    ``host_until`` us; chip 0's plane ends with the step that ends at
    ``last_a`` us and chip 1's with the one at ``last_b`` us (a plane that
    stops while the host steps on is one the profiler's buffer cut)."""
    def chip(until):
        steps = [s for s in range(0, 100, 20) if s + 10 <= until]
        ops = [(f"%fusion.{s} = op()", s, 6) for s in steps]
        ops += [("%all-reduce.1 = op()", s + 6, 4) for s in steps]
        return ops, [("jit_spec_step(1)", s, 10) for s in steps]
    steps = "".join(f"events {{ metadata_id: 2 offset_ps: {s * 1000000} "
                    f"duration_ps: 10000000 }} "
                    for s in range(0, 100, 20) if s + 10 <= host_until)
    host = ('planes { id: 9 name: "/host:CPU" lines { id: 1 name: "python" '
            'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
            'duration_ps: 100000000 } } lines { id: 2 name: "steps" '
            f'timestamp_ns: 0 {steps}}} event_metadata {{ key: 1 value '
            '{ id: 1 name: "bench.window" } } event_metadata { key: 2 value '
            '{ id: 2 name: "bench.step" } } }')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        _plane(1, 0, *chip(last_a)) + _plane(2, 1, *chip(last_b)) + host)


def test_the_window_ends_where_a_chips_plane_does():
    r = tracing.reduce_profile(_two_chips(50))
    # chip 1's last op ends at 50 us and the host steps on: both chips are
    # read over [0, 50)
    assert r["span_s"] == pytest.approx(100e-6)
    assert r["last_op_s"] == [pytest.approx(90e-6), pytest.approx(50e-6)]
    assert r["window_s"] == pytest.approx(50e-6)
    assert r["busy_by_chip"] == [pytest.approx(30e-6)] * 2
    assert r["busy_s"] == pytest.approx(30e-6)
    assert r["modules"]["spec_step"] == {"s": pytest.approx(60e-6), "n": 6}
    assert r["events"] == 12
    assert r["ops_by_chip"][1] == {"fusion": pytest.approx(18e-6),
                                   "all-reduce": pytest.approx(12e-6)}
    # no plane cut: the whole span, its idle tail after the last step too
    whole = tracing.reduce_profile(_two_chips(100))
    assert whole["window_s"] == pytest.approx(100e-6)
    assert whole["busy_s"] == pytest.approx(50e-6)


def test_an_idle_tail_where_the_host_stops_is_kept():
    # the host dispatches nothing after 50 us, so the chips' planes end
    # there with nothing lost: the 50 us after it are the device's idle
    r = tracing.reduce_profile(_two_chips(50, last_a=50, host_until=50))
    assert r["last_op_s"] == [pytest.approx(50e-6)] * 2
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(30e-6)
    assert max(s for _, s in r["gaps"]) == pytest.approx(50e-6)


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(os.path.dirname(DATA), "..", "..",
                                       "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_collective_share_per_chip_over_busy(reduced):
    read = _reader("collective_share")
    run = type("Run", (), {})()
    # each chip: 4 us of all-reduce in every 10 us step, over the kept window
    run.trace = tracing.reduce_profile(_two_chips(50))
    assert read(run) == pytest.approx(40.0)
    # one chip and no collective: nothing to read
    run.trace = reduced
    assert read(run) is None


def _script(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(os.path.dirname(DATA), "..", "..",
                                      name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_an_excerpt_reads_as_the_trace_it_is_cut_from(profile, reduced):
    from jax.profiler import ProfileData
    excerpt = _script("excerpt").excerpt
    whole = tracing.reduce_profile(ProfileData.from_text_proto(
        excerpt(profile, 1000.0)))
    for key in ("window_s", "busy_s", "events", "modules", "kernels",
                "top_ops", "gaps"):
        assert whole[key] == reduced[key], key
    cut = tracing.reduce_profile(ProfileData.from_text_proto(
        excerpt(profile, 10.0)))
    assert cut["window_s"] == pytest.approx(0.010, rel=1e-9)
    assert 0 < cut["busy_s"] <= cut["window_s"]
    assert 0 < cut["events"] < reduced["events"]


def test_collective_share_on_a_v5e_4_trace():
    """The first 10 ms of a traced window of ``mistral-7b-tp4.decode-b8`` on
    a v5e-4 host, cut by ``bench/excerpt.py``: four chips, and the verify
    pass's all-reduces under the names XLA gives them there."""
    from jax.profiler import ProfileData
    with open(os.path.join(os.path.dirname(DATA),
                           "trace_v5e4.textproto")) as f:
        r = tracing.reduce_profile(ProfileData.from_text_proto(f.read()))
    assert r["chips"] == 4
    assert r["window_s"] == pytest.approx(0.010, rel=1e-9)
    names = set().union(*r["ops_by_chip"])
    assert "all-reduce" in names and "fusion" in names
    want = [100.0 * ops["all-reduce"] / busy
            for ops, busy in zip(r["ops_by_chip"], r["busy_by_chip"])]
    assert all(20.0 < s < 35.0 for s in want)
    read = _reader("collective_share")
    run = type("Run", (), {"trace": r})()
    assert read(run) == pytest.approx(sum(want) / 4, rel=1e-12)
    assert read(run) == pytest.approx(26.43609447963112, rel=1e-9)
