"""The program's own spans and scopes, read from a trace by
``harness/phases.py`` beside the benchmark's reduction.

``trace_v5e_spans.textproto`` is a v5e trace of ``stablelm-2-1.6b.decode-b4``
served by a program that has them: two ``spec_step`` executions around a
step that retires a request and admits the next, cut as
``trace_v5e.textproto`` is (instruction names cut to ``%name = op()``),
keeping each operation's ``tf_op`` and ``program_id`` and the host's
``bench.*`` and ``engine.*`` spans.  ``trace_v5e.textproto`` comes from a
program without them: there the phases find nothing to split, and the
benchmark's own reduction reads what it always read."""
import os

import pytest

from harness import phases, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        data = ProfileData.text_proto_to_serialized_xspace(f.read())
    return ProfileData.from_serialized_xspace(data), data


@pytest.fixture(scope="module")
def spans():
    pd, raw = _profile("trace_v5e_spans.textproto")
    return phases.reduce_profile(pd, raw), tracing.reduce_profile(pd)


@pytest.fixture(scope="module")
def old():
    pd, raw = _profile("trace_v5e.textproto")
    return phases.reduce_profile(pd, raw), tracing.reduce_profile(pd)


def test_engine_spans_in_the_window(spans):
    r, base = spans
    s = r["spans"]
    assert r["step"]["n"] == base["modules"]["spec_step"]["n"] == 2
    assert base["modules"]["admit_slot"]["n"] == 1
    assert s["engine.step"]["n"] == s["engine.done_wait"]["n"] == 2
    assert s["engine.dispatch"]["n"] == 2
    assert s["engine.readback"]["n"] == 1
    assert s["engine.retire"]["n"] == s["engine.admit"]["n"] == 1
    # the wait for the flags is most of a step while the device computes
    assert s["engine.done_wait"]["s"] < s["engine.step"]["s"]


def test_scopes_split_the_step(spans):
    r, base = spans
    sc = r["scopes"]
    assert set(sc) == {"spec.draft", "spec.verify", "spec.commit",
                       phases.UNSCOPED}
    assert all(v > 0 for v in sc.values())
    assert sc["spec.verify"] == max(sc.values())
    # every operation of the two steps is counted once, in one scope
    step = base["modules"]["spec_step"]["s"]
    assert r["step"]["s"] == pytest.approx(step, rel=1e-12)
    assert sum(sc.values()) == pytest.approx(step, rel=0.01)


def test_summary_per_step(spans):
    r, _ = spans
    out = phases.summary(r)
    assert out["step_ms"] == pytest.approx(1e3 * r["step"]["s"] / 2)
    assert sum(out["scope_ms"].values()) == pytest.approx(out["step_ms"],
                                                          rel=0.02)
    s = r["spans"]
    host = 1e3 * (s["engine.step"]["s"] - s["engine.done_wait"]["s"]) / 2
    assert out["host_step_ms"] == pytest.approx(host)


def test_idle_by_span_tiles_the_idle_time(spans):
    r, base = spans
    idle = r["idle_by_span"]
    assert r["window_s"] == base["window_s"]
    assert sum(idle.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-3)
    # the device waits while the host retires and admits
    assert idle["engine.admit"] > 0 and idle["engine.readback"] > 0


def test_gaps_are_named_by_engine_spans(spans):
    r, base = spans
    assert all(n.startswith(phases.ENGINE) for n, _ in r["gaps"])
    assert r["gaps"][0][1] > 1e-3
    # the same gaps as the benchmark's breakdown, named more finely
    assert [g for _, g in r["gaps"]] == [g for _, g in base["gaps"]]


def test_a_program_without_spans_splits_nothing(old):
    r, base = old
    assert r["spans"] == {}
    assert set(r["scopes"]) == {phases.UNSCOPED}
    assert r["scopes"][phases.UNSCOPED] == pytest.approx(
        base["modules"]["spec_step"]["s"], rel=0.01)
    assert set(r["idle_by_span"]) <= {"bench.step", "bench.submit",
                                      "bench.results", "host.other"}
    assert r["gaps"] == base["gaps"]
    assert "host_step_ms" not in phases.summary(r)


def test_the_old_trace_reads_as_before(old):
    """The numbers the benchmark's metrics read from a trace of a program
    without spans or scopes."""
    _, base = old
    assert base["window_s"] == pytest.approx(0.039486358, rel=1e-12)
    assert base["span_s"] == base["window_s"]
    assert base["last_op_s"] == [pytest.approx(0.038886159, rel=1e-12)]
    assert base["busy_s"] == pytest.approx(0.038685239, rel=1e-12)
    assert base["modules"] == {"spec_step": {"s": pytest.approx(
        0.038686358, rel=1e-12), "n": 1}}
    assert base["kernels"]["spec_attention"] == {
        "s": pytest.approx(0.009101331, rel=1e-12), "n": 24}
    assert base["top_ops"][0] == ["copy", pytest.approx(0.014957356,
                                                        rel=1e-9)]
    assert base["gaps"][0] == ["bench.step", pytest.approx(0.000600199,
                                                           rel=1e-9)]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(spec_step)/jit(main)/spec.draft/sort", "spec.draft"),
    ("jit(spec_step)/spec.verify/while/body/dot_general", "spec.verify"),
    ("spec.commit/scatter", "spec.commit"),
    ("jit(spec_step)/spec.verify/spec.commit/add", "spec.verify"),
    ("jit(spec_step)/jit(main)/add", ""),
    ("jit(spec_step)/myspec.draft/add", ""),
])
def test_scope_of(op_name, scope):
    assert phases.scope_of(op_name) == scope


@pytest.mark.parametrize("name,span", [
    ("engine.admit", "engine.admit"),
    ("engine.admit#request_id=7,bucket=256#", "engine.admit"),
    ("bench.window", "bench.window"),
])
def test_span_name(name, span):
    assert phases.span_name(name) == span


def test_the_phases_script_prints_the_split(tmp_path, monkeypatch, capsys):
    """``bench/phases.py`` runs a cell traced and prints its result line,
    then the phases read from the same trace file.  On the CPU the trace
    has no TPU plane, so both reducers stand on the recorded v5e trace."""
    import importlib.util
    import json

    import jax
    import tiny
    from harness import runner
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "bench", "phases.py")
    mod_spec = importlib.util.spec_from_file_location("bench_phases", path)
    script = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(script)
    pd, raw = _profile("trace_v5e_spans.textproto")
    base = tracing.reduce_profile(pd)
    split = phases.reduce_profile(pd, raw)
    read = []
    monkeypatch.setattr(runner, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(tracing, "reduce_file",
                        lambda p: read.append(p) or dict(base))
    monkeypatch.setattr(phases, "reduce_file",
                        lambda p: read.append(p) or split)
    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        assert script.main(["--workload", "tiny.tiny-closed", "--seed",
                            str(2**33 + 5), "--seconds", "2"]) == 0
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
    # one trace file, read by both reducers; the harness's reducer is
    # the tracing module's own again afterwards
    assert len(read) == 2 and read[0] == read[1]
    assert tracing.reduce_file.__name__ == "<lambda>"
    result, line = [json.loads(x) for x in
                    capsys.readouterr().out.strip().splitlines()[-2:]]
    assert result["correct"] is True
    assert result["breakdown"]["idle_gaps"] == base["gaps"]
    got = line["phases"]
    assert got["scope_ms"] == pytest.approx(phases.summary(split)["scope_ms"])
    assert got["host_step_ms"] > 0
    assert got["gaps"] == split["gaps"]
