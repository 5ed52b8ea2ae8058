"""The harness finds a configuration, a cell and a per-layer metric by
name, from new files alone, and drives the served path end to end on a
tiny configuration on the CPU.  The command itself refuses to run without
a TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

import tiny
from tiny import BENCH, ROOT
from harness import accounting, runner, spec, tracing


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _execute(root, cell, trace=False, seed=2**33 + 1):
    return runner.execute(root, spec.load_cell(root, cell), seed, 2.0, trace,
                          jax.devices()[:1], lambda: 1.0)[0]


def test_new_files_are_found_by_name(root):
    cell = spec.load_cell(root, "tiny.tiny-closed")
    assert cell.config_data["hidden_size"] == 64
    assert cell.traffic_data["loop"] == "closed"
    assert cell.limits["served_gap"] == 1e-3
    assert [m.name for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["finished_requests"]
    run = _FakeRun()
    assert spec.reader(root, "finished_requests")(run) == 1
    # a split metric falls back to the reader of its base name
    assert spec.reader(root, "step_ms.arrivals") is not None
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell")


class _FakeRun:
    t1 = 10.0
    recs = [type("R", (), {"completed": 1.0})(),
            type("R", (), {"completed": None})()]


def test_closed_loop_cell_runs_and_is_correct(root):
    out = _execute(root, "tiny.tiny-closed")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["served_gap"]["value"] <= 1e-3


def test_open_loop_cell_runs_and_is_correct(root):
    out = _execute(root, "tiny.tiny-open")
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"request_latency_p95_s",
                                   "ms_per_token_p95", "setup_s"}


def test_traced_run_reports_the_per_layer_metrics(root, monkeypatch):
    fake = {"window_s": 2.0, "span_s": 2.1, "last_op_s": [2.0],
            "events": 9, "busy_s": 1.0, "busy_by_chip": [1.0], "chips": 1,
            "modules": {}, "kernels": {}, "ops_by_chip": [{"fusion": 0.5}],
            "top_ops": [["fusion", 0.5]], "gaps": [["bench.step", 0.1]]}
    monkeypatch.setattr(tracing, "reduce_file", lambda path: fake)
    out = _execute(root, "tiny.tiny-closed", trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"finished_requests"}
    assert out["metrics"]["finished_requests"]["value"] >= 1
    assert out["device"]["busy_s"] == 1.0
    assert out["breakdown"]["idle_gaps"] == [["bench.step", 0.1]]


def test_a_traced_run_records_the_windows_last_seconds(root, monkeypatch):
    fake = {"window_s": 0.5, "span_s": 0.5, "last_op_s": [0.5],
            "events": 9, "busy_s": 0.4, "busy_by_chip": [0.4], "chips": 1,
            "modules": {}, "kernels": {}, "ops_by_chip": [{"fusion": 0.4}],
            "top_ops": [["fusion", 0.4]], "gaps": [["bench.step", 0.1]]}
    monkeypatch.setattr(tracing, "reduce_file", lambda path: fake)
    monkeypatch.setattr(runner, "TRACE_S", 0.5)
    out, run = runner.execute(root, spec.load_cell(root, "tiny.tiny-closed"),
                              5, 2.0, True, jax.devices()[:1], lambda: 1.0)
    assert out["correct"] is True
    # the trace starts at the first step due 1.5 s into the 2 s window
    assert 1.5 <= run.trace_t0 - run.t0 < 1.9
    lo, hi = accounting.window(run)
    assert lo == run.trace_t0 and hi == min(run.t1, run.trace_t0 + 0.5)
    assert accounting.window_steps(run)
    assert all(run.trace_t0 < run.step_times[s]
               for s in accounting.window_steps(run))


def test_benchmark_file_names_every_reader_and_data_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert spec.reader(ROOT, m["name"])
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.chips == spec.mesh_size(cell.config_data)
        assert cell.family.Dims(cell.config_data).layers > 0
        assert cell.limits["served_gap"] > 0


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "stablelm-2-1.6b.decode-b4", "--seed", "1", "--seconds", "10",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
