"""The output check's control, at a size a test run holds: the reference
computed in float8 in the program's place reads a far wider gap than the
program does on the same served tokens.  (The same comparison at each
cell's own size, on the chip, set the limits: PERF.md.)"""
import jax
import numpy as np
import pytest

import tiny
from harness import driver, model, reference, runner, spec, traffic


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A short closed-loop run of the tiny cell: (config, weights, rows,
    served tokens) of the requests it finished."""
    root = tiny.make_root(tmp_path_factory.mktemp("checkout"))
    cell = spec.load_cell(root, "tiny.tiny-closed")
    runner.import_program(root)
    from repro.core.spec_engine import SpecConfig
    from repro.serving import ServingEngine
    fam, c, p = cell.family, cell.config_data, cell.traffic_data
    dims = fam.Dims(c)
    w = model.make_weights(fam, dims, 123)
    eng = ServingEngine(fam.program_params(dims, w),
                        fam.program_config(c, cell.config), SpecConfig(),
                        max_batch=p["slots"], buckets=tuple(p["buckets"]),
                        max_new_cap=p["max_new_cap"], sampling=False)
    reqs, warm = traffic.workload(p, 123, 1.0)
    d = driver.Driver(eng)
    driver.warm_up(d, warm)
    driver.run_closed(d, reqs, p["clients"], 1.0, lambda: None)
    d.drain(30.0)
    done = [r for r in d.recs if r.completed is not None][:8]
    rows = [runner.prompt_row(r.req.prompt, p["buckets"]) for r in done]
    return (fam, c), w, rows, [r.output_ids for r in done], p


def test_control_reads_far_above_the_program(served):
    (fam, c), w, rows, outs, p = served
    g = reference.served_gaps(fam, c, w, rows, outs,
                              T=max(p["buckets"]) + p["max_new_cap"],
                              n_max=p["max_new_cap"], control=True)
    assert g["served_tokens"] > 50
    assert g["served_gap"] <= 1e-3
    assert g["control_gap"] > 10 * max(g["served_gap"], 1e-3)


def test_fp8_rounding_is_coarser_than_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    f8 = np.asarray(reference._fp8(x, -1))
    bf = np.asarray(x.astype(jax.numpy.bfloat16).astype(np.float32))
    xs = np.asarray(x)
    assert np.abs(f8 - xs).max() > 4 * np.abs(bf - xs).max()


def test_a_control_run_is_not_correct(tmp_path):
    """The control put in the program's place fails the run: a run of the
    tiny cell with the control on reads ``correct`` false, on the same
    sample on which the program itself passes."""
    root = tiny.make_root(tmp_path)
    cell = spec.load_cell(root, "tiny.tiny-closed")
    out, _ = runner.execute(root, cell, 2**33 + 7, 2.0, False,
                            jax.devices()[:1], lambda: 1.0, control=True)
    checks = out["checks"]
    assert checks["served_gap"]["value"] <= checks["served_gap"]["limit"]
    assert checks["control_gap"]["value"] > checks["control_gap"]["limit"]
    assert out["correct"] is False
