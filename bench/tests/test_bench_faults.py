"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault a served cell can have.  (A cell on a mesh has
these faults and one more, the exchange between chips left out:
``test_bench_mesh.py`` plants all four on a four-device mesh.)"""
import jax
import numpy as np
import pytest

import tiny
from harness import driver, runner, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _stall(engine):
    """Every step returns the state unchanged."""
    engine._run_step = lambda state: state


def _drop_half(engine):
    """Half of the requests the engine finishes are never handed back."""
    step = engine.step
    engine.step = lambda: [r for r in step() if r.request_id % 2 == 0]


def _alter_token(engine):
    """One token of every answer is changed where it is produced."""
    step = engine.step

    def altered():
        out = step()
        for r in out:
            ids = np.array(r.output_ids)
            ids[len(ids) // 2] = (ids[len(ids) // 2] + 1) % 320
            r.output_ids = ids
        return out
    engine.step = altered


@pytest.mark.parametrize("fault", [_stall, _drop_half, _alter_token])
@pytest.mark.parametrize("cell", ["tiny.tiny-closed", "tiny.tiny-open"])
def test_a_broken_step_is_not_correct(root, monkeypatch, fault, cell):
    warm_up = driver.warm_up

    def broken_after_warm_up(d, warm, **kw):
        warm_up(d, warm, **kw)
        fault(d.engine)
    monkeypatch.setattr(driver, "warm_up", broken_after_warm_up)
    monkeypatch.setattr(runner, "DRAIN_S", 1.0)
    out, _ = runner.execute(root, spec.load_cell(root, cell), 5, 2.0,
                            False, jax.devices()[:1], lambda: 1.0)
    assert out["correct"] is False, out["checks"]
