"""chip_smoke.py off the chip: its phases on a tiny config with the Pallas
kernels in interpret mode, its refusal to run on the CPU, its last line,
and the compile-cache helper its entry points share."""
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec_engine import SpecConfig
from repro.launch import compile_cache
from repro.models.config import ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tiny_smoke(smoke, monkeypatch):
    """The smoke's workload cut to CPU size: 6 requests through 4 slots
    (so retirements admit queued requests), a 256-token bucket."""
    monkeypatch.setattr(smoke, "N_REQUESTS", 6)
    monkeypatch.setattr(smoke, "SLOTS", 4)
    monkeypatch.setattr(smoke, "BUCKET", 256)
    monkeypatch.setattr(smoke, "MAX_NEW", 12)
    monkeypatch.setattr(smoke, "SHORT_NEW", 7)
    return smoke


def test_phases_on_cpu_interpret(tiny_smoke, capsys):
    """Every phase runs; kernels agree with their references and every
    request equals both greedy baselines, linear and paged.  The one
    failure is the kernel check: interpret-mode kernels are plain XLA ops,
    not TPU custom calls, and the smoke must refuse them."""
    cfg = ModelConfig(name="smoke-tiny", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=300,
                      backend="pallas", kernel_block_s=64,
                      param_dtype=jnp.float32,
                      compute_dtype=jnp.float32).validate()
    recs = tiny_smoke.smoke_one_chip(
        cfg, seed=0, spec=SpecConfig(k=4, w=3, backend="pallas"))
    by = {r["phase"]: r for r in recs}
    assert set(by) == {"kernels", "greedy", "linear", "paged"}
    assert by["kernels"]["ok"], by["kernels"]["failures"]
    k = by["kernels"]
    assert k["verify_max_abs_err"] <= k["verify_tol"] \
        < k["verify_bf16_max_abs_err"]
    assert by["greedy"]["uncached_witness"]["disagreements"] == []
    cmp = by["greedy"]["engine_greedy_vs_reference"]
    assert cmp["equal"] == cmp["total"] == 6
    for name in ("linear", "paged"):
        r = by[name]
        for key in ("vs_engine_greedy", "vs_greedy_reference"):
            assert r[key]["equal"] == r[key]["total"] == 6, (name, r[key])
        assert r["uncached_witness"]["tokens"] == 3 * (12 + 7)
        assert r["uncached_witness"]["disagreements"] == []
        assert r["kernels"] == []
        assert not r["ok"]
        assert r["failures"] == [
            "Pallas kernels not compiled for the chip: "
            + str(sorted(["ngram_match", "spec_attention" if name == "linear"
                          else "paged_spec_attention"]))]
        assert r["calls"] > 0 and r["tokens"] == 3 * (12 + 7)
    # every phase line printed is one JSON object
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(x)["phase"] for x in lines] == \
        ["kernels", "greedy", "linear", "paged"]


def test_compare_reports_first_divergence(smoke):
    got = [np.array([1, 2, 3]), np.array([4, 5, 9, 7]), np.array([1])]
    want = [np.array([1, 2, 3, 4]), np.array([4, 5, 6, 7]), np.array([1])]
    cmp = smoke.compare(got, want)
    assert cmp["equal"] == 2 and cmp["total"] == 3
    assert cmp["divergences"] == [
        {"request": 1, "position": 2, "got": 9, "want": 6}]
    tf = {"margin": np.arange(12, dtype=np.float32).reshape(3, 4) / 100}
    smoke.mark_margins(cmp, tf)
    assert cmp["divergences"][0]["top2_margin"] == pytest.approx(0.06)


def test_witness_criterion(smoke):
    """A served token passes as the lone forward's argmax, or below it by
    no more than the forward's own noise between batch shapes; any other
    token is a disagreement, and fails the phase."""
    outs = [np.array([5, 6, 7]), np.array([1, 2])]
    tf = {"argmax": np.array([[5, 9, 7], [3, 2, 0]]),
          "gap": np.array([[0, 1e-3, 0], [2e-2, 0, 0]], np.float32),
          "noise": np.array([[0, 2e-3, 0], [1e-3, 0, 5.0]], np.float32)}
    wit = smoke.witness(tf, outs)
    assert wit["tokens"] == 5 and wit["argmax"] == 3
    assert [(d["request"], d["position"]) for d in wit["near_ties"]] == \
        [(0, 1)]
    assert [(d["request"], d["position"], d["argmax"])
            for d in wit["disagreements"]] == [(1, 0, 3)]
    # the padded position past request 1's output is never read
    assert wit["max_noise"] == pytest.approx(2e-3)
    assert smoke.witness_failures(wit)
    tf["gap"][1, 0] = 0.0
    tf["argmax"][1, 0] = 1
    assert smoke.witness_failures(smoke.witness(tf, outs)) == []


def test_teacher_forced_reads_served_positions(tiny_smoke):
    """The uncached forward picks each token of plain greedy decoding as
    its argmax (gap 0), and padding past a short output reads nothing."""
    from repro.core.spec_engine import greedy_reference
    from repro.models import model as M
    cfg = ModelConfig(name="smoke-tiny", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=300,
                      param_dtype=jnp.float32,
                      compute_dtype=jnp.float32).validate()
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, 16),
                                            0, 300), np.int32)
    ref = np.asarray(greedy_reference(params, cfg, jnp.asarray(prompts),
                                      12))[:, 16:]
    outs = [ref[0], ref[1, :7], ref[2]]
    tf = tiny_smoke.teacher_forced(params, cfg, prompts, outs)
    assert tf["argmax"].shape == tf["noise"].shape == (3, 12)
    np.testing.assert_array_equal(tf["argmax"][0], ref[0])
    wit = tiny_smoke.witness(tf, outs)
    assert wit["tokens"] == 31 and wit["disagreements"] == []
    assert (tf["gap"][0] == 0).all() and (tf["margin"] >= 0).all()


def test_last_line_format(smoke):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = smoke.last_line([dev], 1)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_main_refuses_cpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_import_repro_from_checkout(smoke, monkeypatch, tmp_path):
    """The package must come from this checkout's src/; a copy of the
    script alone, with no src/ beside it, refuses to run."""
    smoke.import_repro()
    monkeypatch.setattr(smoke, "SRC", str(tmp_path / "src"))
    with pytest.raises(SystemExit, match="not found"):
        smoke.import_repro()


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to the fixed ``.jax_cache`` at the root of the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.enable_compile_cache() == want   # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
