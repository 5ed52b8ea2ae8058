"""A configuration served on a mesh, and a family of layers, found from new
files alone.

The meshed runs need four devices, which the CPU gives only with the
host-device override set before JAX starts: ``mesh_runs.py`` makes them in
a process of its own, once for this module."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import tiny
from harness import model, reference, spec

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def meshed(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh_runs.py"),
                        str(tmp)], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_meshed_cell_is_correct(meshed):
    out = meshed["meshed"]
    assert meshed["devices"] == 4
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert out["failed"] == 0 and out["attempted"] > 3
    assert out["checks"]["served_gap"]["value"] <= 1e-3


def test_the_engine_serves_on_the_cells_mesh(meshed):
    # the meshed cell's engine gets the mesh, the plain cell's none
    assert meshed["engine_meshes"] == [{"data": 1, "model": 4}, None]


def test_no_device_holds_a_whole_split_weight(meshed):
    w = meshed["weights"]
    split = [n for n, v in w.items() if "model" in v["rule"]]
    # heads, key/value heads, feed-forward and vocabulary: every matrix
    assert sorted(split) == ["embed", "lm_head", "w_down", "w_gate", "w_up",
                             "wk", "wo", "wq", "wv"]
    for name in split:
        v = w[name]
        # made in the program's rule's sharding: the engine moves nothing
        assert v["sharding"] == v["rule"]
        assert np.prod(v["largest_shard"]) * 4 == np.prod(v["full"]), name


def test_meshed_tokens_equal_the_unmeshed(meshed):
    assert meshed["plain"]["correct"] is True
    assert meshed["compared"] > 10
    assert meshed["equal"] == meshed["compared"]


@pytest.mark.parametrize("fault", ["stall", "drop_half", "alter_token",
                                   "no_exchange"])
def test_a_broken_meshed_step_is_not_correct(meshed, fault):
    f = meshed["faults"][fault]
    assert f["correct"] is False, f["checks"]


def _write_cell(root, config, chips, name="odd"):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "bench", "configs", name + ".json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "limits",
                           f"{name}.tiny-closed.json"), "w") as f:
        json.dump({"served_gap": 1e-3}, f)
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": f"{name}.tiny-closed", "config": name,
                               "traffic": "tiny-closed", "chips": chips,
                               "why": "CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return f"{name}.tiny-closed"


@pytest.mark.parametrize("mesh,chips", [({"data": 1, "model": 4}, 1),
                                        ({"data": 2, "model": 2}, 2),
                                        (None, 4)])
def test_chips_that_are_not_the_mesh_fail_at_load(tmp_path, mesh, chips):
    root = tiny.make_root(tmp_path)
    c = dict(tiny.MESH_CONFIG, mesh=mesh) if mesh else tiny.MESH_CONFIG
    with pytest.raises(ValueError, match="chips"):
        spec.load_cell(root, _write_cell(root, c, chips))


def test_an_unknown_family_fails_at_load(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = _write_cell(root, dict(tiny.CONFIG, family="hybrid"), 1)
    path = os.path.join(root, "bench", "families", "hybrid.py")
    with pytest.raises(FileNotFoundError, match=path):
        spec.load_cell(root, cell)
    c = dict(tiny.CONFIG)
    c.pop("family")
    cell = _write_cell(root, c, 1, name="nameless")
    with pytest.raises(KeyError, match="names no family"):
        spec.load_cell(root, cell)


STUB = '''"""A stub family: the dense block's everything, but a layer that
only adds one to the residual."""
import os

from harness import spec

_dense = spec.family(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "dense")
Dims, weight_shapes, init = _dense.Dims, _dense.weight_shapes, _dense.init
program_config, program_params = _dense.program_config, _dense.program_params
embed, head = _dense.embed, _dense.head


def layer(x, w, i, m, quant):
    return x + 1.0
'''


def test_a_new_family_is_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    before = {os.path.relpath(os.path.join(a, f), root):
              open(os.path.join(a, f), "rb").read()
              for a, _, fs in os.walk(os.path.join(root, "bench"))
              for f in fs if "__pycache__" not in a}
    with open(os.path.join(root, "bench", "families", "stub.py"), "w") as f:
        f.write(STUB)
    cell = spec.load_cell(root, _write_cell(
        root, dict(tiny.CONFIG, family="stub"), 1))
    assert cell.family.__file__ == os.path.join(root, "bench", "families",
                                                "stub.py")
    fam, c = cell.family, cell.config_data
    w = model.make_weights(fam, fam.Dims(c), 3)
    rows = np.full((1, 8), 5, np.int32)
    got = reference.logits(fam, c, w, rows, np.asarray([4]), 2)
    # embedding, the stub's layers (one added per layer), the dense head
    x = w["embed"][5] + 2.0
    want = reference.mm(reference.norm(x, w["norm_f_w"], None, 1e-5),
                        w["lm_head"], False)
    # jitted against eager float32: equal to a few units in the last place
    np.testing.assert_allclose(got[0, 0], want, rtol=1e-6, atol=1e-6)
    # no file the benchmark had was touched
    for rel, data in before.items():
        assert open(os.path.join(root, rel), "rb").read() == data, rel


# the weights and reference logits of three tiny variants as the harness
# made them before the dense block moved into bench/families/dense.py
PINNED = {
    "rms_gqa": ("a998d6b62c00f157142a2bff02a6597ed393071d1cabaea1215c76e9de8d2eb9",
                3799.328125, [0.6659136414527893, 0.8856092095375061,
                              -1.2319294214248657, -1.9689539670944214,
                              -0.7594888210296631]),
    "ln_partial": ("402d1c62f96c13adf1cbcd6dec8f429683f661975257cf7631eed2ff72007885",
                   3612.433349609375, [-0.373330295085907, 0.16827145218849182,
                                       0.6018795967102051, 0.5724726915359497,
                                       0.21378028392791748]),
    "ln_bf16": ("95824a6ddf7ae020607a25be6ad70ce6d2112dd6095218380699f7ab2c23376d",
                3568.23046875, None),
}


def _variant(name):
    c = dict(tiny.CONFIG)
    if name != "rms_gqa":
        c.pop("rms_norm_eps")
        c.update(num_key_value_heads=4, partial_rotary_factor=0.25,
                 layer_norm_eps=1e-5, sliding_window=6)
    if name == "ln_bf16":
        c["torch_dtype"] = "bfloat16"
    return c


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_dense_family_keeps_weights_and_reference(name):
    sha, total, first = PINNED[name]
    c = _variant(name)
    fam = spec.family(tiny.ROOT, "dense")
    w = model.make_weights(fam, fam.Dims(c), 7)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == sha
    rows = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                         320), np.int32)
    P = np.asarray([10, 12], np.int32)
    lg = np.asarray(reference.logits(fam, c, w, rows, P, 8))
    # float32 sums of 5120 logits: the same operations as before, so equal
    # to rounding in the last place of the sum
    assert np.abs(lg).sum() == pytest.approx(total, rel=1e-6)
    if first:
        np.testing.assert_allclose(lg[0, 0, :5], first, rtol=1e-6)
