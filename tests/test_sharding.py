"""Sharding-rule unit tests (no multi-device backend needed: rules are pure
functions of mesh *shape*; we build a Mesh over 1 real device is impossible
for 16x16, so we test the PartitionSpec logic through a fake mesh object)."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.distributed import sharding as shd
from repro.models.config import BlockSpec, ModelConfig


class FakeMesh:
    """Duck-typed stand-in: the rules only read ``mesh.shape``."""

    def __init__(self, shape_dict):
        self.shape = shape_dict


POD = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_resolve_axis_divisibility_fallback():
    assert shd.resolve_axis(POD, "kv", 8) is None          # 8 % 16 != 0
    assert shd.resolve_axis(POD, "kv", 32) == "model"
    assert shd.resolve_axis(POD, "embed", 4096) == "data"
    assert shd.resolve_axis(MULTI, "embed", 4096) == ("pod", "data")
    assert shd.resolve_axis(MULTI, "embed", 16) == "data"  # 16 % 32 != 0
    assert shd.resolve_axis(POD, None, 123) is None


class _Leaf:
    def __init__(self, shape):
        self.shape = shape


class _K:
    def __init__(self, key):
        self.key = key


def test_param_pspec_attention():
    spec = shd.param_pspec(POD, (_K("p0"), _K("mixer"), _K("wq")),
                           _Leaf((32, 4096, 8192)))
    assert tuple(spec) == (None, "data", "model")
    # kv proj with kv*hd=1024 divisible
    spec = shd.param_pspec(POD, (_K("p0"), _K("mixer"), _K("wk")),
                           _Leaf((32, 4096, 1024)))
    assert tuple(spec) == (None, "data", "model")


def test_param_pspec_moe_expert_fallback():
    # 16 experts: shard expert dim
    spec = shd.param_pspec(POD, (_K("p1"), _K("mlp"), _K("w_gate")),
                           _Leaf((9, 16, 8192, 24576)))
    assert tuple(spec) == (None, "model", "data", None)
    # 8 experts (mixtral): not divisible -> shard ffn instead
    spec = shd.param_pspec(POD, (_K("p0"), _K("mlp"), _K("w_gate")),
                           _Leaf((32, 8, 4096, 14336)))
    assert tuple(spec) == (None, None, "data", "model")


def test_state_pspec_kv_cache():
    # kv=8 not divisible by model=16 -> shard the cache SEQUENCE (it-5)
    spec = shd.state_pspec(POD, (_K("groups"), _K("p0"), _K("k")),
                           _Leaf((32, 128, 32768, 8, 128)))
    assert tuple(spec) == (None, "data", "model", None, None)
    # kv=32 divisible
    spec = shd.state_pspec(POD, (_K("groups"), _K("p0"), _K("k")),
                           _Leaf((24, 128, 32768, 32, 64)))
    assert tuple(spec) == (None, "data", None, "model", None)
    # batch=1 (long_500k), kv non-divisible: seq goes to "model"
    spec = shd.state_pspec(POD, (_K("groups"), _K("p0"), _K("k")),
                           _Leaf((32, 1, 8192, 8, 128)))
    assert tuple(spec) == (None, None, "model", None, None)


def test_state_pspec_recurrent():
    spec = shd.state_pspec(POD, (_K("groups"), _K("p0"), _K("ssm")),
                           _Leaf((63, 128, 16384, 16)))
    assert tuple(spec) == (None, "data", "model", None)
    # mlstm C: nh=4 not divisible -> shard dh
    spec = shd.state_pspec(POD, (_K("groups"), _K("p0"), _K("C")),
                           _Leaf((9, 32, 4, 384, 384)))
    assert tuple(spec) == (None, "data", None, "model", None)


def test_resolve_axis_warns_once_on_replication_fallback():
    """Silent degradation to replication must be surfaced: one
    ShardingFallbackWarning per (logical, dim, mesh), never repeated, and
    suppressed for probe call sites (warn=False) and size-1 dims."""
    shd.reset_fallback_warnings()
    with pytest.warns(shd.ShardingFallbackWarning, match="'vocab'"):
        assert shd.resolve_axis(POD, "vocab", 61) is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second time: silent
        assert shd.resolve_axis(POD, "vocab", 61) is None
        assert shd.resolve_axis(POD, "kv", 8, warn=False) is None
        assert shd.resolve_axis(POD, "embed", 1) is None    # size-1 is free
        assert shd.resolve_axis(POD, "embed", 4096) == "data"  # divisible
    # a DIFFERENT mesh shape for the same (axis, dim) warns again
    with pytest.warns(shd.ShardingFallbackWarning):
        assert shd.resolve_axis(MULTI, "vocab", 61) is None
    assert ("vocab", 61) in shd.fallback_report()
    shd.reset_fallback_warnings()
    assert shd.fallback_report() == []


def _dpath(*names):
    return tuple(_K(n) for n in names)


def test_decode_state_pspec_serving_leaves():
    """Serving-level DecodeState leaves: slot axis over data, trailing dims
    replicated; model-cache leaves keep the state_pspec rules."""
    mesh = FakeMesh({"data": 2, "model": 2})
    assert tuple(shd.decode_state_pspec(mesh, _dpath("buf"),
                                        _Leaf((4, 64)))) == ("data", None)
    assert tuple(shd.decode_state_pspec(mesh, _dpath("done"),
                                        _Leaf((4,)))) == ("data",)
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("stats", "accept_hist"),
        _Leaf((4, 6)))) == ("data", None)
    # sampling leaves (DESIGN.md §12) are ordinary per-slot rows: the
    # rng key's trailing (2,) stays replicated, the controls slot-shard
    assert tuple(shd.decode_state_pspec(mesh, _dpath("rng_key"),
                                        _Leaf((4, 2)))) == ("data", None)
    assert tuple(shd.decode_state_pspec(mesh, _dpath("temperature"),
                                        _Leaf((4,)))) == ("data",)
    assert tuple(shd.decode_state_pspec(mesh, _dpath("top_p"),
                                        _Leaf((4,)))) == ("data",)
    # odd slot count -> replicated, not an error
    assert tuple(shd.decode_state_pspec(mesh, _dpath("buf_len"),
                                        _Leaf((3,)))) == (None,)
    # linear cache under the model subtree: kv=2 divides model=2
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("model", "groups", "p0", "k"),
        _Leaf((1, 4, 32, 2, 16)))) == (None, "data", None, "model", None)


def test_decode_state_pspec_paged_pool():
    """The paged pool's page axis shards like the sequence axis (ROADMAP):
    over data when kv takes the model axis, extended over model when the
    kv heads cannot; bookkeeping stays slot-sharded / replicated."""
    mesh = FakeMesh({"data": 2, "model": 2})
    # kv=2 divides model -> pages over data only
    spec = shd.decode_state_pspec(mesh, _dpath("model", "groups", "p0", "k"),
                                  _Leaf((1, 16, 8, 2, 16)), paged=True)
    assert tuple(spec) == (None, "data", None, "model", None)
    # kv=1 (MQA) cannot take model -> pages over (data, model)
    spec = shd.decode_state_pspec(mesh, _dpath("model", "groups", "p0", "v"),
                                  _Leaf((1, 16, 8, 1, 16)), paged=True)
    assert tuple(spec) == (None, ("data", "model"), None, None, None)
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("model", "page_table"),
        _Leaf((4, 8)))) == ("data", None)
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("model", "free_list"), _Leaf((16,)))) == (None,)
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("model", "free_top"), _Leaf(()))) == ()


def test_decode_state_shardings_walks_real_state_paths():
    """Path-name extraction must understand the registered-dataclass
    GetAttrKey entries a real DecodeState flattens to (decode_state_pspec
    keys rules on those names)."""
    import jax.numpy as jnp

    from repro.core.spec_engine import DecodeState
    B, L = 2, 8
    state = DecodeState(
        buf=jnp.zeros((B, L), jnp.int32),
        buf_len=jnp.zeros((B,), jnp.int32),
        prompt_len=jnp.zeros((B,), jnp.int32),
        budget=jnp.zeros((B,), jnp.int32),
        eos_id=jnp.zeros((B,), jnp.int32),
        done=jnp.zeros((B,), bool),
        active=jnp.zeros((B,), bool),
        model={"cur_len": jnp.zeros((B,), jnp.int32),
               "groups": {"p0": {"k": jnp.zeros((1, B, L, 2, 4)),
                                 "v": jnp.zeros((1, B, L, 2, 4))}}},
        stats={"calls": jnp.zeros((B,), jnp.int32)},
        rng_key=jnp.zeros((B, 2), jnp.uint32),
        temperature=jnp.zeros((B,), jnp.float32),
        top_p=jnp.ones((B,), jnp.float32))
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    names = {"/".join(shd._path_names(p)) for p, _ in flat}
    assert "buf" in names
    assert "model/groups/p0/k" in names
    assert "stats/calls" in names
    assert "rng_key" in names and "temperature" in names


def test_act_sharding_activated_scoped_and_exception_safe():
    """The scoped installer restores the PREVIOUS sharder on exit — even on
    exception, even nested — and uninstall() clears a bare install()."""
    from repro.distributed import act_sharding as act
    mesh_a, mesh_b = object(), object()     # only identity matters here
    assert not act.installed()
    with act.activated(mesh_a):
        assert act.installed()
        with act.activated(mesh_b):
            assert act._MESH is mesh_b
        assert act._MESH is mesh_a          # restored, not cleared
    assert not act.installed()
    with pytest.raises(RuntimeError):
        with act.activated(mesh_a):
            raise RuntimeError("boom")
    assert not act.installed()
    act.install(mesh_a)
    assert act.installed()
    act.uninstall()
    assert not act.installed()


def test_mesh_toggles_pallas_eligibility_gate():
    """attn_verify's backend gate (the documented dispatch seam): the
    Pallas kernel is eligible exactly while NO activation sharder is
    installed — and a scoped activation must round-trip the gate."""
    import jax.numpy as jnp

    from repro.distributed import act_sharding as act
    from repro.models.attention import _use_verify_kernel
    cfg = ModelConfig(name="gate", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=32,
                      backend="pallas",
                      param_dtype=jnp.float32,
                      compute_dtype=jnp.float32).validate()
    cur = jnp.zeros((1,), jnp.int32)
    assert _use_verify_kernel(cfg, cur)
    with act.activated(object()):
        assert not _use_verify_kernel(cfg, cur)     # mesh pins XLA
    assert _use_verify_kernel(cfg, cur)             # eligibility restored


def test_mesh_pins_ngram_sweep_to_xla(monkeypatch):
    """Same seam for the DRAFTER sweep: the Pallas ngram kernel is a
    single-device pallas_call the SPMD partitioner cannot split, so an
    installed activation sharder must route ngram_sweep to the XLA path
    (and back, once the mesh scope exits)."""
    import jax.numpy as jnp

    from repro.distributed import act_sharding as act
    from repro.kernels import dispatch, ops
    hits = {"n": 0}
    real = ops.ngram_match_op

    def spy(*a, **k):
        hits["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(ops, "ngram_match_op", spy)
    buf = jnp.zeros((1, 16), jnp.int32)
    query = jnp.zeros((1, 1), jnp.int32)
    cur = jnp.full((1,), 8, jnp.int32)
    with act.activated(object()):
        m_x, h_x = dispatch.ngram_sweep(buf, query, cur, w=2,
                                        backend="pallas")
    assert hits["n"] == 0, "mesh-active sweep must take the XLA path"
    m_p, h_p = dispatch.ngram_sweep(buf, query, cur, w=2, backend="pallas")
    assert hits["n"] == 1                            # eligibility restored
    import numpy as np
    np.testing.assert_array_equal(np.asarray(m_x), np.asarray(m_p))
    np.testing.assert_array_equal(np.asarray(h_x), np.asarray(h_p))


def test_hostdev_mesh_parsing_and_env_hygiene():
    """The --mesh entry-point helper: shape parsing, argv peeking, and —
    since jax is already imported in this process — refusing to touch the
    environment (the device count is locked; mutating XLA_FLAGS now would
    only mislead subprocesses)."""
    import os

    from repro.launch import hostdev
    assert hostdev.parse_mesh_shape("2x2") == (2, 2)
    assert hostdev.parse_mesh_shape("2x4x2") == (2, 4, 2)
    for bad in ("2", "0x2", "ax2", "2x2x2x2"):
        with pytest.raises(ValueError):
            hostdev.parse_mesh_shape(bad)
    assert hostdev.mesh_arg(["prog", "--mesh", "2x2"]) == "2x2"
    assert hostdev.mesh_arg(["prog", "--mesh=4x1"]) == "4x1"
    assert hostdev.mesh_arg(["prog", "--paged"]) is None
    before = os.environ.get("XLA_FLAGS")
    assert hostdev.ensure_host_devices(8) is False      # jax imported
    assert os.environ.get("XLA_FLAGS") == before


def test_debug_mesh_clear_error_without_devices():
    """On a single-device process a debug mesh must fail with the
    launch-with-XLA_FLAGS recipe, not an opaque jax shape error."""
    from repro.launch.mesh import make_debug_mesh
    if jax.device_count() >= 4:
        pytest.skip("placeholder devices present (sharded lane)")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_debug_mesh((2, 2))


def test_every_assigned_arch_has_full_param_coverage():
    """Every leaf of every assigned arch gets a VALID PartitionSpec (rank
    matches) under both meshes — rule gaps would silently replicate."""
    import jax

    from repro.configs import ALL_ARCHS, get_config
    from repro.models import model as M
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda r: M.init_params(r, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        for mesh in (POD, MULTI):
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    shapes)[0]:
                spec = shd.param_pspec(mesh, path, leaf)
                assert len(spec) == len(leaf.shape), (arch, path)
                # spec axes must divide the dim
                for ax, d in zip(spec, leaf.shape):
                    if ax is None:
                        continue
                    axes = (ax,) if isinstance(ax, str) else ax
                    size = 1
                    for a in axes:
                        size *= mesh.shape[a]
                    assert d % size == 0, (arch, path, spec, leaf.shape)


def test_mla_and_held_expert_leaves_get_a_placement():
    """DeepSeek-V2-Lite on a described v5e-4 host (data 1, model 4): the
    per-head MLA projections split heads, the down-projection to the
    shared latent and its norm replicate over "model", the 16 held
    experts split 4 to a chip, and the latent cache splits its sequence
    (it has one shared head, so no head axis to split)."""
    import jax

    from repro.configs import get_config
    from repro.core.spec_engine import SpecConfig, empty_decode_state
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"),
                              experts_held=16)
    mesh = FakeMesh({"data": 1, "model": 4})
    shapes = jax.eval_shape(lambda r: M.init_params(r, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = {"/".join(shd._path_names(p)): tuple(shd.param_pspec(mesh, p, x))
             for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert specs["p0/mixer/wq"] == (None, "data", "model")
    assert specs["p0/mixer/wkv_a"] == (None, "data", None)
    assert specs["p0/mixer/kv_norm"] == (None, None)
    assert specs["p0/mixer/wkv_b"] == (None, None, "model")
    assert specs["p0/mixer/wo"] == (None, "model", "data")
    assert shapes["p0"]["mlp"]["w_gate"].shape == (26, 16, 2048, 1408)
    assert specs["p0/mlp/w_gate"] == (None, "model", "data", None)
    assert specs["p0/mlp/w_down"] == (None, "model", None, "data")
    assert specs["p0/mlp/router"] == (None, "data", None)
    state = jax.eval_shape(lambda: empty_decode_state(cfg, SpecConfig(), 8,
                                                      524))
    lat = state.model["groups"]["p0"]["latent"]
    assert lat.shape == (26, 8, 524, 576)
    path = _dpath("model", "groups", "p0", "latent")
    assert tuple(shd.decode_state_pspec(mesh, path, lat, strict=True)) == \
        (None, "data", "model", None)
    assert tuple(shd.decode_state_pspec(
        mesh, _dpath("stats", "expert_rows"), state.stats["expert_rows"],
        strict=True)) == ("data",)
    # a cache split over its sequence keeps the commit's scatter
    from repro.distributed import act_sharding as act
    act.install(mesh)
    try:
        assert act.splits_cache_rows(lat.shape)
    finally:
        act.uninstall()
