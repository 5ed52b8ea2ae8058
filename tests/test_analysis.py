"""repro-lint (src/repro/analysis): every rule fires on its planted
violation, and the live codebase is clean modulo baseline/waivers.

Structure mirrors the subsystem: AST rules are exercised on synthetic
sources through ``analyze_source`` (so waiver plumbing is on the path),
jaxpr rules on planted functions/states through the same helpers the
live checks use, and one end-to-end run asserts the zero-findings gate
the CI lint lane enforces.
"""
import dataclasses
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import Baseline, run_all
from repro.analysis import ast_rules as ar
from repro.analysis import jaxpr_rules as jr
from repro.analysis import registry
from repro.analysis.__main__ import main as lint_main
from repro.analysis.findings import Finding, apply_waivers, scan_waivers
from repro.distributed import sharding as shd


def _ast(relpath, source):
    findings, _ = ar.analyze_source(relpath, textwrap.dedent(source))
    return [f for f in findings if not f.waived]


# ---------------------------------------------------------------------------
# AST rules: planted violations + the matching clean variants
# ---------------------------------------------------------------------------
def test_pallas_scope_fires_outside_kernels():
    src = "import jax.experimental.pallas as pl\nout = pl.pallas_call(kern)(x)\n"
    got = _ast("core/rogue.py", src)
    assert [f.rule for f in got] == ["pallas-scope"]
    assert got[0].line == 2 and "pallas_call" in got[0].context


def test_pallas_scope_allowed_inside_kernels():
    src = "import jax.experimental.pallas as pl\nout = pl.pallas_call(kern)(x)\n"
    assert _ast("kernels/attn.py", src) == []


def test_tracer_branch_fires_on_traced_if():
    src = """
    import jax.numpy as jnp
    def f(x):
        y = jnp.sum(x)
        z = y + 1
        if z > 0:
            return 1
        while y:
            pass
    """
    got = _ast("core/rogue.py", src)
    assert sorted(f.rule for f in got) == ["tracer-branch", "tracer-branch"]


def test_tracer_branch_ignores_static_branches():
    src = """
    import jax.numpy as jnp
    def f(x, flag):
        y = jnp.sum(x)
        if x.shape[0] > 1:      # static: shapes are Python ints
            pass
        if flag:                # untraced argument
            pass
        return y
    """
    assert _ast("core/ok.py", src) == []


def test_tracer_branch_scoped_to_core():
    src = "import jax.numpy as jnp\ndef f(x):\n    y = jnp.sum(x)\n    if y > 0:\n        pass\n"
    assert _ast("serving/elsewhere.py", src) == []


def test_hash_constants_fires_on_rederivation():
    got = _ast("core/rogue.py", "MULT = 2654435761\nMIX = 0x9E3779B9\n")
    assert [f.rule for f in got] == ["hash-constants", "hash-constants"]


def test_hash_constants_fires_on_name_redefinition():
    got = _ast("core/rogue.py", "HASH_MULT = 12345\n")
    assert [f.rule for f in got] == ["hash-constants"]


def test_hash_constants_allowed_in_hashing_module():
    assert _ast("kernels/hashing.py", "HASH_MULT = 2654435761\n") == []


def test_global_state_fires_on_module_level_env_mutation():
    got = _ast("launch/rogue.py", "import os\nos.environ['XLA_FLAGS'] = '-x'\n")
    assert [f.rule for f in got] == ["global-state"]


def test_global_state_allows_main_guard_and_functions():
    src = """
    import os
    def setup():
        os.environ['XLA_FLAGS'] = '-x'    # runs when called, not at import
    if __name__ == "__main__":
        os.environ['XLA_FLAGS'] = '-x'    # entry-point pattern (dryrun)
    """
    assert _ast("launch/ok.py", src) == []


def test_global_state_fires_on_unpaired_install():
    src = "from repro.distributed import act_sharding\ndef go(mesh):\n    act_sharding.install(mesh)\n"
    got = _ast("serving/rogue.py", src)
    assert [f.rule for f in got] == ["global-state"]
    # pairing an uninstall in the module satisfies the rule
    assert _ast("serving/ok.py",
                src + "def stop():\n    act_sharding.uninstall()\n") == []


def test_time_in_jit_fires_in_jitted_and_body_fns():
    src = """
    import time, jax
    import numpy as np
    @jax.jit
    def f(x):
        t = time.time()
        return x
    def _step_body(s):
        r = np.random.rand()
        return s
    def host_fn():
        return time.time()       # fine: not a jitted body
    """
    got = _ast("core/rogue.py", src)
    assert sorted(f.rule for f in got) == ["time-in-jit", "time-in-jit"]


def test_serving_sync_rule_and_inventory():
    src = textwrap.dedent("""
    import numpy as np
    class Engine:
        def step(self):
            done = np.asarray(self.state.done)
            # repro-lint: allow(host-sync): test waiver
            ok = np.asarray(self.state.buf)
        def helper(self):
            also = np.asarray(self.state.buf)    # not a critical-path method
    """)
    findings, inventory = ar.analyze_source("serving/engine.py", src)
    sync = [f for f in findings if f.rule == "host-sync"]
    assert len(sync) == 2                       # helper() not scanned
    assert [f.waived for f in sync] == [False, True]
    # the inventory keeps waived entries — the async work needs the full map
    assert len(inventory) == 2
    assert inventory[1]["waived"] and inventory[1]["reason"] == "test waiver"


# ---------------------------------------------------------------------------
# waiver / baseline plumbing
# ---------------------------------------------------------------------------
def test_waiver_comment_applies_to_line_below():
    w = scan_waivers("x = 1\n# repro-lint: allow(a-rule): why\ny = 2\n")
    assert 2 in w and 3 in w and w[3] == ({"a-rule"}, "why")
    f = Finding(rule="a-rule", file="f.py", line=3, message="m")
    assert apply_waivers([f], w)[0].waived
    other = Finding(rule="other", file="f.py", line=3, message="m")
    assert not apply_waivers([other], w)[0].waived


def test_baseline_split_and_covers(tmp_path):
    f1 = Finding(rule="r", file="a.py", line=3, message="m", context="ctx")
    f2 = Finding(rule="r", file="a.py", line=9, message="m", context="new")
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(
        {"entries": [{"rule": "r", "file": "a.py", "context": "ctx"}]}))
    b = Baseline.load(str(p))
    new, accepted = b.split([f1, f2])
    assert accepted == [f1] and new == [f2]
    # context matching survives line drift by construction (no line in key)
    assert b.covers(dataclasses.replace(f1, line=99))


# ---------------------------------------------------------------------------
# jaxpr rules: planted violations
# ---------------------------------------------------------------------------
def test_donation_fires_on_unusable_donation():
    # sum() shrinks the aval: the donated (8,) input matches no output
    struct = jax.ShapeDtypeStruct((8,), jnp.float32)
    got = jr.donation_findings(lambda x: x.sum(), (struct,), struct, "<p>")
    assert got and all(f.rule == "donation" for f in got)


def test_donation_clean_on_in_place_update():
    struct = {"a": jax.ShapeDtypeStruct((8,), jnp.float32)}
    fn = lambda s: {"a": s["a"] + 1}
    assert jr.donation_findings(fn, (struct,), struct, "<p>") == []


def test_shared_buffer_fires():
    z = jnp.zeros((4,), jnp.float32)            # same buffer, two leaves
    got = jr.shared_buffer_findings({"a": z, "b": z}, "<p>")
    assert len(got) == 1 and "share one device buffer" in got[0].message


def test_shared_buffer_clean_on_distinct_buffers():
    tree = {"a": jnp.zeros((4,)), "b": jnp.zeros((4,))}
    assert jr.shared_buffer_findings(tree, "<p>") == []


def test_signature_fires_on_aval_drift():
    struct = {"x": jax.ShapeDtypeStruct((4,), jnp.int32)}
    got = jr.signature_findings(lambda s: {"x": s["x"][:2]}, struct, "<p>")
    assert len(got) == 1 and "drifts" in got[0].message


def test_signature_fires_on_structure_drift():
    struct = {"x": jax.ShapeDtypeStruct((4,), jnp.int32)}
    got = jr.signature_findings(
        lambda s: {"x": s["x"], "extra": s["x"]}, struct, "<p>")
    assert len(got) == 1 and "only in the output" in got[0].message


def test_signature_clean_on_fixed_point():
    struct = {"x": jax.ShapeDtypeStruct((4,), jnp.int32)}
    assert jr.signature_findings(lambda s: {"x": s["x"] + 1}, struct,
                                 "<p>") == []


def test_host_sync_fires_on_debug_callback():
    def g(x):
        jax.debug.print("x={x}", x=x)
        return x + 1
    got = jr.jaxpr_sync_findings(g, (jnp.ones(3),), "<p>")
    # jax.debug.print binds the debug_print primitive (JAX 0.9)
    assert len(got) == 1 and "debug_print" in got[0].context


def test_host_sync_walks_nested_jaxprs():
    def g(x):
        def body(_, c):
            jax.debug.print("c={c}", c=c)
            return c + 1
        return jax.lax.fori_loop(0, 3, body, x)
    got = jr.jaxpr_sync_findings(g, (jnp.float32(0.0),), "<p>")
    assert got, "callback hidden inside a fori_loop body must be found"


# ---------------------------------------------------------------------------
# sharding coverage + the strict pspec contract (satellite)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def built_linear():
    return registry.build_case(registry.CASES[0])


def test_sharding_coverage_fires_on_ruleless_leaf(built_linear):
    st = built_linear.state
    st2 = dataclasses.replace(
        st, model={**st.model, "mystery": jnp.zeros((4, 4), jnp.float32)})
    b2 = dataclasses.replace(built_linear, state=st2)
    got = jr.check_sharding_coverage(b2)
    assert got and all("mystery" in f.message for f in got)
    assert len(got) == len(registry.MESHES)      # raised on every mesh


def test_strict_pspec_raises_on_unknown_leaf():
    mesh = registry.MESHES[0]
    path = (jax.tree_util.DictKey("model"), jax.tree_util.DictKey("mystery"))
    leaf = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    with pytest.raises(KeyError, match="DECODE_STATE_LEAF_RULES"):
        shd.decode_state_pspec(mesh, path, leaf, strict=True)
    # non-strict keeps the engine's replicate-unknown behaviour
    spec = shd.decode_state_pspec(mesh, path, leaf, strict=False)
    assert tuple(spec) == (None, None)


def test_leaf_rules_table_covers_every_registry_state():
    """The satellite contract: DECODE_STATE_LEAF_RULES is the single
    source of truth, and every leaf the engine actually builds (all
    registry cases, paged included) matches an entry."""
    for case in registry.CASES:
        built = registry.build_case(case)
        flat = jax.tree_util.tree_flatten_with_path(built.state)[0]
        for path, _ in flat:
            names = shd._path_names(path)
            assert (names[0] in shd.DECODE_STATE_LEAF_RULES
                    or names[-1] in shd.DECODE_STATE_LEAF_RULES), names


# ---------------------------------------------------------------------------
# end to end: the live codebase is clean, and the CLI gates on it
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_live_codebase_clean_modulo_baseline():
    findings, inventory = run_all()              # both levels, full registry
    baseline = Baseline.load(
        __import__("repro.analysis", fromlist=["DEFAULT_BASELINE"]
                   ).DEFAULT_BASELINE)
    new, _ = baseline.split(findings)
    assert new == [], "new findings:\n" + "\n".join(f.format() for f in new)
    # the engine's one structural sync (the retire done-flag readback) must
    # stay in the inventory — the async PR diffs against this map
    assert any(e["method"] == "_retire_finished" for e in inventory)


def test_cli_level2_strict_and_syncmap(tmp_path):
    out = tmp_path / "BENCH_syncmap.json"
    rc = lint_main(["--level", "2", "--strict", "--syncmap", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["total"] == len(data["inventory"]) >= 1
    assert data["waived"] >= 1                   # engine waivers are mapped


def test_cli_fails_on_stale_baseline_only_when_strict(tmp_path):
    stale = tmp_path / "baseline.json"
    stale.write_text(json.dumps({"entries": [
        {"rule": "ghost", "file": "gone.py", "context": "x"}]}))
    assert lint_main(["--level", "2", "--baseline", str(stale)]) == 0
    assert lint_main(["--level", "2", "--strict",
                      "--baseline", str(stale)]) == 1
