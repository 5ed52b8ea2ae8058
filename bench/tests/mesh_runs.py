"""Runs of a tiny configuration on a four-device mesh, for
``test_bench_mesh.py``; one JSON line on standard output.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/mesh_runs.py <scratch dir>

It needs the host-device override, which has to be set before JAX starts,
so the test runs it in a process of its own.  In one process: the meshed
cell through ``runner.execute``, the same configuration without a mesh on
the same seed, and the meshed cell with each fault a cell on several chips
can have planted under the timed path.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import tiny  # noqa: E402
from harness import driver, model, runner, spec  # noqa: E402

SEED = 2**33 + 11
MESHED = "tiny-tp4.tiny-closed"
PLAIN = "tiny-plain.tiny-closed"


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


AFTER_WARM_UP = {"stall": _stall, "drop_half": _drop_half,
                 "alter_token": _alter_token}


def _no_exchange():
    """The MLP's exchange between chips left out: each device keeps its own
    partial sum of the feed-forward output, which the rules split over
    ``model``, as if it were the whole (the all-reduce never runs).
    Returns a function that puts the program's MLP back."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import act_sharding
    from repro.models import transformer
    mlp = transformer.apply_mlp

    def local(params, x, cfg, kind):
        mesh = act_sharding._MESH
        if mesh is None:
            return mlp(params, x, cfg, kind)
        specs = {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                 "w_down": P("model", None)}
        return jax.shard_map(
            lambda p, h: mlp(p, h, cfg, kind), mesh=mesh,
            in_specs=({k: specs[k] for k in params}, P()), out_specs=P(),
            check_vma=False)(params, x)
    transformer.apply_mlp = local
    return lambda: setattr(transformer, "apply_mlp", mlp)


def _root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, mesh, chips in (("tiny-tp4", {"data": 1, "model": 4}, 4),
                              ("tiny-plain", None, 1)):
        c = dict(tiny.MESH_CONFIG, mesh=mesh) if mesh else \
            dict(tiny.MESH_CONFIG)
        with open(os.path.join(root, "bench", "configs", name + ".json"),
                  "w") as f:
            json.dump(c, f)
        cell = f"{name}.tiny-closed"
        with open(os.path.join(root, "bench", "limits", cell + ".json"),
                  "w") as f:
            json.dump({"served_gap": 1e-3}, f)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny-closed", "chips": chips,
                                   "why": "CPU test"})
        for m in bench["end_to_end"]:
            if m["name"] == "tokens_per_s":
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _execute(root, name):
    cell = spec.load_cell(root, name)
    out, run = runner.execute(root, cell, SEED, 2.0, False,
                              jax.devices()[:cell.chips], lambda: 1.0)
    return out, {i: r.output_ids.tolist() for i, r in enumerate(run.recs)
                 if r.completed is not None and not r.error}


def main(tmp: str) -> dict:
    root = _root(tmp)
    runner.import_program(root)
    jax.config.update("jax_compilation_cache_dir", os.path.join(tmp, "jc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import serving
    made, meshes = [], []
    make_weights, engine = model.make_weights, serving.ServingEngine

    def spy_weights(family, m, seed, mesh=None):
        w = make_weights(family, m, seed, mesh)
        made.append((family, m, mesh, w))
        return w

    def spy_engine(*a, **kw):
        meshes.append(kw.get("mesh"))
        return engine(*a, **kw)
    model.make_weights = spy_weights
    serving.ServingEngine = spy_engine
    out, meshed = _execute(root, MESHED)
    family, m, mesh, w = made[-1]
    shards = model.shardings(family, m, mesh)
    split = {}
    for name, arr in w.items():
        full = tuple(arr.shape)
        split[name] = {
            "rule": str(shards[name].spec),
            "sharding": str(arr.sharding.spec),
            "largest_shard": max(tuple(s.data.shape)
                                 for s in arr.addressable_shards),
            "full": full}
    del made[:], w
    plain_out, plain = _execute(root, PLAIN)
    model.make_weights = make_weights
    serving.ServingEngine = engine
    both = sorted(set(meshed) & set(plain))
    faults = {}
    warm_up = driver.warm_up
    for fault, plant in AFTER_WARM_UP.items():
        def broken(d, warm, _plant=plant, **kw):
            warm_up(d, warm, **kw)
            _plant(d.engine)
        driver.warm_up = broken
        runner.DRAIN_S = 1.0
        try:
            faults[fault] = _execute(root, MESHED)[0]
        finally:
            driver.warm_up = warm_up
            runner.DRAIN_S = 60.0
    restore = _no_exchange()
    try:
        faults["no_exchange"] = _execute(root, MESHED)[0]
    finally:
        restore()
    return {"devices": len(jax.devices()), "meshed": out,
            "engine_meshes": [None if x is None else dict(x.shape)
                              for x in meshes],
            "weights": split, "plain": plain_out,
            "compared": len(both),
            "equal": sum(meshed[i] == plain[i] for i in both),
            "faults": {k: {"correct": v["correct"], "checks": v["checks"]}
                       for k, v in faults.items()}}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])), flush=True)
