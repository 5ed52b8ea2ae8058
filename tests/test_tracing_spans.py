"""The program's own instrumentation: host spans of the serving engine on the
profiler's trace, the phase scopes of the compiled step, and the table-build
timer.

A tiny continuous engine is traced with ``jax.profiler``:
``engine.step`` / ``engine.done_wait`` every step, ``engine.dispatch`` on
every step that has work, ``engine.readback`` on retire rounds, and
``engine.admit`` / ``engine.retire`` once per request under its
``request_id``.  The compiled ``spec_step`` names its phases in the ops'
``op_name`` metadata (``spec.draft``, ``spec.verify``, ``spec.commit``).
"""
import glob
import time

import jax
import pytest

from repro.core.ngram_tables import NGramTables, build_bigram, build_unigram
from repro.core.spec_engine import SpecConfig, init_decode_state, spec_step
from repro.models import model as M
from repro.serving import ServingEngine

SPEC = SpecConfig(k=4, w=3, strategy="mixed", max_new_tokens=8)
PROMPTS = ("first request", "second one", "a third")


def _tables(params, cfg):
    fwd = jax.jit(lambda t: M.forward(params, cfg, tokens=t)[0][:, -1])
    topk, chain = build_bigram(fwd, cfg.vocab_size, k_max=8, w_max=8,
                               batch=cfg.vocab_size)
    uni = build_unigram(params["embed"]["embedding"],
                        params["embed"].get("lm_head",
                                            params["embed"]["embedding"].T),
                        k_max=8)
    return NGramTables(uni, topk, chain)


def _host_events(tdir):
    """[(name, start_ns, end_ns, {stat: value})] of every host-plane event,
    in start order."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(tiny_dense, tmp_path_factory):
    """Three requests through two slots, traced from the constructor on:
    the third is admitted into the slot the first one frees."""
    cfg, params = tiny_dense
    tdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        eng = ServingEngine(params, cfg, SPEC, max_batch=2, buckets=(16,),
                            max_new_cap=8)
        ctor_s = time.perf_counter() - t0
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(PROMPTS, (4, 8, 6))]
        steps, busy = 0, 0
        done = []
        while eng.scheduler.pending() or eng.in_flight() or not steps:
            done += eng.step()
            steps += 1
            busy += eng.in_flight() > 0
    finally:
        jax.profiler.stop_trace()
    return dict(engine=eng, ctor_s=ctor_s, reqs=reqs, done=done,
                steps=steps, busy=busy, events=_host_events(tdir))


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_every_step_has_its_spans(traced):
    ev = traced["events"]
    steps = _named(ev, "engine.step")
    assert len(steps) == traced["steps"]
    assert len(_named(ev, "engine.done_wait")) == traced["steps"]
    # a step with no request in a slot dispatches nothing
    assert len(_named(ev, "engine.dispatch")) == traced["busy"]
    # every engine span of the served path lies inside one engine.step
    for name in ("engine.done_wait", "engine.dispatch", "engine.readback",
                 "engine.admit", "engine.retire"):
        for _, a, b, _ in _named(ev, name):
            assert any(s <= a and b <= t for _, s, t, _ in steps), name
    assert 1 <= len(_named(ev, "engine.readback")) <= len(PROMPTS)


def test_admit_and_retire_once_per_request(traced):
    ev = traced["events"]
    ids = sorted(r.request_id for r in traced["reqs"])
    assert sorted(r.request_id for r in traced["done"]) == ids
    admits = _named(ev, "engine.admit")
    retires = _named(ev, "engine.retire")
    assert sorted(e[3]["request_id"] for e in admits) == ids
    assert sorted(e[3]["request_id"] for e in retires) == ids
    assert {e[3]["bucket"] for e in admits} == {16}
    # a request's retire follows its admit
    start = {e[3]["request_id"]: e[1] for e in admits}
    assert all(e[1] > start[e[3]["request_id"]] for e in retires)


def test_set_up_spans_and_table_timer(traced, tiny_dense):
    # set-up is timed by the counter alone: no span outside engine.step
    ev = traced["events"]
    steps = _named(ev, "engine.step")
    assert all(any(s <= a and b <= t for _, s, t, _ in steps)
               for name, a, b, _ in ev
               if name.startswith("engine.") and name != "engine.step")
    eng = traced["engine"]
    # the table build is part of the constructor
    assert eng.tables_s is not None
    assert 0 < eng.tables_s <= traced["ctor_s"]
    cfg, params = tiny_dense
    given = ServingEngine(params, cfg, SPEC, tables=eng.tables, max_batch=2,
                          buckets=(16,), max_new_cap=8)
    assert given.tables_s is None


def _step_hlo(tiny_dense, spec):
    cfg, params = tiny_dense
    tables = _tables(params, cfg) if spec.strategy != "greedy" else None
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    state = init_decode_state(params, cfg, spec, prompt)
    return spec_step.lower(params, cfg, spec, state,
                           tables).compile().as_text()


@pytest.mark.parametrize("spec,scopes", [
    (SPEC, ("spec.draft", "spec.verify", "spec.commit")),
    (SpecConfig(k=3, w=3, strategy="mixed", tree=True, tree_branch=2,
                max_new_tokens=8),
     ("spec.draft", "spec.verify", "spec.commit")),
    (SpecConfig(strategy="greedy", max_new_tokens=8),
     ("spec.verify", "spec.commit")),
], ids=["linear", "tree", "greedy"])
def test_compiled_step_names_its_phases(tiny_dense, spec, scopes):
    hlo = _step_hlo(tiny_dense, spec)
    named = {s for s in ("spec.draft", "spec.verify", "spec.commit")
             if f"/{s}/" in hlo}
    assert named == set(scopes)
    # the verify forward's matrix products are the verify phase's
    dots = [line for line in hlo.splitlines()
            if " dot(" in line and "op_name=" in line]
    assert dots and all("spec.verify" in d for d in dots
                        if "/spec.draft/" not in d)

