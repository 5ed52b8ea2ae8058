"""The trace reduction, on a recorded v5e trace of one StableLM-2-1.6B
``spec_step`` (4 slots; the ops line cut to instruction names)."""
import os

import pytest

from harness import tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.textproto")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        return tracing.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_programs_and_kernels_by_name(reduced):
    assert reduced["modules"]["spec_step"]["n"] == 1
    # one verify-attention call per layer, one n-gram sweep per step
    assert reduced["kernels"]["spec_attention"]["n"] == 24
    assert reduced["kernels"]["ngram_match"]["n"] == 1
    assert set(reduced["kernels"]) == {"spec_attention", "ngram_match"}


def test_busy_is_the_union_within_the_window(reduced):
    step = reduced["modules"]["spec_step"]["s"]
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # nested operations (a while and its body) are counted once
    assert reduced["busy_s"] == pytest.approx(step, rel=0.01)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in reduced["gaps"]) == pytest.approx(idle,
                                                               rel=1e-3)


def test_top_ops_are_self_times(reduced):
    names = [n for n, _ in reduced["top_ops"]]
    assert "while" not in names
    assert len(names) <= 10
    assert sum(s for _, s in reduced["top_ops"]) <= reduced["busy_s"]


def test_gaps_are_named_by_host_span(reduced):
    assert reduced["gaps"][0][0] == "bench.step"
    assert all(n in tracing.HOST_SPANS + ("host.other",)
               for n, _ in reduced["gaps"])


@pytest.mark.parametrize("text,want", [
    ("%spec_attention.7 = bf16[4,32,110,64] custom-call()", "spec_attention"),
    ("%ngram_match = (s32[4]) custom-call()", "ngram_match"),
    ("%compare_select_fusion.36 = (s32[4]) fusion()",
     "compare_select_fusion"),
])
def test_instruction_names(text, want):
    assert tracing.instruction(text) == want


def test_module_names():
    assert tracing.module("jit_spec_step(13879119432686850200)") == \
        "spec_step"
    assert tracing.module("jit_admit_slot(1)") == "admit_slot"


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read().replace('"bench.window"', '"other"')
    with pytest.raises(ValueError):
        tracing.reduce_profile(ProfileData.from_text_proto(text))
