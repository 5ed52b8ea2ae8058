"""Useful model operations of the window over the traced window's length
times the chips' peak, in percent (``bench/roofline/step.py``, counted by
the sizes the configuration's family gives, ``Dims.flops_dims``): every
prompt prefilled in the window and a lower bound on the output tokens its
verify calls committed (``harness/accounting.py``); rejected draft
positions count nothing.  The window is the part of it that every chip's
trace holds."""
import importlib.util
import os

from harness.accounting import committed_in_window


def _step(root):
    path = os.path.join(root, "bench", "roofline", "step.py")
    spec = importlib.util.spec_from_file_location("roofline_step", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    st, dims = _step(run.root), run.dims.flops_dims()
    flops = 0.0
    for tokens, prompt, prefilled in committed_in_window(run):
        if prefilled:
            flops += st.prefill_flops(prompt, **dims)
        flops += tokens * st.token_flops(prompt, **dims)
    peak = run.peak()["flops"] * run.trace["chips"]
    return 100.0 * flops / (run.trace["window_s"] * peak)
