"""Useful model operations of the window over the traced window's length
times the chips' peak, in percent (``bench/roofline/step.py``): every
prompt prefilled in the window and a lower bound on the output tokens its
verify calls committed (``harness/accounting.py``); rejected draft
positions count nothing."""
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
    st, m = _step(run.root), run.dims
    dims = dict(non_embedding=m.non_embedding_params(), d_model=m.d,
                vocab=m.vocab, layers=m.layers, heads=m.heads,
                head_dim=m.hd, window=m.window)
    flops = 0.0
    for tokens, prompt, prefilled in committed_in_window(run):
        if prefilled:
            flops += st.prefill_flops(prompt, **dims)
        flops += tokens * st.token_flops(prompt, **dims)
    peak = run.peak()["flops"] * run.trace["chips"]
    return 100.0 * flops / (run.trace["window_s"] * peak)
