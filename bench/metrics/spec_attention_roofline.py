"""The verify-attention kernel's share of its roofline, in percent.

Roofline time of one call: the larger of its operations over the chip's
peak and its bytes over the peak bandwidth, counted at each active slot's
live cache length (``bench/roofline/spec_attention.py``; a lower bound on
the live length, see ``harness/accounting.py``).  Mean roofline time per
call over the window's steps, over the mean device time per call of the
``spec_attention`` kernel in the trace."""
import importlib.util
import os

from harness.accounting import live_by_step


def _roofline(root):
    path = os.path.join(root, "bench", "roofline", "spec_attention.py")
    spec = importlib.util.spec_from_file_location("roofline_spec_attention",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("spec_attention")
    live = live_by_step(run)
    if not k or not k["n"] or not live:
        return None
    rf, peak, m = _roofline(run.root), run.peak(), run.dims
    w1 = run.spec_w + 1
    total = 0.0
    for slots in live.values():
        f, b = rf.call_work(slots, heads=m.heads, kv_heads=m.kv,
                            head_dim=m.hd, rows=run.spec_k * w1, w1=w1,
                            elem_bytes=m.dtype.itemsize)
        total += rf.seconds(f, b, peak["flops"], peak["hbm_bytes_per_s"])
    return 100.0 * (total / len(live)) / (k["s"] / k["n"])
