"""Figure 3 (+ App. A) reproduction: the (k, w) strategy grid.

tokens/call is MEASURED with the mixed strategy on the trained tiny model;
wall-time speedup is DERIVED for the paper-scale Mistral-7B on TPU v5e as
  speedup(k, w) = tokens_per_call(k, w) / slowdown(k, w | ell)
(core/phase.py roofline call-cost model; ell = mean decode context).
This is exactly the trade-off surface of the paper's Fig. 3: tokens/call
rises with (k, w) while the call gets slower once compute-bound.
"""
from __future__ import annotations

import csv
import os

from repro.configs import get_config
from repro.core.phase import slowdown
from repro.core.spec_engine import SpecConfig
from repro.launch.compile_cache import enable_compile_cache

from .common import TASKS, ensure_dirs, get_tables, get_trained, measure

KS = (1, 5, 10, 25)
WS = (2, 6, 10, 14)
FULL_KS = (1, 5, 10, 20, 25)
FULL_WS = (2, 4, 6, 8, 10, 12, 14)


def run(out_dir: str = "experiments/results", full: bool = False,
        max_new: int = 48) -> dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params)
    target = get_config("mistral-7b")     # speedup model target
    ks = FULL_KS if full else KS
    ws = FULL_WS if full else WS
    path = os.path.join(out_dir, "fig3_kw_grid.csv")
    best = {}
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(["task", "k", "w", "tokens_per_call",
                     "modeled_slowdown_v5e", "modeled_speedup_v5e",
                     "cpu_wall_s"])
        for task in TASKS:
            for k in ks:
                for w in ws:
                    spec = SpecConfig(k=k, w=w, strategy="mixed",
                                      max_new_tokens=max_new)
                    r = measure(cfg, params, tables, task, spec, n_prompts=4)
                    sl = slowdown(target, ell=512, k=k, w=w)
                    sp = r.tokens_per_call / sl
                    wr.writerow([task, k, w, f"{r.tokens_per_call:.3f}",
                                 f"{sl:.3f}", f"{sp:.3f}",
                                 f"{r.wall_s:.2f}"])
                    cur = best.get(task, (0.0, None))
                    if sp > cur[0]:
                        best[task] = (sp, (k, w), r.tokens_per_call)
    return {"csv": path, "best": best}


def main():
    enable_compile_cache()
    res = run()
    print("fig3_kw_grid ->", res["csv"])
    for task, (sp, kw, tpc) in res["best"].items():
        print(f"  {task:5s}: best (k*,w*)={kw} tok/call={tpc:.2f} "
              f"modeled v5e speedup={sp:.2f}x")


if __name__ == "__main__":
    main()
