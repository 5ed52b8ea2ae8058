"""Table 1 reproduction: tokens/call + speedup per (model size, task).

Two tiny trained models stand in for the paper's {Phi3B, Mistral7B,
Vicuna13B}; for each task we report the default (10, 10) strategy and the
best (k*, w*) from a small sweep — tokens/call measured, wall-time speedup
modeled on TPU v5e via the roofline call-cost (and CPU wall-time speedup
vs the greedy engine as a secondary, noisy, signal).
"""
from __future__ import annotations

import csv
import os

from repro.configs import get_config
from repro.core.phase import slowdown
from repro.core.spec_engine import SpecConfig
from repro.launch.compile_cache import enable_compile_cache

from .common import (SIZES, TASKS, ensure_dirs, get_tables, get_trained,
                     measure)

SWEEP = [(10, 10), (5, 4), (10, 4), (25, 2), (5, 10)]


def run(out_dir: str = "experiments/results", max_new: int = 48) -> dict:
    ensure_dirs()
    target = get_config("mistral-7b")
    path = os.path.join(out_dir, "table1_speedup.csv")
    rows = []
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(["model", "task", "strategy", "k", "w",
                     "tokens_per_call", "modeled_speedup_v5e",
                     "cpu_speedup_vs_greedy"])
        for size in SIZES:
            cfg, params = get_trained(size)
            tables = get_tables(cfg, params)
            for task in TASKS:
                greedy = measure(cfg, params, tables, task,
                                 SpecConfig(strategy="greedy",
                                            max_new_tokens=max_new),
                                 n_prompts=4)
                results = {}
                for (k, w) in SWEEP:
                    spec = SpecConfig(k=k, w=w, strategy="mixed",
                                      max_new_tokens=max_new)
                    r = measure(cfg, params, tables, task, spec, n_prompts=4)
                    sp = r.tokens_per_call / slowdown(target, 512, k, w)
                    cpu_sp = greedy.wall_s / max(r.wall_s, 1e-9)
                    results[(k, w)] = (r.tokens_per_call, sp, cpu_sp)
                # default row + best row (by modeled speedup)
                for label, kw in (("default", (10, 10)),
                                  ("best", max(results,
                                               key=lambda x: results[x][1]))):
                    tpc, sp, cpu_sp = results[kw]
                    wr.writerow([size, task, label, kw[0], kw[1],
                                 f"{tpc:.3f}", f"{sp:.3f}", f"{cpu_sp:.3f}"])
                    rows.append((size, task, label, kw, tpc, sp, cpu_sp))
    return {"csv": path, "rows": rows}


def main():
    enable_compile_cache()
    res = run()
    print("table1_speedup ->", res["csv"])
    for size, task, label, kw, tpc, sp, cpu_sp in res["rows"]:
        print(f"  {size:9s} {task:5s} {label:7s} (k,w)={kw}: "
              f"tok/call={tpc:.2f} v5e-speedup={sp:.2f}x cpu={cpu_sp:.2f}x")


if __name__ == "__main__":
    main()
