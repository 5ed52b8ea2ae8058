"""Figure 4 reproduction: strategy ablations for the mixed (10, 10) setup.

Top: distribution of acceptance lengths per call.
Middle: rank (winning row index) distribution among the top-k.
Bottom: allocation — how many of the k rows the context N-gram filled.
Plus the per-strategy accepted-token split (context vs extended bigram).
"""
from __future__ import annotations

import csv
import os

import numpy as np

from repro.core.spec_engine import SpecConfig
from repro.launch.compile_cache import enable_compile_cache

from .common import TASKS, ensure_dirs, get_tables, get_trained, measure


def run(out_dir: str = "experiments/results", max_new: int = 48) -> dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params)
    spec = SpecConfig(k=10, w=10, strategy="mixed", max_new_tokens=max_new)
    path = os.path.join(out_dir, "fig4_ablations.csv")
    summary = {}
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(["task", "histogram", "bin", "count"])
        for task in TASKS:
            r = measure(cfg, params, tables, task, spec, n_prompts=6)
            acc = r.stats["accept_hist"].sum(0)
            rank = r.stats["rank_hist"].sum(0)
            alloc = r.stats["alloc_ctx"].sum(0)
            for i, v in enumerate(acc):
                wr.writerow([task, "accept_len", i, int(v)])
            for i, v in enumerate(rank):
                wr.writerow([task, "winning_rank", i, int(v)])
            for i, v in enumerate(alloc):
                wr.writerow([task, "ctx_allocation", i, int(v)])
            n_ctx_tok = int(r.stats["accepted_ctx"].sum())
            n_big_tok = int(r.stats["accepted_bigram"].sum())
            wr.writerow([task, "accepted_by_strategy", "context", n_ctx_tok])
            wr.writerow([task, "accepted_by_strategy", "bigram", n_big_tok])
            mean_acc = (np.arange(len(acc)) * acc).sum() / max(acc.sum(), 1)
            summary[task] = dict(mean_accept=float(mean_acc),
                                 ctx_tokens=n_ctx_tok, bigram_tokens=n_big_tok,
                                 tokens_per_call=r.tokens_per_call)
    return {"csv": path, "summary": summary}


def main():
    enable_compile_cache()
    res = run()
    print("fig4_ablations ->", res["csv"])
    for task, s in res["summary"].items():
        print(f"  {task:5s}: mean accept={s['mean_accept']:.2f} "
              f"ctx/bigram accepted={s['ctx_tokens']}/{s['bigram_tokens']} "
              f"tok/call={s['tokens_per_call']:.2f}")


if __name__ == "__main__":
    main()
