"""Figure 2 reproduction: tokens/call as a function of k for the
model-derived unigram, bigram, and extended bigram (w in {1, 2, 3}).

Run on the tiny trained benchmark model over the code + chat tasks (the
paper uses MT-Bench + HumanEval on Mistral-7B-Instruct).
"""
from __future__ import annotations

import csv
import os

from repro.core.spec_engine import SpecConfig
from repro.launch.compile_cache import enable_compile_cache

from .common import ensure_dirs, get_tables, get_trained, measure

KS = (1, 5, 10, 25)


def run(out_dir: str = "experiments/results", max_new: int = 48) -> dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params)
    path = os.path.join(out_dir, "fig2_topk_curves.csv")
    best = {}
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(["task", "strategy", "k", "w", "tokens_per_call"])
        for task in ("code", "chat"):
            for strat, w in (("unigram", 1), ("bigram", 1), ("bigram", 2),
                             ("bigram", 3)):
                for k in KS:
                    spec = SpecConfig(k=k, w=w, strategy=strat,
                                      max_new_tokens=max_new)
                    r = measure(cfg, params, tables, task, spec,
                                n_prompts=4)
                    wr.writerow([task, f"{strat}-w{w}", k,
                                 w, f"{r.tokens_per_call:.3f}"])
                    best[(task, strat, w, k)] = r.tokens_per_call
    return {"csv": path, "results": best}


def main():
    enable_compile_cache()
    res = run()
    print("fig2_topk_curves ->", res["csv"])
    for (task, strat, w, k), v in sorted(res["results"].items()):
        if k == 25:
            print(f"  {task:5s} {strat:8s} w={w} k={k}: {v:.2f} tok/call")


if __name__ == "__main__":
    main()
