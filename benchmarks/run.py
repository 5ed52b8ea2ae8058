"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  - us_per_call: measured CPU microseconds per model call for the tiny
    benchmark model (this container has no TPU), where applicable;
  - derived: the headline derived metric of that experiment (tokens/call,
    modeled v5e speedup, slowdown, or dominant-term counts).
"""
from __future__ import annotations

from repro.launch.compile_cache import enable_compile_cache


def _row(name, us_per_call, derived):
    print(f"{name},{us_per_call},{derived}", flush=True)


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")

    from . import phase_transition
    res = phase_transition.run()
    _row("fig1_phase_transition", "n/a",
         f"slowdown(10;10|ell=500)={res['slowdown_10_10'][500]:.2f}x")

    from . import table1_speedup
    t1 = table1_speedup.run()
    for size, task, label, kw, tpc, sp, cpu_sp in t1["rows"]:
        if label == "best":
            _row(f"table1_{size}_{task}", "n/a",
                 f"tok/call={tpc:.2f};v5e_speedup={sp:.2f}x")

    from . import topk_curves
    t2 = topk_curves.run()
    best_b = max((v for (task, s, w, k), v in t2["results"].items()
                  if s == "bigram" and w == 2), default=0)
    _row("fig2_topk_curves", "n/a", f"bigram_w2_best_tok/call={best_b:.2f}")

    from . import kw_grid
    t3 = kw_grid.run()
    for task, (sp, kw, tpc) in t3["best"].items():
        _row(f"fig3_kw_grid_{task}", "n/a",
             f"(k*;w*)={kw[0]};{kw[1]};speedup={sp:.2f}x")

    from . import ablation_strategies
    t4 = ablation_strategies.run()
    for task, s in t4["summary"].items():
        _row(f"fig4_ablation_{task}", "n/a",
             f"mean_accept={s['mean_accept']:.2f}")

    from . import spec_call_bench
    t5 = spec_call_bench.run()
    for name, us, derived in t5["rows"]:
        _row(name, f"{us:.0f}", derived)

    t5b = spec_call_bench.run_backends()
    for backend, r in t5b["backends"].items():
        _row(f"backend_sweep_{backend}", f"{r['verify_call_us']:.0f}",
             f"tokens/s={r['tokens_per_s']:.1f};"
             f"tok/call={r['tokens_per_call']:.2f}")

    try:
        from . import roofline
        res = roofline.analyze()
        ok = [r for r in res.values() if r["status"] == "ok"]
        if ok:
            doms = {}
            for r in ok:
                doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
            _row("roofline_dryrun", "n/a",
                 f"cases={len(ok)};dominant=" + ";".join(
                     f"{k}:{v}" for k, v in sorted(doms.items())))
            import json
            import os
            os.makedirs("experiments/results", exist_ok=True)
            with open("experiments/results/roofline.md", "w") as f:
                f.write(roofline.to_markdown(res) + "\n")
            with open("experiments/results/roofline.json", "w") as f:
                json.dump(res, f, indent=1)
        else:
            _row("roofline_dryrun", "n/a", "no-dryrun-artifacts")
    except Exception as e:  # dry-run artifacts may not exist yet
        _row("roofline_dryrun", "n/a", f"unavailable:{type(e).__name__}")


if __name__ == "__main__":
    main()
