"""A tiny cell in a copy of the checkout, for the harness's CPU tests."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CONFIG = {
    "source": "a tiny stand-in for the CPU tests", "model_type": "mistral",
    "family": "dense",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 320, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "float32", "weights_seed": 0}
# the same widths split four ways on a (data 1, model 4) mesh: query and
# key/value heads, the feed-forward width and the vocabulary all divide
MESH_CONFIG = dict(CONFIG, num_attention_heads=8, num_key_value_heads=4,
                   head_dim=8)
CLOSED = {
    "loop": "closed", "slots": 2, "clients": 3,
    "mix": {"code": 1, "math": 1, "chat": 1},
    "prompt_tokens": {"median": 30, "sigma": 0.5, "min": 12, "max": 60},
    "output_tokens": {"median": 10, "sigma": 0.4, "min": 6, "max": 16},
    "buckets": [32, 64], "max_new_cap": 16, "pool": 12,
    "warmup_new_tokens": 2, "reference_requests": 4}
OPEN = dict(CLOSED, loop="open", rate_per_s=6.0, phases=[[0.5, 1.0],
                                                         [1.5, 0.5]])
OPEN.pop("clients")
OPEN.pop("pool")
READER = '''"""Requests the window finished (a count)."""


def read(run):
    return sum(1 for r in run.recs
               if r.completed is not None and r.completed <= run.t1)
'''


def make_root(tmp):
    """A copy of the benchmark with a tiny configuration, two cells and a
    per-layer metric added as new files and entries; no file the
    benchmark has is edited."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(CONFIG, f)
    for name, mix in (("tiny-closed", CLOSED), ("tiny-open", OPEN)):
        with open(os.path.join(b, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(b, "limits", f"tiny.{name}.json"), "w") as f:
            json.dump({"served_gap": 1e-3}, f)
        bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                   "traffic": name, "chips": 1,
                                   "why": "CPU test"})
    with open(os.path.join(b, "metrics", "finished_requests.py"), "w") as f:
        f.write(READER)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, unit, cell in (("tokens_per_s", "tokens/s", "tiny-closed"),
                             ("request_latency_p95_s", "s", "tiny-open"),
                             ("ms_per_token_p95", "ms/token", "tiny-open")):
        m = e2e.setdefault(name, {"name": name, "unit": unit,
                                  "better": "lower", "bound": 0.25,
                                  "source": "host_clock", "workloads": []})
        m["workloads"].append("tiny." + cell)
    bench["end_to_end"] = list(e2e.values())
    bench["per_layer"].append({
        "name": "finished_requests", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "engine scheduler",
        "moves": "tokens_per_s", "workloads": ["tiny.tiny-closed"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
