"""Device time of the drafter's ``ngram_match`` kernel per ``spec_step``
execution, in milliseconds."""


def read(run):
    t = run.trace or {}
    k = t.get("kernels", {}).get("ngram_match")
    m = t.get("modules", {}).get("spec_step")
    if not k or not m or not m["n"]:
        return None
    return 1e3 * k["s"] / m["n"]
