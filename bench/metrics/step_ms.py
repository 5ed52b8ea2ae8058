"""Device time of one execution of the ``spec_step`` program (draft,
verify, commit), mean over the traced window, in milliseconds."""


def read(run):
    m = (run.trace or {}).get("modules", {}).get("spec_step")
    if not m or not m["n"]:
        return None
    return 1e3 * m["s"] / m["n"]
