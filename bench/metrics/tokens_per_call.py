"""Output tokens per verify call of the requests completed in the window
(a count, from the program's per-request stats; with random weights it is
set by the model, not by the traffic)."""


def read(run):
    done = [r for r in run.recs
            if r.completed is not None and r.completed <= run.t1
            and not r.error]
    calls = sum(r.calls for r in done)
    return sum(r.new_tokens for r in done) / calls if calls else None
