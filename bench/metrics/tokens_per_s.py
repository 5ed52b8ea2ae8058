"""Output tokens per second per chip, on the benchmark's clock.

From the first request completion in the window to the last; the numerator
is the output tokens of the requests completed after the first, so that
neither edge of the window biases the rate."""


def read(run):
    done = sorted((r.completed, r.new_tokens) for r in run.recs
                  if r.completed is not None and r.completed <= run.t1
                  and not r.error)
    if len(done) < 2 or done[-1][0] <= done[0][0]:
        return None
    first = done[0][0]
    tokens = sum(n for t, n in done if t > first)
    return tokens / (done[-1][0] - first) / run.chips
