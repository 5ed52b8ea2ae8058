"""95th percentile, over the requests completed in the window, of the time
from admission to completion per output token, in milliseconds."""
from harness.accounting import percentile


def read(run):
    return percentile(
        (1e3 * (r.completed - r.admitted) / r.new_tokens for r in run.recs
         if r.completed is not None and r.completed <= run.t1
         and not r.error and r.admitted is not None and r.new_tokens),
        95)
