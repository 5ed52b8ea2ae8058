"""95th percentile of how late the load generator sent a request after
its scheduled arrival, in milliseconds.  It sends between ``step()`` calls,
so a long step makes it late; a starved generator reads as lag here, not
as a fast server."""
from harness.accounting import percentile


def read(run):
    return percentile(
        (1e3 * (r.submitted - r.scheduled) for r in run.recs
         if r.scheduled is not None and r.submitted <= run.t1), 95)
