"""95th percentile, over the requests due in the window, of the wait from
scheduled arrival to admission into a slot; a request not admitted when
the window closes counts at its age then."""
from harness.accounting import percentile


def read(run):
    return percentile(
        ((r.admitted if r.admitted is not None and r.admitted <= run.t1
          else run.t1) - r.scheduled
         for r in run.recs
         if r.scheduled is not None and r.scheduled < run.t1), 95)
