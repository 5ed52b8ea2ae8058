"""95th percentile, over every request due in the window, of completion
time minus its scheduled arrival.  A request still open when the window
closes counts at its age then; one that failed counts at the longest wait
a run allows (the window and the drain after it)."""
from harness.accounting import percentile
from harness.runner import DRAIN_S


def read(run):
    lat = []
    for r in run.recs:
        if r.scheduled is None or r.scheduled >= run.t1:
            continue
        if r.error:
            lat.append(run.seconds + DRAIN_S)
        elif r.completed is not None and r.completed <= run.t1:
            lat.append(r.completed - r.scheduled)
        else:
            lat.append(run.t1 - r.scheduled)
    return percentile(lat, 95)
