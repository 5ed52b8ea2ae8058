"""Device time of the ``admit_slot`` programs (one prompt prefill per
admitted request) as a share of the device's busy time, in percent (both
summed over the chips)."""


def read(run):
    t = run.trace or {}
    m = t.get("modules", {}).get("admit_slot")
    if not m or not t.get("busy_s"):
        return None
    return 100.0 * m["s"] / (t["busy_s"] * t["chips"])
