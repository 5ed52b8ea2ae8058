"""Device time of the collective operations -- all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all, their start and done
halves included -- as a share of the device's busy time in the traced
window, per chip and then the mean over the chips, in percent.  Nothing to
read where no chip ran a collective."""
import re

COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")


def read(run):
    t = run.trace or {}
    shares = []
    for ops, busy in zip(t.get("ops_by_chip", []), t.get("busy_by_chip", [])):
        coll = sum(s for name, s in ops.items() if COLLECTIVE.match(name))
        shares.append(100.0 * coll / busy if busy > 0 else 0.0)
    if not any(shares):
        return None
    return sum(shares) / len(shares)
