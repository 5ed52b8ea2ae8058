"""Process start to the first timed request: device start, weights made
from the seed, the engine with its drafter tables, compilation or cache
loads, and the warm-up."""


def read(run):
    return run.setup_s
