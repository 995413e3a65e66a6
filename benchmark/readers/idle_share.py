"""1 - (union of the device operations' intervals) / (traced window), in %,
averaged over the devices."""
from benchmark.lib import trace as T


def read(facts):
    tr = facts["trace"]
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - T.busy_s(tr) / tr.window_s)
