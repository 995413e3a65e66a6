"""Device time of the operations that match, over the device's busy time,
in %."""
from benchmark.lib import trace as T


def read(facts, kernel):
    tr = facts["trace"]
    if tr is None:
        return None
    busy = T.busy_s(tr)
    kernel_s = T.total_s(T.matching(tr.ops, kernel))
    if busy <= 0 or kernel_s <= 0:
        return None
    return 100.0 * kernel_s / busy
