"""Device time of a jitted program, by name pattern on the "XLA Modules"
line: the median (or another percentile) of one execution, in ms."""
from benchmark.lib import trace as T
from benchmark.lib.window import percentile


def read(facts, pattern, percentile_q=50):
    tr = facts["trace"]
    if tr is None:
        return None
    durs = T.durations_ms(T.matching(tr.modules, pattern))
    return percentile(durs, percentile_q)
