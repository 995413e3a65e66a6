"""A percentile of the idle gaps between consecutive device programs, in ms.
``under_span`` keeps only gaps that a host span of that name covers most of
(for serving: gaps while requests are running are those under the engine's
spans or between them, so by default every gap counts)."""
from benchmark.lib import trace as T
from benchmark.lib.window import percentile


def read(facts, percentile_q=50, under_span=None):
    tr = facts["trace"]
    if tr is None:
        return None
    gaps = [dur / 1e6 for label, _, dur in T.gaps(tr)
            if under_span is None or label == under_span]
    return percentile(gaps, percentile_q)
