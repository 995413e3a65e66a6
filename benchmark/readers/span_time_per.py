"""Host time the program's own spans cover, over a count of one of them:
the summed durations (the part inside the traced window) of the host spans
whose name matches ``spans`` and not ``exclude``, divided by the number of
``per`` spans that start in the window. In ms. The spans are the program's
``telemetry.span``s, which arrive on the profiler's clock as
``TraceAnnotation``s; they are flat (none encloses another), so summing
counts no time twice. A time taken without a chip is no more a rate than a
device time is: without ``peaks`` (no chip) nothing is read."""
import re


def read(facts, spans, per, exclude=None):
    tr = facts["trace"]
    if tr is None or facts["peaks"] is None or tr.window is None:
        return None
    lo, hi = tr.window
    keep = re.compile(spans)
    drop = re.compile(exclude) if exclude else None
    total_ns = sum(
        min(h.end, hi) - max(h.start, lo) for h in tr.host
        if keep.search(h.name) and not (drop and drop.search(h.name)))
    count = sum(1 for h in tr.host if h.name == per and lo <= h.start < hi)
    if total_ns <= 0 or not count:
        return None
    return total_ns / 1e6 / count
