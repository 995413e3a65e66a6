"""Summed device time of the programs that match, over a count the client
side knows: ``per`` names a fact (a list: its sum; a number: itself) and
``scale`` divides it (1000 for "per thousand tokens"). In ms."""
from benchmark.lib import trace as T


def read(facts, pattern, per, scale=1.0):
    tr = facts["trace"]
    if tr is None:
        return None
    total_ms = T.total_s(T.matching(tr.modules, pattern)) * 1e3
    count = facts.get(per)
    count = sum(count) if isinstance(count, (list, tuple)) else count
    if not count or total_ms <= 0:
        return None
    return total_ms / (count / scale)
