"""A percentile of a list the driver counted on the client side (such as how
late each send ran against its schedule)."""
from benchmark.lib.window import percentile


def read(facts, fact, percentile_q=95):
    return percentile(list(facts.get(fact) or ()), percentile_q)
