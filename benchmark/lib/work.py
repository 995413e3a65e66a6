"""What every architecture's work counts share.

A share of a peak or of a roofline divides operations or bytes that the
mathematics needs by a measured time. The counts themselves belong to the
architecture (``arch/<name>.py``, named by the configuration): the readers ask
it, and a count it does not give is a metric left out of the line.
"""
from __future__ import annotations


def causal_pairs(seq):
    """(query, key) pairs a causal mask keeps in one sequence of ``seq``."""
    return seq * (seq + 1) // 2


def roofline_seconds(work, peak):
    """Least time for ``work`` on a chip with ``peak``; says which bound."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
