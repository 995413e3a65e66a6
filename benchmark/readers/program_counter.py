"""A number the program keeps about itself, read after the window: either a
dotted ``path`` into ``engine.stats()`` (``perf.decode_step.step_s.p50``) or
the mean of a histogram ``family`` of the program's metrics registry (the
sum over its series of ``sum``, over that of ``count``), times ``scale``.
The program's counters run from engine start (warm-up and ramp included)
and its ``stats`` windows cover the last 128 steps or 120 s, not the
benchmark's window. They are times on the host's clock: without ``peaks``
(no chip) nothing is read, as for every other time."""
from benchmark.readers import stat


def _family_mean(family):
    from paddle_tpu import telemetry

    series = (telemetry.snapshot().get(family) or {}).get("series") or ()
    count = sum(s.get("count", 0) for s in series)
    if not count:
        return None
    return sum(s.get("sum", 0.0) for s in series) / count


def read(facts, path=None, family=None, scale=1.0):
    if facts["peaks"] is None:
        return None
    if (path is None) == (family is None):
        raise ValueError("program_counter reads a path or a family")
    if path is not None:
        return stat.read(facts, path, scale=scale)
    value = _family_mean(family)
    return None if value is None else scale * value
