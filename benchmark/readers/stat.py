"""A number the program counts, by dotted path into ``engine.stats()``
(``prefix_cache.hits``), optionally over another (``per``)."""


def _dig(doc, path):
    for part in path.split("."):
        if not isinstance(doc, dict) or part not in doc:
            return None
        doc = doc[part]
    return doc


def read(facts, path, per=None, scale=1.0):
    value = _dig(facts.get("stats") or {}, path)
    if value is None:
        return None
    if per is not None:
        base = _dig(facts.get("stats") or {}, per)
        if not base:
            return None
        return scale * value / base
    return scale * float(value)
