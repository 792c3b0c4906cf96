"""Median time from the client's submit to the engine's ``generate``
being entered, per Work (host spans)."""
from bench.common import median
from bench.counts import span_pairs


def read(result, trace):
    pairs = span_pairs(result, "submit", "generate")
    return None if not pairs else 1e3 * median([g[2] - s[2] for s, g in pairs])
