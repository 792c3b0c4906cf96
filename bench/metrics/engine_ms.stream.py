"""Median duration of the engine's ``generate``, its lock wait included
(host spans)."""
from bench.common import median


def read(result, trace):
    spans = result["spans"].named("generate")
    return None if not spans else 1e3 * median([s[3] - s[2] for s in spans])
