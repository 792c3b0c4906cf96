"""Median time from ``generate`` returning to the client's wait seeing the
Work ``Finished`` (host spans)."""
from bench.common import median
from bench.counts import span_pairs


def read(result, trace):
    pairs = span_pairs(result, "generate", "wait")
    return None if not pairs else 1e3 * median([w[3] - g[3] for g, w in pairs])
