"""Share of the engine's stepping time spent in prefill (its own
``prefill_s`` and ``decode_s`` counters)."""


def read(result, trace):
    e = result["engine"]
    busy = e["prefill_s"] + e["decode_s"]
    return None if busy <= 0 else 100.0 * e["prefill_s"] / busy
