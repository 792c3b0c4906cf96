"""Host time per decode step over the window: the engine's own
``decode_s`` over its ``decode_steps`` counter."""


def read(result, trace):
    e = result["engine"]
    return None if not e["decode_steps"] else 1e3 * e["decode_s"] / e["decode_steps"]
