"""Share of the roofline the decode step reaches: the least time a step
could take, the larger of the FLOPs it needs over peak bf16 FLOP/s and the
bytes it needs (weights once, each active slot's cache up to its own
length) over peak HBM bandwidth, over the device time of one execution of
the jitted ``decode_all`` program in the trace.  Bytes bound it at these
sizes."""
from bench.counts import decode_bytes, decode_flops


def read(result, trace):
    steps = result["engine"]["decode_steps"]
    if trace is None or not steps:
        return None
    mods = [v for n, v in trace["modules"].items() if "decode_all" in n]
    count = sum(v["count"] for v in mods)
    if not count:
        return None
    per_step = sum(v["seconds"] for v in mods) / count
    peaks = result["peaks"]
    least = max(decode_flops(result) / steps / peaks["bf16_flops"],
                decode_bytes(result) / steps / peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_step
