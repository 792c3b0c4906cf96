"""The window's algorithmic FLOPs over the summed ``generate`` time times
peak bf16 FLOP/s."""
from bench.counts import serve_flops


def read(result, trace):
    busy = sum(s[3] - s[2] for s in result["spans"].named("generate"))
    flops = serve_flops(result)
    return None if not flops or busy <= 0 else 100.0 * flops / (busy * result["peaks"]["bf16_flops"])
