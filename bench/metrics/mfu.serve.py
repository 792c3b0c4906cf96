"""FLOPs the window's prompt and output tokens need (causal attention over
each request's own length) over the window times peak bf16 FLOP/s."""
from bench.counts import serve_flops


def read(result, trace):
    flops = serve_flops(result)
    return None if not flops else 100.0 * flops / (result["window_s"] * result["peaks"]["bf16_flops"])
