"""Forward and backward FLOPs per token times tokens/s, over the chips'
peak bf16 FLOP/s."""


def read(result, trace):
    peak = result["chips"] * result["peaks"]["bf16_flops"]
    return 100.0 * result["flops_per_token"] * result["tokens_per_s"] / peak
