"""Algorithmic work of a serve window, from the requests' own lengths.

A request with an n-token prompt and T served tokens costs a prefill of
its n positions (causal attention over its own length, which also gives
the first token) and T - 1 decode steps at positions n .. n + T - 2.
"""
from __future__ import annotations


def _requests(result):
    for d in result["done"]:
        if d.tokens is None:
            continue
        for prompt, tokens in zip(d.spec.prompts, d.tokens):
            yield len(prompt), len(tokens)


def serve_flops(result) -> float:
    ref, c = result["reference"], result["config"]
    total = 0.0
    for n, t in _requests(result):
        total += ref.prefill_flops(c, n)
        total += sum(ref.decode_flops(c, n + j) for j in range(t - 1))
    return total


def decode_flops(result) -> float:
    """FLOPs of the window's decode steps: each served token after the first
    at its own position."""
    ref, c = result["reference"], result["config"]
    return sum(ref.decode_flops(c, n + j) for n, t in _requests(result) for j in range(t - 1))


def decode_bytes(result) -> float:
    """Bytes the window's decode steps need: the weights once per step and
    each request's cache up to its own length once per step it decodes."""
    ref, c = result["reference"], result["config"]
    steps = result["engine"]["decode_steps"]
    kv = ref.kv_bytes_per_token(c)
    caches = sum(kv * (n + j + 1) for n, t in _requests(result) for j in range(t - 1))
    return steps * ref.weight_bytes(c) + caches


def span_pairs(result, first: str, second: str):
    """(first span, second span) pairs of one Work, matched by tag."""
    spans = result["spans"]
    firsts = {s[1]: s for s in spans.named(first)}
    return [(firsts[s[1]], s) for s in spans.named(second) if s[1] in firsts]
