"""The program's tracer: host spans, counters, and the model's named scopes.

Spans.  ``span(name, **attrs)`` is always a ``jax.profiler.TraceAnnotation``
called ``repro.<name>``, so a profiler trace holds it on the clock of the
device's events.  While a JAX profiler trace is being collected, and only
then, each span is also kept in memory, in a bounded buffer, as a ``Span`` on
``time.perf_counter`` with the id of the span that was open around it on the
same thread: starting the trace is the one switch.  ``drain()`` returns what
was kept and empties the buffer.  Nothing is written out: whoever started the
trace drains it.

Counters.  ``count(name, n)`` adds to a process-wide counter; counters always
count.  This module holds the program's one ``jax.monitoring`` listener: it
counts backend compiles (``compile.count``, ``compile.s``) and persistent
cache hits and misses (``compile.cache_hits``, ``compile.cache_misses``), and
while recording it keeps each compile as a ``compile`` span under the
innermost span open on the compiling thread.

Scopes.  ``SCOPES`` names every block of the model.  ``scope(name)`` is the
``jax.named_scope`` that the block's own function carries, so train, prefill
and decode put the same name into each operation's ``op_name``; a scope
changes that metadata and nothing else.  ``op_scopes`` reads it back from a
compiled program's HLO text, as a phase and a scope per operation.

Imports nothing beyond the standard library and ``jax``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import re
import threading
import time
from typing import Any, Iterator, NamedTuple

import jax

#: every block of the model that carries a named scope; ``layers`` is the
#: trunk's scan over layers, whose own operations (slicing and stacking the
#: per-layer weights, activations and gradients) sit under no block
SCOPES = ("embed", "norm", "attention", "mlp", "moe", "time_mix", "channel_mix", "mamba",
          "layers", "head_loss", "optimizer")

#: what an operation of a train step is part of, from its ``op_name``
PHASES = ("forward", "backward", "recompute", "optimizer")

#: spans kept at most while recording; the oldest go first
BUFFER_SPANS = 100_000


class Span(NamedTuple):
    name: str
    id: int
    parent_id: int | None
    start: float
    end: float
    attrs: dict[str, Any]


_lock = threading.Lock()
_spans: collections.deque[Span] = collections.deque(maxlen=BUFFER_SPANS)
_counters: dict[str, float] = collections.defaultdict(float)
_ids = itertools.count(1)
_open = threading.local()  # .stack: [(id, name)] of the spans open on this thread


def recording() -> bool:
    """Whether spans are kept: while a JAX profiler trace is collected."""
    return jax.profiler.TraceAnnotation.is_enabled()


def _stack() -> list[tuple[int, str]]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[None]:
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1][0] if stack else None
    keep = recording()
    stack.append((sid, name))
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield
    finally:
        end = time.perf_counter()
        stack.pop()
        if keep:
            _spans.append(Span(name, sid, parent, start, end, attrs))


def count(name: str, n: float = 1) -> None:
    with _lock:
        _counters[name] += n


def counters() -> dict[str, float]:
    with _lock:
        return dict(_counters)


def drain() -> list[Span]:
    """The spans kept so far, oldest first; the buffer is left empty."""
    out = []
    while True:
        try:
            out.append(_spans.popleft())
        except IndexError:
            return out


def _on_duration(event: str, duration: float, **_: object) -> None:
    if event != "/jax/core/compile/backend_compile_duration":
        return
    count("compile.count")
    count("compile.s", duration)
    if recording():
        stack = _stack()
        parent, inside = stack[-1] if stack else (None, None)
        end = time.perf_counter()
        _spans.append(Span("compile", next(_ids), parent, end - duration, end, {"in": inside}))


def _on_event(event: str, **_: object) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        count("compile.cache_hits")
    elif event == "/jax/compilation_cache/cache_misses":
        count("compile.cache_misses")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------
def scope(name: str):
    """The ``jax.named_scope`` of one model block; also a decorator."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")
    return jax.named_scope(name)


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?\bop_name="((?:[^"\\]|\\.)*)"',
                    re.MULTILINE)
_TRANSFORM = re.compile(r"^[\w-]+\((.*)\)$")


def _unwrap(component: str) -> str:
    """``transpose(jvp(layers))`` -> ``layers``: a scope under a transform."""
    while (m := _TRANSFORM.match(component)) is not None:
        component = m.group(1)
    return component


def op_phase_scope(op_name: str) -> tuple[str, str]:
    """(phase, scope) of one ``op_name``: the innermost of ``SCOPES`` on its
    path (``unscoped`` if none); ``optimizer`` under that scope, else
    ``recompute`` in a remat's ``rematted_computation``, ``backward`` under a
    ``transpose``, and ``forward`` otherwise."""
    names = [_unwrap(c) for c in op_name.split("/")]
    scopes = [n for n in names if n in SCOPES]
    inner = scopes[-1] if scopes else "unscoped"
    if "optimizer" in scopes:
        phase = "optimizer"
    elif "rematted_computation" in names:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return phase, inner


def op_scopes(hlo_text: str) -> dict[str, tuple[str, str]]:
    """Each operation's short name (``fusion.683``, as a device trace names
    it) to its (phase, scope), from a compiled program's ``as_text()``.
    A fusion carries its root's ``op_name``; operations with none are left
    out."""
    return {m.group(1): op_phase_scope(m.group(2)) for m in _INSTR.finditer(hlo_text)}
