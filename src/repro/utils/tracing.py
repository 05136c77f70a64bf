"""The program's spans, on the profiler's clock.

``span(name, **meta)`` marks a stretch of host work. With tracing on it
is a ``jax.profiler.TraceAnnotation``, which lands on the profiler's
host plane on the same clock as the device planes; with tracing off it
is one shared no-op, and nothing is formatted. Spans are kept only
while a profiler trace runs (``jax.profiler.start_trace``, a
benchmark's or an operator's own): this module has no buffer and no
exporter.

A ``jax.monitoring`` listener, registered once at import, marks each
program JAX lowers while tracing is on with an instant span
``repro.lowering`` whose metadata names the function. JAX lowers on a
miss in its in-memory jit cache, whether or not the persistent cache
then supplies the executable, so the spans count the programs that a
traced stretch of work had not warmed.
"""
from __future__ import annotations

import contextlib

import jax

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOWERING_SPAN = "repro.lowering"

_NOOP = contextlib.nullcontext()
_on = False


def enable(on: bool = True) -> None:
    """Turn the spans on (or off) for the whole process."""
    global _on
    _on = bool(on)


def span(name: str, **meta):
    """A context manager marking ``name``, with ``meta`` as the span's
    metadata, while tracing is on; the shared no-op while it is off."""
    if not _on:
        return _NOOP
    return jax.profiler.TraceAnnotation(name, **meta)


def _on_duration(event: str, duration: float, **meta) -> None:
    if event == LOWERING_EVENT:
        with span(LOWERING_SPAN, **meta):
            pass


jax.monitoring.register_event_duration_secs_listener(_on_duration)
