"""Named host spans on the profiler's clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation`` while a
profiler trace is being recorded, and otherwise returns one shared no-op
context, so the served path pays a function call and a flag test per span
when nothing records. A span lands in the profiler's host plane, in the same
``.xplane.pb`` and on the same clock as the device planes, so an idle gap of
the device can be put down to the span open over it.

The served path's spans all start with ``pbvd.``; a span is never held open
across an ``await``.
"""

from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

__all__ = ["span"]

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A context that records host span ``name`` (with ``args`` as its
    metadata) in a profiler trace being recorded; a shared no-op otherwise."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return _OFF
