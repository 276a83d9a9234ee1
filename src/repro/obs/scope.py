"""Scoped program spans on the profiler's clock.

``span(name, parent=None, **labels)`` opens
``jax.profiler.TraceAnnotation("funky." + name, **labels)``.  While a
profile is being collected the span lands in its ``.xplane.pb`` on the
profiler's clock, on the thread that ran the block, with ``labels`` as the
event's stats, so it lines up with the device's operations.  With no
profile it is an inactive TraceMe of a microsecond or two.  Given
``parent`` (an obs ``Span``), it also records the obs child span of the
same name and labels, which ``with ... as`` receives; without one it
records no obs span and ``as`` receives ``None``.

``device_scope(name)`` names the device operations a block of traced
code emits (``jax.named_scope("funky." + name)``).

A span whose start and end fall on different threads (a queue wait) cannot
be scoped, and is recorded through the obs ``Tracer`` alone.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.profiler import TraceAnnotation

from .tracer import Span

PREFIX = "funky."


def device_scope(name: str):
    """``jax.named_scope("funky." + name)``: inside a traced function, the
    device operations it emits carry the name, so a profile attributes
    them to this part of the model."""
    return jax.named_scope(PREFIX + name)


def span(name: str, parent: Optional[Span] = None,
         **labels: Any) -> TraceAnnotation:
    """Context manager; see the module docstring."""
    if parent is None:
        return _Annotation(PREFIX + name, **labels)
    return _WithChild(name, parent, labels)


class _Annotation(TraceAnnotation):
    """The profiler's span alone (the hot path: no Python ``__exit__``)."""

    __slots__ = ()

    def __enter__(self) -> None:
        TraceAnnotation.__enter__(self)


class _WithChild(TraceAnnotation):
    """The profiler's span and the obs child span of ``parent``."""

    __slots__ = ("_name", "_parent", "_labels", "_child")

    def __init__(self, name: str, parent: Span, labels: dict):
        super().__init__(PREFIX + name, **labels)
        self._name = name
        self._parent = parent
        self._labels = labels

    def __enter__(self) -> Span:
        TraceAnnotation.__enter__(self)
        self._child = self._parent.child(self._name, **self._labels)
        return self._child

    def __exit__(self, *exc: Any) -> None:
        self._child.end()
        TraceAnnotation.__exit__(self, *exc)
