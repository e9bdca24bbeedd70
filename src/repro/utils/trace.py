"""Named host spans on the profiler's clock.

``span(name, **stats)`` marks one step of the federated loop (an event-loop
drain, a server sweep, a client upload) with a
``jax.profiler.TraceAnnotation`` while a profile session is active, so the
step lands on the same host timeline as the device trace and a viewer
(Perfetto, TensorBoard) can put each idle gap of the device down to it.
With no session active it returns one shared null context and does nothing
else. Stats are read when the span closes, and only while tracing: a value,
or a callable of no arguments for a number known only at the end (bytes of
arrays the span creates). README's "Tracing" section lists the spans.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np
from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


class _Span(TraceAnnotation):
    def __init__(self, name: str, stats: dict):
        super().__init__(name)
        self._stats = stats

    def __exit__(self, exc_type, exc, tb):
        # on an exception a callable may name a value never assigned
        if exc_type is None and self._stats:
            self.set_metadata(**{k: v() if callable(v) else v
                                 for k, v in self._stats.items()})
        return super().__exit__(exc_type, exc, tb)


def span(name: str, **stats):
    """A context manager marking ``name`` on the profiler's host timeline
    while a profile session is active; the shared null context otherwise."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, stats)


def nbytes(*trees) -> int:
    """Bytes of every array leaf of ``trees``."""
    return sum(leaf.nbytes for t in trees for leaf in jax.tree.leaves(t))


def host_nbytes(*trees) -> int:
    """Bytes of the host (NumPy) leaves of ``trees``: what a device
    operation on them uploads first."""
    return sum(leaf.nbytes for t in trees for leaf in jax.tree.leaves(t)
               if isinstance(leaf, np.ndarray))
