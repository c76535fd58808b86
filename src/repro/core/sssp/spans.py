"""Host spans and the blocking reads of the serving path.

A span is a ``jax.profiler.TraceAnnotation``: it lands in a profiler
trace on the host's clock, beside the device's operations, and costs
about nothing while no profiler runs.  Identifiers ride as keywords,
never formatted into the name, and :func:`ids` sets identifiers that
every span opened inside it carries too, so the spans of one wave (or
one delta) share its id down through the solver facades.

:func:`fetch` is the one blocking device->host read of the serving
path: ``jax.device_get`` inside an ``sssp.fetch`` span, counted by
:func:`fetches` (the service's ``stats["host_syncs"]``).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.profiler

_IDS: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "sssp_span_ids", default={})
_fetches = 0


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A span named ``name`` carrying ``ids`` and the enclosing ids."""
    return jax.profiler.TraceAnnotation(name, **{**_IDS.get(), **ids})


@contextlib.contextmanager
def ids(**kw):
    """Every span opened inside carries ``kw`` as keywords."""
    token = _IDS.set({**_IDS.get(), **kw})
    try:
        yield
    finally:
        _IDS.reset(token)


def fetch(tree):
    """Blocking device->host read of ``tree`` (one sync, however many
    leaves) inside an ``sssp.fetch`` span."""
    global _fetches
    _fetches += 1
    with span("sssp.fetch"):
        return jax.device_get(tree)


def fetches() -> int:
    """Number of :func:`fetch` reads made in this process so far."""
    return _fetches
