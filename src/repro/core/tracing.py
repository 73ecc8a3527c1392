"""Host spans at the store's layer boundaries, on the profiler's clock.

`span(name, **ids)` opens a `jax.profiler.TraceAnnotation`, so the span
lands in the same trace as the device's work, with `ids` as its event
stats; the span's parent on the same thread is the span it nests in.
Every name is declared in `counters_registry.SPANS` (the `counter` lint
pass checks both directions). `PERF.md` lists what reads each one.

Spans are on after `enable()`, off after `disable()`, and otherwise
follow the JAX profiler: on while a profiler session records. When off,
`span()` returns one shared no-op context manager. The switch is read
from one module global; in the default mode `next_op_id()`, drawn once
per request, is where it is refreshed from the profiler, so a request
that starts inside a profiler session is traced whole.

An operator turns spans on for a window with `enable()` inside
`jax.profiler.trace(...)`, or leaves the default and just profiles.

`next_op_id()` draws the id that every span of one request carries as
`op=`, including spans that run on pool or DPU worker threads.

Spans never block, never wait for the device and never change control
flow: with spans off the program does exactly what it does without them.
"""
from __future__ import annotations

import itertools
from typing import Optional

from jax.profiler import TraceAnnotation


class _NoSpan:
    """The shared span returned while spans are off."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **ids) -> None:
        pass


NOOP = _NoSpan()

_forced: Optional[bool] = None     # None: follow the profiler
_on = False
_op_ids = itertools.count(1)


def enable() -> None:
    """Spans on, whether or not a profiler records."""
    global _forced, _on
    _forced = _on = True


def disable() -> None:
    """Spans off, even while a profiler records."""
    global _forced, _on
    _forced = _on = False


def active() -> bool:
    return _on


def next_op_id() -> int:
    """A new request id (and, in the default mode, a fresh look at
    whether a profiler records)."""
    global _on
    if _forced is None:
        _on = TraceAnnotation.is_enabled()
    return next(_op_ids)


def span(name: str, **ids):
    """A span named `name` with `ids` as its stats, or the shared no-op.
    The returned object takes `set_metadata(**ids)` for stats known only
    inside the span."""
    if not _on:
        return NOOP
    return TraceAnnotation(name, **ids)
